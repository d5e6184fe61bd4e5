"""Quantized (int8 dequant-in-epilogue) tile format: round-trip, kernel
parity, pytree transparency, and planner byte-accounting invariants.

The single :class:`TileFormat` descriptor with a ScaleSpec drives every
layer under test here: the pack layer emits int8 tiles + per-(Kb,Nb)-tile
f32 scales, the kernels (dense fused-A, grouped, ragged) consume the scale
grid through a BlockSpec mirroring B's index map and dequantize on the f32
accumulator ahead of the fused epilogues, and both weight pytrees carry the
scale grid as a second leaf. Tolerances: kernel-vs-DEQUANT-oracle parity is
tight (both compute the same dequantized function); quantized-vs-float
parity is bounded by the per-tile quantization step (absmax/127).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo import given, settings, st

from repro.core import GroupedPackedWeight, PackedWeight
from repro.core.planner import GemmPlan, plan_gemm, plan_grouped_gemm, should_pack
from repro.core.tile_format import ScaleSpec, TileFormat, as_tile_format
from repro.kernels import ref
from repro.kernels.gemm_grouped import (gemm_grouped_packed,
                                        gemm_grouped_packed_ragged,
                                        gemm_grouped_packed_ragged_jnp)
from repro.kernels.gemm_packed import gemm_packed_fused_a
from repro.kernels.pack import pack_b, pack_b_grouped
from repro.roofline.hw import V5E

QFMT = TileFormat(bk=32, bn=64, dtype="int8", scale=ScaleSpec())


def _qfmt(bk=32, bn=64, layout="row"):
    return TileFormat(bk=bk, bn=bn, layout=layout, dtype="int8",
                      scale=ScaleSpec())


# ---------------------------------------------------------------------------
# TileFormat descriptor
# ---------------------------------------------------------------------------

def test_tile_format_geometry_and_hashability():
    fmt = _qfmt()
    assert fmt.tile_shape == (32, 64) and fmt.rhs_contract == 0
    col = dataclasses.replace(fmt, layout="col")
    assert col.tile_shape == (64, 32) and col.rhs_contract == 1
    assert fmt.packed_shape(70, 130) == (3, 3, 32, 64)
    assert fmt.scale_shape(70, 130) == (3, 3)
    # int8 tile + one f32 scale vs the bf16 tile it replaces: ~half bytes
    bf16 = TileFormat(bk=32, bn=64, dtype="bfloat16")
    assert fmt.tile_bytes() == 32 * 64 + 4
    assert fmt.tile_bytes() < bf16.tile_bytes()
    # hashable/static: usable as dict keys and pytree aux data
    assert len({fmt, col, bf16}) == 3
    # normalizer: legacy int args and an existing format both resolve
    assert as_tile_format(16, 32).bk == 16
    assert as_tile_format(fmt) is fmt


def test_tile_format_validation():
    with pytest.raises(ValueError):
        TileFormat(bk=8, bn=8, layout="diag")
    with pytest.raises(ValueError):
        TileFormat(bk=8, bn=8, dtype="float32", scale=ScaleSpec())
    with pytest.raises(ValueError):
        ScaleSpec(granularity="row")


def test_plan_b_format_single_source():
    """The plan's b_format is the descriptor every layer consumes: quantized
    iff b_dtype is a narrow int under a float compute dtype."""
    plan = plan_gemm(256, 512, 512, "bfloat16", b_dtype="int8")
    fmt = plan.b_format
    assert fmt.is_quantized and fmt.dtype == "int8"
    assert (fmt.bk, fmt.bn, fmt.layout) == (plan.bk, plan.bn, plan.layout_b)
    assert not plan_gemm(256, 512, 512, "bfloat16").b_format.is_quantized
    # true-integer GEMM (a int8 too) is NOT the dequant format
    assert not plan_gemm(256, 512, 512, "int8").b_format.is_quantized


# ---------------------------------------------------------------------------
# Pack/unpack round trip vs the dequant oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n", [(64, 64), (70, 130), (33, 7)])
@pytest.mark.parametrize("layout", ["row", "col"])
def test_quantized_roundtrip_error_bound(rng, k, n, layout):
    """Dequantized values reconstruct the original within half a quantization
    step per tile (absmax/127/2), elementwise."""
    fmt = _qfmt(layout=layout)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    packed, scales = ref.pack_b_ref(w, fmt)
    assert packed.dtype == jnp.int8
    assert scales.shape == fmt.scale_shape(k, n)
    back = ref.unpack_b_dequant_ref(packed, scales, k, n, layout)
    # per-element bound: its tile's scale / 2 (+ float eps)
    step = np.asarray(scales)[
        (np.arange(n)[None, :] // fmt.bn), (np.arange(k)[:, None] // fmt.bk)]
    err = np.abs(np.asarray(back) - np.asarray(w))
    assert np.all(err <= step / 2 + 1e-6)


@pytest.mark.parametrize("layout", ["row", "col"])
def test_pallas_quantized_packer_matches_ref(rng, layout):
    fmt = _qfmt(layout=layout)
    w = jnp.asarray(rng.normal(size=(100, 90)), jnp.float32)
    got_q, got_s = pack_b(w, fmt)
    want_q, want_s = ref.pack_b_ref(w, fmt)
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_zero_tiles_quantize_exact(rng):
    """All-zero (remainder-fill) tiles get scale 1.0 and reconstruct exactly
    — the packer's zero-fill contract survives quantization."""
    fmt = _qfmt(bk=16, bn=16)
    w = jnp.zeros((40, 40), jnp.float32)
    packed, scales = ref.pack_b_ref(w, fmt)
    np.testing.assert_array_equal(np.asarray(scales),
                                  np.ones_like(np.asarray(scales)))
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.zeros_like(np.asarray(packed)))


def test_grouped_quantized_pack_matches_dense_per_expert(rng):
    fmt = _qfmt()
    w = jnp.asarray(rng.normal(size=(3, 70, 130)), jnp.float32)
    gq, gs = pack_b_grouped(w, fmt)
    for e in range(3):
        dq, ds = ref.pack_b_ref(w[e], fmt)
        np.testing.assert_array_equal(np.asarray(gq[e]), np.asarray(dq))
        np.testing.assert_array_equal(np.asarray(gs[e]), np.asarray(ds))


# ---------------------------------------------------------------------------
# Kernel-vs-reference parity (dense, grouped, ragged)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(40, 96, 80), (128, 64, 128), (7, 33, 65)])
@pytest.mark.parametrize("layout", ["row", "col"])
def test_fused_a_kernel_quantized_parity(rng, m, k, n, layout):
    """The kernel's per-K-step dequant computes exactly the dequant-oracle
    GEMM (tight tolerance: same function, different schedule)."""
    fmt = _qfmt(layout=layout)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    q, s = pack_b(w, fmt)
    got = gemm_packed_fused_a(a, q, n, bm=32, layout_b=layout, b_scales=s)
    want = ref.matmul_ref(a, ref.unpack_b_dequant_ref(q, s, k, n, layout),
                          jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fused_a_kernel_quantized_bias_epilogue(rng):
    """Dequant lands BEFORE bias/activation in the store epilogue."""
    fmt = _qfmt()
    a = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    q, s = pack_b(w, fmt)
    got = gemm_packed_fused_a(a, q, 64, bm=8, b_scales=s, bias=bias,
                              epilogue="relu")
    deq = ref.unpack_b_dequant_ref(q, s, 64, 64)
    want = jnp.maximum(a @ deq + bias, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_quantized_error_vs_float_bounded(rng):
    """Quantized GEMM vs the float GEMM: error scales with the quantization
    step times sqrt(K) — loose sanity bound, not a parity assertion."""
    a = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    pw = PackedWeight.pack(w, quantize="int8", backend="jnp")
    got = pw.matmul(a)
    want = a @ w
    rel = (np.abs(np.asarray(got) - np.asarray(want)).max()
           / np.abs(np.asarray(want)).max())
    assert rel < 0.02, rel


@pytest.mark.parametrize("e,m,k,n", [(3, 33, 48, 65), (4, 64, 64, 128)])
def test_grouped_kernel_quantized_parity(rng, e, m, k, n):
    fmt = _qfmt()
    a = jnp.asarray(rng.normal(size=(e, m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    q, s = pack_b_grouped(w, fmt)
    got = gemm_grouped_packed(a, q, n, bm=16, b_scales=s)
    deq = ref.unpack_b_grouped_ref(q, k, n, scales=s)
    want = ref.grouped_matmul_ref(a, deq, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_grouped_kernel_quantized_silu_gate(rng):
    """Both stacks dequantize with their OWN scale grids inside the fused
    gate/up pass."""
    e, m, k, n = 3, 40, 96, 64
    fmt = _qfmt()
    a = jnp.asarray(rng.normal(size=(e, m, k)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    qg, sg = pack_b_grouped(wg, fmt)
    qu, su = pack_b_grouped(wu, fmt)
    got = gemm_grouped_packed(a, qg, n, b2_packed=qu, bm=16, b_scales=sg,
                              b2_scales=su, epilogue="silu_gate")
    want = ref.grouped_silu_gate_ref(
        a, ref.unpack_b_grouped_ref(qg, k, n, scales=sg),
        ref.unpack_b_grouped_ref(qu, k, n, scales=su), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_grouped_quantized_silu_gate_requires_both_scales(rng):
    e, k, n = 2, 32, 32
    a = jnp.asarray(rng.normal(size=(e, 16, k)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    fmt = _qfmt(bk=16, bn=16)
    qg, sg = pack_b_grouped(wg, fmt)
    with pytest.raises(ValueError, match="BOTH scale grids"):
        gemm_grouped_packed(a, qg, n, b2_packed=qg, b_scales=sg,
                            epilogue="silu_gate")


@pytest.mark.parametrize("counts_kind", ["mixed", "empty", "full"])
def test_ragged_kernel_quantized_parity(rng, counts_kind):
    """The ragged counts path runs quantized unchanged: scalar-prefetch grid
    + per-tile dequant + masked tail stores."""
    e, s, c, k, n = 3, 2, 24, 48, 64
    fmt = _qfmt(bk=16, bn=32)
    a = jnp.asarray(rng.normal(size=(e, s, c, k)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    qg, sg = pack_b_grouped(wg, fmt)
    qu, su = pack_b_grouped(wu, fmt)
    counts = {
        "mixed": jnp.asarray(rng.integers(0, c + 1, (e, s)), jnp.int32),
        "empty": jnp.zeros((e, s), jnp.int32),
        "full": jnp.full((e, s), c, jnp.int32),
    }[counts_kind]
    deq_g = ref.unpack_b_grouped_ref(qg, k, n, scales=sg)
    deq_u = ref.unpack_b_grouped_ref(qu, k, n, scales=su)
    want = ref.grouped_ragged_ref(a, deq_g, counts, b2=deq_u,
                                  out_dtype=jnp.float32)
    got = gemm_grouped_packed_ragged(a, qg, n, counts, b2_packed=qu, bm=8,
                                     b_scales=sg, b2_scales=su,
                                     epilogue="silu_gate")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    got_jnp = gemm_grouped_packed_ragged_jnp(a, qg, n, counts, b2_packed=qu,
                                             bm=8, b_scales=sg, b2_scales=su,
                                             epilogue="silu_gate")
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_quantized_bf16_activations(rng):
    """bf16 activations against int8 tiles: the kernel casts the tile up to
    the activation dtype and accumulates f32 (quantization-appropriate
    tolerance for bf16 inputs)."""
    fmt = _qfmt()
    a = jnp.asarray(rng.normal(size=(32, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    q, s = pack_b(w, fmt)
    got = gemm_packed_fused_a(a, q, 64, bm=16, b_scales=s,
                              out_dtype=jnp.float32)
    deq = ref.unpack_b_dequant_ref(q, s, 64, 64)
    # int8 values are exact in bf16, so the kernel's cast-up-and-scale path
    # equals the f32 dequant oracle on the bf16 activations.
    want = np.asarray(a, np.float32) @ np.asarray(deq)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Weight pytrees: scales ride flattening / jit / scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_packed_weight_quantized_backends_agree(rng, backend):
    a = jnp.asarray(rng.normal(size=(40, 96)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(96, 130)), jnp.float32)
    pw = PackedWeight.pack(w, quantize="int8", backend=backend)
    assert pw.scales is not None and pw.fmt.is_quantized
    got = pw.matmul(a, backend=backend)
    deq = ref.unpack_b_dequant_ref(pw.packed, pw.scales, 96, 130,
                                   pw.plan.layout_b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ deq),
                               rtol=1e-4, atol=1e-4)


def test_scale_leaf_flattens_with_packed_buffer(rng):
    w = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    pw = PackedWeight.pack(w, quantize="int8", backend="jnp")
    leaves, treedef = jax.tree_util.tree_flatten(pw)
    assert len(leaves) == 2  # packed + scales
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.plan == pw.plan and back.scales is not None
    # unquantized weights flatten to ONE leaf (scales=None is structure)
    pf = PackedWeight.pack(w, backend="jnp")
    assert len(jax.tree_util.tree_flatten(pf)[0]) == 1


def test_quantized_weight_jit_and_scan_transparent(rng):
    """The ScaleSpec'd format is static aux data; the scale grid is a leaf:
    quantized weights jit and scan-slice like any parameter."""
    a = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    pw = PackedWeight.pack(w, quantize="int8", backend="jnp")
    jitted = jax.jit(lambda weight, x: weight.matmul(x))
    np.testing.assert_allclose(np.asarray(jitted(pw, a)),
                               np.asarray(pw.matmul(a)), rtol=1e-6, atol=1e-6)
    stacked = jax.tree.map(lambda x: jnp.stack([x, 2 * x]), pw)

    def body(carry, pw_l):
        return carry, pw_l.matmul(a)

    _, ys = jax.lax.scan(body, 0, stacked)
    assert ys.shape == (2, 16, 48)
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(pw.matmul(a)),
                               rtol=1e-5, atol=1e-5)


def test_grouped_quantized_weight_scan_stacked(rng):
    """[L,E,K,N] stacks pack to [L,E,Nb,Kb,bk,bn] + [L,E,Nb,Kb] scales and
    slice through scan per layer."""
    w = jnp.asarray(rng.normal(size=(2, 3, 32, 48)), jnp.float32)
    gw = GroupedPackedWeight.pack(w, quantize="int8", backend="jnp")
    assert gw.packed.ndim == 6 and gw.scales.ndim == 4
    a = jnp.asarray(rng.normal(size=(3, 16, 32)), jnp.float32)

    def body(carry, gw_l):
        return carry, gw_l.matmul(a)

    _, ys = jax.lax.scan(body, 0, gw)
    per_layer = GroupedPackedWeight.pack(w[1], plan=gw.plan,
                                         quantize="int8", backend="jnp")
    np.testing.assert_allclose(np.asarray(ys[1]),
                               np.asarray(per_layer.matmul(a)),
                               rtol=1e-5, atol=1e-5)


def test_silu_gate_rejects_mixed_quantization(rng):
    w = jnp.asarray(rng.normal(size=(2, 32, 32)), jnp.float32)
    plan = plan_grouped_gemm(2, 16, 32, 32, "float32", n_b_streams=2,
                             b_dtype="int8")
    gq = GroupedPackedWeight.pack(w, plan=plan, quantize="int8",
                                  backend="jnp")
    gf = GroupedPackedWeight.pack(
        w, plan=dataclasses.replace(plan, b_dtype=None), backend="jnp")
    a = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
    with pytest.raises(ValueError):
        gq.silu_gate(gf, a)


def test_pack_rejects_quantize_without_quantized_plan(rng):
    w = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    float_plan = plan_gemm(16, 32, 32, "float32")
    with pytest.raises(ValueError, match="b_dtype"):
        PackedWeight.pack(w, plan=float_plan, quantize="int8")
    with pytest.raises(ValueError, match="int8"):
        PackedWeight.pack(w, quantize="int2")
    with pytest.raises(ValueError, match="col"):
        PackedWeight.pack(w, quantize="int4:row")


# ---------------------------------------------------------------------------
# Planner: bytes-aware plans and crossover
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 8192), k=st.integers(1, 16384),
       n=st.integers(1, 16384),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       budget_mb=st.sampled_from([4, 16, 64, 128]))
def test_property_int8_plans_fit_vmem(m, k, n, dtype, budget_mb):
    """Planner invariant: int8-B plans never exceed the VMEM budget, and the
    emitted format is the quantized one."""
    plan = plan_gemm(m, k, n, dtype, b_dtype="int8",
                     vmem_budget=budget_mb * 2**20)
    assert plan.vmem_working_set() <= plan.vmem_budget
    assert plan.b_format.is_quantized
    plan.validate()


@settings(max_examples=20, deadline=None)
@given(e=st.integers(2, 32), m=st.integers(1, 2048),
       k=st.integers(1, 8192), n=st.integers(1, 8192),
       streams=st.sampled_from([1, 2]))
def test_property_int8_grouped_plans_fit_vmem(e, m, k, n, streams):
    plan = plan_grouped_gemm(e, m, k, n, "bfloat16", n_b_streams=streams,
                             b_dtype="int8")
    acc_item = 4
    extra = (streams - 1) * (plan.double_buffer * plan.b_format.tile_bytes()
                             + plan.bm * plan.bn * acc_item)
    assert plan.vmem_working_set() + extra <= V5E.vmem_bytes
    plan.validate()


def test_int8_b_halves_working_set_at_fixed_blocks():
    """At identical block sizes, the int8-B working set drops by the B
    stream's halved bytes — the quantity that buys deeper bk."""
    f = GemmPlan(bm=128, bk=512, bn=512, dtype="bfloat16",
                 acc_dtype="float32")
    q = dataclasses.replace(f, b_dtype="int8")
    saved = f.vmem_working_set() - q.vmem_working_set()
    # B stream: dbuf * bk * bn * (2 - 1) bytes, minus the tiny scale stream
    assert saved == 2 * 512 * 512 * 1 - 2 * 4


def test_should_pack_bytes_aware_crossover():
    """int8 B halves the resident footprint: a B matrix just past the bf16
    pack crossover sits inside it at int8 (the VMEM-residency condition)."""
    m, k, n = 4096, 1024, 1024  # k*n*2 above vmem/32 (1.5 MiB); *1 below
    assert should_pack(m, k, n, "bfloat16", fused=True)
    assert not should_pack(m, k, n, "bfloat16", b_dtype="int8", fused=True)
    # far past the crossover both pack
    assert should_pack(m, 4 * k, 4 * n, "bfloat16", b_dtype="int8",
                       fused=True)


def test_int8_plan_buys_deeper_bk():
    """A tight budget: the narrow B stream leaves VMEM for a deeper
    contraction block (the paper's 'larger kc' applied to bytes)."""
    kwargs = dict(vmem_budget=2**21)
    deep = plan_gemm(512, 65536, 2048, "bfloat16", b_dtype="int8", **kwargs)
    base = plan_gemm(512, 65536, 2048, "bfloat16", **kwargs)
    assert deep.bk >= base.bk


# ---------------------------------------------------------------------------
# Model / serving integration
# ---------------------------------------------------------------------------

def _moe_cfg():
    from repro.configs import reduced_config
    return dataclasses.replace(reduced_config("mixtral-8x22b"),
                               compute_dtype="float32", capacity_factor=16.0)


def test_pack_model_params_quantizes_every_packed_weight():
    """quantize="int8" reaches the dense projections, the LM head, and all
    three expert stacks — each with a scale grid riding the packed leaf."""
    from repro.models import build
    from repro.models.layers import pack_model_params
    cfg = _moe_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    packed = pack_model_params(cfg, params, quantize="int8")
    moe = packed["layers"]["moe"]
    for key in ("wg", "wu", "wo"):
        assert isinstance(moe[key], GroupedPackedWeight), key
        assert moe[key].packed.dtype == jnp.int8
        assert moe[key].scales is not None and moe[key].scales.ndim == 4
    assert moe["wg"].plan == moe["wu"].plan
    head = packed["head_packed"]
    assert head.packed.dtype == jnp.int8 and head.scales is not None
    attn = packed["layers"]["attn"]
    for key in ("wq", "wk", "wv", "wo"):
        assert attn[key].packed.dtype == jnp.int8, key


def test_engine_int8_serving_parity(rng):
    """int8 packed serving end to end (dense linear + LM head + all three
    ragged MoE expert contractions) tracks the float engine to quantization
    error."""
    from repro.models import build
    from repro.serve.engine import Engine, ServeConfig
    cfg = _moe_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    plain = Engine(model, params, ServeConfig(max_len=32))
    quant = Engine(model, params, ServeConfig(max_len=32, pack_weights=True,
                                              quantize="int8"))
    l0, c0 = plain._prefill(plain.params, {"tokens": prompt})
    l1, c1 = quant._prefill(quant.params, {"tokens": prompt})
    scale = np.abs(np.asarray(l0)).max()
    assert np.abs(np.asarray(l1) - np.asarray(l0)).max() <= 0.05 * scale
    tok = jnp.argmax(l0, axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.full((2,), 6, jnp.int32)
    d0, _ = plain._decode(plain.params, c0, tok, pos)
    d1, _ = quant._decode(quant.params, c1, tok, pos)
    scale_d = np.abs(np.asarray(d0)).max()
    assert np.abs(np.asarray(d1) - np.asarray(d0)).max() <= 0.05 * scale_d
    toks = quant.generate({"tokens": prompt}, max_new_tokens=4)
    assert toks.shape == (2, 4)
    assert np.all((toks >= 0) & (toks < cfg.vocab_size))


def test_engine_quantize_requires_pack_weights(rng):
    from repro.models import build
    from repro.serve.engine import Engine, ServeConfig
    cfg = _moe_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="pack_weights"):
        Engine(model, params, ServeConfig(quantize="int8"))
