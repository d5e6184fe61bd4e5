"""Sub-byte (int4) pipeline: nibble round-trip, storage geometry, kernel
parity vs the dequant oracle on every serving contraction, col-granularity
store-only dequant, and the byte-accounting claims the planner and benches
ride on.

Error-bound conventions under test:

* Nibble packing itself is LOSSLESS — pack/unpack round-trips every int in
  [-8, 7] bitwise, so kernel-vs-dequant-oracle parity stays TIGHT (both
  compute the same dequantized function; tolerance covers only f32
  reduction-order drift).
* Quantization error per element is bounded by its scale group's step:
  absmax/7/2 per (Kb, Nb) tile ("tile") or per Nb column ("col"). Col
  groups are supersets of tile groups, so the col bound is never tighter —
  the accuracy ordering col >= tile is asserted where the weight's tile
  magnitudes actually vary.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo import HAVE_HYPOTHESIS, given, settings, st

from repro.core import GroupedPackedWeight, PackedWeight
from repro.core.planner import plan_gemm, plan_grouped_gemm
from repro.core.tile_format import (ScaleSpec, TileFormat, pack_nibbles,
                                    unpack_nibbles)
from repro.kernels import ref
from repro.kernels.gemm_grouped import (gemm_grouped_packed,
                                        gemm_grouped_packed_ragged,
                                        gemm_grouped_packed_ragged_jnp)
from repro.kernels.gemm_packed import gemm_packed_fused_a
from repro.kernels.pack import pack_b, pack_b_grouped


def _fmt4(bk=32, bn=64, layout="row", granularity="tile"):
    return TileFormat(bk=bk, bn=bn, layout=layout, dtype="int4",
                      scale=ScaleSpec(granularity=granularity))


# ---------------------------------------------------------------------------
# Nibble pack/unpack: lossless, shape-halving, edge shapes
# ---------------------------------------------------------------------------

def test_nibble_roundtrip_exhaustive_int4_range():
    """Every representable int4 value survives the byte round trip bitwise
    (including -8: the sign-extending unpack covers the full two's
    complement range, not just the quantizer's [-7, 7])."""
    vals = jnp.arange(-8, 8, dtype=jnp.int8)
    pairs = jnp.stack(jnp.meshgrid(vals, vals, indexing="ij"),
                      axis=-1).reshape(-1, 2)          # all 256 (lo, hi)
    packed = pack_nibbles(pairs)
    assert packed.shape == (256, 1) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_nibbles(packed)),
                                  np.asarray(pairs))


def test_nibble_pairing_is_minor_axis_low_then_high():
    """Of a minor axis of length t, element i lands in the LOW nibble and
    element i + t/2 in the HIGH nibble of byte i — the layout contract the
    in-kernel shift/mask unpack assumes (low half, then high half)."""
    q = jnp.asarray([[1, -2, 3, -4]], jnp.int8)
    packed = np.asarray(pack_nibbles(q)).view(np.uint8)
    want = np.asarray([[(1 & 0xF) | ((3 & 0xF) << 4),
                        (-2 & 0xF) | ((-4 & 0xF) << 4)]], np.uint8)
    np.testing.assert_array_equal(packed, want)
    np.testing.assert_array_equal(np.asarray(unpack_nibbles(pack_nibbles(q))),
                                  np.asarray(q))


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(k=st.integers(1, 97), n=st.integers(1, 130),
           layout=st.sampled_from(["row", "col"]),
           granularity=st.sampled_from(["tile", "col"]),
           seed=st.integers(0, 2**16))
    def test_property_nibble_roundtrip_odd_shapes(k, n, layout, granularity,
                                                  seed):
        """Pack -> unpack reconstructs within the quantization step for ANY
        (K, N) — odd edges exercise the zero-filled remainder nibbles."""
        r = np.random.default_rng(seed)
        fmt = _fmt4(bk=16, bn=16, layout=layout, granularity=granularity)
        w = jnp.asarray(r.normal(size=(k, n)), jnp.float32)
        packed, scales = ref.pack_b_ref(w, fmt)
        assert packed.shape == fmt.packed_shape(k, n)
        assert packed.dtype == jnp.int8           # storage dtype
        assert scales.shape == fmt.scale_shape(k, n)
        back = ref.unpack_b_dequant_ref(packed, scales, k, n, layout,
                                        fmt=fmt)
        kb, nb = -(-k // fmt.bk), -(-n // fmt.bn)
        s = np.asarray(scales)
        if granularity == "col":
            s = np.repeat(s[:, None], kb, axis=1)  # [Nb] -> [Nb, Kb]
        step = s[(np.arange(n)[None, :] // fmt.bn),
                 (np.arange(k)[:, None] // fmt.bk)]
        err = np.abs(np.asarray(back) - np.asarray(w))
        assert np.all(err <= step / 2 + 1e-6)
else:  # keep the node visible (and skipping) without hypothesis
    @given()
    def test_property_nibble_roundtrip_odd_shapes():
        pass  # pragma: no cover


def test_int4_storage_geometry_and_bytes():
    fmt = _fmt4(bk=32, bn=64)
    assert fmt.sub_byte and fmt.storage_dtype == "int8"
    assert fmt.tile_shape == (32, 64)
    assert fmt.storage_tile_shape == (32, 32)       # trailing dim halved
    assert fmt.packed_shape(64, 128) == (2, 2, 32, 32)
    assert fmt.itemsize == 0.5
    # int4 tile + one f32 scale: a quarter of the bf16 tile it replaces
    int8 = TileFormat(bk=32, bn=64, dtype="int8", scale=ScaleSpec())
    assert fmt.tile_bytes() == 32 * 64 // 2 + 4
    # col granularity: one scale per Nb column instead of one per tile —
    # this is what actually clears the <=0.5x-int8 B-traffic bar (per-tile
    # scales leave int4 at 0.501x: the 4-byte scale no longer amortizes)
    col = _fmt4(granularity="col")
    assert col.scale_shape(256, 128) == (2,)
    assert col.packed_bytes(256, 128) < fmt.packed_bytes(256, 128)
    assert col.packed_bytes(256, 128) <= 0.5 * int8.packed_bytes(256, 128)
    with pytest.raises(ValueError):
        _fmt4(bn=33)                                # odd trailing tile dim


def test_int4_not_inferable_from_buffer(rng):
    """A nibble-packed stack is physically int8 with a halved trailing dim;
    ``from_packed`` CANNOT see that — the explicit format is authoritative
    and geometry checks reject the misread."""
    fmt = _fmt4(bk=16, bn=32)
    w = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    q, s = ref.pack_b_ref(w, fmt)
    inferred = TileFormat.from_packed(q, "row", has_scales=True)
    assert inferred.dtype == "int8" and inferred.bn == 16  # the misread
    # the kernel with the true format still matches the oracle
    a = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    got = gemm_packed_fused_a(a, q, 64, bm=8, b_scales=s, b_format=fmt)
    want = ref.matmul_ref(
        a, ref.unpack_b_dequant_ref(q, s, 32, 64, fmt=fmt), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layout", ["row", "col"])
@pytest.mark.parametrize("granularity", ["tile", "col"])
def test_pallas_int4_packer_matches_ref(rng, layout, granularity):
    fmt = _fmt4(layout=layout, granularity=granularity)
    w = jnp.asarray(rng.normal(size=(100, 90)), jnp.float32)
    got_q, got_s = pack_b(w, fmt)
    want_q, want_s = ref.pack_b_ref(w, fmt)
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


# ---------------------------------------------------------------------------
# Kernel parity vs the dequant oracle (dense / grouped / ragged)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(40, 96, 80), (7, 33, 66)])
@pytest.mark.parametrize("granularity", ["tile", "col"])
def test_fused_a_kernel_int4_parity(rng, m, k, n, granularity):
    """In-kernel nibble unpack + dequant equals the dequant-oracle GEMM
    (tight tolerance: identical function, different schedule)."""
    fmt = _fmt4(granularity=granularity)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    q, s = pack_b(w, fmt)
    got = gemm_packed_fused_a(a, q, n, bm=32, b_scales=s, b_format=fmt)
    want = ref.matmul_ref(
        a, ref.unpack_b_dequant_ref(q, s, k, n, fmt=fmt), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("granularity", ["tile", "col"])
def test_fused_a_int4_bias_epilogue_ordering(rng, granularity):
    """Dequant — per K-step (tile) or store-only (col) — always lands
    BEFORE bias/activation in the epilogue."""
    fmt = _fmt4(granularity=granularity)
    a = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    q, s = pack_b(w, fmt)
    got = gemm_packed_fused_a(a, q, 64, bm=8, b_scales=s, bias=bias,
                              epilogue="relu", b_format=fmt)
    deq = ref.unpack_b_dequant_ref(q, s, 64, 64, fmt=fmt)
    want = jnp.maximum(a @ deq + bias, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("granularity", ["tile", "col"])
def test_grouped_int4_silu_gate_parity(rng, granularity):
    e, m, k, n = 3, 40, 96, 64
    fmt = _fmt4(granularity=granularity)
    a = jnp.asarray(rng.normal(size=(e, m, k)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    qg, sg = pack_b_grouped(wg, fmt)
    qu, su = pack_b_grouped(wu, fmt)
    got = gemm_grouped_packed(a, qg, n, b2_packed=qu, bm=16, b_scales=sg,
                              b2_scales=su, epilogue="silu_gate",
                              b_format=fmt)
    want = ref.grouped_silu_gate_ref(
        a, ref.unpack_b_grouped_ref(qg, k, n, scales=sg, fmt=fmt),
        ref.unpack_b_grouped_ref(qu, k, n, scales=su, fmt=fmt), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("counts_kind", ["mixed", "empty", "full"])
@pytest.mark.parametrize("granularity", ["tile", "col"])
def test_ragged_kernel_int4_parity(rng, counts_kind, granularity):
    """The ragged counts path runs int4 unchanged: scalar-prefetch grid +
    in-kernel nibble unpack + masked tail stores, both granularities."""
    e, s_, c, k, n = 3, 2, 24, 48, 64
    fmt = _fmt4(bk=16, bn=32, granularity=granularity)
    a = jnp.asarray(rng.normal(size=(e, s_, c, k)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    qg, sg = pack_b_grouped(wg, fmt)
    qu, su = pack_b_grouped(wu, fmt)
    counts = {
        "mixed": jnp.asarray(rng.integers(0, c + 1, (e, s_)), jnp.int32),
        "empty": jnp.zeros((e, s_), jnp.int32),
        "full": jnp.full((e, s_), c, jnp.int32),
    }[counts_kind]
    deq_g = ref.unpack_b_grouped_ref(qg, k, n, scales=sg, fmt=fmt)
    deq_u = ref.unpack_b_grouped_ref(qu, k, n, scales=su, fmt=fmt)
    want = ref.grouped_ragged_ref(a, deq_g, counts, b2=deq_u,
                                  out_dtype=jnp.float32)
    got = gemm_grouped_packed_ragged(a, qg, n, counts, b2_packed=qu, bm=8,
                                     b_scales=sg, b2_scales=su,
                                     epilogue="silu_gate", b_format=fmt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    got_jnp = gemm_grouped_packed_ragged_jnp(
        a, qg, n, counts, b2_packed=qu, bm=8, b_scales=sg, b2_scales=su,
        epilogue="silu_gate", b_format=fmt)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Accuracy ordering: col-granularity is coarser, never more accurate
# ---------------------------------------------------------------------------

def test_col_vs_tile_accuracy_ordering(rng):
    """A col scale group is the union of its column's tile groups, so its
    absmax (hence its quantization step) dominates each tile's: per-element
    round-trip error under "col" >= under "tile" wherever tile magnitudes
    vary down a column — and both respect their own scale/2 bound."""
    fmt_t = _fmt4(bk=16, bn=16)
    fmt_c = _fmt4(bk=16, bn=16, granularity="col")
    k, n = 96, 64
    # magnitudes growing down K: within a column, tile absmaxes differ 8x
    w = (rng.normal(size=(k, n))
         * np.geomspace(1.0, 8.0, k)[:, None]).astype(np.float32)
    w = jnp.asarray(w)
    qt, st_ = ref.pack_b_ref(w, fmt_t)
    qc, sc = ref.pack_b_ref(w, fmt_c)
    back_t = np.asarray(ref.unpack_b_dequant_ref(qt, st_, k, n, fmt=fmt_t))
    back_c = np.asarray(ref.unpack_b_dequant_ref(qc, sc, k, n, fmt=fmt_c))
    err_t = np.abs(back_t - np.asarray(w))
    err_c = np.abs(back_c - np.asarray(w))
    assert err_c.max() >= err_t.max()
    assert err_c.mean() > err_t.mean()
    # each respects its own documented bound (scale/2 per element)
    assert err_c.max() <= np.asarray(sc).max() / 2 + 1e-6
    # the col scale per column dominates that column's tile scales
    assert np.all(np.asarray(sc)[:, None] >= np.asarray(st_) - 1e-7)


# ---------------------------------------------------------------------------
# Planner + weight pytrees + layered quantize strings
# ---------------------------------------------------------------------------

def test_planner_int4_byte_accounting():
    p8 = plan_gemm(256, 512, 512, "bfloat16", b_dtype="int8")
    p4 = plan_gemm(256, 512, 512, "bfloat16", b_dtype="int4")
    f8, f4 = p8.b_format, p4.b_format
    assert f4.sub_byte and f4.itemsize == 0.5
    pc = plan_gemm(256, 512, 512, "bfloat16", b_dtype="int4",
                   scale_granularity="col")
    assert pc.b_scale == "col"
    assert pc.b_format.scale.granularity == "col"
    # guarded B-bytes claim at matched multi-tile geometry: int4:col
    # <= 0.5x int8 (needs kb >= 2 so the int8 per-tile scales outweigh the
    # int4 per-column ones)
    fmt8 = dataclasses.replace(f8, bk=128, bn=128)
    fmt4c = dataclasses.replace(pc.b_format, bk=128, bn=128)
    assert fmt4c.packed_bytes(512, 512) <= 0.5 * fmt8.packed_bytes(512, 512)
    gp = plan_grouped_gemm(4, 256, 512, 512, "bfloat16", b_dtype="int4",
                           scale_granularity="col")
    assert gp.b_format.scale.granularity == "col"


@pytest.mark.parametrize("quantize", ["int4", "int4:col", "int8:col"])
def test_packed_weight_quantize_strings(rng, quantize):
    """The layered quantize strings parse to (dtype, granularity) and both
    backends agree with the dequant oracle through the weight facade."""
    a = jnp.asarray(rng.normal(size=(24, 96)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(96, 80)), jnp.float32)
    pw = PackedWeight.pack(w, quantize=quantize, backend="jnp")
    assert pw.fmt.is_quantized
    assert pw.fmt.sub_byte == quantize.startswith("int4")
    want_scale_ndim = 1 if quantize.endswith(":col") else 2
    assert pw.scales.ndim == want_scale_ndim
    deq = ref.unpack_b_dequant_ref(pw.packed, pw.scales, 96, 80,
                                   pw.plan.layout_b, fmt=pw.fmt)
    want = np.asarray(a @ deq)
    for backend in ("jnp", "pallas"):
        np.testing.assert_allclose(np.asarray(pw.matmul(a, backend=backend)),
                                   want, rtol=1e-4, atol=1e-4)


def test_int4_weight_pytree_and_scan(rng):
    """int4 stacks flatten to (packed, scales) leaves and scan-slice; the
    sub-byte format rides the static plan aux data."""
    a = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    pw = PackedWeight.pack(w, quantize="int4:col", backend="jnp")
    leaves, treedef = jax.tree_util.tree_flatten(pw)
    assert len(leaves) == 2
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.plan == pw.plan and back.fmt.sub_byte
    jitted = jax.jit(lambda weight, x: weight.matmul(x))
    np.testing.assert_allclose(np.asarray(jitted(pw, a)),
                               np.asarray(pw.matmul(a)), rtol=1e-6,
                               atol=1e-6)
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), pw)

    def body(carry, pw_l):
        return carry, pw_l.matmul(a)

    _, ys = jax.lax.scan(body, 0, stacked)
    assert ys.shape == (2, 16, 48)
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(pw.matmul(a)),
                               rtol=1e-5, atol=1e-5)


def test_grouped_int4_ragged_counts_through_weight_facade(rng):
    """The full serving route — GroupedPackedWeight.matmul with counts —
    matches the dequant oracle for int4 on both granularities."""
    e, s_, c, k, n = 2, 2, 64, 96, 64
    a = jnp.asarray(rng.normal(size=(e, s_, c, k)), jnp.float32)
    counts = jnp.asarray([[60, 3], [64, 0]], jnp.int32)
    w = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    for quantize in ("int4", "int4:col"):
        gw = GroupedPackedWeight.pack(w, quantize=quantize, backend="jnp")
        got = gw.matmul(a, counts=counts)
        deq = ref.unpack_b_grouped_ref(gw.packed, k, n, gw.plan.layout_b,
                                       scales=gw.scales, fmt=gw.fmt)
        want = ref.grouped_ragged_ref(a.reshape(e, s_ * c, k)
                                      .reshape(e, s_, c, k),
                                      deq, counts, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
