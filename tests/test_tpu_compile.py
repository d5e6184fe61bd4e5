"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
described inside a fixture, at the published widths the serving path runs —
olmo-1b's dense projections and LM head (decode M=1, prefill M=512; bf16,
int8 and int4 column-scaled packed weights), the load-time packer, and
mixtral-8x22b's grouped and ragged expert GEMMs. Mosaic refuses here what
interpret mode cannot see: plans over the scoped-VMEM limit, blocks that
break the (8, 128) rule, vector shapes it cannot lay out. Each test asserts
that the compiled program holds the Pallas kernel (``tpu_custom_call``).

The topology is described only once a test of this file runs (never at
import), so every test worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dtypes as mdt
from repro.core.layered import GroupedPackedWeight, PackedWeight
from repro.core.planner import plan_gemm
from repro.kernels import gemm_grouped, gemm_packed
from repro.kernels.pack import pack_b

OLMO_D, OLMO_FF, OLMO_VOCAB = 2048, 8192, 50304        # olmo-1b
MIX_E, MIX_D, MIX_FF, MIX_C = 8, 6144, 16384, 320      # mixtral-8x22b
OLMO_GEMMS = {"qkv": (OLMO_D, OLMO_D), "up": (OLMO_D, OLMO_FF),
              "down": (OLMO_FF, OLMO_D), "lm_head": (OLMO_D, OLMO_VOCAB)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Compile the kernels (the CPU host would pick interpret mode) with
    the persistent compilation cache off: entries written for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    for mod in (gemm_packed, gemm_grouped):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    """The shapes of a pytree of arrays/shape structs, placed on the chip."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("quantize", [None, "int8"])
@pytest.mark.parametrize("m", [1, 512])
@pytest.mark.parametrize("gemm", sorted(OLMO_GEMMS))
def test_fused_a_olmo_widths(one_chip, gemm, m, quantize):
    """Load-time-packed dense GEMM (the serving fast path) with the plan
    ``PackedWeight.pack`` makes and the runtime M-block clamp."""
    k, n = OLMO_GEMMS[gemm]
    w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
    pw = jax.eval_shape(
        lambda w: PackedWeight.pack(w, backend="jnp", quantize=quantize), w)
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(
        lambda a, pw: pw._matmul_impl(a, bias=None, epilogue="none",
                                      out_dtype=None, backend="pallas"),
        a, _on(pw, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [1, 512])
def test_fused_a_int4_col_scale(one_chip, m):
    """Nibble-packed int4 tiles with per-column scales: the in-kernel widen
    and the store-epilogue dequant."""
    w = jax.ShapeDtypeStruct((OLMO_D, OLMO_FF), jnp.bfloat16)
    pw = jax.eval_shape(
        lambda w: PackedWeight.pack(w, backend="jnp", quantize="int4:col"), w)
    a = jax.ShapeDtypeStruct((m, OLMO_D), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(
        lambda a, pw: pw._matmul_impl(a, bias=None, epilogue="silu",
                                      out_dtype=None, backend="pallas"),
        a, _on(pw, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("gemm", ["up", "down", "lm_head"])
def test_pack_b_olmo_widths(one_chip, gemm):
    """The load-time packer at the tile formats the serving plans use."""
    k, n = OLMO_GEMMS[gemm]
    fmt = plan_gemm(1024, k, n, "bfloat16").b_format
    w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(lambda w: pack_b(w, fmt, interpret=False), w)
    assert "tpu_custom_call" in text


def _expert_stacks(quantize, gate):
    k, n = (MIX_D, MIX_FF) if gate else (MIX_FF, MIX_D)
    w = jax.ShapeDtypeStruct((MIX_E, k, n), jnp.bfloat16)
    return k, jax.eval_shape(
        lambda w: GroupedPackedWeight.pack(
            w, backend="jnp", quantize=quantize,
            n_b_streams=2 if gate else 1), w)


@pytest.mark.parametrize("ragged", [True, False])
@pytest.mark.parametrize("quantize,gate", [(None, True), (None, False),
                                           ("int8", True)])
def test_grouped_mixtral_widths(one_chip, quantize, gate, ragged):
    """MoE expert GEMMs at mixtral-8x22b widths: the fused silu-gate pair
    (two B streams, two accumulators) and the down-projection, padded and
    ragged (scalar-prefetched counts)."""
    k, gw = _expert_stacks(quantize, gate)
    sub, _ = mdt.alignment("bfloat16")
    assert MIX_C > sub   # prefill-shaped capacity: the kernel path
    stacks = (gw, gw) if gate else (gw,)
    a = jax.ShapeDtypeStruct((MIX_E, 1, MIX_C, k), jnp.bfloat16,
                             sharding=one_chip)
    counts = jax.ShapeDtypeStruct((MIX_E, 1), jnp.int32, sharding=one_chip)

    def run(a, counts, w, up=None):
        if ragged:
            return w._ragged(a, counts, b2=up, backend="pallas",
                             epilogue="silu_gate" if gate else "none")
        a3 = a.reshape(MIX_E, MIX_C, k)
        if gate:
            return w._silu_gate_impl(up, a3, out_dtype=None, backend="pallas")
        return w._matmul_impl(a3, bias=None, epilogue="none", out_dtype=None,
                              backend="pallas")

    text = _compile_text(run, a, counts, *_on(stacks, one_chip))
    assert "tpu_custom_call" in text


def test_topology_is_a_v5e(topo):
    """The described chip is the one the hardware table keys kernels to."""
    from repro.roofline.hw import V5E, target_for
    kinds = {d.device_kind for d in topo.devices}
    assert kinds and all(target_for(kind) is V5E for kind in kinds)
    assert dataclasses.asdict(V5E)["vmem_limit_bytes"] <= V5E.vmem_capacity
