"""Roofline extraction: HLO parser units + scan trip-count amplification."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.analysis import (HloCostModel, Roofline,
                                     _collective_traffic, _group_size,
                                     _shape_bytes, parse_collectives)
from repro.roofline.hw import V5E


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("bf16[64]") == 128
    assert _shape_bytes("(f32[8]{0}, s32[4])") == 32 + 16
    assert _shape_bytes("pred[]") == 1  # scalar = one element


def test_group_size_formats():
    assert _group_size("replica_groups=[2,4]<=[8]") == 4
    assert _group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4


def test_collective_traffic_model():
    assert _collective_traffic("all-gather", 100, 4) == 100
    assert _collective_traffic("all-reduce", 100, 4) == 150
    assert _collective_traffic("reduce-scatter", 100, 4) == 300
    assert _collective_traffic("collective-permute", 100, 2) == 100


def test_parse_collectives_synthetic():
    text = """
  %ar = f32[1024]{0} all-reduce(%x), replica_groups=[2,4]<=[8], to_apply=%add
  %ag = bf16[512,2]{1,0} all-gather(%y), replica_groups={{0,1},{2,3}}
  %done = f32[8] all-gather-done(%h)
"""
    stats = parse_collectives(text)
    assert stats.op_counts == {"all-reduce": 1, "all-gather": 1}
    assert stats.op_bytes["all-reduce"] == 2 * 4096 * 3 / 4
    assert stats.op_bytes["all-gather"] == 2048


def test_scan_amplification_matches_unroll():
    def f_scan(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        out, _ = jax.lax.scan(body, x, w)
        return out.sum()

    def f_unroll(x, w):
        c = x
        for i in range(8):
            c = jnp.tanh(c @ w[i])
        return c.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    rs = HloCostModel(jax.jit(f_scan).lower(x, w).compile().as_text()).rollup()
    ru = HloCostModel(
        jax.jit(f_unroll).lower(x, w).compile().as_text()).rollup()
    assert rs.flops == ru.flops == 8 * 2 * 64 ** 3
    # XLA's own analysis counts the body once (the bug this model fixes)
    ca = jax.jit(f_scan).lower(x, w).compile().cost_analysis()
    assert ca["flops"] < rs.flops / 4


def test_nested_scan_amplification():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ c2), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out.sum()

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    r = HloCostModel(jax.jit(f).lower(x).compile().as_text()).rollup()
    assert r.flops == 5 * 3 * 2 * 32 ** 3


def test_roofline_terms_and_bottleneck():
    r = Roofline(arch="a", shape="s", mesh="single", chips=256,
                 flops_per_device=V5E.peak_bf16_flops,      # 1s compute
                 bytes_per_device=V5E.hbm_bw / 2,           # 0.5s memory
                 collective_bytes_per_device=V5E.ici_link_bw / 4,  # 0.25s
                 model_flops=V5E.peak_bf16_flops * 256 * 0.5)
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 0.5) < 1e-9
    assert abs(r.collective_s - 0.25) < 1e-9
    assert r.bottleneck == "compute"
    assert abs(r.useful_flops_ratio - 0.5) < 1e-9
    assert abs(r.roofline_fraction - 1.0) < 1e-9


def test_hardware_model_keyed_by_device_kind():
    from repro.roofline.hw import TARGETS, target_for
    assert target_for("TPU v5 lite") is V5E
    assert set(TARGETS) >= {"TPU v5 lite"}
    try:
        target_for("TPU v9 imaginary")
    except KeyError as e:
        assert "TPU v9 imaginary" in str(e)
    else:
        raise AssertionError("an unknown TPU kind must not fall back to v5e")


def test_unknown_tpu_kind_raises_where_kernels_are_planned(monkeypatch):
    """On an attached TPU of a kind the table does not hold, planning a
    kernel is an error — never v5e's VMEM numbers by default."""
    import types

    from repro.core.planner import plan_gemm
    from repro.roofline import hw
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 x")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    for fn in (hw.current_target, lambda: plan_gemm(128, 256, 256)):
        try:
            fn()
        except KeyError:
            continue
        raise AssertionError("planned for an unknown TPU kind")


def test_peak_flops_raises_for_unknown_dtype():
    from repro.roofline.hw import peak_flops
    assert peak_flops("bfloat16") == V5E.peak_bf16_flops
    assert peak_flops("int8") == V5E.peak_int8_ops
    try:
        peak_flops("float8_e4m3fn")
    except KeyError:
        return
    raise AssertionError("an unknown dtype must not get the bf16 peak")


def test_vmem_budget_and_kernel_limit_share_one_source():
    """The planner budgets within the limit every kernel declares, and the
    declared limit fits the chip's physical VMEM."""
    from repro.core.planner import GemmPlan, plan_gemm
    from repro.kernels.common import tpu_compiler_params
    limit = tpu_compiler_params(("parallel",)).vmem_limit_bytes
    assert limit == V5E.vmem_limit_bytes <= V5E.vmem_capacity
    assert GemmPlan(bm=128, bk=128, bn=128, dtype="bfloat16",
                    acc_dtype="float32").vmem_budget == V5E.vmem_bytes
    assert V5E.vmem_bytes < limit
    for m, k, n in ((1, 8192, 2048), (2048, 8192, 2048), (512, 2048, 50304)):
        assert plan_gemm(m, k, n, "bfloat16").vmem_working_set() <= \
            V5E.vmem_bytes


def test_interpret_mode_is_refused_on_a_tpu(monkeypatch):
    from repro.kernels import common
    assert common.pallas_kwargs(interpret=True,
                                dimension_semantics=("parallel",)) == \
        {"interpret": True}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.default_interpret() is False
    try:
        common.pallas_kwargs(interpret=True, dimension_semantics=("parallel",))
    except RuntimeError:
        return
    raise AssertionError("interpret mode ran on a TPU backend")
