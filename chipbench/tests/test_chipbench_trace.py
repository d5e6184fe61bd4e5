"""The trace reduction and the per-layer readers: on a hand-built trace
whose every number is known (two scheduler ticks, the first admitting one
request: a prefill program, then the batched step; the second a batched
step alone), and on two ticks of a trace recorded on a TPU v5e."""
import os

import pytest

from chipbench import cell, flops, trace as T
from chipbench.arch import dense
from chipbench.cell import metric_reader
from chipbench.peaks import peaks

MS = 1_000_000  # ns
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

ARCH = {"hidden_size": 2048, "intermediate_size": 8192, "head_dim": 128,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_hidden_layers": 16, "vocab_size": 50304}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("host_ms_per_tick", "device_idle_share", "mfu.decode",
           "mfu.prefill", "gemm_roofline.decode", "gemm_roofline.prefill")


def _gemm(name, rows, weight):
    """A packed GEMM kernel's HLO text as a TPU trace names it: ``[rows,
    2048] @ [2048, 8192]`` over a weight of 8 x 1 tiles of 2048 x 1024."""
    return (f"%{name} = bf16[{rows},8192]{{1,0:T(8,128)(2,1)S(1)}} "
            f"custom-call(bf16[{rows},2048]{{1,0:T(8,128)(2,1)S(1)}} %a.1, "
            f"bf16[8,1,2048,1024]{{3,2,1,0:T(8,128)(2,1)S(1)}} %{weight}), "
            f'custom_call_target="tpu_custom_call"')


STAGE = ("%ds.1 = bf16[8,1,2048,1024]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "fusion(bf16[16,8,1,2048,1024]{4,3,1,2,0:T(8,128)(2,1)} %gte.1, "
         "s32[]{:T(128)} %gte.2), kind=kLoop, calls=%fused_computation.1")
LOOP = "%while.9 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1)"


def _context():
    # Trace clock in ns; host clock in s (any offset: ticks pair by order).
    ops = [
        (1 * MS, 3 * MS // 2, STAGE, ""),                            # prefill
        (3 * MS // 2, 3 * MS, _gemm("closed_call.58", 64, "ds.1"), ""),
        (3 * MS, 4 * MS, "%fusion.2 = bf16[64,2048]{1,0} fusion()", ""),
        (5 * MS, 6 * MS, "%scatter.3 = bf16[4]{0} scatter()", ""),   # insert
        (7 * MS, 10 * MS, LOOP, ""),                                 # step 1
        (7 * MS, 9 * MS, _gemm("closed_call.58", 4, "p.7"), ""),
        (9 * MS, 10 * MS, "%fusion.5 = bf16[4,2048]{1,0} fusion()", ""),
        (14 * MS, 16 * MS, _gemm("closed_call.58", 4, "p.7"), ""),   # step 2
    ]
    modules = [(1 * MS, 4 * MS, "jit__lambda(1)"),
               (5 * MS, 6 * MS, "jit__scatter_blocks(2)"),
               (7 * MS, 10 * MS, "jit_step(3)"),
               (14 * MS, 16 * MS, "jit_step(3)")]
    spans = [(0, 12 * MS, "sched.step"),
             (1 * MS, 4 * MS, "engine.prefill_request"),
             (13 * MS, 17 * MS, "sched.step")]
    reduced = T.Reduced(ops=sorted(ops), modules=modules, spans=spans, t0=0,
                        t1=20 * MS)
    host0 = 100.0
    ticks = [(host0, host0 + 0.012), (host0 + 0.013, host0 + 0.017)]
    calls = [(host0 + 0.0045, 1, [(7, 0)]),               # first token of 7
             (host0 + 0.011, 4, [(7, 1), (3, 5)]),        # step 1: two rows
             (host0 + 0.0165, 4, [(7, 2), (3, 6)])]       # step 2
    return T.Context(trace=reduced, model=dense, arch=ARCH,
                     serving={"max_live": 4}, peaks=PEAKS,
                     prompt_len={7: 64, 3: 128},
                     calls=calls, ticks=ticks,
                     host_window=(host0 - 0.001, host0 + 0.02))


def test_busy_union_idle_gaps_and_breakdown():
    r = _context().trace
    # Busy: [1,4) [5,6) [7,10) [14,16) ms = 9 ms of a 20 ms window.
    assert T.busy_s(r) == pytest.approx(0.009)
    assert T.idle_gaps(r)[0] == (0, 1 * MS)
    b = T.breakdown(r)
    # The loop around step 1's ops holds them: it is not counted again.
    assert b["device_ops"][0] == ["closed_call.58 bf16[4,8192]", 0.004]
    assert not any(k.startswith("while") for k, _ in b["device_ops"])
    # Gaps 10-14 and 16-20 ms fall (at their middle) outside any tick;
    # 0-1, 4-5 and 6-7 ms inside the first tick.
    assert b["idle_gaps"] == [["host outside the serving step", 0.004]] * 2 \
        + [["sched.step", 0.001]] * 3


def test_programs_are_told_apart_by_tick_structure():
    ctx = _context()
    steps = ctx.step_modules()
    assert [m[2] for m, _ in steps] == ["jit_step(3)", "jit_step(3)"]
    assert steps[0][1] == [(7, 1), (3, 5)]
    pre = ctx.prefill_modules()
    assert pre == [((1 * MS, 4 * MS, "jit__lambda(1)"), 64)]
    assert ctx.gemm_time_s([m for m, _ in steps]) == pytest.approx(0.004)
    # The prefill's GEMM time holds the staging of its weight (1-1.5 ms).
    assert ctx.gemm_time_s([m for m, _ in pre]) == pytest.approx(0.002)
    assert T.containers(ctx.trace.ops) == {ctx.trace.ops.index(
        (7 * MS, 10 * MS, LOOP, ""))}


def test_readers_on_the_known_trace():
    ctx = _context()
    read = {n: metric_reader(n).read(ctx) for n in READERS}
    # Tick 1: 12 ms with 3+1+3 = 7 ms busy; tick 2: 4 ms with 2 ms busy.
    assert read["host_ms_per_tick"] == pytest.approx((5 + 2) / 2)
    assert read["device_idle_share"] == pytest.approx(55.0)
    positions = [64 + 1 - 1, 128 + 5 - 1, 64 + 2 - 1, 128 + 6 - 1]
    ops = sum(dense.decode_token_flops(ARCH, p) for p in positions)
    assert read["mfu.decode"] == pytest.approx(100 * ops / (0.005 * 197e12))
    assert read["mfu.prefill"] == pytest.approx(
        100 * dense.prefill_flops(ARCH, 64) / (0.003 * 197e12))
    ideal = flops.step_gemm_ideal_s(dense.weight_gemms(ARCH, 4), 197e12, 819e9)
    assert read["gemm_roofline.decode"] == pytest.approx(100 * 2 * ideal / 0.004)
    ideal = flops.step_gemm_ideal_s(dense.weight_gemms(ARCH, 64, head_rows=1),
                                    197e12, 819e9)
    assert read["gemm_roofline.prefill"] == pytest.approx(100 * ideal / 0.002)


def test_reduced_trace_round_trips_through_json():
    r = _context().trace
    assert T.Reduced.from_json(r.to_json()) == r


def _recorded():
    spec = cell.load(ROOT, "olmo1b-prefill")
    return T.load_context(os.path.join(HERE, "data",
                                       "olmo1b-prefill.trace.json.gz"),
                          spec.model, spec.arch, spec.serving,
                          peaks("TPU v5 lite"))


def test_readers_on_a_recorded_chip_trace():
    """Two ticks of olmo1b-prefill on a TPU v5e, each admitting a prompt of
    1920 tokens and then running the batched step."""
    ctx = _recorded()
    steps = ctx.step_modules()
    assert len(steps) == 2
    assert [n for _, n in ctx.prefill_modules()] == [1920, 1920]
    shapes = ctx.gemm_shapes()
    for m, _ in steps + ctx.prefill_modules():
        ops = ctx.program_ops(m)
        # Seven weight GEMMs per layer and the LM head, each a kernel.
        assert sum(T.gemm_weight_operand(o, shapes) is not None
                   for o in ops) == 7 * 16 + 1
    read = {n: metric_reader(n).read(ctx) for n in READERS}
    for name in READERS:
        assert read[name] is not None and read[name] > 0, name
    for name in ("mfu.decode", "mfu.prefill", "gemm_roofline.decode",
                 "gemm_roofline.prefill", "device_idle_share"):
        assert read[name] < 100, (name, read[name])
    assert read["gemm_roofline.decode"] == pytest.approx(72.7427, abs=1e-3)
    assert read["gemm_roofline.prefill"] == pytest.approx(77.9251, abs=1e-3)


def test_gemm_kernels_alone_leave_out_their_weights_fetch():
    """Inside the layer loop each kernel reads weight tiles another op has
    already brought on chip: the kernels' own time is less than the bytes
    of the weights need at the HBM rate, so it is not the GEMM's time."""
    ctx = _recorded()
    shapes = ctx.gemm_shapes()
    step = ctx.step_modules()[0][0]
    ops = ctx.program_ops(step)
    kernels_s = sum(o[1] - o[0] for o in ops
                    if T.gemm_weight_operand(o, shapes) is not None) / 1e9
    ideal = flops.step_gemm_ideal_s(ctx.model.weight_gemms(ctx.arch, 8),
                                    197e12, 819e9)
    assert kernels_s < ideal < ctx.gemm_time_s([step])
