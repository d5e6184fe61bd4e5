"""What the program says about itself in a traced run (``chipbench.program``)
and the two readers built on it, ``kv_view_ms.decode`` and
``queue_wait_p95_ms``: on an ``.xplane.pb`` written here whose every number
is known, on two ticks of each cell recorded on a TPU v5e, and, unchanged,
the six readers on the trace recorded before the program had spans."""
import gzip
import json
import os

import pytest

from chipbench import cell, program as P, trace as T
from chipbench.arch import dense
from chipbench.cell import metric_reader
from chipbench.peaks import peaks

MS = 1_000_000  # ns
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("host_ms_per_tick", "device_idle_share", "mfu.decode",
           "mfu.prefill", "gemm_roofline.decode", "gemm_roofline.prefill")
# The host spans of one tick, in the order they open.
TICK_PHASES = ("serve.admit", "serve.kv_grow", "serve.step_dispatch",
               "serve.token_wait", "serve.commit")
ADMIT_PHASES = ("serve.prefill", "serve.token_wait", "serve.commit")


def _gemm(name, rows):
    """A packed GEMM kernel's HLO text: ``[rows, 2048] @ [2048, 8192]`` over
    a weight of 8 x 1 tiles of 2048 x 1024."""
    return (f"%{name} = bf16[{rows},8192]{{1,0}} custom-call(bf16[{rows},"
            f"2048]{{1,0}} %a.1, bf16[8,1,2048,1024]{{3,2,1,0}} %p.7), "
            f'custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# A known trace: one tick whose batched step [30, 40) ms runs over a KV pool
# of 1 layer, 2 rows x 4 positions in blocks of 2, 1 head of 8 (pool: 5
# blocks of 2 positions; dense view: 2 x 4 positions)
# ---------------------------------------------------------------------------

ARCH = {"hidden_size": 2048, "intermediate_size": 8192, "head_dim": 8,
        "num_attention_heads": 16, "num_key_value_heads": 1,
        "num_hidden_layers": 1, "vocab_size": 50304}
SERVING = {"max_live": 2, "max_len": 4, "block_size": 2}
# (start ms, end ms, HLO text, the op's own scope): a gather loop scoped
# kv_gather whose body ops carry no scope; the scan's stacking of the
# blended cache (the view's size, the scan's scope); a pool copy (the
# pool's size, no scope); attention; the scatter; a GEMM kernel.
STEP_OPS = [
    (30, 33, "%while.13 = (s32[]) while(s32[] %t)",
     "jit(step)/kv_gather/gather"),
    (30, 31, "%dus.4 = bf16[4,1,2,1,8]{} fusion()", ""),
    (31, 32, "%dus.4 = bf16[4,1,2,1,8]{} fusion()", ""),
    (33, 35, "%add_dus.3 = (bf16[1,2,4,1,8]{}, bf16[1,2,4,1,8]{}) fusion()",
     "jit(step)/while/body/dynamic_update_slice"),
    (35, 36, "%mr.2 = f32[2,4,1]{} fusion()",
     "jit(step)/while/body/closed_call/attention/dot_general"),
    (36, 37, "%copy.73 = bf16[1,5,2,1,8]{} copy()", ""),
    (37, 38, "%scatter.5 = bf16[1,10,1,8]{} fusion()",
     "jit(step)/kv_scatter/scatter"),
    (38, 39, _gemm("gemm_packed_fused_a.58", 2),
     "jit(step)/while/body/closed_call/mlp/gemm_packed_fused_a/pallas_call"),
]
HOST_SPANS = [(29, 41, "sched.step"), (29, 41, "serve.tick"),
              (29, 30, "serve.step_dispatch"), (30, 40, "serve.token_wait"),
              (40, 41, "serve.commit")]


def _pb(field, value):
    """One protobuf field: an int as a varint, bytes or text
    length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(data)) + data


def _entry(key, msg):
    return _pb(1, key) + _pb(2, msg)


def _stat(mid, text=None, ref=None):
    return _pb(1, mid) + (_pb(5, text) if text is not None else _pb(7, ref))


def _xspace(ops=STEP_OPS, host=HOST_SPANS, own_stat_of=None) -> bytes:
    """An XSpace laid out as the TPU profiler writes one: each op's scope
    is the stat ``tf_op`` of its event metadata (the first op's by
    reference to a stat metadata name, the others as text), or, for op
    ``own_stat_of``, a stat of the event itself. Times in ms."""
    names = sorted({o[2] for o in ops})
    stat_md = b"".join(_pb(5, _entry(k, _pb(1, k) + _pb(2, n))) for k, n in
                       [(1, "tf_op"), (2, ops[0][3]), (3, "hlo_category")])
    scopes = {}
    for i, (_, _, name, scope) in enumerate(ops):
        if i != own_stat_of:
            scopes.setdefault(name, scope)
    md = []
    for k, name in enumerate(names, start=10):
        scope = scopes.get(name, "")
        st = [_stat(3, text="fusion")]
        if scope == ops[0][3]:
            st.append(_stat(1, ref=2))
        elif scope:
            st.append(_stat(1, text=scope))
        md.append(_pb(4, _entry(k, _pb(1, k) + _pb(2, name)
                                + b"".join(_pb(5, s) for s in st))))
    ids = {n: k for k, n in enumerate(names, start=10)}
    events = b"".join(
        _pb(4, _pb(1, ids[name]) + _pb(2, s * MS * 1000)
            + _pb(3, (e - s) * MS * 1000)
            + (_pb(4, _stat(1, text=scope)) if i == own_stat_of else b""))
        for i, (s, e, name, scope) in enumerate(ops))
    mods = _pb(4, _entry(1, _pb(1, 1) + _pb(2, "jit_step(3)")))
    tpu = (_pb(1, 1) + _pb(2, "/device:TPU:0")
           + _pb(3, _pb(1, 2) + _pb(2, "Steps") + _pb(3, 0))
           + _pb(3, _pb(1, 1) + _pb(2, "XLA Ops") + _pb(3, 0) + events)
           + _pb(3, _pb(1, 3) + _pb(2, "XLA Modules") + _pb(3, 0) + _pb(
               4, _pb(1, 1) + _pb(2, 30 * MS * 1000) + _pb(3, 10 * MS * 1000)))
           + b"".join(md) + mods + stat_md)
    host_names = sorted({h[2] for h in host})
    hid = {n: k for k, n in enumerate(host_names, start=1)}
    line = _pb(1, 1) + _pb(2, "python") + _pb(3, 0) + b"".join(
        _pb(4, _pb(1, hid[n]) + _pb(2, s * MS * 1000)
            + _pb(3, (e - s) * MS * 1000)) for s, e, n in host)
    host_plane = _pb(1, 2) + _pb(2, "/host:CPU") + _pb(3, line) + b"".join(
        _pb(4, _entry(k, _pb(1, k) + _pb(2, n))) for n, k in hid.items())
    return _pb(1, host_plane) + _pb(1, tpu)


def _known(tmp_path, **kw):
    """The known trace written as an ``.xplane.pb``, and its ``Context``."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(**kw))
    reduced = T.reduce_profile(ProfileData.from_file(str(path)))
    ctx = T.Context(trace=reduced, model=dense, arch=ARCH, serving=SERVING,
                    peaks=PEAKS, prompt_len={7: 2},
                    calls=[(1.011, 2, [(7, 1)])], ticks=[(1.0, 1.012)],
                    host_window=(0.9, 1.1))
    return str(path), ctx


def test_op_scopes_are_read_from_the_event_metadata(tmp_path):
    path, ctx = _known(tmp_path, own_stat_of=4)
    scopes = P.op_scopes(path)
    assert [n for n, _ in scopes] == [o[2] for o in STEP_OPS]
    assert [s for _, s in scopes] == [o[3] for o in STEP_OPS]
    ops, prog = P.read_xplane(path)
    assert ops == [o[:3] for o in ctx.trace.ops]
    # In the reduced trace's order (the loop sorts before its first body op).
    by_op = {(s * MS, e * MS, n): sc for s, e, n, sc in STEP_OPS}
    assert prog.op_scopes == [by_op[o[:3]] for o in ctx.trace.ops]
    assert prog.spans == [(s * MS, e * MS, n) for s, e, n in
                          sorted(HOST_SPANS) if n.startswith("serve.")]
    assert P.load(ctx, str(tmp_path)).op_scopes == prog.op_scopes
    # Another run's ops: the trace is not this context's.
    ctx.trace.ops = ctx.trace.ops[1:]
    assert P.load(ctx, str(tmp_path)) is None
    assert P.load(ctx, str(tmp_path / "none")) is None


def test_kv_view_and_the_step_scopes(tmp_path, capsys):
    path, ctx = _known(tmp_path)
    assert dense.kv_elements(ARCH, SERVING) == (5 * 2 * 8, 2 * 4 * 8)
    prog = P.load(ctx, str(tmp_path))
    ms = P.step_scope_ms(ctx, prog.op_scopes)
    # The loop's body takes its scope (2 ms; the loop itself is left out);
    # the view's stacking (2 ms) and the pool copy (1 ms) count by size.
    assert ms["kv_gather"] == pytest.approx(2.0)
    assert ms["kv_scatter"] == pytest.approx(1.0)
    assert ms["kv_write"] == 0 and ms["lm_head"] == 0
    assert ms["attention"] == pytest.approx(1.0)
    assert ms["mlp"] == pytest.approx(1.0)
    assert ms["kv_view"] == pytest.approx(2 + 2 + 1 + 1)
    read = metric_reader("kv_view_ms.decode").read
    assert read(ctx, str(tmp_path)) == pytest.approx(6.0)
    printed = capsys.readouterr().out
    assert "[trace] batched step: 1 traced, device 10.000000 ms" in printed
    assert "under a phase span 100.000%" in printed
    # A program that names no KV scope (the one before these scopes).
    path, bare = _known(tmp_path, ops=[o[:3] + ("",) for o in STEP_OPS])
    assert P.step_scope_ms(bare, [""] * len(STEP_OPS)) is None
    assert read(bare, str(tmp_path)) is None


def test_idle_inside_a_tick_is_given_to_its_phases(tmp_path):
    _, ctx = _known(tmp_path)
    prog = P.load(ctx, str(tmp_path))
    # Idle in the tick: 29-30 ms under step_dispatch, 39-40 ms under
    # token_wait (the step's last op ends at 39), 40-41 ms under commit.
    assert P.tick_idle_by_phase(ctx.trace, prog.spans) == {
        "serve.step_dispatch": pytest.approx(0.001),
        "serve.token_wait": pytest.approx(0.001),
        "serve.commit": pytest.approx(0.001)}
    spans = [s for s in prog.spans if s[2] != "serve.step_dispatch"]
    spans.append((40 * MS, 40 * MS + MS // 2, "serve.kv_grow"))
    idle = P.tick_idle_by_phase(ctx.trace, spans)
    assert idle["serve.tick"] == pytest.approx(0.001)      # 29-30: no phase
    assert idle["serve.kv_grow"] == pytest.approx(0.0005)  # inside commit


def test_queue_wait_reads_the_program_request_times():
    from repro.core import health
    read = metric_reader("queue_wait_p95_ms").read
    times = {7: {"queued_t": 0.5, "admit_t": 0.75, "first_token_t": 0.8},
             8: {"queued_t": 0.5, "admit_t": 0.5, "first_token_t": 0.6},
             9: {"queued_t": 0.6, "admit_t": None, "first_token_t": None},
             10: {"queued_t": 0.9, "admit_t": 1.5, "first_token_t": 1.6}}
    ctx = T.Context(trace=None, model=dense, arch=ARCH, serving=SERVING,
                    peaks=PEAKS, prompt_len={7: 2, 8: 2, 9: 2, 10: 2},
                    calls=[], ticks=[], host_window=(0, 1))
    # Two requests admitted before the profiler stopped (at 1 s) waited 250
    # and 0 ms; the third never was admitted, the fourth only after.
    assert read(ctx, times) == pytest.approx(250)
    assert read(ctx, {10: times[10]}) is None
    health.clear_serve()
    try:
        for rid, t in times.items():
            health.SERVE.admitted(rid, queued_t=t["queued_t"])
            if t["admit_t"] is not None:
                health.SERVE.live(rid, admit_t=t["admit_t"],
                                  first_token_t=t["first_token_t"])
        health.SERVE.admitted(1 << 30, queued_t=0.0)     # a warm-up request
        assert P.request_times(ctx.prompt_len) == times
        assert read(ctx) == pytest.approx(250)
        health.clear_serve()
        assert read(ctx) is None
    finally:
        health.clear_serve()


# ---------------------------------------------------------------------------
# Recorded on a TPU v5e
# ---------------------------------------------------------------------------

def _recorded(name, stem=""):
    spec = cell.load(ROOT, name)
    return T.load_context(os.path.join(DATA, name + stem + ".trace.json.gz"),
                          spec.model, spec.arch, spec.serving,
                          peaks("TPU v5 lite"))


def _program(name):
    with gzip.open(os.path.join(DATA, name + ".spans.program.json.gz"),
                   "rt") as f:
        d = json.load(f)
    return (P.ProgramTrace(d["op_scopes"], [tuple(s) for s in d["spans"]]),
            {int(r): t for r, t in d["requests"].items()})


def test_the_six_readers_on_the_trace_recorded_before_the_program_spans():
    """Two ticks of olmo1b-prefill recorded before the program had spans
    or scopes: every reader reads what it read then."""
    ctx = _recorded("olmo1b-prefill")
    read = {n: metric_reader(n).read(ctx) for n in READERS}
    assert read == pytest.approx({
        "host_ms_per_tick": 6.4744765, "device_idle_share": 6.630680333782979,
        "mfu.decode": 0.10348758191635013, "mfu.prefill": 58.887586338751994,
        "gemm_roofline.decode": 72.74267824836296,
        "gemm_roofline.prefill": 77.9251306313882}, rel=1e-12)


RECORDED = {"olmo1b-decode": {"kv_view_ms.decode": 32.8230665},
            "olmo1b-prefill": {"kv_view_ms.decode": 32.833122,
                               "queue_wait_p95_ms": 32.751856999993834}}


def _nested(outer, spans):
    return [s for s in spans if s is not outer and outer[0] <= s[0]
            and s[1] <= outer[1]]


def _top_level(spans):
    return [s for s in spans if not any(
        o is not s and o[0] <= s[0] and s[1] <= o[1] for o in spans)]


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_ticks_spans_scopes_and_request_times(name):
    ctx = _recorded(name, ".spans")
    prog, times = _program(name)
    assert len(prog.op_scopes) == len(ctx.trace.ops)
    ticks = [s for s in prog.spans if s[2] == "serve.tick"]
    assert len(ticks) == 2
    # Each tick lies in the benchmark's sched.step, and holds its phases in
    # order; an admission holds its prefill and its first token.
    inner = [s for s in prog.spans if s[2] != "serve.tick"]
    assert sum(len(_nested(t, inner)) for t in ticks) == len(inner)
    for tick in ticks:
        assert any(s[0] <= tick[0] and tick[1] <= s[1]
                   for s in ctx.trace.spans if s[2] == "sched.step")
        top = _top_level(_nested(tick, inner))
        order = [TICK_PHASES.index(s[2]) for s in top]
        assert order == sorted(order)
        assert [s[2] for s in top if s[2] != "serve.admit"] == \
            list(TICK_PHASES[1:])
        for adm in (s for s in top if s[2] == "serve.admit"):
            assert [s[2] for s in _nested(adm, inner)] == list(ADMIT_PHASES)
    # Every GEMM kernel carries its pallas_call's name.
    shapes = ctx.gemm_shapes()
    gemms = [o for o in ctx.trace.ops
             if T.gemm_weight_operand(o, shapes) is not None]
    assert gemms and all(T.short_name(o[2]).startswith("gemm_packed_fused_a.")
                         for o in gemms)
    ms = P.step_scope_ms(ctx, prog.op_scopes)
    steps = [m for m, _ in ctx.step_modules()]
    assert 0 < ms["kv_view"] < min(m[1] - m[0] for m in steps) / 1e6
    pins = RECORDED[name]
    assert ms["kv_view"] == pytest.approx(pins["kv_view_ms.decode"], rel=1e-9)
    if "queue_wait_p95_ms" in pins:
        ctx_q = T.Context(trace=ctx.trace, model=ctx.model, arch=ctx.arch,
                          serving=ctx.serving,
                          peaks=ctx.peaks, prompt_len=ctx.prompt_len,
                          calls=ctx.calls, ticks=ctx.ticks,
                          host_window=ctx.host_window)
        got = metric_reader("queue_wait_p95_ms").read(ctx_q, times)
        assert got == pytest.approx(pins["queue_wait_p95_ms"], rel=1e-9)
    for t in times.values():
        if t["admit_t"] is not None:
            assert t["queued_t"] <= t["admit_t"] <= t["first_token_t"]
