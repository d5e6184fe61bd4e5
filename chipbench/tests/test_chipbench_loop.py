"""The serving loop on a fake scheduler and clock (``fakeloop``): the
closed loop keeps its backlog and stops at the window's close, the open
loop is the one every earlier measurement ran, and the window's
``tokens_per_s`` follows the program's speed in a closed loop and the
schedule in an open one."""
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import generator, serve, window
from chipbench.tests.fakeloop import fake_server

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _closed(step_s, seconds=30.0, max_live=16, backlog=4, seed=3):
    mix = _mix("backlog-olmo1b")
    arr = generator.arrivals(mix, seed, seconds, 50304)
    server, clock = fake_server(max_live, step_s)
    wlog = serve.run_window(server, arr, mix["lead_in_s"], seconds,
                            outstanding=max_live + backlog, clock=clock)
    return server.sched, wlog


def test_closed_loop_keeps_max_live_plus_backlog_in_flight():
    sched, wlog = _closed(0.048)
    # Every tick that starts before the window closes finds 16 + 4
    # requests submitted and not finished, and the submissions of a tick
    # top the loop up to exactly that.
    done_at = sorted(max(t for r, _, t in sched.events if r == rid)
                     for rid in sched.results)
    sent_at = [t for _, t in sched.submitted]
    starts = [ts for ts, _ in wlog.ticks if ts < wlog.end]
    assert len(starts) > 500
    for ts in starts:
        sent = np.searchsorted(sent_at, ts, side="right")
        done = np.searchsorted(done_at, ts, side="right")
        assert sent - done == 20, ts
    assert max(n for _, n in sched.in_flight) == 20
    assert wlog.queue_mid == 4


def test_closed_loop_submits_nothing_after_the_window():
    sched, wlog = _closed(0.048)
    assert max(t for _, t in sched.submitted) < wlog.end
    # Everything submitted drained, and the loop stopped there.
    assert set(sched.results) == set(wlog.due)
    assert all(r.status == "completed" for r in sched.results.values())
    assert wlog.drained_at >= wlog.end
    assert sched.events[-1][2] == wlog.drained_at


def test_closed_loop_due_and_attempted_are_the_requests_submitted():
    sched, wlog = _closed(0.048)
    assert list(wlog.due) == [r for r, _ in sched.submitted]
    assert list(wlog.due.values()) == [t for _, t in sched.submitted]
    # Far fewer than the schedule holds: the rest were never sent.
    assert 20 < len(wlog.due) < generator.CLOSED_LOOP_REQUESTS
    assert list(wlog.due) == list(range(len(wlog.due)))


def test_closed_loop_that_runs_out_of_requests_raises():
    mix = _mix("backlog-olmo1b")
    arr = generator.arrivals(mix, 3, 30.0, 50304)[:30]
    server, clock = fake_server(16, 0.048)
    with pytest.raises(RuntimeError, match="ran out"):
        serve.run_window(server, arr, mix["lead_in_s"], 30.0, outstanding=20,
                         clock=clock)


@pytest.mark.parametrize("step_s", [0.048, 0.0142])
def test_saturated_closed_loop_reads_max_live_over_step(step_s):
    sched, wlog = _closed(step_s)
    m = window.end_to_end(sched.events, wlog.due, wlog.start, wlog.end)
    assert m["tokens_per_s"] == pytest.approx(16 / step_s, rel=0.01)
    assert m["tpot_ms"] == pytest.approx(1e3 * step_s, rel=0.01)


# ``generator.arrivals`` of each open-loop mix at two seeds over a 30 s
# window, digested as written by the generator every earlier measurement ran.
OPEN_LOOP_DIGESTS = {
    ("decode-heavy-olmo1b", 2 ** 31 + 977): (38, "75a422cf382ec28f"),
    ("decode-heavy-olmo1b", 7): (38, "552b917dea98231d"),
    ("prefill-heavy-olmo1b", 2 ** 31 + 977): (119, "0968572cb99741ca"),
    ("prefill-heavy-olmo1b", 7): (119, "806c417e4893b1c0"),
}


def _digest(arr):
    h = hashlib.sha256()
    for a in arr:
        h.update(np.float64(a.due_s).tobytes())
        h.update(np.int64(a.max_new_tokens).tobytes())
        h.update(np.ascontiguousarray(a.tokens, np.int32).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("mix,seed", sorted(OPEN_LOOP_DIGESTS))
def test_open_loop_arrivals_are_unchanged(mix, seed):
    arr = generator.arrivals(_mix(mix), seed, 30.0, 50304)
    assert (len(arr), _digest(arr)) == OPEN_LOOP_DIGESTS[(mix, seed)]


def test_open_loop_window_log_is_unchanged():
    mix = _mix("decode-heavy-olmo1b")
    arr = generator.arrivals(mix, 5, 30.0, 50304)
    server, clock = fake_server(16, 0.048)
    wlog = serve.run_window(server, arr, mix["lead_in_s"], 30.0, clock=clock,
                            sleep=clock.sleep)
    # As read from the same replay of the loop every earlier measurement used.
    assert (wlog.start, wlog.end) == (120.0, 150.0)
    assert (wlog.queue_mid, wlog.queue_end) == (0, 0)
    assert wlog.drained_at == pytest.approx(179.872)
    assert len(wlog.ticks) == 1664 and len(wlog.due) == 38
    assert sum(wlog.late_s) == pytest.approx(0.9624589879864658)
    assert sorted(wlog.due.items())[:3] == [
        (0, 100.0), (1, pytest.approx(101.0296044405456)),
        (2, pytest.approx(101.1969690907035))]
    assert list(wlog.due) == [a.request_id for a in arr]


# The decode cell's fixed schedule replayed with 16 rows, one token per
# row per step and no prefill cost. On a TPU v5e the program read 254.03
# tokens/s at a 48.4 ms step and 202.88 at 14.2 ms (PERF.md, section 2): the
# open loop's count is the lead-in's backlog spilling into the window, so
# a faster step reads fewer tokens in it.
@pytest.mark.parametrize("step_s,tokens_per_s", [(0.048, 253.2667),
                                                 (0.0142, 202.6667)])
def test_open_loop_decode_replay_reads_the_schedule(step_s, tokens_per_s):
    mix = _mix("decode-heavy-olmo1b")
    arr = generator.arrivals(mix, 1, 30.0, 50304)
    server, clock = fake_server(16, step_s)
    wlog = serve.run_window(server, arr, mix["lead_in_s"], 30.0, clock=clock,
                            sleep=clock.sleep)
    m = window.end_to_end(server.sched.events, wlog.due, wlog.start, wlog.end)
    assert m["tokens_per_s"] == pytest.approx(tokens_per_s, abs=1e-3)
    assert m["tpot_ms"] == pytest.approx(1e3 * step_s, rel=1e-6)
