"""Request-stream front-end: soak (conservation under every fault site),
per-request bitwise fault isolation, retry/backoff, deadlines, typed
shedding, and the bounded thread-safe registries.

Run plain (no ``REPRO_FAULT``) the soak asserts the healthy-path
invariants. The CI fault matrix re-runs this file with ``REPRO_FAULT`` set
to each serving site (``engine_step`` / ``sample`` / ``admission``) armed
for the WHOLE process, and the same soak then asserts the matching
degradation contract — the conservation invariant (every offered request
ends exactly once: completed, evicted, deadline-missed, or shed; no losses,
no duplicates) holds in every column. Targeted nth-hit tests disarm the
process-level site first (monkeypatch) and arm their own via
``faults.inject``.
"""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import health
from repro.models import build
from repro.serve import (Engine, Overloaded, Request, RequestResult,
                         ServeConfig, StreamConfig, StreamFrontend,
                         VirtualClock)
from repro.serve.frontend import RETRYABLE_CLASSES
from repro.testing import faults

pytestmark = []


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(reduced_config("olmo-1b"),
                              compute_dtype="float32", capacity_factor=16.0)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # temperature > 0: the bitwise-isolation claims must hold for SAMPLED
    # streams (greedy would hide a broken key derivation).
    return Engine(model, params, ServeConfig(max_len=32, temperature=0.7,
                                             seed=3))


@pytest.fixture(autouse=True)
def _isolate():
    faults.reset()
    health.clear_serve()
    health.clear_health()
    yield
    faults.reset()
    health.clear_serve()
    health.clear_health()


@pytest.fixture
def no_fault(monkeypatch):
    """Disarm any process-level REPRO_FAULT (targeted tests arm their own
    site via ``faults.inject``) and the numerics guard."""
    monkeypatch.delenv(faults.ENV_FAULT, raising=False)
    monkeypatch.delenv(health.ENV_NUMERICS_GUARD, raising=False)
    faults.reset()


def _requests(n, *, seed=0, lengths=(4, 6, 8), budgets=(2, 3, 4),
              deadline_s=None):
    r = np.random.default_rng(seed)
    vocab = 64
    return [Request(request_id=i,
                    tokens=r.integers(0, vocab, r.choice(lengths))
                    .astype(np.int32),
                    max_new_tokens=int(r.choice(budgets)),
                    deadline_s=deadline_s)
            for i in range(n)]


def _frontend(engine, **kw):
    clock = VirtualClock()
    cfg = StreamConfig(**{"queue_capacity": 8, "max_live": 2, **kw})
    return StreamFrontend(engine, cfg, clock=clock, sleep=clock.sleep), clock


def _serve_all(engine, reqs, **kw):
    fe, _ = _frontend(engine, **kw)
    for r in reqs:
        fe.submit(r)
    fe.drain()
    return fe


def _assert_conservation(fe, n_offered):
    c = fe.stats()
    assert c["offered"] == n_offered
    assert c["offered"] == c["admitted"] + c["shed"]
    assert c["admitted"] == (c["completed"] + c["evicted"]
                             + c["deadline_miss"])
    assert c["queued"] == 0 and c["live"] == 0
    # exactly one terminal result per offered request, no duplicates
    assert len(fe.results) == n_offered
    assert all(r.status in health.TERMINAL_STATES
               for r in fe.results.values())


# ---------------------------------------------------------------------------
# Soak: ~100 Poisson-arrival requests under whatever site the matrix armed
# ---------------------------------------------------------------------------

def test_soak_poisson_stream_conservation(engine, monkeypatch):
    site, _ = faults.active()   # hard error on a typo'd REPRO_FAULT
    # The guard is part of the serving posture under test: with the
    # ``sample`` site armed it turns silent NaN logits into evictions.
    monkeypatch.setenv(health.ENV_NUMERICS_GUARD, "1")
    n = 100
    reqs = _requests(n, seed=1)
    gaps = np.random.default_rng(2).exponential(scale=0.35, size=n)
    schedule = list(zip(np.cumsum(gaps), reqs))   # Poisson arrivals
    clock = VirtualClock()
    fe = StreamFrontend(
        engine, StreamConfig(queue_capacity=12, max_live=4, max_retries=2,
                             backoff_base_s=0.001, backoff_cap_s=0.004),
        clock=clock, sleep=clock.sleep)
    results = fe.run(schedule, tick_s=1.0)

    _assert_conservation(fe, n)
    assert set(results) == {r.request_id for r in reqs}
    c = fe.stats()
    if site is None:
        # overloaded healthy stream: both completions and typed sheds,
        # nothing evicted
        assert c["completed"] > 0 and c["shed"] > 0
        assert c["evicted"] == 0
        for r in results.values():
            if r.status == "shed":
                assert isinstance(r, Overloaded)
            else:
                assert r.status == "completed"
                assert len(r.tokens) > 0
    elif site == "engine_step":
        # every step of every request fails: retries exhaust, everything
        # admitted is evicted — and the eviction is RECORDED, not lost
        assert c["completed"] == 0
        assert c["evicted"] == c["admitted"] > 0
        assert c["retries"] >= c["evicted"] * 2   # capped retry per step
    elif site == "sample":
        # every sampling step sees NaN logits; the guard evicts each
        # request at its first step
        assert c["completed"] == 0
        assert c["evicted"] == c["admitted"] > 0
        report = engine.serve_report()
        causes = [r["events"][-1]["detail"]
                  for r in report["requests"].values()
                  if r["status"] == "evicted"]
        assert causes and all(d.startswith("numerics") for d in causes)
    elif site == "admission":
        # the admission path itself fails: everything is shed with the
        # typed Overloaded result, nothing is silently dropped
        assert c["admitted"] == 0 and c["shed"] == n
        assert all(isinstance(r, Overloaded) for r in results.values())
    # whatever happened is visible through the engine's serve report
    report = engine.serve_report()
    assert report["counters"] == {k: c[k] for k in report["counters"]}


# ---------------------------------------------------------------------------
# Targeted nth-hit behavior (process-level site disarmed)
# ---------------------------------------------------------------------------

def test_single_step_fault_is_retried_bitwise(engine, no_fault):
    reqs = _requests(6, seed=3)
    base = _serve_all(engine, reqs)
    assert all(r.status == "completed" for r in base.results.values())

    health.clear_serve()
    with faults.inject("engine_step", nth=4):
        fe = _serve_all(engine, _requests(6, seed=3), max_retries=2)
    c = fe.stats()
    assert c["completed"] == 6 and c["evicted"] == 0 and c["retries"] == 1
    for rid, r in base.results.items():
        np.testing.assert_array_equal(fe.results[rid].tokens, r.tokens)
    # the retry (with its backoff) is on the request's lifecycle record
    retried = [rec for rec in engine.serve_report()["requests"].values()
               if rec["retries"]]
    assert len(retried) == 1
    ev = [e for e in retried[0]["events"] if e["event"] == "retry"]
    assert ev and ev[0]["detail"] in RETRYABLE_CLASSES
    assert ev[0]["backoff_s"] > 0


def test_step_fault_eviction_isolates_survivors_bitwise(engine, no_fault):
    """The acceptance-criterion proof, runtime-class variant: one faulted
    request is evicted, every survivor's output is bitwise identical to the
    fault-free run."""
    reqs = _requests(6, seed=3)
    base = _serve_all(engine, reqs)
    health.clear_serve()
    with faults.inject("engine_step", nth=7):
        fe = _serve_all(engine, _requests(6, seed=3), max_retries=0)
    evicted = [rid for rid, r in fe.results.items() if r.status == "evicted"]
    assert len(evicted) == 1
    c = fe.stats()
    assert c["completed"] == 5 and c["evicted"] == 1
    for rid, r in base.results.items():
        if rid in evicted:
            continue
        np.testing.assert_array_equal(fe.results[rid].tokens, r.tokens)
    # partial prefix of the evicted stream still matches the healthy run
    partial = fe.results[evicted[0]].tokens
    np.testing.assert_array_equal(
        partial, base.results[evicted[0]].tokens[:len(partial)])


def test_numerics_guard_evicts_poisoned_request_bitwise(engine, no_fault,
                                                        monkeypatch):
    """The acceptance-criterion proof, numerics variant: NaN logits under
    REPRO_NUMERICS_GUARD evict exactly the poisoned request — no retry —
    and survivors are bitwise identical to the undisturbed run."""
    reqs = _requests(6, seed=3)
    base = _serve_all(engine, reqs)
    health.clear_serve()
    monkeypatch.setenv(health.ENV_NUMERICS_GUARD, "1")
    with faults.inject("sample", nth=5):
        fe = _serve_all(engine, _requests(6, seed=3), max_retries=2)
    evicted = [rid for rid, r in fe.results.items() if r.status == "evicted"]
    assert len(evicted) == 1
    c = fe.stats()
    assert c["evicted"] == 1 and c["completed"] == 5
    assert c["retries"] == 0        # numerics is never retried
    assert fe.results[evicted[0]].detail.startswith("numerics")
    for rid, r in base.results.items():
        if rid not in evicted:
            np.testing.assert_array_equal(fe.results[rid].tokens, r.tokens)


def test_without_guard_poisoned_logits_complete_silently(engine, no_fault):
    """The guard is what turns corruption into an eviction: disarmed, the
    poisoned request 'completes' — the motivation for REPRO_NUMERICS_GUARD
    in the serving posture."""
    with faults.inject("sample", nth=5):
        fe = _serve_all(engine, _requests(4, seed=3))
    assert all(r.status == "completed" for r in fe.results.values())


def test_admission_fault_sheds_typed_not_dropped(engine, no_fault):
    reqs = _requests(4, seed=5)
    with faults.inject("admission", nth=2):
        fe, _ = _frontend(engine)
        outcomes = [fe.submit(r) for r in reqs]
        fe.drain()
    assert outcomes[0] is None and outcomes[2] is None
    assert isinstance(outcomes[1], Overloaded)
    assert "admission failure (resource)" in outcomes[1].detail
    _assert_conservation(fe, 4)
    assert fe.stats()["completed"] == 3


# ---------------------------------------------------------------------------
# Backpressure, deadlines, budgets
# ---------------------------------------------------------------------------

def test_queue_overflow_rejects_newest_with_typed_overloaded(engine,
                                                             no_fault):
    reqs = _requests(7, seed=6)
    fe, _ = _frontend(engine, queue_capacity=3, max_live=1)
    outcomes = [fe.submit(r) for r in reqs]
    # reject-newest: the first capacity-many are admitted, the rest shed
    assert [o is None for o in outcomes] == [True] * 3 + [False] * 4
    for o in outcomes[3:]:
        assert isinstance(o, Overloaded) and o.status == "shed"
        assert o.queue_depth == 3 and "queue full" in o.detail
    fe.drain()
    _assert_conservation(fe, 7)
    assert fe.stats() == {**fe.stats(), "completed": 3, "shed": 4}


def test_deadline_missed_mid_stream_returns_partial_tokens(engine, no_fault):
    req = Request(request_id=0, tokens=np.arange(1, 5, dtype=np.int32),
                  max_new_tokens=10, deadline_s=3.5)
    fe, clock = _frontend(engine)
    fe.submit(req)
    results = {}
    while not results:
        results.update(fe.step())
        clock.sleep(1.0)          # each tick costs 1 virtual second
    res = results[0]
    assert res.status == "deadline_miss"
    assert 0 < len(res.tokens) < 10
    assert res.latency_s > 3.5
    rec = engine.serve_report()["requests"]["0"]
    assert rec["status"] == "deadline_miss"
    assert rec["events"][-1]["event"] == "deadline_miss"


def test_token_budget_completes_exactly(engine, no_fault):
    fe = _serve_all(engine, [Request(request_id=9,
                                     tokens=np.arange(1, 7, dtype=np.int32),
                                     max_new_tokens=5)])
    res = fe.results[9]
    assert res.status == "completed" and len(res.tokens) == 5


def test_retry_backoff_is_capped_exponential(engine, no_fault):
    sleeps = []
    fe = StreamFrontend(
        engine,
        StreamConfig(max_retries=4, backoff_base_s=0.01, backoff_cap_s=0.04),
        clock=lambda: 0.0, sleep=sleeps.append)
    fe.submit(Request(request_id=0, tokens=np.arange(1, 5, dtype=np.int32),
                      max_new_tokens=2))
    with faults.inject("engine_step"):     # every hit fails
        fe.drain()
    assert fe.results[0].status == "evicted"
    assert sleeps == [0.01, 0.02, 0.04, 0.04]


def test_duplicate_request_id_is_an_error(engine, no_fault):
    fe, _ = _frontend(engine)
    fe.submit(Request(request_id=1, tokens=np.arange(1, 4, dtype=np.int32)))
    with pytest.raises(ValueError, match="duplicate"):
        fe.submit(Request(request_id=1,
                          tokens=np.arange(1, 4, dtype=np.int32)))
    fe.drain()


# ---------------------------------------------------------------------------
# Per-request sampling determinism (the isolation substrate)
# ---------------------------------------------------------------------------

def test_request_stream_independent_of_neighbors(engine, no_fault):
    """A request's sampled tokens depend only on (params, prompt,
    request_id): serving it alone or among neighbors is bitwise identical
    — the fold_in(request_id) key derivation."""
    reqs = _requests(5, seed=7)
    together = _serve_all(engine, reqs)
    health.clear_serve()
    alone = _serve_all(engine, [_requests(5, seed=7)[2]])
    np.testing.assert_array_equal(alone.results[2].tokens,
                                  together.results[2].tokens)


def test_generate_request_ids_reseed_rows(engine, no_fault):
    """Engine.generate derives per-row keys from request_ids: changing a
    row's id changes its stream; the default ids are stable."""
    prompt = np.arange(1, 7, dtype=np.int32)[None].repeat(2, axis=0)
    a = engine.generate({"tokens": prompt}, max_new_tokens=4)
    b = engine.generate({"tokens": prompt}, max_new_tokens=4)
    np.testing.assert_array_equal(a, b)
    c = engine.generate({"tokens": prompt}, max_new_tokens=4,
                        request_ids=[100, 101])
    assert not np.array_equal(a, c)


# ---------------------------------------------------------------------------
# Bounded, thread-safe registries
# ---------------------------------------------------------------------------

def test_health_registry_ring_bound_counts_drops():
    reg = health.HealthRegistry(max_records=2)
    for i in range(4):
        reg.record(f"spec{i}", "low", "runtime", "ref")
    assert len(reg) == 2 and reg.dropped == 2
    # surviving rows keep counting; the bound never corrupts them
    reg.record("spec3", "low", "runtime", "ref")
    assert [r.count for r in reg.records()
            if r.spec == "spec3"] == [2]
    reg.clear()
    assert len(reg) == 0 and reg.dropped == 0


def test_serve_registry_ring_prefers_dropping_terminal_rows():
    reg = health.ServeRegistry(max_records=3)
    for i in range(3):
        reg.admitted(i)
    reg.finalize(0, "completed", step=1, tokens_emitted=1, latency_s=0.0)
    reg.admitted(3)   # over bound: terminal row 0 dropped, live rows kept
    assert reg.dropped == 1
    report = reg.report()
    assert set(report["requests"]) == {"1", "2", "3"}
    # counters are monotonic and unaffected by the ring
    assert report["counters"]["admitted"] == 4
    assert report["counters"]["completed"] == 1


def test_registries_are_thread_safe():
    reg = health.ServeRegistry(max_records=64)
    hreg = health.HealthRegistry(max_records=8)

    def work(base):
        for i in range(200):
            rid = base * 1000 + i
            reg.admitted(rid)
            reg.retry(rid, 0, "runtime", 0.001)
            reg.finalize(rid, "completed", step=1, tokens_emitted=1,
                         latency_s=0.0)
            hreg.record(f"spec{base}_{i % 16}", "low", "runtime", "ref")

    threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    c = reg.counters()
    assert c["admitted"] == c["completed"] == c["retries"] == 800
    assert len(reg) <= 64
    assert len(hreg) <= 8
    total = sum(r.count for r in hreg.records()) + hreg.dropped
    assert total >= 8   # no lost updates on surviving rows


def test_serve_report_schema(engine, no_fault):
    _serve_all(engine, _requests(2, seed=8))
    report = engine.serve_report()
    assert set(report) == {"counters", "dropped_records", "requests",
                           "dispatch_health"}
    assert set(report["counters"]) == {"offered", "admitted", "shed",
                                       "completed", "evicted",
                                       "deadline_miss", "retries",
                                       "preempted", "resumed"}
    rec = next(iter(report["requests"].values()))
    assert set(rec) == {"status", "retries", "tokens_emitted", "latency_s",
                        "queued_t", "admit_t", "first_token_t", "events"}
    assert rec["events"][0]["event"] == "admitted"
    assert rec["events"][-1]["event"] == "completed"
