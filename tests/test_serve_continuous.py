"""Continuous-batching scheduler: bitwise parity with the batch-1 front-end,
paged-KV backpressure (preempt-and-resume, never a crash), blast-radius
bisection, the step watchdog, allocator accounting, and the extended
conservation invariant — plus a property sweep over random arrival
schedules, KV budgets, and fault placements.

Run plain (no ``REPRO_FAULT``) the soak asserts the healthy-path contract
(including real KV exhaustion → preemptions, zero evictions). The CI fault
matrix re-runs this file with ``REPRO_FAULT=kv_alloc`` and
``REPRO_FAULT=batch_step`` armed for the whole process; the same soak then
asserts the matching degradation contract — the EXTENDED conservation
invariant (``admitted == completed + evicted + deadline_miss + open +
preempted_open``) closes in every column. Targeted nth-hit tests disarm the
process-level site first and arm their own via ``faults.inject``.
"""
import dataclasses

import jax
import numpy as np
import pytest

from hypo import HAVE_HYPOTHESIS, given, settings, st

from repro.configs import reduced_config
from repro.core import health
from repro.models import build
from repro.serve import (ContinuousConfig, ContinuousScheduler, Engine,
                         Overloaded, Request, ServeConfig, StreamConfig,
                         StreamFrontend, VirtualClock)
from repro.serve.kv_cache import BlockAllocator, PagedKVCache
from repro.testing import faults

pytestmark = []


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(reduced_config("olmo-1b"),
                              compute_dtype="float32", capacity_factor=16.0)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # temperature > 0: preempt-resume and bisection survivor claims must
    # hold for SAMPLED streams (greedy would hide a broken key derivation).
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


def _requests(n, *, seed=0, lengths=(4, 6, 8), budgets=(2, 3, 4, 6),
              deadline_s=None):
    r = np.random.default_rng(seed)
    return [Request(request_id=i,
                    tokens=r.integers(0, 64, int(r.choice(lengths)))
                    .astype(np.int32),
                    max_new_tokens=int(r.choice(budgets)),
                    deadline_s=deadline_s)
            for i in range(n)]


def _sched(engine, **kw):
    clock = VirtualClock()
    cfg = ContinuousConfig(**{"queue_capacity": 32, "max_live": 3,
                              "block_size": 8, **kw})
    return (ContinuousScheduler(engine, cfg, clock=clock, sleep=clock.sleep),
            clock)


def _serve_all(engine, reqs, **kw):
    cs, _ = _sched(engine, **kw)
    for r in reqs:
        cs.submit(r)
    cs.drain(max_ticks=20_000)
    return cs


def _assert_conservation(cs, n_offered=None):
    """The EXTENDED invariant, closed (quiescent: nothing open/preempted)."""
    s = cs.stats()
    assert s["offered"] == s["admitted"] + s["shed"]
    assert s["admitted"] == (s["completed"] + s["evicted"]
                             + s["deadline_miss"] + s["queued"] + s["live"]
                             + s["preempted_open"])
    assert s["queued"] == 0 and s["live"] == 0 and s["preempted_open"] == 0
    assert s["resumed"] <= s["preempted"]
    if n_offered is not None:
        assert s["offered"] == n_offered
        assert len(cs.results) == n_offered
    # the allocator never leaks: a drained scheduler owns zero blocks
    assert cs.kv.alloc.free_count == cs.kv.alloc.capacity
    assert cs.kv.accounting_consistent()
    return s


def _batch1_reference(engine, reqs):
    """The batch-1 front-end's terminal token streams (the bitwise oracle)."""
    clock = VirtualClock()
    fe = StreamFrontend(engine,
                        StreamConfig(queue_capacity=64, max_live=2),
                        clock=clock, sleep=clock.sleep)
    for r in reqs:
        fe.submit(r)
    fe.drain()
    ref = {rid: res.tokens.copy() for rid, res in fe.results.items()}
    health.clear_serve()   # the oracle run must not pollute the counters
    return ref


# ---------------------------------------------------------------------------
# Allocator / paged-cache units
# ---------------------------------------------------------------------------

def test_allocator_deterministic_lowest_first(no_fault):
    a = BlockAllocator(6)
    assert a.try_alloc(2) == [1, 2]
    assert a.try_alloc(1) == [3]
    a.free([2])
    assert a.try_alloc(2) == [2, 4]   # recycled lowest id first
    assert a.free_count + a.used_count == a.capacity


def test_allocator_exhaustion_is_typed_not_raised(no_fault):
    a = BlockAllocator(2)
    assert a.try_alloc(3) is None     # backpressure, not an exception
    assert a.free_count == 2          # failed alloc takes nothing
    got = a.try_alloc(2)
    assert a.try_alloc(1) is None
    a.free(got)
    assert a.free_count == a.capacity


def test_allocator_double_free_detected(no_fault):
    a = BlockAllocator(2)
    got = a.try_alloc(1)
    a.free(got)
    with pytest.raises(ValueError, match="double free"):
        a.free(got)
    with pytest.raises(ValueError, match="double free"):
        a.free([2])                   # never allocated


def test_kv_alloc_fault_site_fires_in_try_alloc(no_fault):
    a = BlockAllocator(4)
    with faults.inject("kv_alloc", nth=2):
        assert a.try_alloc(1) == [1]
        with pytest.raises(faults.InjectedFault) as ei:
            a.try_alloc(1)
        assert ei.value.failure_class == "resource"
        assert a.free_count == 3      # the injected failure allocated nothing


def test_paged_cache_rejects_unpageable_shapes(engine):
    cfg = engine.model.cfg
    with pytest.raises(ValueError, match="multiple of"):
        PagedKVCache(cfg, max_live=2, max_len=30, block_size=8, num_blocks=8)
    swa = dataclasses.replace(cfg, attention_type="sliding_window",
                              sliding_window=8)
    with pytest.raises(ValueError, match="not pageable"):
        PagedKVCache(swa, max_live=2, max_len=32, block_size=8, num_blocks=8)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_release_scrubs_the_pool_in_place(engine, quantize):
    """Release writes the pool in place: the scrubbed pool replaces the one
    it was given (donated), so releasing several slots in a tick holds one
    pool per leaf, not one per release."""
    kv = PagedKVCache(engine.model.cfg, max_live=2, max_len=32, block_size=8,
                      num_blocks=8, quantize=quantize)
    assert kv.grow(0, 6) and kv.grow(1, 6)
    before = dict(kv.pool)
    kv.release(0)
    kv.release(1)
    assert all(before[name].is_deleted() for name in before)
    assert np.all(np.asarray(kv.pool["k"]) == 0)
    assert kv.alloc.free_count == kv.alloc.capacity


# ---------------------------------------------------------------------------
# Bitwise parity with the batch-1 front-end
# ---------------------------------------------------------------------------

def test_continuous_matches_batch1_bitwise(engine, no_fault):
    """Requests sharing the batched program produce EXACTLY the tokens the
    batch-1 front-end produces — the property every containment claim
    (bisection, preempt-resume) is built on."""
    reqs = _requests(8, seed=1)
    ref = _batch1_reference(engine, reqs)
    cs = _serve_all(engine, _requests(8, seed=1))
    s = _assert_conservation(cs, 8)
    assert s["completed"] == 8 and s["preempted"] == 0
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)


@pytest.mark.parametrize("arch", ["qwen3-4b", "llama4-scout-17b-a16e",
                                  "command-r-plus-104b"])
def test_paged_step_matches_batch1_bitwise_per_family(arch, no_fault):
    """Each family the pool pages (dense with qk-norm and GQA, MoE,
    parallel-block) runs the batched step's per-layer paged read through
    its own ``decode_block`` path, bitwise the batch-1 front-end."""
    cfg = dataclasses.replace(reduced_config(arch), compute_dtype="float32",
                              capacity_factor=16.0)
    model = build(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 ServeConfig(max_len=32, temperature=0.7, seed=3))
    ref = _batch1_reference(eng, _requests(6, seed=4))
    cs = _serve_all(eng, _requests(6, seed=4))
    assert _assert_conservation(cs, 6)["completed"] == 6
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)


def _avals(jaxpr):
    """Every value's abstract shape in a jaxpr and the jaxprs it holds,
    with each ``scan``'s outputs apart."""
    vals, scan_outs = [], []
    for eqn in jaxpr.eqns:
        vals += [v.aval for v in eqn.outvars]
        if eqn.primitive.name == "scan":
            scan_outs += [v.aval for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            v, so = _avals(sub)
            vals += v
            scan_outs += so
    return vals, scan_outs


@pytest.mark.parametrize("kv_quantize", [None, "int8"])
def test_batched_step_builds_no_dense_view(engine, no_fault, kv_quantize):
    """The batched step reads each layer's blocks inside the layer scan and
    emits only the written positions: no value of the dense ``[L, B,
    max_len, Hkv, D]`` view (in any layout, nor its int8 scales ``[L, B,
    max_len]``) is built, and the scan's outputs hold no ``max_len`` axis.
    The sizes make the view's element count differ from the pool's."""
    import jax.numpy as jnp
    max_len = 48
    eng = Engine(engine.model, engine.params, ServeConfig(max_len=max_len))
    cs, _ = _sched(eng, num_kv_blocks=9, kv_quantize=kv_quantize)
    mc, kv, B = eng.model.cfg, cs.kv, cs.cfg.max_live
    L, hkv, d = mc.num_layers, mc.num_kv_heads, mc.head_dim
    assert kv.pool["k"].size != L * B * max_len * hkv * d
    scales = [kv.scales["k"], kv.scales["v"]] if kv_quantize else []
    args = [eng.params, kv.pool["k"], kv.pool["v"], *scales,
            kv.device_tables(), jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B,), jnp.int32)]
    vals, scan_outs = _avals(jax.make_jaxpr(cs._jit_step)(*args).jaxpr)
    sizes = {int(np.prod(a.shape)) for a in vals if hasattr(a, "shape")}
    assert L * B * max_len * hkv * d not in sizes
    assert L * B * max_len not in sizes
    assert scan_outs and all(max_len not in a.shape for a in scan_outs)
    # the same step, run, is still the batched decode: the pool it returns
    # holds each row's written position
    out = cs._jit_step(*args)
    assert out[0].shape == (B, mc.vocab_size)
    assert not np.array_equal(np.asarray(out[1]), np.asarray(kv.pool["k"]))


# ---------------------------------------------------------------------------
# KV backpressure: preempt + resume, bitwise; exhaustion never crashes
# ---------------------------------------------------------------------------

def test_kv_exhaustion_preempts_and_resumes_bitwise(engine, no_fault):
    """A pool far too small for the offered load produces PREEMPTIONS —
    never an allocation failure, never a dropped request — and every
    resumed stream is bitwise identical to its uninterrupted run."""
    reqs = _requests(8, seed=1)
    ref = _batch1_reference(engine, reqs)
    # 3 blocks of 8 positions for 3 slots of up-to-14-position sequences:
    # guaranteed contention.
    cs = _serve_all(engine, _requests(8, seed=1), num_kv_blocks=3)
    s = _assert_conservation(cs, 8)
    assert s["completed"] == 8 and s["evicted"] == 0
    assert s["preempted"] >= 1 and s["resumed"] == s["preempted"]
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)
    # lifecycle records show the preempted -> resumed bracket
    report = engine.serve_report()
    bounced = [rec for rec in report["requests"].values()
               if any(e["event"] == "preempted" for e in rec["events"])]
    assert bounced
    for rec in bounced:
        events = [e["event"] for e in rec["events"]]
        assert events.index("preempted") < events.index("resumed")
        assert rec["status"] == "completed"
    # results carry the preemption count
    assert any(r.preemptions > 0 for r in cs.results.values())


def test_preempted_request_keeps_original_deadline(engine, no_fault):
    """Preemption parks a request but its deadline clock keeps running from
    ORIGINAL admission — the watchdog finalizes it from the queue."""
    reqs = [Request(request_id=i, tokens=np.arange(4, dtype=np.int32) + i,
                    max_new_tokens=20, deadline_s=0.5)
            for i in range(3)]
    cs, clock = _sched(engine, num_kv_blocks=3, max_live=3)
    for r in reqs:
        cs.submit(r)
    # burn virtual time so every tick costs 0.2s: deadlines bite mid-stream
    for _ in range(200):
        if not (cs._queue or cs._live):
            break
        cs.step()
        clock.sleep(0.2)
    s = _assert_conservation(cs, 3)
    assert s["deadline_miss"] >= 1
    assert s["deadline_miss"] + s["completed"] + s["evicted"] == 3


# ---------------------------------------------------------------------------
# Blast-radius containment: retry, then bisection
# ---------------------------------------------------------------------------

def test_single_batch_fault_retries_bitwise(engine, no_fault):
    """One transient batched-step failure is retried; nothing is evicted
    and every stream is bitwise identical to the fault-free run."""
    reqs = _requests(6, seed=2)
    ref = _batch1_reference(engine, reqs)
    with faults.inject("batch_step", nth=2):
        cs = _serve_all(engine, _requests(6, seed=2), max_retries=2)
    s = _assert_conservation(cs, 6)
    assert s["completed"] == 6 and s["evicted"] == 0
    assert s["retries"] >= 1
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)


def test_bisection_exonerates_all_when_no_row_guilty(engine, no_fault):
    """The batched attempt fails past its retry budget but every per-row
    re-run passes: all rows are exonerated, committed from their re-runs,
    ZERO evictions, all streams bitwise."""
    reqs = _requests(6, seed=2)
    ref = _batch1_reference(engine, reqs)
    # hits 1+2 = batched attempt + its single retry; re-runs all clean
    with faults.inject("batch_step", nth=(1, 2)):
        cs = _serve_all(engine, _requests(6, seed=2), max_retries=1)
    s = _assert_conservation(cs, 6)
    assert s["completed"] == 6 and s["evicted"] == 0
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)
    verdicts = [e["detail"].split(":")[0]
                for rec in engine.serve_report()["requests"].values()
                for e in rec["events"] if e["event"] == "bisect"]
    assert verdicts and set(verdicts) == {"exonerated"}


def test_bisection_evicts_exactly_one_guilty_row(engine, no_fault):
    """The acceptance-criterion proof: the batched step is poisoned AND one
    re-run stays poisoned — exactly that request is evicted; every survivor
    is bitwise identical to the fault-free run."""
    reqs = _requests(8, seed=1)
    ref = _batch1_reference(engine, reqs)
    # hits 1+2 = batched attempt + retry; hit 3 = FIRST per-row re-run
    with faults.inject("batch_step", nth=(1, 2, 3)):
        cs = _serve_all(engine, _requests(8, seed=1), max_retries=1)
    s = _assert_conservation(cs, 8)
    assert s["evicted"] == 1 and s["completed"] == 7
    evicted = [rid for rid, r in cs.results.items()
               if r.status == "evicted"]
    assert len(evicted) == 1
    assert "bisection" in cs.results[evicted[0]].detail
    for rid, toks in ref.items():
        if rid in evicted:
            partial = cs.results[rid].tokens
            np.testing.assert_array_equal(partial, toks[:len(partial)])
        else:
            np.testing.assert_array_equal(cs.results[rid].tokens, toks)
    report = engine.serve_report()
    guilty = [rec for rec in report["requests"].values()
              if any(e["event"] == "bisect"
                     and e["detail"].startswith("guilty")
                     for e in rec["events"])]
    assert len(guilty) == 1 and guilty[0]["status"] == "evicted"


def test_injected_kv_alloc_fault_is_retried_bitwise(engine, no_fault):
    """A single injected allocator failure is classified resource,
    retried, and costs nothing."""
    reqs = _requests(6, seed=4)
    ref = _batch1_reference(engine, reqs)
    with faults.inject("kv_alloc", nth=3):
        cs = _serve_all(engine, _requests(6, seed=4), max_retries=2)
    s = _assert_conservation(cs, 6)
    assert s["completed"] == 6 and s["evicted"] == 0
    assert s["retries"] >= 1
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)


# ---------------------------------------------------------------------------
# Watchdog, shedding, validation
# ---------------------------------------------------------------------------

def test_watchdog_deadline_checked_at_step_granularity(engine, no_fault):
    cs, clock = _sched(engine)
    cs.submit(Request(request_id=0, tokens=np.arange(4, dtype=np.int32),
                      max_new_tokens=25, deadline_s=0.3))
    emitted = 0
    for _ in range(100):
        done = cs.step()
        clock.sleep(0.1)
        if done:
            break
        emitted = max(emitted, len(cs._live[0].emitted) if cs._live else 0)
    res = cs.results[0]
    assert res.status == "deadline_miss"
    assert 0 < len(res.tokens) < 25    # partial stream returned
    _assert_conservation(cs, 1)


def test_queue_full_sheds_typed(engine, no_fault):
    cs, _ = _sched(engine, queue_capacity=2, max_live=1)
    outcomes = [cs.submit(r) for r in _requests(5, seed=6)]
    # slots fill from the queue only at step(); 3 of 5 queue slots exist
    shed = [o for o in outcomes if o is not None]
    assert shed and all(isinstance(o, Overloaded) for o in shed)
    cs.drain(max_ticks=20_000)
    _assert_conservation(cs, 5)


def test_oversized_request_rejected_loudly(engine, no_fault):
    cs, _ = _sched(engine)
    with pytest.raises(ValueError, match="exceeds max_len"):
        cs.submit(Request(request_id=0,
                          tokens=np.zeros((30,), np.int32),
                          max_new_tokens=16))


# ---------------------------------------------------------------------------
# Request timings and the host spans of a tick
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held_by", ["rows", "kv_blocks"])
def test_request_times_show_the_queue_wait(engine, no_fault, held_by):
    """Under the fake clock every time is exact: request 1 queues at 0.1 s
    behind request 0, which holds the only row (or the only KV block) from
    its admission at 0.5 s until it completes in the 0.6 s tick; request 1
    is admitted at the next tick, 0.7 s, and waited 0.6 s."""
    kw = ({"max_live": 1} if held_by == "rows"
          else {"max_live": 2, "num_kv_blocks": 1})
    cs, clock = _sched(engine, **kw)
    cs.submit(Request(request_id=0, tokens=np.arange(4, dtype=np.int32),
                      max_new_tokens=3))
    clock.sleep(0.1)
    cs.submit(Request(request_id=1, tokens=np.arange(4, dtype=np.int32),
                      max_new_tokens=3))
    clock.sleep(0.4)
    ticks = 0
    while cs._queue or cs._live:
        cs.step()
        ticks += 1
        clock.sleep(0.1)
    assert ticks == 4
    recs = engine.serve_report()["requests"]
    times = {int(r): (rec["queued_t"], rec["admit_t"], rec["first_token_t"])
             for r, rec in recs.items()}
    assert times == {0: pytest.approx((0.0, 0.5, 0.5)),
                     1: pytest.approx((0.1, 0.7, 0.7))}
    for queued, admit, first in times.values():
        assert queued <= admit <= first
    _assert_conservation(cs, 2)


# The host spans of one tick, in the order they open: admissions (each
# holding its prefill and its first token), then KV growth, the batched
# step's dispatch, the wait for its tokens, and their commit.
TICK_PHASES = ("serve.admit", "serve.kv_grow", "serve.step_dispatch",
               "serve.token_wait", "serve.commit")
ADMIT_PHASES = ("serve.prefill", "serve.token_wait", "serve.commit")


def _host_spans(trace_dir):
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name, dict(e.stats))
                             for e in line.events
                             if e.name.startswith("serve."))
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def test_tick_spans_nest_in_order_under_the_profiler(engine, no_fault,
                                                     tmp_path):
    """The CPU profiler records the scheduler's host spans: every phase
    lies inside its tick in the documented order, each admission names
    its request, and none reuses a benchmark span's name (the benchmark
    counts ticks by its own ``sched.step``)."""
    from chipbench.trace import SPAN_NAMES
    cs, _ = _sched(engine)
    reqs = _requests(4, seed=11)
    for r in reqs:
        cs.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        cs.drain(max_ticks=1000)
    spans = _host_spans(tmp_path)
    names = {s[2] for s in spans}
    assert names == {"serve.tick", "serve.prefill"} | set(TICK_PHASES)
    assert not names & set(SPAN_NAMES)
    ticks = [s for s in spans if s[2] == "serve.tick"]
    inner = [s for s in spans if s[2] != "serve.tick"]
    assert sum(len(_inner_of(t, inner)) for t in ticks) == len(inner)
    admitted = []
    for tick in ticks:
        top = _top_level(_inner_of(tick, inner))
        order = [TICK_PHASES.index(s[2]) for s in top]
        assert order == sorted(order), [s[2] for s in top]
        assert [s[2] for s in top if s[2] != "serve.admit"] == \
            list(TICK_PHASES[1:])
        for adm in (s for s in top if s[2] == "serve.admit"):
            admitted.append(adm[3]["request_id"])
            assert [s[2] for s in _inner_of(adm, inner)] == list(ADMIT_PHASES)
    assert sorted(admitted) == [r.request_id for r in reqs]


def _inner_of(outer, spans):
    return [s for s in spans if s is not outer and outer[0] <= s[0]
            and s[1] <= outer[1]]


def _top_level(spans):
    return [s for s in spans
            if not any(o is not s and o[0] <= s[0] and s[1] <= o[1]
                       for o in spans)]


# ---------------------------------------------------------------------------
# Soak: Poisson arrivals under whatever site the CI matrix armed
# ---------------------------------------------------------------------------

def test_soak_poisson_continuous_conservation(engine, monkeypatch):
    site, _ = faults.active()   # hard error on a typo'd REPRO_FAULT
    monkeypatch.setenv(health.ENV_NUMERICS_GUARD, "1")
    n = 60
    reqs = _requests(n, seed=7)
    gaps = np.random.default_rng(8).exponential(scale=0.3, size=n)
    schedule = list(zip(np.cumsum(gaps), reqs))
    clock = VirtualClock()
    cs = ContinuousScheduler(
        engine,
        ContinuousConfig(queue_capacity=10, max_live=4, max_retries=1,
                         backoff_base_s=0.001, backoff_cap_s=0.004,
                         block_size=8, num_kv_blocks=6),  # forced contention
        clock=clock, sleep=clock.sleep)
    results = cs.run(schedule, tick_s=1.0)
    s = _assert_conservation(cs)
    assert set(results) == {r.request_id for r in reqs}
    if site is None:
        # healthy overloaded stream under real KV pressure: completions,
        # typed sheds, PREEMPTIONS — and zero evictions (exhaustion is
        # backpressure, never a failure)
        assert s["completed"] > 0 and s["preempted"] > 0
        assert s["evicted"] == 0
    elif site == "kv_alloc":
        # every allocation attempt fails: retries exhaust and everything
        # admitted is evicted TYPED at its allocation point — recorded,
        # never crashed, never dropped
        assert s["completed"] == 0
        assert s["evicted"] == s["admitted"] > 0
        assert s["retries"] > 0
    elif site == "batch_step":
        # every batched attempt AND every bisection re-run fails: each
        # admitted request is eventually evicted guilty; admission-path
        # prefill (batch-1, not a batch_step site) still works
        assert s["completed"] == 0
        assert s["evicted"] == s["admitted"] > 0
    report = engine.serve_report()
    assert report["counters"] == {k: s[k] for k in report["counters"]}


# ---------------------------------------------------------------------------
# Property sweep: arrivals × KV budgets × fault placements
# ---------------------------------------------------------------------------

def _property_case(engine, *, n, seed, num_blocks, fault_site, fault_nth):
    """One property draw: serve a random stream under a random KV budget
    and fault placement; assert the invariant closes, the allocator is
    leak-free, and (when nothing was evicted) streams are bitwise equal to
    the batch-1 oracle."""
    faults.reset()
    health.clear_serve()
    reqs = _requests(n, seed=seed)
    ref = _batch1_reference(engine, reqs)
    health.clear_serve()
    ctx = (faults.inject(fault_site, nth=fault_nth) if fault_site
           else _NullCtx())
    with ctx:
        cs = _serve_all(engine, _requests(n, seed=seed),
                        num_kv_blocks=num_blocks, max_retries=1)
    s = _assert_conservation(cs, n)                      # (a) closes
    assert s["resumed"] == s["preempted"]                # (c) no leaks is
    #     inside _assert_conservation; resumed==preempted at quiescence
    for rid, res in cs.results.items():                  # (b) bitwise
        if res.status == "completed":
            np.testing.assert_array_equal(res.tokens, ref[rid])
        elif res.status in ("evicted", "deadline_miss"):
            np.testing.assert_array_equal(
                res.tokens, ref[rid][:len(res.tokens)])
    return s


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# The deterministic grid keeps the property coverage alive where hypothesis
# isn't installed (the CI fault-matrix jobs and the seed image); the
# hypothesis sweep below widens it where it is.
@pytest.mark.parametrize("seed,num_blocks,fault_site,fault_nth", [
    (11, 3, None, None),              # heavy KV pressure, healthy
    (12, 4, "kv_alloc", 2),           # alloc fault under pressure
    (13, 3, "batch_step", (2, 3)),    # batch fault + guilty re-run
    (14, 12, "batch_step", 1),        # transient batch fault, no pressure
    (15, 2, None, None),              # extreme pressure: 2 blocks
])
def test_property_grid(engine, no_fault, seed, num_blocks, fault_site,
                       fault_nth):
    _property_case(engine, n=6, seed=seed, num_blocks=num_blocks,
                   fault_site=fault_site, fault_nth=fault_nth)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000),
           num_blocks=st.integers(2, 14),
           fault=st.sampled_from([None, "kv_alloc", "batch_step"]),
           nth=st.integers(1, 6))
    def test_property_sweep_conservation_bitwise_no_leak(seed, num_blocks,
                                                         fault, nth):
        import os
        os.environ.pop(faults.ENV_FAULT, None)
        os.environ.pop(health.ENV_NUMERICS_GUARD, None)
        engine = _property_engine()
        _property_case(engine, n=5, seed=seed, num_blocks=num_blocks,
                       fault_site=fault, fault_nth=nth)
else:  # keep the node visible (and skipping) without hypothesis
    @given()
    def test_property_sweep_conservation_bitwise_no_leak():
        pass  # pragma: no cover


# ---------------------------------------------------------------------------
# Quantized paged-KV pool (ContinuousConfig.kv_quantize="int8")
# ---------------------------------------------------------------------------

def test_paged_cache_quantized_units(engine, no_fault):
    """Pool dtype/scale-leaf geometry, byte accounting, insert->gather
    round-trip within the per-position int8 bound, and scrub-on-release
    resetting scales to 1.0 (so recycled blocks dequantize to exact zero)."""
    import jax.numpy as jnp
    cfg = engine.model.cfg
    mk = dict(max_live=2, max_len=32, block_size=8, num_blocks=8)
    kv = PagedKVCache(cfg, **mk, quantize="int8")
    f32 = PagedKVCache(cfg, **mk)
    assert kv.pool["k"].dtype == jnp.int8
    assert kv.scales["k"].shape == kv.pool["k"].shape[:3]
    assert np.all(np.asarray(kv.scales["k"]) == 1.0)
    # int8 values + f32 per-position scales land well under the f32 pool
    # (measured ~0.266x): the honest total a block budget must cover
    assert kv.pool_bytes() < 0.3 * f32.pool_bytes()
    assert kv.bytes_per_block() < f32.bytes_per_block() // 3
    # quantize-on-write / dequantize-on-read round-trip: each position's
    # error is bounded by its own scale/2 = absmax/254
    _, caches = engine.prefill_request(np.arange(6, dtype=np.int32))
    assert kv.grow(0, 6)
    kv.insert_dense(0, caches)
    got = kv.gather_slot(0)
    for name in ("k", "v"):
        want = np.asarray(caches["kv"][name], np.float32)
        back = np.asarray(got["kv"][name], np.float32)
        assert back.dtype == want.dtype
        bound = np.abs(want).max(axis=(-2, -1), keepdims=True) / 254 + 1e-6
        assert np.all(np.abs(back - want) <= bound)
    # release scrubs values to zero AND scales back to 1.0
    kv.release(0)
    assert np.all(np.asarray(kv.pool["k"]) == 0)
    assert np.all(np.asarray(kv.scales["k"]) == 1.0)
    assert kv.alloc.free_count == kv.alloc.capacity
    # null block stays all-zero with unit scales after the full cycle
    assert np.all(np.asarray(kv.pool["v"][:, 0]) == 0)
    assert np.all(np.asarray(kv.scales["v"][:, 0]) == 1.0)
    with pytest.raises(ValueError, match="int8"):
        PagedKVCache(cfg, **mk, quantize="int4")


def test_kv_quantized_preempt_resume_bitwise_greedy(engine, no_fault):
    """Greedy decode over a QUANTIZED pool: a tight pool's preempt/resume
    cycle reproduces the roomy quantized run bitwise — quantize-exactly-once
    means parking and replaying a stream never re-rounds its history."""
    greedy = Engine(engine.model, engine.params,
                    ServeConfig(max_len=32, temperature=0.0))
    roomy = _serve_all(greedy, _requests(8, seed=1), num_kv_blocks=12,
                       kv_quantize="int8")
    ref = {rid: r.tokens.copy() for rid, r in roomy.results.items()}
    assert _assert_conservation(roomy, 8)["preempted"] == 0
    health.clear_serve()
    tight = _serve_all(greedy, _requests(8, seed=1), num_kv_blocks=3,
                       kv_quantize="int8")
    s = _assert_conservation(tight, 8)
    assert s["completed"] == 8 and s["evicted"] == 0
    assert s["preempted"] >= 1 and s["resumed"] == s["preempted"]
    for rid, toks in ref.items():
        np.testing.assert_array_equal(tight.results[rid].tokens, toks)


def test_kv_quantized_preempt_resume_bitwise_sampled(engine, no_fault):
    """The same bitwise claim under SAMPLED decode (temperature 0.7): the
    per-step sampling keys are position-derived, so a bit-identical replayed
    cache yields bit-identical draws."""
    roomy = _serve_all(engine, _requests(8, seed=1), num_kv_blocks=12,
                       kv_quantize="int8")
    ref = {rid: r.tokens.copy() for rid, r in roomy.results.items()}
    health.clear_serve()
    tight = _serve_all(engine, _requests(8, seed=1), num_kv_blocks=3,
                       kv_quantize="int8")
    s = _assert_conservation(tight, 8)
    assert s["completed"] == 8 and s["evicted"] == 0
    assert s["preempted"] >= 1 and s["resumed"] == s["preempted"]
    for rid, toks in ref.items():
        np.testing.assert_array_equal(tight.results[rid].tokens, toks)


@pytest.mark.parametrize("fault_site,fault_nth", [
    (None, None), ("kv_alloc", 2), ("batch_step", 2)])
def test_kv_quantized_fault_conservation(engine, no_fault, fault_site,
                                         fault_nth):
    """The fault-containment contract carries over to quantized pools: a
    transient alloc/batch fault under KV pressure is retried, conservation
    closes, nothing leaks, and streams match the roomy quantized oracle."""
    roomy = _serve_all(engine, _requests(6, seed=21), num_kv_blocks=12,
                       kv_quantize="int8")
    ref = {rid: r.tokens.copy() for rid, r in roomy.results.items()}
    health.clear_serve()
    ctx = (faults.inject(fault_site, nth=fault_nth) if fault_site
           else _NullCtx())
    with ctx:
        cs = _serve_all(engine, _requests(6, seed=21), num_kv_blocks=3,
                        kv_quantize="int8", max_retries=2)
    s = _assert_conservation(cs, 6)
    assert s["completed"] == 6 and s["evicted"] == 0
    if fault_site:
        assert s["retries"] >= 1
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)


def test_drain_detects_kv_leak_typed(engine, no_fault):
    """A block held past a full drain is a LEAK: drain raises typed and the
    health registry records a kv_leak degradation (the CI-visible signal)."""
    cs, _ = _sched(engine)
    assert cs.kv.alloc.try_alloc(1)    # steal a block behind the scheduler
    with pytest.raises(RuntimeError, match="kv_leak"):
        cs.drain(max_ticks=100)
    report = health.health_report()
    assert any(rec["cause"] == "kv_leak" for rec in report.values())
    leak = [rec for rec in report.values() if rec["cause"] == "kv_leak"][0]
    assert "1 of" in leak["detail"]


_PROPERTY_ENGINE = []


def _property_engine():
    """Module fixture equivalent for the hypothesis path (hypothesis tests
    cannot take function-scoped pytest fixtures)."""
    if not _PROPERTY_ENGINE:
        cfg = dataclasses.replace(reduced_config("olmo-1b"),
                                  compute_dtype="float32",
                                  capacity_factor=16.0)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _PROPERTY_ENGINE.append(
            Engine(model, params,
                   ServeConfig(max_len=32, temperature=0.7, seed=3)))
    return _PROPERTY_ENGINE[0]
