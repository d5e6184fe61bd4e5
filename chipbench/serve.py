"""The system under test, as the benchmark drives it.

``repro``'s ``Engine`` over packed bfloat16 weights, behind its
``ContinuousScheduler`` (paged KV pool, one batched decode program). The
benchmark builds the weights itself from the seed (``chipbench.weights``),
lays them out as the program's parameter tree (the configuration's
architecture module, ``chipbench/arch/<name>.py``) and packs them in the
same jitted call, so no float32 or unpacked copy is ever resident. It then
drives ``ContinuousScheduler.step()`` in its own loop (``run_window``, open
or closed), and timestamps every token by wrapping the public
``sample_tokens`` of its own ``Engine``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W


def check_layout(model, program, arch: dict) -> None:
    """The benchmark's tree (``model``, the architecture module, lays it
    out) must have exactly the ``program``'s structure and shapes (its
    dtypes are the served ones, so they may differ)."""
    want = jax.eval_shape(program.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: model.param_tree(k, arch),
                         jax.random.PRNGKey(0))
    ws = jax.tree.map(lambda a: a.shape, want)
    gs = jax.tree.map(lambda a: a.shape, got)
    if ws != gs:
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{gs} vs {ws}")


def build_params(model, program, arch: dict, seed: int,
                 quantize: Optional[str] = None):
    """Served weights of the ``program`` on the device in one jitted call:
    draw and lay out (``model``, the architecture module), pack
    (``quantize`` switches on the program's own int8 weight path)."""
    from repro.models.layers import pack_model_params
    check_layout(model, program, arch)

    @jax.jit
    def make(key):
        return pack_model_params(program.cfg, model.param_tree(key, arch),
                                 quantize=quantize)

    params = make(W.root_key(seed))
    jax.block_until_ready(params)
    return params


class TokenClock:
    """Wraps ``engine.sample_tokens``: blocks until the tokens are on the
    host, then records ``(request_id, index, t, token)`` once per pair (the
    batched step pads idle rows with a live row's pair) and each call's
    committed pairs. It also keeps, on the device, each row's logits at the
    vocabulary ids ``probe_ids`` (one small gather per call), so the check
    can compare the logits the timed path produced with the reference's.
    Once ``rows_in_use`` is set (a callable giving, for a batch as wide as
    its result, which rows hold a request), rows that do not are skipped:
    they compute garbage under a live row's pair. ``rows`` maps each
    request to the batch row its batched-step tokens were sampled in."""

    def __init__(self, engine, probe_ids, clock=time.perf_counter):
        from jax.profiler import TraceAnnotation
        self.events: List[Tuple[int, int, float, int]] = []
        self.calls: List[Tuple[float, int, List[Tuple[int, int]]]] = []
        self._seen = set()
        self._probes: list = []                  # device [width, K] per call
        self._probed: Dict[Tuple[int, int], Tuple[int, int]] = {}
        self.rows: Dict[int, int] = {}
        self.rows_in_use = None
        ids = jnp.asarray(probe_ids, jnp.int32)
        take = jax.jit(lambda logits: logits[:, ids].astype(jnp.float32))
        original = engine.sample_tokens

        def sample_tokens(logits, request_ids, step):
            with TraceAnnotation("engine.sample_tokens"):
                out = original(logits, request_ids, step)
                probe = take(logits)
                host = np.asarray(out)
            t = clock()
            rids = np.atleast_1d(np.asarray(request_ids))
            steps = np.broadcast_to(np.asarray(step), rids.shape)
            in_use = self.rows_in_use() if self.rows_in_use else None
            if in_use is not None and len(in_use) != len(rids):
                in_use = None
            pairs = []
            for row, (r, s, tok) in enumerate(zip(rids.tolist(), steps.tolist(),
                                                  host.tolist())):
                if (r, s) in self._seen or (in_use is not None
                                            and not in_use[row]):
                    continue
                self._seen.add((r, s))
                self.events.append((r, s, t, tok))
                self._probed[(r, s)] = (len(self._probes), row)
                if len(rids) > 1:               # the batched step's sampler
                    self.rows.setdefault(r, row)
                pairs.append((r, s))
            self._probes.append(probe)
            self.calls.append((t, int(logits.shape[0]), pairs))
            return out

        engine.sample_tokens = sample_tokens

    def probed_logits(self, request_id: int, n: int) -> np.ndarray:
        """[n, K]: the logits at the probe ids from which tokens 0..n-1 of
        the request were sampled."""
        rows = []
        for s in range(n):
            if (request_id, s) not in self._probed:
                raise KeyError(f"no logits captured for token {s} of request "
                               f"{request_id}")
            call, row = self._probed[(request_id, s)]
            if not isinstance(self._probes[call], np.ndarray):
                self._probes[call] = np.asarray(self._probes[call])
            rows.append(self._probes[call][row])
        return np.stack(rows)


def annotate_prefill(engine) -> None:
    from jax.profiler import TraceAnnotation
    original = engine.prefill_request

    def prefill_request(tokens):
        with TraceAnnotation("engine.prefill_request"):
            return original(tokens)

    engine.prefill_request = prefill_request


class CompileCounter:
    """Counts programs built (compiled, or loaded from the persistent
    cache) through ``jax.monitoring``'s duration events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1


@dataclasses.dataclass
class Server:
    engine: object
    sched: object
    clock: TokenClock


def build_server(model, arch: dict, serving: dict, seed: int, probe_ids,
                 quantize: Optional[str] = None) -> Server:
    """The engine and scheduler over the served weights of the program that
    the architecture module ``model`` configures."""
    from repro.models import build
    from repro.serve.engine import Engine, ServeConfig
    from repro.serve.scheduler import ContinuousConfig, ContinuousScheduler
    program = build(model.program_config(arch))
    params = build_params(model, program, arch, seed, quantize)
    engine = Engine(program, params, ServeConfig(
        max_len=serving["max_len"], temperature=0.0,
        cache_dtype=serving["cache_dtype"], pack_weights=False))
    clock = TokenClock(engine, probe_ids)
    annotate_prefill(engine)
    sched = ContinuousScheduler(engine, ContinuousConfig(
        queue_capacity=serving["queue_capacity"],
        max_live=serving["max_live"], block_size=serving["block_size"],
        default_max_new_tokens=1))
    # A row holds a request while its block table names a real block (the
    # pool's block 0 is the null block every idle row points at).
    clock.rows_in_use = lambda: sched.kv.tables.max(axis=1) > 0
    return Server(engine, sched, clock)


WARM_ID = 1 << 30   # warm-up request ids, apart from the traffic's


def warm_up(server: Server, prompt_lengths) -> None:
    """Serve one request of every prompt length the traffic uses, two
    tokens each: every program of the window (each prefill length, the
    samplers at both widths, insert, the batched step, scrub) is built
    here."""
    from repro.serve.requests import Request
    for i, s in enumerate(sorted(set(prompt_lengths))):
        server.sched.submit(Request(request_id=WARM_ID + i,
                                    tokens=np.arange(s, dtype=np.int32),
                                    max_new_tokens=2))
    server.sched.drain()


@dataclasses.dataclass
class WindowLog:
    start: float
    end: float
    due: Dict[int, float]
    late_s: List[float]
    ticks: List[Tuple[float, float]]
    queue_mid: Optional[int] = None
    queue_end: Optional[int] = None
    drained_at: float = 0.0


def _submit(sched, arrival) -> None:
    from repro.serve.requests import Request
    sched.submit(Request(request_id=arrival.request_id, tokens=arrival.tokens,
                         max_new_tokens=arrival.max_new_tokens))


def run_window(server: Server, arrivals, lead_in_s: float, seconds: float,
               outstanding: Optional[int] = None, on_tick=None,
               clock=time.perf_counter, sleep=time.sleep) -> WindowLog:
    """Serves ``arrivals`` for the lead-in and the window; after the window
    closes no new request is submitted and the requests in flight are
    drained, so each has a whole timeline.

    Open loop (``outstanding`` None): each arrival is submitted at the first
    tick after it is due. Closed loop: until the window closes, each tick
    first tops the requests submitted and not finished up to
    ``outstanding`` with the next arrivals, each due when submitted, so
    ``due`` holds only the requests submitted."""
    from jax.profiler import TraceAnnotation
    sched = server.sched
    t0 = clock()
    log = WindowLog(start=t0 + lead_in_s, end=t0 + lead_in_s + seconds,
                    due={}, late_s=[], ticks=[])
    if outstanding is None:
        log.due = {a.request_id: t0 + a.due_s for a in arrivals}
    mid = log.start + seconds / 2
    i, n = 0, len(arrivals)
    base = len(sched.results)
    while True:
        now = clock()
        if outstanding is None:
            while i < n and t0 + arrivals[i].due_s <= now:
                _submit(sched, arrivals[i])
                log.late_s.append(now - (t0 + arrivals[i].due_s))
                i += 1
        elif now < log.end:
            while i - (len(sched.results) - base) < outstanding:
                if i >= n:
                    raise RuntimeError(f"the closed loop's {n} requests ran "
                                       f"out before the window closed")
                _submit(sched, arrivals[i])
                log.due[arrivals[i].request_id] = now
                i += 1
        if log.queue_mid is None and now >= mid:
            log.queue_mid = sched.stats()["queued"]
        if log.queue_end is None and now >= log.end:
            log.queue_end = sched.stats()["queued"]
        if on_tick is not None:
            on_tick(now)
        if i == len(sched.results) - base:      # nothing in flight
            if outstanding is not None or i >= n:
                break
            sleep(max(0.0, t0 + arrivals[i].due_s - clock()))
            continue
        ts = clock()
        with TraceAnnotation("sched.step"):
            sched.step()
        log.ticks.append((ts, clock()))
    log.drained_at = clock()
    if log.queue_end is None:
        log.queue_end = 0
    return log
