"""Slot-recycling continuous-batching scheduler over the paged KV cache.

``StreamFrontend`` (PR 7) hardened the request lifecycle but decodes every
request in its own jit'd batch-1 program — the fused kernels' throughput is
left on the table exactly the way an unpacked GEMM leaves the micro kernel
starved. This scheduler moves all live requests into ONE jit'd batched decode
program of fixed width ``max_live`` (rows are recycled slots, the live-row
count is a host scalar exactly like the MoE router's occupancy counts) while
preserving EVERY clause of the front-end's request-lifecycle contract:

* **Admission / backpressure** — same bounded queue, same reject-newest
  shedding, same typed :class:`~repro.serve.requests.Overloaded` result.
  KV-block exhaustion is a SECOND backpressure signal below admission: the
  paged allocator (``serve.kv_cache``) returns ``None`` instead of raising,
  and the scheduler answers with **preemption**, never a crash.
* **Preempt and resume** — when a live request cannot grow its KV blocks
  (pool exhausted), the NEWEST-admitted live request is preempted: its
  blocks are released (scrubbed), its generated prefix is parked, and it
  re-enters the FRONT of the queue in the transient ``preempted`` state.
  Resume re-prefills the prompt and replays the generated prefix
  teacher-forced through the batch-1 decode path — sampling keys are
  per-(request_id, step) ``fold_in`` derivations, so the resumed stream is
  BITWISE identical to the uninterrupted run. The conservation invariant
  extends to ``admitted == completed + evicted + deadline_miss + open +
  preempted_open`` (see ``repro.core.health``).
* **Blast-radius containment (bisection)** — a failed batched step is
  classified (``health.classify_failure``), retried with capped backoff,
  and on retry exhaustion BISECTED: every live row is re-run alone on the
  batch-1 path against its gathered dense cache view (bitwise the batched
  computation for that row); rows whose re-run fails are evicted as
  ``guilty``, rows that pass are ``exonerated`` and their re-run result is
  committed directly — one poisoned request costs exactly one eviction and
  survivors stay bitwise identical to a fault-free run. Fault site
  ``batch_step`` fires once per shared attempt AND once per re-run, so the
  multi-hit arming form (``batch_step:n1,n2``) stages the whole story.
* **Step watchdog** — deadlines are checked every scheduler tick at step
  granularity across the whole batch (injectable clock), and freed rows
  admit queued requests on the next tick.
* **Per-request isolation** — per-row sampling keys and per-row numerics
  guarding: a non-finite logits row under ``REPRO_NUMERICS_GUARD=1`` evicts
  that row only.

Every preemption, resume, and bisection verdict lands in the process-global
``repro.core.health.SERVE`` registry and surfaces through
``Engine.serve_report()``, with each request's ``queued_t``, ``admit_t`` and
``first_token_t`` on the scheduler's clock.

Under the JAX profiler each tick is a host span ``serve.tick`` holding its
phases in order: ``serve.admit`` per admission (argument ``request_id``;
it holds ``serve.prefill`` and the first token's ``serve.token_wait`` and
``serve.commit``), ``serve.kv_grow``, ``serve.step_dispatch``,
``serve.token_wait`` (the host blocked on the chip) and ``serve.commit``.
The batched step names its KV traffic ``kv_gather`` (each layer's read of
its blocks) and ``kv_scatter`` (the written positions put into the pool)
(``jax.named_scope``).
"""
from __future__ import annotations

import collections
import dataclasses
import time
import weakref
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import health
from repro.serve.frontend import RETRYABLE_CLASSES, VirtualClock  # noqa: F401
from repro.serve.kv_cache import PagedKVCache
from repro.serve.requests import Overloaded, Request, RequestResult
from repro.testing import faults


@dataclasses.dataclass
class ContinuousConfig:
    """Scheduler knobs: the StreamConfig surface plus the KV-block budget."""

    queue_capacity: int = 16       # bounded admission queue (backpressure)
    max_live: int = 4              # rows of the shared batched decode program
    max_retries: int = 2           # per-step retry budget (retryable classes)
    backoff_base_s: float = 0.005  # first retry's backoff
    backoff_cap_s: float = 0.08    # exponential backoff cap
    default_max_new_tokens: int = 16
    default_deadline_s: Optional[float] = None  # None = no deadline
    block_size: int = 16           # KV block granularity (positions)
    num_kv_blocks: Optional[int] = None  # pool size; None = worst case
    #   (max_live * max_len / block_size — no backpressure, only recycling)
    kv_quantize: Optional[str] = None    # "int8": int8 pool + per-position
    #   f32 scales — ~2x resident tokens per byte budget; reads dequantize,
    #   writes quantize once (see serve.kv_cache's quantized-pool contract)


@dataclasses.dataclass
class _QEntry:
    """A queued request: fresh, or preempted with its generated prefix."""

    req: Request
    admit_t: float
    admit_seq: int
    emitted: List[int]
    preempted: bool = False
    preemptions: int = 0
    retries: int = 0


@dataclasses.dataclass
class _CSlot:
    """One live request's state in the shared batch (row = slot index)."""

    req: Request
    row: int
    budget: int
    deadline_s: Optional[float]
    admit_t: float
    admit_seq: int
    emitted: List[int]
    retries: int = 0
    preemptions: int = 0


# jit'd batched-step programs cached per (engine, batch shape): schedulers
# are cheap to construct (tests/benches build many over one engine) and the
# program depends only on the engine's model + the batch geometry.
_STEP_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


class ContinuousScheduler:
    """Continuous batching with paged-KV backpressure under the
    request-lifecycle contract (see module docstring). API mirrors
    :class:`~repro.serve.frontend.StreamFrontend`:
    ``submit`` / ``step`` / ``drain`` / ``run`` / ``stats`` / ``results``.
    """

    def __init__(self, engine, cfg: ContinuousConfig = ContinuousConfig(), *,
                 clock=time.monotonic, sleep=time.sleep):
        self.engine = engine
        self.cfg = cfg
        self._clock = clock
        self._sleep = sleep
        max_len = engine.cfg.max_len
        num_blocks = cfg.num_kv_blocks
        if num_blocks is None:
            num_blocks = cfg.max_live * (max_len // cfg.block_size)
        self.kv = PagedKVCache(
            engine.model.cfg, max_live=cfg.max_live, max_len=max_len,
            block_size=cfg.block_size, num_blocks=num_blocks,
            cache_dtype=engine.cfg.cache_dtype, quantize=cfg.kv_quantize)
        self._queue: collections.deque = collections.deque()  # _QEntry
        self._live: Dict[int, _CSlot] = {}                    # row -> slot
        self.results: Dict[int, RequestResult] = {}
        self._seen: set = set()
        self._admit_seq = 0
        key = (cfg.max_live, max_len, cfg.block_size, cfg.kv_quantize)
        cache = _STEP_CACHE.setdefault(engine, {})
        if key not in cache:
            cache[key] = self._build_step()
        self._jit_step = cache[key]

    # ----- the shared batched decode program ------------------------------

    def _build_step(self):
        """One jit'd program for the whole batch, compiled ONCE. Inside the
        model's layer scan each layer reads its rows' blocks straight from
        the pool (``kv_cache.pool_view``, scope ``kv_gather``), puts the new
        position in place by a select and attends; the scan emits only the
        written positions ``[L, B, Hkv, D]``, and one scatter per leaf
        (``kv_scatter``) writes them into a new pool. No dense ``[L, B,
        max_len, Hkv, D]`` view is built. Dead rows (all-null tables, token
        0, pos 0) compute identical garbage and land their write in the null
        block — masked everywhere, bitwise inert.

        A quantized pool (``cfg.kv_quantize``) threads the per-position
        scale leaves through the same program: the read dequantizes ``q *
        scale`` into the compute dtype (elementwise, so each row reads
        bitwise ``gather_slot``'s view), and the scatter quantizes the
        written positions with the shared ``kv_cache.quantize_kv_position``
        formula — the same bytes a batch-1 ``write_position`` of that vector
        would commit."""
        from repro.serve.kv_cache import _write_pos, _write_pos_q, pool_view
        model = self.engine.model
        B = self.cfg.max_live
        bs = self.kv.block_size
        compute_dtype = self.kv.compute_dtype.name

        def step(params, pool_k, pool_v, tables, tokens, pos):
            logits, new = model.decode(
                params, pool_view(pool_k, pool_v, tables), tokens, pos)
            dest = tables[jnp.arange(B), pos // bs] * bs + pos % bs  # [B]
            with jax.named_scope("kv_scatter"):
                return (logits[:, 0], _write_pos(pool_k, dest, new["kv"]["k"]),
                        _write_pos(pool_v, dest, new["kv"]["v"]))

        def step_q(params, pool_k, pool_v, scale_k, scale_v, tables,
                   tokens, pos):
            view = pool_view(pool_k, pool_v, tables, scale_k, scale_v,
                             dtype=compute_dtype)
            logits, new = model.decode(params, view, tokens, pos)
            dest = tables[jnp.arange(B), pos // bs] * bs + pos % bs  # [B]
            with jax.named_scope("kv_scatter"):
                pk, sk = _write_pos_q(pool_k, scale_k, dest, new["kv"]["k"])
                pv, sv = _write_pos_q(pool_v, scale_v, dest, new["kv"]["v"])
            return logits[:, 0], pk, pv, sk, sv

        return jax.jit(step_q if self.cfg.kv_quantize else step)

    # ----- admission ------------------------------------------------------

    def submit(self, request: Request) -> Optional[Overloaded]:
        """Offer one request. None when ADMITTED; the typed
        :class:`Overloaded` result when shed — never raises for load."""
        rid = request.request_id
        if rid in self._seen:
            raise ValueError(f"duplicate request_id {rid}")
        budget = request.max_new_tokens or self.cfg.default_max_new_tokens
        if request.tokens.shape[0] + budget > self.engine.cfg.max_len:
            raise ValueError(
                f"request {rid}: prompt ({request.tokens.shape[0]}) + budget "
                f"({budget}) exceeds max_len ({self.engine.cfg.max_len})")
        self._seen.add(rid)
        try:
            faults.maybe_fail("admission")
        except Exception as exc:  # noqa: BLE001 — classified, recorded, typed
            cause = health.classify_failure(exc)
            return self._shed(request, f"admission failure ({cause}): {exc}")
        if len(self._queue) >= self.cfg.queue_capacity:
            return self._shed(
                request, f"queue full (capacity {self.cfg.queue_capacity})")
        now = self._clock()
        health.SERVE.admitted(rid, queued_t=now)
        self._queue.append(_QEntry(req=request, admit_t=now,
                                   admit_seq=self._admit_seq, emitted=[]))
        self._admit_seq += 1
        return None

    def _shed(self, request: Request, detail: str) -> Overloaded:
        health.SERVE.shed(request.request_id, detail)
        result = Overloaded(
            request_id=request.request_id, status="shed",
            tokens=np.zeros((0,), np.int32), detail=detail,
            queue_depth=len(self._queue))
        self.results[request.request_id] = result
        return result

    # ----- lifecycle helpers ----------------------------------------------

    def _finalize_slot(self, slot: _CSlot, status: str,
                       detail: str = "") -> RequestResult:
        self.kv.release(slot.row)
        self._live.pop(slot.row, None)
        return self._finalize(slot.req, status, slot.emitted, slot.admit_t,
                              slot.retries, slot.preemptions, detail)

    def _finalize_queued(self, entry: _QEntry, status: str,
                         detail: str = "") -> RequestResult:
        return self._finalize(entry.req, status, entry.emitted, entry.admit_t,
                              entry.retries, entry.preemptions, detail)

    def _finalize(self, req: Request, status: str, emitted: List[int],
                  admit_t: float, retries: int, preemptions: int,
                  detail: str) -> RequestResult:
        latency = self._clock() - admit_t
        health.SERVE.finalize(req.request_id, status, step=len(emitted),
                              tokens_emitted=len(emitted),
                              latency_s=latency, detail=detail)
        result = RequestResult(
            request_id=req.request_id, status=status,
            tokens=np.asarray(emitted, np.int32), detail=detail,
            retries=retries, latency_s=latency, preemptions=preemptions)
        self.results[req.request_id] = result
        return result

    def _preempt(self, slot: _CSlot, detail: str) -> None:
        """Park a live request back at the queue FRONT under KV pressure:
        release (scrub) its blocks, keep its tokens — transient state, never
        terminal, re-queue exempt from the admission capacity (it was
        already admitted; dropping it would break conservation)."""
        health.SERVE.preempted(slot.req.request_id, step=len(slot.emitted),
                               detail=detail)
        self.kv.release(slot.row)
        self._live.pop(slot.row, None)
        self._queue.appendleft(_QEntry(
            req=slot.req, admit_t=slot.admit_t, admit_seq=slot.admit_seq,
            emitted=list(slot.emitted), preempted=True,
            preemptions=slot.preemptions + 1, retries=slot.retries))

    def _newest_live(self) -> Optional[_CSlot]:
        if not self._live:
            return None
        return max(self._live.values(), key=lambda s: s.admit_seq)

    # ----- admission stepping ---------------------------------------------

    def _free_row(self) -> Optional[int]:
        for row in range(self.cfg.max_live):
            if row not in self._live:
                return row
        return None

    def _admit_one(self, entry: _QEntry, row: int,
                   done: Dict[int, RequestResult]) -> None:
        """Move one queue entry into a batch row: allocate KV for its
        occupied positions, prefill the prompt (and replay the generated
        prefix if resuming), guarded exactly like the front-end's step."""
        admit_t = self._clock()
        req = entry.req
        rid = req.request_id
        S = req.tokens.shape[0]
        k = len(entry.emitted)
        occupied = S + max(0, k - 1)   # positions written so far
        slot = _CSlot(req=req, row=row,
                      budget=req.max_new_tokens
                      or self.cfg.default_max_new_tokens,
                      deadline_s=(req.deadline_s if req.deadline_s is not None
                                  else self.cfg.default_deadline_s),
                      admit_t=entry.admit_t, admit_seq=entry.admit_seq,
                      emitted=list(entry.emitted), retries=entry.retries,
                      preemptions=entry.preemptions)
        # KV allocation first: an injected kv_alloc failure is retried with
        # capped backoff then EVICTS (typed) — under every-hit arming the
        # alternative (requeue) livelocks. Real exhaustion never lands here
        # (_admissions checks affordability before calling).
        attempts = 0
        while True:
            try:
                ok = self.kv.grow(row, occupied)
            except Exception as exc:  # noqa: BLE001 — injected alloc failure
                cause = health.classify_failure(exc)
                if cause in RETRYABLE_CLASSES \
                        and attempts < self.cfg.max_retries:
                    attempts += 1
                    backoff = min(
                        self.cfg.backoff_base_s * (2 ** (attempts - 1)),
                        self.cfg.backoff_cap_s)
                    health.SERVE.retry(rid, k, cause, backoff)
                    slot.retries += 1
                    self._sleep(backoff)
                    continue
                self.kv.release(row)
                self._live[row] = slot  # so _finalize_slot pops it
                done[rid] = self._finalize_slot(
                    slot, "evicted", f"kv allocation failed ({cause}): {exc}")
                return
            if not ok:  # raced a concurrent admission; wait in queue
                self._queue.appendleft(entry)
                return
            break
        with TraceAnnotation("serve.prefill"):
            logits = self._prefill(entry, slot, done)
        if logits is None:
            return
        self._live[row] = slot
        if entry.preempted:
            health.SERVE.resumed(rid, step=k)
        else:
            with TraceAnnotation("serve.token_wait"):
                tok = np.asarray(self.engine.sample_tokens(logits, [rid],
                                                           step=0))
            with TraceAnnotation("serve.commit"):
                slot.emitted.append(int(tok[0]))
                health.SERVE.live(rid, admit_t=admit_t,
                                  first_token_t=self._clock())
                if len(slot.emitted) >= slot.budget:
                    done[rid] = self._finalize_slot(slot, "completed")

    def _prefill(self, entry: _QEntry, slot: _CSlot,
                 done: Dict[int, RequestResult]):
        """Prefill the slot's prompt into its row (replaying a resumed
        request's generated prefix): the prompt's last logits, or None when
        the request was evicted."""
        req, row = entry.req, slot.row
        rid = req.request_id
        S = req.tokens.shape[0]
        k = len(entry.emitted)
        # Prefill (+ teacher-forced replay of the resumed prefix): pure in
        # (prompt, prefix), so the whole sequence retries as a unit (pool
        # writes are deterministic overwrites, safe to redo). A quantized
        # pool replays through the paged cache itself — insert (quantize
        # prompt positions once), then gather-dequant → decode →
        # quantize-write per replayed token, the exact cycle the live
        # batched path ran — so the resumed pool bytes equal the
        # uninterrupted run's and the bitwise-resume contract holds.
        attempts = 0
        while True:
            try:
                faults.maybe_fail("engine_step")
                logits, caches = self.engine.prefill_request(req.tokens)
                if self.kv.quantize:
                    self.kv.insert_dense(row, caches)
                    for i in range(k - 1):
                        tok = jnp.asarray([[slot.emitted[i]]], jnp.int32)
                        raw, caches = self.engine.decode_request(
                            self.kv.gather_slot(row), tok, S + i)
                        self.kv.write_position(row, S + i, caches)
                else:
                    for i in range(k - 1):
                        tok = jnp.asarray([[slot.emitted[i]]], jnp.int32)
                        raw, caches = self.engine.decode_request(
                            caches, tok, S + i)
                logits = faults.corrupt("sample", logits)
                if health.numerics_guard_enabled() \
                        and health.has_nonfinite(logits):
                    raise health.NumericsError(
                        f"non-finite logits for request {rid} at admission")
            except Exception as exc:  # noqa: BLE001 — classify, retry/evict
                cause = health.classify_failure(exc)
                if cause in RETRYABLE_CLASSES \
                        and attempts < self.cfg.max_retries:
                    attempts += 1
                    backoff = min(
                        self.cfg.backoff_base_s * (2 ** (attempts - 1)),
                        self.cfg.backoff_cap_s)
                    health.SERVE.retry(rid, k, cause, backoff)
                    slot.retries += 1
                    self._sleep(backoff)
                    continue
                self._live[row] = slot
                done[rid] = self._finalize_slot(
                    slot, "evicted", f"{cause}: {exc}")
                return None
            break
        if not self.kv.quantize:
            # Quantized pools already committed in the guarded loop above
            # (an insert here would re-quantize dequantized values — drift).
            self.kv.insert_dense(row, caches)
        return logits

    def _admissions(self, done: Dict[int, RequestResult]) -> None:
        now = self._clock()
        while self._queue and len(self._live) < self.cfg.max_live:
            entry = self._queue[0]
            deadline = (entry.req.deadline_s
                        if entry.req.deadline_s is not None
                        else self.cfg.default_deadline_s)
            if deadline is not None and now - entry.admit_t > deadline:
                self._queue.popleft()
                done[entry.req.request_id] = self._finalize_queued(
                    entry, "deadline_miss",
                    f"deadline {deadline:.3f}s elapsed in queue")
                continue
            occupied = entry.req.tokens.shape[0] \
                + max(0, len(entry.emitted) - 1)
            need = self.kv.blocks_for(occupied)
            if need > self.kv.alloc.capacity:
                self._queue.popleft()
                done[entry.req.request_id] = self._finalize_queued(
                    entry, "evicted",
                    f"resource: needs {need} KV blocks, pool capacity "
                    f"{self.kv.alloc.capacity}")
                continue
            if need > self.kv.alloc.free_count:
                break  # backpressure: wait for live rows to free blocks
            self._queue.popleft()
            row = self._free_row()
            before = len(done)
            with TraceAnnotation("serve.admit",
                                 request_id=entry.req.request_id):
                self._admit_one(entry, row, done)
            if row not in self._live and len(done) == before:
                break  # entry went back to the queue head; stop admitting

    # ----- stepping -------------------------------------------------------

    def step(self) -> Dict[int, RequestResult]:
        """One scheduler tick: admit/resume into free rows, deadline-sweep
        the batch, grow KV (preempting under exhaustion), then advance every
        live row one token through the shared batched program. Returns newly
        finalized results."""
        with TraceAnnotation("serve.tick"):
            done: Dict[int, RequestResult] = {}
            self._admissions(done)
            now = self._clock()
            for row in sorted(self._live):
                slot = self._live[row]
                if slot.deadline_s is not None \
                        and now - slot.admit_t > slot.deadline_s:
                    done[slot.req.request_id] = self._finalize_slot(
                        slot, "deadline_miss",
                        f"deadline {slot.deadline_s:.3f}s elapsed")
            with TraceAnnotation("serve.kv_grow"):
                self._grow_all(done)
            if self._live:
                self._batched_step(done)
            return done

    def _grow_all(self, done: Dict[int, RequestResult]) -> None:
        """Ensure every live row's next write position is block-backed,
        preempting the newest-admitted live request on real exhaustion
        (oldest rows grow first, so the victim ordering is deterministic)."""
        for slot in sorted(self._live.values(), key=lambda s: s.admit_seq):
            if slot.row not in self._live:
                continue  # preempted by an earlier grower this tick
            rid = slot.req.request_id
            write_pos = slot.req.tokens.shape[0] + len(slot.emitted) - 1
            attempts = 0
            while True:
                try:
                    ok = self.kv.grow(slot.row, write_pos + 1)
                except Exception as exc:  # noqa: BLE001 — injected kv_alloc
                    cause = health.classify_failure(exc)
                    if cause in RETRYABLE_CLASSES \
                            and attempts < self.cfg.max_retries:
                        attempts += 1
                        backoff = min(
                            self.cfg.backoff_base_s * (2 ** (attempts - 1)),
                            self.cfg.backoff_cap_s)
                        health.SERVE.retry(rid, len(slot.emitted), cause,
                                           backoff)
                        slot.retries += 1
                        self._sleep(backoff)
                        continue
                    done[rid] = self._finalize_slot(
                        slot, "evicted",
                        f"kv allocation failed ({cause}): {exc}")
                    break
                if ok:
                    break
                victim = self._newest_live()
                self._preempt(
                    victim,
                    f"kv pool exhausted growing request {rid} "
                    f"(free {self.kv.alloc.free_count})")
                if victim is slot:
                    break  # self-preempted: parked, resumes later

    def _batched_step(self, done: Dict[int, RequestResult]) -> None:
        """Advance the whole batch one token: guarded shared attempt with
        classified retry, then bisection on retry exhaustion."""
        cfg = self.cfg
        failure = None
        with TraceAnnotation("serve.step_dispatch"):
            tokens = np.zeros((cfg.max_live, 1), np.int32)
            pos = np.zeros((cfg.max_live,), np.int32)
            for row, slot in self._live.items():
                tokens[row, 0] = slot.emitted[-1]
                pos[row] = slot.req.tokens.shape[0] + len(slot.emitted) - 1
            live_rows = sorted(self._live)
            attempts = 0
            while True:
                try:
                    faults.maybe_fail("batch_step")
                    kv = self.kv
                    if kv.quantize:
                        logits, pk, pv, sk, sv = self._jit_step(
                            self.engine.params, kv.pool["k"], kv.pool["v"],
                            kv.scales["k"], kv.scales["v"],
                            kv.device_tables(), jnp.asarray(tokens),
                            jnp.asarray(pos))
                    else:
                        sk = sv = None
                        logits, pk, pv = self._jit_step(
                            self.engine.params, kv.pool["k"], kv.pool["v"],
                            kv.device_tables(), jnp.asarray(tokens),
                            jnp.asarray(pos))
                except Exception as exc:  # noqa: BLE001 — retry, bisect
                    cause = health.classify_failure(exc)
                    if cause in RETRYABLE_CLASSES \
                            and attempts < cfg.max_retries:
                        attempts += 1
                        backoff = min(
                            cfg.backoff_base_s * (2 ** (attempts - 1)),
                            cfg.backoff_cap_s)
                        for row in live_rows:
                            slot = self._live[row]
                            health.SERVE.retry(slot.req.request_id,
                                               len(slot.emitted), cause,
                                               backoff)
                            slot.retries += 1
                        self._sleep(backoff)
                        continue
                    failure = (cause, exc)
                break
        if failure is not None:
            self._bisect(done, *failure)
            return
        # Commit only after a clean shared step (retries/bisection never see
        # a half-mutated pool — the jit'd step returned NEW pool arrays).
        self.kv.pool["k"], self.kv.pool["v"] = pk, pv
        if sk is not None:
            self.kv.scales["k"], self.kv.scales["v"] = sk, sv
        self._commit_rows(done, live_rows, logits)

    def _bisect(self, done: Dict[int, RequestResult], cause, exc) -> None:
        """Blast-radius containment: re-run each live row ALONE on the
        batch-1 path against its gathered dense cache (bitwise the batched
        computation for that row). A row whose re-run fails is GUILTY and
        evicted; an exonerated row's re-run result is committed directly, so
        survivors are bitwise identical to an undisturbed run."""
        for row in sorted(self._live):
            slot = self._live[row]
            rid = slot.req.request_id
            step_idx = len(slot.emitted)
            write_pos = slot.req.tokens.shape[0] + step_idx - 1
            try:
                faults.maybe_fail("batch_step")   # per-re-run probe
                dense = self.kv.gather_slot(row)
                tok = jnp.asarray([[slot.emitted[-1]]], jnp.int32)
                raw, new_caches = self.engine.decode_request(
                    dense, tok, write_pos)
                logits_row = raw[:, 0]
                if health.numerics_guard_enabled() \
                        and health.has_nonfinite(logits_row):
                    raise health.NumericsError(
                        f"non-finite logits for request {rid} "
                        f"at step {step_idx}")
            except Exception as exc2:  # noqa: BLE001 — guilty verdict
                cause2 = health.classify_failure(exc2)
                health.SERVE.bisect(rid, step_idx, "guilty",
                                    f"{cause2}: {exc2}")
                done[rid] = self._finalize_slot(
                    slot, "evicted",
                    f"bisection: batched step failed ({cause}: {exc}); "
                    f"re-run guilty ({cause2}: {exc2})")
                continue
            health.SERVE.bisect(rid, step_idx, "exonerated",
                                f"batched step failed ({cause})")
            self.kv.write_position(row, write_pos, new_caches)
            self._commit_rows(done, [row], logits_row, row_index={row: 0})

    def _commit_rows(self, done: Dict[int, RequestResult], rows: List[int],
                     logits_b, row_index: Optional[Dict[int, int]] = None
                     ) -> None:
        """Sample + commit one token per row (per-row numerics guard first:
        a poisoned row is evicted alone, its committed write scrubbed by
        release).

        ``logits_b`` is a device logits batch; row ``r`` samples from
        ``logits_b[row_index[r]]`` (identity when ``row_index`` is None —
        the batched step's full ``[max_live, V]`` output). Sampling runs at
        the FULL batch width with non-committing positions padded by the
        first committing row's (rid, step): rows are independent in the
        sampler's vmap, so padding can't perturb a real row's token, and one
        compiled width serves every tick instead of one per live-row count
        (plus it skips the per-row slice/re-stack dispatches)."""
        commit = []
        for row in rows:
            slot = self._live[row]
            idx = row if row_index is None else row_index[row]
            if health.numerics_guard_enabled() \
                    and health.has_nonfinite(logits_b[idx]):
                done[slot.req.request_id] = self._finalize_slot(
                    slot, "evicted",
                    f"numerics: non-finite logits at step "
                    f"{len(slot.emitted)}")
                continue
            commit.append(row)
        if not commit:
            return
        width = logits_b.shape[0]
        rids = np.full((width,), self._live[commit[0]].req.request_id,
                       np.int32)
        steps = np.full((width,), len(self._live[commit[0]].emitted),
                        np.int32)
        for row in commit:
            idx = row if row_index is None else row_index[row]
            rids[idx] = self._live[row].req.request_id
            steps[idx] = len(self._live[row].emitted)
        with TraceAnnotation("serve.token_wait"):
            toks = np.asarray(self.engine.sample_tokens(logits_b, rids,
                                                        steps))
        with TraceAnnotation("serve.commit"):
            for row in commit:
                idx = row if row_index is None else row_index[row]
                slot = self._live[row]
                slot.emitted.append(int(toks[idx]))
                if len(slot.emitted) >= slot.budget:
                    done[slot.req.request_id] = self._finalize_slot(
                        slot, "completed")

    # ----- driving loops --------------------------------------------------

    def drain(self, max_ticks: int = 1_000_000) -> Dict[int, RequestResult]:
        """Step until every admitted request reaches a terminal state.

        A full drain must return EVERY block to the pool (the no-leak clause
        of the block-accounting contract): a shortfall here is a scheduler
        bug, not load — it is recorded as a ``kv_leak`` health event and
        raised, never silently absorbed into a shrunken pool."""
        done: Dict[int, RequestResult] = {}
        ticks = 0
        while self._queue or self._live:
            done.update(self.step())
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("drain exceeded max_ticks — a request "
                                   "is not making progress")
        alloc = self.kv.alloc
        if alloc.free_count != alloc.capacity:
            leaked = alloc.capacity - alloc.free_count
            detail = (f"{leaked} of {alloc.capacity} KV blocks still held "
                      "after a full drain")
            health.record_degradation("continuous_scheduler.drain",
                                      "paged_kv", "kv_leak", "none", detail)
            raise RuntimeError(f"kv_leak: {detail}")
        return done

    def run(self, schedule: Iterable[Tuple[float, Request]],
            tick_s: float = 0.0) -> Dict[int, RequestResult]:
        """Serve a timed arrival schedule ``[(arrival_s, request), ...]``
        exactly like ``StreamFrontend.run``."""
        sched = sorted(schedule, key=lambda it: it[0])
        results: Dict[int, RequestResult] = {}
        t0 = self._clock()
        i = 0
        while i < len(sched) or self._queue or self._live:
            now = self._clock() - t0
            while i < len(sched) and sched[i][0] <= now:
                req = sched[i][1]
                i += 1
                res = self.submit(req)
                if res is not None:
                    results[req.request_id] = res
            if not self._queue and not self._live:
                if i < len(sched):   # idle: wait for the next arrival
                    self._sleep(max(sched[i][0] - now, 1e-9))
                continue
            results.update(self.step())
            if tick_s:
                self._sleep(tick_s)
        return results

    # ----- observability --------------------------------------------------

    def stats(self) -> Dict[str, int]:
        """Queue/slot depths, KV-block accounting, and the registry's
        conservation counters. ``preempted_open`` is the transient
        preempted population (in the extended invariant ``admitted ==
        completed + evicted + deadline_miss + open + preempted_open``)."""
        stats = dict(health.SERVE.counters())
        stats["queued"] = sum(1 for e in self._queue if not e.preempted)
        stats["preempted_open"] = sum(1 for e in self._queue if e.preempted)
        stats["live"] = len(self._live)
        stats["kv_blocks_free"] = self.kv.alloc.free_count
        stats["kv_blocks_capacity"] = self.kv.alloc.capacity
        return stats
