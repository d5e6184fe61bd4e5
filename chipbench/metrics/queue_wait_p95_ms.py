"""Scheduler layer (``serve/scheduler.py``): how long requests wait in the
queue.

The 95th percentile (nearest rank), over the traffic's requests admitted
into a row from the start of the run until the profiler stopped, of the
time from entering the queue to the start of their admission (``queued_t``
to ``admit_t``, which the scheduler stamps into its serving registry;
``program.request_times``): the wait for a free row or free KV blocks, and
behind earlier prefills of the same tick. Stopping the profiler stalls the
serving loop for seconds (6-11 s after a 3 s trace on a TPU v5e host), so
requests admitted later waited on the benchmark's tracing, not on the
program: they are left out. The scheduler's clock (``time.monotonic``, the benchmark
keeps the default) and the profiler's window (``time.perf_counter``) are
compared through their offset now. Also prints admission to first token.
Moves ``ttft_p95_ms``.
"""
import time

from chipbench import program as P
from chipbench.window import percentile

UNIT, LAYER, MOVES = "ms", "scheduler", "ttft_p95_ms"


def read(ctx, times=None):
    times = P.request_times(ctx.prompt_len) if times is None else times
    stop = ctx.host_window[1] - (time.perf_counter() - time.monotonic())
    kept = {r: t for r, t in times.items()
            if t.get("admit_t") is not None and t["admit_t"] <= stop}
    waits = P.waits_s(kept, "queued_t", "admit_t")
    first = P.waits_s(kept, "admit_t", "first_token_t")
    if first:
        print(f"[trace] admission to first token over {len(first)} requests: "
              f"p50 {1e3 * percentile(first, 50):.6f} ms, p95 "
              f"{1e3 * percentile(first, 95):.6f} ms", flush=True)
    return 1e3 * percentile(waits, 95) if waits else None
