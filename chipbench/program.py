#!/usr/bin/env python3
"""What the program says about itself in a traced run, for the readers that
need more than ``trace.Context`` carries.

* Its host spans, named ``serve.*``: each scheduler tick (``serve.tick``)
  holding its phases in order (``serve.admit`` per admission, holding
  ``serve.prefill``; then ``serve.kv_grow``, ``serve.step_dispatch``,
  ``serve.token_wait`` and ``serve.commit``). None of them is one of
  ``trace.SPAN_NAMES``, so the benchmark's own spans still count each tick
  once.
* Each device op's program scope: the ``op_name`` it was compiled with
  (``jit(step)/kv_gather/gather``), which carries the program's
  ``jax.named_scope`` names. The TPU trace keeps it as the stat ``tf_op``
  of the op's event metadata; ``jax.profiler.ProfileData`` does not read
  metadata stats, so ``op_scopes`` reads them from the ``.xplane.pb``.
* Its request times (``queued_t``, ``admit_t``, ``first_token_t``), which
  the scheduler stamps on its clock into ``repro.core.health.SERVE``.

A reader finds the traced run's ``.xplane.pb`` in ``TRACE_DIR``, where
``run.py`` and ``calibrate.py`` trace, before the run deletes it, and uses
it only when its ops are exactly those of the reader's ``Context``.

  python chipbench/program.py record --workload <cell> --seed 7 \\
      --seconds 4 --ticks 2 --out <dir>/<cell>.trace.json.gz

records ``--ticks`` scheduler ticks as ``calibrate.py record`` does (the
reduced trace in ``--out``) and writes beside it ``<cell>.program.json.gz``:
the ticks' program spans, the ops' scopes in the reduced trace's order, and
the run's request times.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import math
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace as T  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
SPAN_PREFIX = "serve."
# The program's named scopes: the batched step's KV view, and the model's.
KV_SCOPES = ("kv_gather", "kv_write", "kv_scatter")
SCOPES = KV_SCOPES + ("attention", "mlp", "lm_head", "sample")
REQUEST_TIMES = ("queued_t", "admit_t", "first_token_t")


# ----- the .xplane.pb ------------------------------------------------------
# A protobuf reader for what ProfileData leaves out, the stats of an event's
# metadata (``tensorflow/tsl/profiler/protobuf/xplane.proto``: XSpace planes
# 1; XPlane name 2, lines 3, event_metadata 4, stat_metadata 5; XLine name
# 2, events 4; XEvent metadata_id 1, stats 4; XEventMetadata name 2, stats
# 5; XStatMetadata name 2; XStat metadata_id 1, str_value 5, ref_value 7).

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, span: Tuple[int, int]):
    """``(field, value)`` of one message in ``buf[span[0]:span[1]]``: an int
    for scalar fields, a ``(start, end)`` span for length-delimited ones."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span):
    """``(key, value span)`` of one entry of a protobuf map field."""
    key, value = 0, (0, 0)
    for f, v in _fields(buf, span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(path: str, stat: str = "tf_op") -> List[Tuple[str, str]]:
    """``(name, scope)`` of each event of the first TPU's ``XLA Ops`` lines
    in an ``.xplane.pb``, in the file's order: the scope is the event's (or
    else its metadata's) stat ``stat``; ``""`` where it has none."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for field, span in _fields(buf, (0, len(buf))):
        if field == 1:
            name = next((_text(buf, v) for f, v in _fields(buf, span)
                         if f == 2), "")
            if name.startswith("/device:TPU:"):
                planes.append((int(re.sub(r"\D", "", name) or 0), span))
    if not planes:
        return []
    stat_names: Dict[int, str] = {}
    metadata: Dict[int, Tuple[str, list]] = {}
    lines = []
    for field, span in _fields(buf, min(planes)[1]):
        if field == 5:
            key, value = _map_entry(buf, span)
            stat_names[key] = next((_text(buf, v) for f, v in
                                    _fields(buf, value) if f == 2), "")
        elif field == 4:
            key, value = _map_entry(buf, span)
            name, stats = "", []
            for f, v in _fields(buf, value):
                if f == 2:
                    name = _text(buf, v)
                elif f == 5:
                    stats.append(v)
            metadata[key] = (name, stats)
        elif field == 3:
            lines.append(span)

    def value_of(stats) -> Optional[str]:
        for st in stats:
            sid, val = None, None
            for f, v in _fields(buf, st):
                if f == 1:
                    sid = v
                elif f == 5:
                    val = _text(buf, v)
                elif f == 7:
                    val = stat_names.get(v, "")
            if stat_names.get(sid) == stat and val is not None:
                return val
        return None

    scope_of = {k: value_of(st) or "" for k, (_, st) in metadata.items()}
    out: List[Tuple[str, str]] = []
    for line in lines:
        events, name = [], None
        for f, v in _fields(buf, line):
            if f == 2:
                name = _text(buf, v)
                if name != "XLA Ops":
                    break
            elif f == 4:
                events.append(v)
        if name != "XLA Ops":
            continue
        for ev in events:
            mid, stats = 0, []
            for f, v in _fields(buf, ev):
                if f == 1:
                    mid = v
                elif f == 4:
                    stats.append(v)
            own = value_of(stats) if stats else None
            out.append((metadata.get(mid, ("", []))[0],
                        own if own is not None else scope_of.get(mid, "")))
    return out


@dataclasses.dataclass
class ProgramTrace:
    op_scopes: List[str]              # aligned with Context.trace.ops
    spans: List[T.Interval]           # the program's serve.* host spans


def read_xplane(path: str) -> Tuple[list, ProgramTrace]:
    """The first TPU's ops as ``trace.reduce_profile`` orders them
    (``(start, end, name)``), with the program's scopes and spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    scopes = op_scopes(path)
    ops, spans = [], []
    tpus = sorted((p for p in data.planes
                   if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(re.sub(r"\D", "", p.name) or 0))
    if tpus:
        for line in tpus[0].lines:
            if line.name == "XLA Ops":
                ops.extend((int(e.start_ns), int(e.end_ns), e.name)
                           for e in line.events)
    if [n for n, _ in scopes] != [o[2] for o in ops]:
        raise ValueError(f"{path}: its XLA Ops differ from ProfileData's")
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((int(e.start_ns), int(e.end_ns), e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    order = sorted(range(len(ops)), key=lambda i: ops[i])
    return ([ops[i] for i in order],
            ProgramTrace([scopes[i][1] for i in order], sorted(spans)))


def load(ctx, directory: str = TRACE_DIR) -> Optional[ProgramTrace]:
    """The program's spans and scopes for ``ctx``'s trace: from the newest
    ``.xplane.pb`` under ``directory``, when it holds exactly ``ctx``'s
    ops; else None."""
    try:
        path = T.xplane_file(directory)
    except FileNotFoundError:
        return None
    ops, program = read_xplane(path)
    if ops != [o[:3] for o in ctx.trace.ops]:
        return None
    return program


# ----- device time by scope -------------------------------------------------

def holders(ops) -> List[Optional[int]]:
    """For each op, the index of the innermost op that holds it (a
    ``while`` around its body: an op inside whose span it starts), or None
    (``trace.containers`` is the set of these)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    out: List[Optional[int]] = [None] * len(ops)
    open_: List[int] = []
    for i in order:
        while open_ and ops[open_[-1]][1] <= ops[i][0]:
            open_.pop()
        if open_:
            out[i] = open_[-1]
        open_.append(i)
    return out


def named_scopes(scope: str) -> set:
    """The program's named scopes in an op's scope path."""
    return set(scope.split("/")) & set(SCOPES)


def result_shape(text: str) -> Tuple[int, ...]:
    """The op's (first) result shape, from its HLO text."""
    _, _, rest = text.partition(" = ")
    m = re.match(r"\(?\w+\[([\d,]*)\]", rest)
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else ()


def step_scope_ms(ctx, scopes: List[str]) -> Optional[Dict[str, float]]:
    """Device ms per traced batched step under each named scope, and
    ``kv_view``: the ops scoped ``kv_gather``, ``kv_write`` or
    ``kv_scatter``, with the ops of no named scope that produce a whole KV
    buffer, the pool or the dense view in any layout (their sizes are the
    architecture's ``kv_elements``: the pool's copies, and the scan's
    stacking of the blended cache, which carries the scan's ``op_name``).
    An op with no scope of its own takes that of the op holding it (a
    gather loop's body takes the loop's); ops that hold others are left
    out, so no time counts twice. Each is the union of its ops' intervals.
    None when no op of the steps has a KV scope.
    ``scopes``: aligned with ``ctx.trace.ops``."""
    steps = [m for m, _ in ctx.step_modules()]
    if not steps or len(scopes) != len(ctx.trace.ops):
        return None
    kv_sizes = set(ctx.model.kv_elements(ctx.arch, ctx.serving))
    starts = [o[0] for o in ctx.trace.ops]
    found = False
    ivs: Dict[str, list] = {k: [] for k in SCOPES + ("kv_view",)}
    for m in steps:
        lo = bisect.bisect_left(starts, m[0])
        hi = bisect.bisect_left(starts, m[1])
        ops, own = ctx.trace.ops[lo:hi], scopes[lo:hi]
        up = holders(ops)
        held = {h for h in up if h is not None}
        for i, o in enumerate(ops):
            if i in held:
                continue
            j, scope = i, own[i]
            while not scope and up[j] is not None:
                j = up[j]
                scope = own[j]
            names = named_scopes(scope)
            for n in names:
                ivs[n].append(o[:2])
            kv = bool(names & set(KV_SCOPES))
            found |= kv
            if kv or (not names
                      and math.prod(result_shape(o[2])) in kv_sizes):
                ivs["kv_view"].append(o[:2])
    if not found:
        return None
    return {k: sum(e - s for s, e in T.union(v)) / 1e6 / len(steps)
            for k, v in ivs.items()}


# ----- host time by phase ---------------------------------------------------

def tick_idle_by_phase(trace, spans: List[T.Interval]) -> Dict[str, float]:
    """Device idle time (s) inside the program's ticks, each part given to
    the innermost ``serve.*`` span holding it: a phase, or ``serve.tick``
    itself where no phase does."""
    ticks = [s for s in spans if s[2] == "serve.tick"]
    phases = sorted(s for s in spans if s[2] != "serve.tick")
    starts = [s[0] for s in phases]
    out: Dict[str, float] = {}
    for a, b in T.idle_gaps(trace):
        for t0, t1, _ in ticks:
            lo, hi = max(a, t0), min(b, t1)
            if hi <= lo:
                continue
            inner = [p for p in phases[:bisect.bisect_left(starts, hi)]
                     if p[1] > lo]
            cuts = sorted({lo, hi} | {x for p in inner for x in p[:2]
                                      if lo < x < hi})
            for x, y in zip(cuts, cuts[1:]):
                held = [p for p in inner if p[0] <= x and y <= p[1]]
                name = (min(held, key=lambda p: p[1] - p[0])[2] if held
                        else "serve.tick")
                out[name] = out.get(name, 0.0) + (y - x) / 1e9
    return out


def describe(ctx, program: ProgramTrace) -> List[str]:
    """Printed only: the batched step's device time, ops and ms per named
    scope, and the ticks' device idle time by phase."""
    out = []
    steps = [m for m, _ in ctx.step_modules()]
    if steps:
        ms = step_scope_ms(ctx, program.op_scopes)
        out.append(
            f"[trace] batched step: {len(steps)} traced, device "
            f"{sum(m[1] - m[0] for m in steps) / 1e6 / len(steps):.6f} ms and "
            f"{len(ctx.program_ops(steps[0]))} ops each; ms per step by scope "
            f"{json.dumps(ms)}")
    n = sum(1 for s in program.spans if s[2] == "serve.tick")
    idle = tick_idle_by_phase(ctx.trace, program.spans)
    total = sum(idle.values())
    if n and total:
        per_tick = {k: v * 1e3 / n for k, v in sorted(idle.items())}
        under = 100 * (1 - idle.get("serve.tick", 0.0) / total)
        out.append(f"[trace] device idle inside {n} ticks: "
                   f"{total * 1e3 / n:.6f} ms per tick; by innermost span, ms "
                   f"per tick {json.dumps(per_tick)}; under a phase span "
                   f"{under:.3f}%")
    return out


# ----- request times --------------------------------------------------------

def request_times(ids) -> Dict[int, dict]:
    """The program's request times for the requests ``ids`` (the traffic's;
    warm-up requests are left out): those its serving registry still holds,
    with the times it stamped (None where it stamps none)."""
    from repro.core import health
    ids = set(ids)
    return {int(r): {k: rec.get(k) for k in REQUEST_TIMES}
            for r, rec in health.serve_report()["requests"].items()
            if int(r) in ids}


def waits_s(times: Dict[int, dict], start: str, end: str) -> List[float]:
    """``end - start`` of each request with both times."""
    return [t[end] - t[start] for t in times.values()
            if t.get(start) is not None and t.get(end) is not None]


# ----- recording -------------------------------------------------------------

def record(spec, seed: int, seconds: float, ticks: int, out_path: str,
           device) -> None:
    """``calibrate.record`` with the profiler on for ``ticks`` scheduler
    ticks, then the program's side of the same trace beside it."""
    import time
    from chipbench import calibrate, cell

    class TickTracer(cell.Tracer):
        """Stops after ``ticks`` calls of the serving loop once started."""

        def __init__(self, directory, start, length):
            super().__init__(directory, start, length)
            self.calls = 0

        def __call__(self, now):
            if self.t_on is None:
                super().__call__(now)
            elif self.t_off is None:
                self.calls += 1        # a tick ran since the last call
                if self.calls >= ticks:
                    self.stop()

    t = time.perf_counter()
    tracer, cell.Tracer = cell.Tracer, TickTracer
    try:
        calibrate.record(spec, seed, seconds, 3600.0, out_path, device)
    finally:
        cell.Tracer = tracer
    raw = out_path + ".xplane.pb.gz"
    tmp = raw[:-3]
    with gzip.open(raw, "rb") as src, open(tmp, "wb") as dst:
        dst.write(src.read())
    with gzip.open(out_path, "rt") as f:
        prompts = json.load(f)["prompt_len"]
    _, program = read_xplane(tmp)
    os.remove(tmp)
    side = re.sub(r"\.trace\.json\.gz$", "", out_path) + ".program.json.gz"
    with gzip.open(side, "wt") as f:
        json.dump({"op_scopes": program.op_scopes, "spans": program.spans,
                   "requests": {str(r): v for r, v in request_times(
                       int(r) for r in prompts).items()}}, f)
    print(json.dumps({"wrote": side, "wall_s": time.perf_counter() - t}),
          flush=True)


def main(argv=None) -> int:
    import argparse
    from chipbench import cell
    from chipbench.run import enable_cache, require_chips
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("record",))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--ticks", type=int, default=2)
    ap.add_argument("--out", required=True)
    ap.add_argument("--traffic", default="{}")
    args = ap.parse_args(argv)
    spec = cell.load(ROOT, args.workload)
    spec = dataclasses.replace(spec, traffic=dict(spec.traffic,
                                                  **json.loads(args.traffic)))
    device = require_chips(spec.chips)[0]
    enable_cache(ROOT)
    record(spec, args.seed, args.seconds, args.ticks, args.out, device)
    return 0


if __name__ == "__main__":
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
