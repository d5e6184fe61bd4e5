"""Reduction of a JAX profiler trace to what the per-layer metrics read.

``load`` reads the newest ``.xplane.pb`` under a trace directory with
``jax.profiler.ProfileData`` and keeps three kinds of interval, each as
``(start_ns, end_ns, name)`` on the profiler's clock:

* ``ops``: device operations of the first TPU (its ``XLA Ops`` line), each
  named by its HLO text (``%closed_call.56 = bf16[16,2048]{...}
  custom-call(...)``, operands included), with the op's HLO category where
  the trace gives one. An op that holds others (a ``while`` around a layer
  loop) is on that line too, around its body's ops;
* ``modules``: device programs of that TPU (its ``XLA Modules`` line);
* ``spans``: the benchmark's own host spans (``sched.step``,
  ``engine.prefill_request``, ``engine.sample_tokens``).

The traced window is from the first to the last event of any of these. A
device is busy where at least one op runs: the union of op intervals.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import shutil
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_NAMES = ("sched.step", "engine.prefill_request", "engine.sample_tokens")

Interval = Tuple[int, int, str]


@dataclasses.dataclass
class Reduced:
    ops: List[Tuple[int, int, str, str]]     # start, end, name, category
    modules: List[Interval]
    spans: List[Interval]
    t0: int
    t1: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Reduced":
        return cls(ops=[tuple(o) for o in d["ops"]],
                   modules=[tuple(m) for m in d["modules"]],
                   spans=[tuple(s) for s in d["spans"]],
                   t0=d["t0"], t1=d["t1"])


def xplane_file(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _stat(event, name: str):
    for k, v in event.stats:
        if k == name:
            return v
    return None


def reduce_profile(data) -> Reduced:
    """``data``: a ``jax.profiler.ProfileData``."""
    ops, modules, spans = [], [], []
    tpus = sorted((p for p in data.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(re.sub(r"\D", "", p.name) or 0))
    if tpus:
        for line in tpus[0].lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    cat = _stat(e, "hlo_category")
                    ops.append((int(e.start_ns), int(e.end_ns), e.name,
                                str(cat) if cat is not None else ""))
            elif line.name == "XLA Modules":
                modules.extend((int(e.start_ns), int(e.end_ns), e.name)
                               for e in line.events)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((int(e.start_ns), int(e.end_ns), e.name)
                             for e in line.events if e.name in SPAN_NAMES)
    ops.sort()
    modules.sort()
    spans.sort()
    points = [x for group in (ops, modules, spans) for iv in group
              for x in iv[:2]]
    if not points:
        raise ValueError("the trace holds no device op and no span")
    return Reduced(ops=ops, modules=modules, spans=spans, t0=min(points),
                   t1=max(points))


def load(directory: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(xplane_file(directory)))


def save_context(path: str, reduced: Reduced, calls, ticks, host_window,
                 prompt_len: Dict[int, int], trace_dir: Optional[str] = None,
                 xplane_max_bytes: int = 32 << 20) -> None:
    """Keep what the readers read as gzipped JSON (and, when it is small,
    the raw ``.xplane.pb`` beside it as ``<path>.xplane.pb.gz``)."""
    with gzip.open(path, "wt") as f:
        json.dump({"reduced": reduced.to_json(), "calls": calls,
                   "ticks": ticks, "host_window": list(host_window),
                   "prompt_len": {str(r): n for r, n in prompt_len.items()}},
                  f)
    if trace_dir is not None:
        raw = xplane_file(trace_dir)
        if os.path.getsize(raw) <= xplane_max_bytes:
            with open(raw, "rb") as src, gzip.open(path + ".xplane.pb.gz",
                                                    "wb") as dst:
                shutil.copyfileobj(src, dst)


def load_context(path: str, model, arch: dict, serving: dict, peaks: dict):
    """A ``Context`` from a file ``save_context`` wrote."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return Context(trace=Reduced.from_json(d["reduced"]), model=model,
                   arch=arch, serving=serving, peaks=peaks,
                   prompt_len={int(r): n for r, n in d["prompt_len"].items()},
                   calls=[(t, w, [tuple(p) for p in pairs])
                          for t, w, pairs in d["calls"]],
                   ticks=[tuple(t) for t in d["ticks"]],
                   host_window=tuple(d["host_window"]))


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]




class Coverage:
    """Union of intervals, queried for how much of ``[lo, hi)`` it covers."""

    def __init__(self, intervals):
        self.iv = union(intervals)
        self.starts = [s for s, _ in self.iv]

    def covered(self, lo: int, hi: int) -> int:
        if hi <= lo:
            return 0
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        j = bisect.bisect_left(self.starts, hi)
        total = 0
        for s, e in self.iv[i:j]:
            total += max(0, min(e, hi) - max(s, lo))
        return total


def busy_s(r: Reduced) -> float:
    return Coverage([(o[0], o[1]) for o in r.ops]).covered(r.t0, r.t1) / 1e9


def inside(ops, modules: Sequence[Interval]):
    """The ops that start inside one of ``modules`` (both sorted)."""
    starts = [o[0] for o in ops]
    out = []
    for m in modules:
        out.extend(ops[bisect.bisect_left(starts, m[0]):
                       bisect.bisect_left(starts, m[1])])
    return out


def idle_gaps(r: Reduced) -> List[Tuple[int, int]]:
    busy = union([(o[0], o[1]) for o in r.ops])
    gaps, cur = [], r.t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if r.t1 > cur:
        gaps.append((cur, r.t1))
    return gaps


def host_activity(r: Reduced, t: int) -> str:
    """The innermost benchmark span holding ``t``, or what lies outside."""
    best: Optional[Interval] = None
    for s in r.spans:
        if s[0] <= t < s[1] and (best is None or s[1] - s[0] < best[1] - best[0]):
            best = s
    return best[2] if best else "host outside the serving step"


def op_label(text: str) -> str:
    """An op's name and result type (``copy.18 bf16[16,16,64,16,16,128]``)."""
    _, _, rest = text.partition(" = ")
    m = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    return short_name(text) + (" " + m.group(1) if m else "")


def breakdown(r: Reduced, top: int = 10) -> Dict[str, list]:
    """The device ops that took most time (by name and result type; an op
    that holds others, such as a ``while`` around a layer loop, is left out
    so that no time counts twice), and the longest idle gaps named by what
    the host was doing at their middle."""
    by_op: Dict[str, int] = {}
    held = containers(r.ops)
    for i, (s, e, name, cat) in enumerate(r.ops):
        if i in held:
            continue
        key = op_label(name) + (f" [{cat}]" if cat else "")
        by_op[key] = by_op.get(key, 0) + (e - s)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(r), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[host_activity(r, (a + b) // 2), (b - a) / 1e9]
                          for a, b in gaps]}


_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9_\-]*)\(")
_OPERAND = re.compile(r"(\w+)\[([\d,]*)\]\{[^}]*\} %([\w.\-]+)")
# What may stage a GEMM's weight tiles for it: the copies and slices XLA
# puts between the stacked weights and the kernel's operand.
_STAGING = ("fusion", "custom-call", "slice-start", "slice-done",
            "copy-start", "copy-done", "copy", "dynamic-slice", "bitcast")


def short_name(text: str) -> str:
    """An op's HLO name (``closed_call.56``) from the text the trace gives."""
    return text.split(" = ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    """The op's HLO opcode (``custom-call``, ``fusion``, ``while``)."""
    _, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    return m.group(1) if m else ""


def operands(text: str) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(dtype, shape, name)`` of each operand of an op's HLO text."""
    _, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    args = rest[m.end():] if m else rest
    return [(d, tuple(int(x) for x in dims.split(",") if x), name)
            for d, dims, name in _OPERAND.findall(args)]


def gemm_weight_operand(op, shapes) -> Optional[str]:
    """For a packed-weight GEMM kernel, the name of its weight operand; else
    None. Such a kernel is a ``tpu_custom_call`` one of whose operands is a
    packed tile grid ``[Nb, Kb, t0, t1]`` of one of the model's weights
    ``(K, N)``: ``Kb * t0 == K`` and ``Nb == ceil(N / t1)``. Kernels are
    named after their callers in a trace (``closed_call.56``), so shapes,
    not names, tell a GEMM from another kernel."""
    text = op[2]
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    for _, shape, name in operands(text):
        if len(shape) == 4:
            nb, kb, t0, t1 = shape
            if any(kb * t0 == k and nb == -(-n // t1) for k, n in shapes):
                return name
    return None


def containers(ops) -> set:
    """Indices of ops that hold others (a ``while`` around its body): on
    the trace's op line, an op inside whose span another starts."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    out, open_ = set(), []
    for i in order:
        while open_ and ops[open_[-1]][1] <= ops[i][0]:
            open_.pop()
        if open_:
            out.add(open_[-1])
        open_.append(i)
    return out


def gemm_intervals(ops, shapes) -> List[Tuple[int, int]]:
    """Where one program's weight GEMMs run: each GEMM kernel, and the ops
    that stage its weight operand for it (a dynamic slice of the stacked
    layer weights into the kernel's memory, and what that slice is made
    from, up to three steps back). ``ops``: the program's ops."""
    by_name: Dict[str, list] = {}
    for o in ops:
        by_name.setdefault(short_name(o[2]), []).append(o)
    out, staged, todo = [], set(), []
    for o in ops:
        w = gemm_weight_operand(o, shapes)
        if w is not None:
            out.append((o[0], o[1]))
            todo.append((w, 0))
    while todo:
        name, depth = todo.pop()
        if name in staged or name not in by_name or depth > 3:
            continue
        events = by_name[name]
        if opcode(events[0][2]) not in _STAGING:
            continue
        staged.add(name)
        out.extend((e[0], e[1]) for e in events)
        todo.extend((n, depth + 1) for _, _, n in operands(events[0][2]))
    return out


@dataclasses.dataclass
class Tick:
    """One traced scheduler tick: its span on the trace's clock, the
    sampling calls the host made in it, and the device programs that began
    in it and hold a GEMM, in order."""
    span: Interval
    calls: list
    programs: List[Interval]

    def prefills(self) -> List[Interval]:
        """A tick admits first (one prefill program per admitted request,
        each followed by a one-row sampling call) and then runs the batched
        step: the leading programs are the prefills."""
        n = sum(1 for _, width, _ in self.calls if width == 1)
        return self.programs[:n]

    def step(self, width: int) -> Optional[Interval]:
        """The batched step: the last GEMM program of a tick whose last
        sampling call spans the whole batch."""
        if self.calls and self.calls[-1][1] == width and self.programs:
            return self.programs[-1]
        return None


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader gets. ``model`` is the cell's
    architecture module (``chipbench/arch/<name>.py``) and ``arch`` what its
    ``arch_of`` read from the configuration."""
    trace: Reduced
    model: object
    arch: dict
    serving: dict
    peaks: dict
    prompt_len: Dict[int, int]         # request id -> prompt length
    calls: list                        # TokenClock.calls (host clock)
    ticks: List[Tuple[float, float]]   # scheduler ticks (host clock)
    host_window: Tuple[float, float]   # profiler on / off (host clock)

    def traced_ticks(self) -> List[Interval]:
        return [s for s in self.trace.spans if s[2] == "sched.step"]

    def gemm_shapes(self) -> List[Tuple[int, int]]:
        return self.model.weight_shapes(self.arch)

    def program_ops(self, module: Interval) -> list:
        return inside(self.trace.ops, [module])

    def tick_programs(self) -> List[Tick]:
        """The traced ``sched.step`` spans are the host ticks that began
        after the profiler started, in order (it starts and stops between
        ticks): pair them, and give each its calls and the programs that
        hold a weight GEMM."""
        spans = self.traced_ticks()
        host = [t for t in self.ticks if t[0] >= self.host_window[0]]
        starts = [m[0] for m in self.trace.modules]
        shapes = self.gemm_shapes()
        out = []
        for span, (h0, h1) in zip(spans, host):
            calls = [c for c in self.calls if h0 <= c[0] <= h1]
            mods = self.trace.modules[bisect.bisect_left(starts, span[0]):
                                      bisect.bisect_left(starts, span[1])]
            progs = [m for m in mods
                     if any(gemm_weight_operand(o, shapes) is not None
                            for o in self.program_ops(m))]
            out.append(Tick(span, calls, progs))
        return out

    def step_modules(self) -> List[Tuple[Interval, list]]:
        """Each traced batched step with the (request, token index) pairs
        it produced for live rows."""
        width = self.serving["max_live"]
        out = []
        for tick in self.tick_programs():
            step = tick.step(width)
            if step is not None:
                out.append((step, tick.calls[-1][2]))
        return out

    def prefill_modules(self) -> List[Tuple[Interval, int]]:
        """Each traced prefill program with its prompt length."""
        out = []
        for tick in self.tick_programs():
            firsts = [pairs[0][0] for _, width, pairs in tick.calls
                      if width == 1 and pairs]
            for prog, rid in zip(tick.prefills(), firsts):
                out.append((prog, self.prompt_len[rid]))
        return out

    def gemm_time_s(self, modules: Sequence[Interval]) -> float:
        """Device time in which the programs' weight GEMMs ran, their
        weights' staging included (``gemm_intervals``)."""
        shapes = self.gemm_shapes()
        total = 0
        for m in modules:
            iv = union(gemm_intervals(self.program_ops(m), shapes))
            total += sum(e - s for s, e in iv)
        return total / 1e9
