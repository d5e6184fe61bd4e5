"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), per the assignment:

  compute    = HLO_FLOPs_global / (chips * peak_FLOP/s)
  memory     = HLO_bytes_global / (chips * HBM_bw)
  collective = collective_bytes_global / (chips * link_bw)

``cost_analysis()`` reports the *partitioned per-device* module, so global =
per_device * chips. Collective bytes are not in cost_analysis: we parse the
compiled HLO text and sum per-op traffic with a ring-model byte count:

  all-gather           result_bytes                  (each device receives it)
  all-reduce           2 * result_bytes * (g-1)/g    (reduce-scatter + gather)
  reduce-scatter       result_bytes * (g-1)          (input streams in)
  all-to-all           result_bytes * (g-1)/g
  collective-permute   result_bytes

where g is the replica-group size parsed from the op.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

import numpy as np

from repro.roofline.hw import V5E, TpuTarget, peak_flops

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5,  # sub-byte: two nibbles per stored byte
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %foo = f32[128,256]{1,0} all-gather(...)  or  (f32[8]{0}, f32[8]{0}) all-reduce(
_OP_RE = re.compile(
    r"=\s*(?P<types>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(types: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(types):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2  # conservative default


@dataclasses.dataclass
class CollectiveStats:
    per_device_bytes: float = 0.0
    op_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    op_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, op: str, nbytes: float):
        self.op_counts[op] = self.op_counts.get(op, 0) + 1
        self.op_bytes[op] = self.op_bytes.get(op, 0.0) + nbytes
        self.per_device_bytes += nbytes


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum modeled per-device collective traffic over the compiled module.

    Ops inside a while-loop body appear once in the text; the dry-run treats
    the per-step cost as the module cost (scan trip counts multiply both the
    FLOP and collective sides equally for per-layer collectives, so term
    *ratios* are unaffected; absolute seconds are per-compiled-call).
    """
    stats = CollectiveStats()
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        # -start/-done pairs: count the op once (on start; done repeats shape)
        if "-done(" in line:
            continue
        nbytes = _shape_bytes(m.group("types"))
        g = _group_size(line)
        if op == "all-reduce":
            traffic = 2.0 * nbytes * (g - 1) / g
        elif op == "all-gather":
            traffic = float(nbytes)
        elif op == "reduce-scatter":
            traffic = float(nbytes) * (g - 1)
        elif op == "all-to-all":
            traffic = float(nbytes) * (g - 1) / g
        else:  # collective-permute
            traffic = float(nbytes)
        stats.add(op, traffic)
    return stats


# ---------------------------------------------------------------------------
# HLO static cost model with call-graph rollup
# ---------------------------------------------------------------------------
# XLA's HloCostAnalysis counts while-loop bodies ONCE, so a scanned-layers
# model would report ~1/L of its real FLOPs. This analyzer parses the compiled
# module text, attributes dot FLOPs / streamed bytes / collective traffic to
# each computation, and rolls costs up the call graph multiplying while bodies
# by their known_trip_count (scan trip counts are static in our programs).

_TRIP_RE = re.compile(r'known_trip_count[":]+\s*\{\s*"n"\s*:\s*"(\d+)"')
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((?P<args>.*)\)"
                          r"\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
                       r"(?P<type>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)"
                       r"\s*(?P<op>[\w\-]+)\((?P<operands>[^)]*)")
_PARAM_RE = re.compile(r"%?([\w.\-]+):\s*([a-z0-9]+\[[0-9,]*\])")
_CALL_EDGE_RE = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_CALL_MULTI_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    bytes: float = 0.0          # streamed bytes: dot operands/results + slices
    coll_bytes: float = 0.0
    coll_bytes_bf16adj: float = 0.0  # f32 collectives halved (TPU moves bf16)
    coll_ops: Dict[str, float] = dataclasses.field(default_factory=dict)
    edges: List = dataclasses.field(default_factory=list)  # (callee, mult)


def _dims(shape_str: str) -> List[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _collective_traffic(op: str, nbytes: int, g: int) -> float:
    if op == "all-reduce":
        return 2.0 * nbytes * (g - 1) / g
    if op == "all-gather":
        return float(nbytes)
    if op == "reduce-scatter":
        return float(nbytes) * (g - 1)
    if op == "all-to-all":
        return float(nbytes) * (g - 1) / g
    return float(nbytes)  # collective-permute


class HloCostModel:
    """Whole-module FLOPs / streamed-bytes / collective model from HLO text."""

    def __init__(self, hlo_text: str):
        self.symbols: Dict[str, str] = {}     # instr/param name -> type string
        self.comps: Dict[str, CompCost] = {}
        self.entry: Optional[str] = None
        self._parse(hlo_text)

    def _parse(self, text: str) -> None:
        current: Optional[str] = None
        pending: List[tuple] = []
        for raw in text.splitlines():
            line = raw.rstrip()
            hdr = _COMP_HDR_RE.match(line.strip())
            if hdr and line.strip().endswith("{"):
                current = hdr.group(1)
                self.comps[current] = CompCost()
                if raw.lstrip().startswith("ENTRY"):
                    self.entry = current
                for pname, ptype in _PARAM_RE.findall(hdr.group("args")):
                    self.symbols[pname] = ptype
                continue
            if current is None:
                continue
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            name, type_str, op = m.group(1), m.group("type"), m.group("op")
            self.symbols[name] = type_str
            pending.append((current, name, type_str, op,
                            m.group("operands"), line))
        for comp, name, type_str, op, operands, line in pending:
            self._attribute(comp, name, type_str, op, operands, line)

    def _attribute(self, comp: str, name: str, type_str: str, op: str,
                   operands: str, line: str) -> None:
        cost = self.comps[comp]
        ops = _OPERAND_NAME_RE.findall(operands)
        if op == "dot":
            out_dims = _dims(type_str)
            lhs = self.symbols.get(ops[0], "") if ops else ""
            k = 1
            mk = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
            if mk and lhs:
                ld = _dims(lhs)
                for idx in mk.group(1).split(","):
                    if idx and int(idx) < len(ld):
                        k *= ld[int(idx)]
            flops = 2.0 * float(np.prod(out_dims) if out_dims else 0) * k
            cost.flops += flops
            cost.bytes += _shape_bytes(type_str)
            for o in ops[:2]:
                cost.bytes += _shape_bytes(self.symbols.get(o, ""))
        elif op in ("dynamic-slice", "gather"):
            cost.bytes += _shape_bytes(type_str)
        elif op == "dynamic-update-slice":
            if len(ops) >= 2:
                cost.bytes += _shape_bytes(self.symbols.get(ops[1], ""))
        elif op in _COLLECTIVES or any(op.startswith(c + "-") and
                                       not op.endswith("-done")
                                       for c in _COLLECTIVES):
            base = op
            for c in _COLLECTIVES:
                if op == c or op.startswith(c + "-"):
                    base = c
                    break
            if op.endswith("-done"):
                return
            nbytes = _shape_bytes(type_str)
            g = _group_size(line)
            traffic = _collective_traffic(base, nbytes, g)
            cost.coll_bytes += traffic
            # XLA:CPU reduces bf16 dot partials in f32 (pre-convert); the TPU
            # partitioner moves the converted bf16 value. Halve f32-typed
            # collective traffic for the TPU-adjusted term (documented in
            # EXPERIMENTS.md §Roofline caveats).
            adj = 0.5 if "f32[" in type_str else 1.0
            cost.coll_bytes_bf16adj += traffic * adj
            cost.coll_ops[base] = cost.coll_ops.get(base, 0.0) + traffic
        # call edges
        if op in ("fusion", "while", "call", "conditional", "reduce",
                  "reduce-window", "sort", "scatter", "custom-call", "map",
                  "all-reduce", "reduce-scatter"):
            trip = 1
            if op == "while":
                mt = _TRIP_RE.search(line)
                trip = int(mt.group(1)) if mt else 1
            for m_edge in _CALL_EDGE_RE.finditer(line):
                cost.edges.append((m_edge.group(1), trip))
            for m_edge in _CALL_MULTI_RE.finditer(line):
                for callee in _OPERAND_NAME_RE.findall(m_edge.group(1)):
                    cost.edges.append((callee, trip))

    def rollup(self, comp: Optional[str] = None, _memo=None) -> CompCost:
        comp = comp or self.entry
        _memo = {} if _memo is None else _memo
        if comp in _memo:
            return _memo[comp]
        base = self.comps.get(comp)
        if base is None:
            return CompCost()
        total = CompCost(flops=base.flops, bytes=base.bytes,
                         coll_bytes=base.coll_bytes,
                         coll_bytes_bf16adj=base.coll_bytes_bf16adj,
                         coll_ops=dict(base.coll_ops))
        _memo[comp] = total  # cycle guard (HLO call graphs are acyclic)
        for callee, mult in base.edges:
            sub = self.rollup(callee, _memo)
            total.flops += mult * sub.flops
            total.bytes += mult * sub.bytes
            total.coll_bytes += mult * sub.coll_bytes
            total.coll_bytes_bf16adj += mult * sub.coll_bytes_bf16adj
            for k, v in sub.coll_ops.items():
                total.coll_ops[k] = total.coll_ops.get(k, 0.0) + mult * v
        return total


_CONVERT_RE = re.compile(
    r"=\s*(?P<out>f32\[[0-9,]*\])(?:\{[^}]*\})?\s*convert\(\s*%(?P<src>[\w.\-]+)")


def cpu_bf16_emulation_bytes(hlo_text: str, threshold: int = 2 ** 28) -> int:
    """Bytes of f32<-bf16 ``convert`` buffers that only exist on the CPU
    backend (XLA:CPU emulates bf16 dots by widening operands to f32 and hoists
    loop-invariant widenings to whole-stack buffers). On the TPU target the
    MXU consumes bf16 operands natively, so these buffers do not exist. Used
    to report a TPU-estimate peak alongside the raw CPU-backend peak."""
    symbols: Dict[str, str] = {}
    for m in re.finditer(r"%([\w.\-]+)\s*=\s*([a-z0-9]+\[[0-9,]*\])", hlo_text):
        symbols[m.group(1)] = m.group(2)
    for m in re.finditer(r"%([\w.\-]+):\s*([a-z0-9]+\[[0-9,]*\])", hlo_text):
        symbols.setdefault(m.group(1), m.group(2))
    total = 0
    for m in _CONVERT_RE.finditer(hlo_text):
        out_bytes = _shape_bytes(m.group("out"))
        if out_bytes < threshold:
            continue
        src_type = symbols.get(m.group("src"), "")
        if src_type.startswith("bf16"):
            total += out_bytes
    return total


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_bytes_bf16adj: float = 0.0
    compute_dtype: str = "bfloat16"
    model_flops: float = 0.0            # 6*N*D analytic
    argument_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    collective_ops: Dict[str, float] = dataclasses.field(default_factory=dict)
    target: TpuTarget = V5E

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / peak_flops(self.compute_dtype,
                                                  self.target)

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.target.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.target.ici_link_bw

    @property
    def collective_s_tpu(self) -> float:
        """Collective term with f32-typed traffic halved (the TPU lowering
        moves bf16 where XLA:CPU widens — §Roofline caveats)."""
        return (self.collective_bytes_bf16adj or
                self.collective_bytes_per_device) / self.target.ici_link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs_global (remat/redundancy waste detector)."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable fraction of peak on the dominant-term model."""
        if self.step_time_s == 0:
            return 0.0
        return self.compute_s / self.step_time_s

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "collective_s_tpu": self.collective_s_tpu,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "argument_bytes": self.argument_bytes,
            "temp_bytes": self.temp_bytes,
            "output_bytes": self.output_bytes,
            "collective_ops": self.collective_ops,
            "compute_dtype": self.compute_dtype,
        }


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float, compute_dtype: str = "bfloat16",
            target: TpuTarget = V5E) -> Roofline:
    """Roofline terms from a compiled SPMD executable.

    FLOPs/bytes/collectives come from the HLO text cost model (scan bodies
    multiplied by trip count — see HloCostModel); XLA's own cost_analysis is
    taken as a floor (it covers elementwise FLOPs the text model skips, but
    counts loop bodies once).
    """
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    rolled = HloCostModel(text).rollup()
    flops = max(float(ca.get("flops", 0.0)), rolled.flops)
    nbytes = max(float(ca.get("bytes accessed", 0.0)), rolled.bytes)
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        pass
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=nbytes,
        collective_bytes_per_device=rolled.coll_bytes,
        collective_bytes_bf16adj=rolled.coll_bytes_bf16adj,
        compute_dtype=compute_dtype, model_flops=model_flops,
        argument_bytes=getattr(ma, "argument_size_in_bytes", None),
        temp_bytes=getattr(ma, "temp_size_in_bytes", None),
        output_bytes=getattr(ma, "output_size_in_bytes", None),
        collective_ops=dict(rolled.coll_ops), target=target,
    )
