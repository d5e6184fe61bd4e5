"""Declarative contraction API: ContractionSpec + the capability registry.

The paper's central design move is a *declarative interface between layers*:
the ``llvm.matrix`` intrinsic lets tiling/packing and the micro kernel evolve
independently because the contract between them is a declared signature, not a
hard-coded call path. This module is that interface for the whole framework:

  * :class:`ContractionSpec` — one frozen, hashable descriptor of a GEMM-
    shaped contraction: dense vs grouped, operand geometry and dtypes, the
    weight's kind (raw array vs load-time-packed tiles, including the packed
    :class:`~repro.core.tile_format.TileFormat`), whether valid-row counts
    accompany the call (ragged), the accumulation contract, and the
    :class:`~repro.core.epilogue.EpilogueSpec` store chain.
  * :class:`Lowering` + :func:`register_lowering` — the capability registry.
    Every lowering (the per-call codegen strategies, the library proxy, the
    packed-weight kernel paths) registers ``supports(spec) -> bool`` plus a
    planner-derived cost hint; nothing outside the registry probes weight
    types or strategy names.
  * :func:`dispatch` — THE selection point. Precedence is
    ``explicit > env > auto`` in exactly one place: an explicit strategy
    name must support the spec (hard error otherwise), the
    ``REPRO_GEMM_STRATEGY`` env override is honored only when it names a
    lowering of the same kind that supports the spec (so a dense override
    forced by an integration test can never hijack a grouped contraction),
    and auto takes the cheapest supporting lowering by the registered cost
    hints.

Execution (operand folding + running the chosen lowering) lives in
``repro.core.gemm.contract``; the four legacy entry points are thin facades
over it. Extending the system — a new epilogue, a new weight format, a new
kernel — means a new table entry or registry record, never an edit to the
dispatch ladder.

Guarded execution (:func:`fallback_chain` + :func:`run_guarded`): env/auto
dispatch never crashes on a failing lowering. The runner classifies the
failure (``repro.core.health``), records the degradation in the health
registry, and degrades down the chain of supporting lowerings ordered by
cost — bottoming out at the always-supporting jnp reference lowerings
(:data:`REFERENCE_LOWERINGS`, cost :data:`REFERENCE_COST`: finite so they
sit at the chain's end, huge so auto never picks them outright). An
explicit ``strategy=`` choice is a contract and NEVER silently degrades —
it raises.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import health
from repro.core.epilogue import EpilogueSpec, as_epilogue_spec
from repro.core.tile_format import TileFormat

_ENV_STRATEGY = "REPRO_GEMM_STRATEGY"
_ENV_BACKEND = "REPRO_GEMM_BACKEND"

KINDS = ("dense", "grouped")
WEIGHT_KINDS = ("raw", "packed")
ACCUMS = ("native", "f32")


def default_backend() -> str:
    """Execution backend: env override, else pallas on TPU, jnp elsewhere."""
    env = os.environ.get(_ENV_BACKEND)
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def kernel_backend() -> bool:
    """Whether auto-dispatch targets the hand-scheduled kernels: on a TPU,
    outside a device mesh. A Pallas kernel is a one-device program with no
    partitioning rule and no transpose rule, so the sharded (and
    differentiated) programs that run under ``repro.parallel.mesh.use_mesh``
    — the training driver's — take the library lowerings."""
    from repro.parallel.mesh import current_mesh
    return jax.default_backend() == "tpu" and current_mesh() is None


# ---------------------------------------------------------------------------
# Weight-kind probe — the ONE place weight objects are classified
# ---------------------------------------------------------------------------

def weight_kind(w) -> str:
    """"packed" for the load-time-packed weight pytrees, "raw" for arrays.

    Keyed on the ``weight_kind`` attribute the packed pytrees declare
    (``repro.core.layered._PackedCommon``) — no isinstance probes, so new
    packed formats join by declaring the attribute."""
    return getattr(w, "weight_kind", "raw")


def is_packed(w) -> bool:
    return weight_kind(w) == "packed"


def weight_format(w) -> Optional[TileFormat]:
    """The packed weight's TileFormat (None for raw arrays)."""
    return w.fmt if is_packed(w) else None


def as_compute_weight(w, dtype):
    """Cast a raw weight to the compute dtype; packed weights pass through
    (they were packed in the compute dtype at load time). The model layers'
    weight accessor — replaces their per-module isinstance probes."""
    return w if is_packed(w) else w.astype(dtype)


# ---------------------------------------------------------------------------
# ContractionSpec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ContractionSpec:
    """One declared contraction: ``out = epilogue(a @ w (* gate) ...)``.

    ``kind``      "dense" (a: [M, K] after folding) or "grouped" (a:
                  [E, M, K] per-expert batch; ``e`` experts).
    ``m, k, n``   folded problem geometry. Dense: M is the total row count
                  across leading batch dims. Grouped: M is the PER-EXPERT
                  row count after folding leading dims in.
    ``dtype``     activation/compute dtype name.
    ``out_dtype`` output dtype name, or None for the legacy default (the
                  c operand's dtype if present, else ``dtype``).
    ``weight``    "raw" | "packed" (load-time tile-major pytree).
    ``b_format``  the packed weight's TileFormat (None for raw) — carries
                  quantized-ness into ``supports``/cost decisions.
    ``counts``    valid-row counts operand present (ragged contract: rows
                  at/past the count are padding, zero in the output).
    ``occupancy`` expected fill fraction of the padded rows, in (0, 1] —
                  the grouped crossover prior (see planner.should_pack).
    ``accum``     "native" keeps the contraction's output dtype native
                  (bf16 cross-shard reduces); "f32" forces full-precision
                  accumulation AND applies the epilogue chain in f32.
    ``epilogue``  the EpilogueSpec store chain.

    Frozen/hashable: safe as a jit cache key, a dispatch-table key, and a
    golden-test pin.
    """

    kind: str
    m: int
    k: int
    n: int
    e: int = 1
    dtype: str = "float32"
    out_dtype: Optional[str] = None
    weight: str = "raw"
    b_format: Optional[TileFormat] = None
    counts: bool = False
    occupancy: float = 1.0
    accum: str = "native"
    epilogue: EpilogueSpec = EpilogueSpec()

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"kind must be one of {KINDS}; got {self.kind!r}")
        if self.weight not in WEIGHT_KINDS:
            raise ValueError(
                f"weight must be one of {WEIGHT_KINDS}; got {self.weight!r}")
        if self.accum not in ACCUMS:
            raise ValueError(
                f"accum must be one of {ACCUMS}; got {self.accum!r}")
        if self.kind == "dense":
            if self.e != 1:
                raise ValueError(f"dense contractions have e=1; got {self.e}")
            if self.counts:
                raise ValueError("counts (ragged) is a grouped-only contract")
            if self.epilogue.gate_mul:
                raise ValueError("gate_mul is a grouped-only epilogue (the "
                                 "MoE gate/up pair)")
        if not (0.0 < self.occupancy <= 1.0):
            raise ValueError(f"occupancy in (0, 1]; got {self.occupancy}")

    # -- constructors ---------------------------------------------------

    @classmethod
    def dense(cls, m: int, k: int, n: int, dtype, *, w=None,
              epilogue=None, bias: bool = False, out_dtype=None,
              accum: str = "native") -> "ContractionSpec":
        """Dense spec; ``w`` (optional) classifies the weight kind/format.
        ``bias=True`` adds the bias stage to the chain (a named spec that
        already declares it, e.g. ``bias_gelu``, keeps it)."""
        epi = as_epilogue_spec(epilogue)
        epi = epi.with_bias(epi.bias or bias)
        return cls(kind="dense", m=int(m), k=int(k), n=int(n),
                   dtype=_dtype_name(dtype),
                   out_dtype=_dtype_name(out_dtype) if out_dtype else None,
                   weight=weight_kind(w), b_format=weight_format(w),
                   accum=accum, epilogue=epi)

    @classmethod
    def grouped(cls, e: int, m: int, k: int, n: int, dtype, *, w=None,
                epilogue=None, bias: bool = False, counts: bool = False,
                occupancy: Optional[float] = None,
                out_dtype=None) -> "ContractionSpec":
        """Grouped spec (``m`` = per-expert folded rows)."""
        epi = as_epilogue_spec(epilogue)
        epi = epi.with_bias(epi.bias or bias)
        return cls(kind="grouped", e=int(e), m=int(m), k=int(k), n=int(n),
                   dtype=_dtype_name(dtype),
                   out_dtype=_dtype_name(out_dtype) if out_dtype else None,
                   weight=weight_kind(w), b_format=weight_format(w),
                   counts=counts, occupancy=occupancy or 1.0, epilogue=epi)

    # -- derived ----------------------------------------------------------

    @property
    def b_dtype(self) -> Optional[str]:
        """The B stream's element dtype when it differs from compute (the
        planner's per-operand byte accounting): quantized formats only."""
        if self.b_format is not None and self.b_format.is_quantized:
            return self.b_format.dtype
        return None

    def resolved_out_dtype(self, a, c=None):
        if self.out_dtype is not None:
            return jnp.dtype(self.out_dtype)
        return c.dtype if c is not None else a.dtype

    def describe(self) -> str:
        """Stable one-line key for dispatch tables and serving reports."""
        geo = (f"E{self.e}x" if self.kind == "grouped" else "") + \
            f"{self.m}x{self.k}x{self.n}"
        fmt = "" if self.b_format is None else f"|{self.b_format.dtype}-tiles"
        flags = "".join([
            "|counts" if self.counts else "",
            f"|occ={self.occupancy:g}" if self.occupancy != 1.0 else "",
            f"|accum={self.accum}" if self.accum != "native" else "",
        ])
        epi = "+".join(self.epilogue.steps) or "none"
        return (f"{self.kind}[{geo}]{self.dtype}"
                f"|{self.weight}{fmt}{flags}|epi={epi}")


def _dtype_name(dtype) -> str:
    return dtype if isinstance(dtype, str) else jnp.dtype(dtype).name


# ---------------------------------------------------------------------------
# Capability registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Lowering:
    """One registered lowering of a contraction.

    ``supports``  the capability predicate: can ``run`` execute this spec?
                  Tested (property sweep) to agree with what ``run``
                  actually accepts.
    ``cost``      planner-derived preference for auto-dispatch: the planner
                  heuristics' pick costs 0.0, viable fallbacks cost more,
                  and ``COMPARISON_COST`` marks a lowering explicit-only
                  (the paper's slower codegen variants are kept runnable
                  for benchmarks but never auto-chosen).
    ``run``       executes the spec on already-folded operands:
                  ``run(spec, a, w, *, w2, c, bias, counts, alpha, beta,
                  plan, backend, interpret)``.
    ``folds``     whether the facade must fold leading batch dims before
                  ``run`` (the library/einsum lowerings keep them unfolded
                  so GSPMD sharding decisions survive). This fixes the
                  operand convention ``run`` sees: folds=True lowerings get
                  dense [M, K] / grouped [E, M, K] activations and [E, S]
                  segment counts; folds=False lowerings get the caller's
                  [*lead, ...] layout and [*lead, E] counts.
    """

    name: str
    kind: str
    supports: Callable[[ContractionSpec], bool]
    cost: Callable[[ContractionSpec], float]
    run: Callable
    folds: bool = True
    # Optional redirect for specs this lowering cannot run but a strictly-
    # more-capable sibling can (returns its name, or None). Lets an
    # explicit/env choice of ``grouped_packed`` on a counts-declaring spec
    # land on the ragged variant — counts strictly add information — in
    # the ONE dispatch point instead of per-facade special cases.
    upgrade: Optional[Callable[[ContractionSpec], Optional[str]]] = None


COMPARISON_COST = float("inf")

# The always-supporting jnp reference lowerings' cost: finite (they join
# the guarded fallback chain, unlike the explicit-only COMPARISON_COST
# lowerings) but astronomically above every real contender, so auto
# dispatch never picks them while any kernel/library lowering supports the
# spec — the golden dispatch tables are unchanged by their registration.
REFERENCE_COST = 1e9

# kind -> name of the always-supporting reference lowering (the guaranteed
# bottom of every fallback chain). Populated by repro.core.strategy at
# registration time.
REFERENCE_LOWERINGS: Dict[str, str] = {}

LOWERINGS: Dict[str, Lowering] = {}


def register_lowering(name: str, kind: str, *, supports, cost, run,
                      folds: bool = True, upgrade=None) -> Lowering:
    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}; got {kind!r}")
    if name in LOWERINGS:
        raise ValueError(f"lowering {name!r} already registered")
    low = Lowering(name=name, kind=kind, supports=supports, cost=cost,
                   run=run, folds=folds, upgrade=upgrade)
    LOWERINGS[name] = low
    return low


def _ensure_registered() -> None:
    # The lowering implementations register at import of their modules;
    # importing repro.core.gemm pulls in all of them (strategy + layered).
    if not LOWERINGS:
        import repro.core.gemm  # noqa: F401  (registration side effect)


def lowerings_for(spec: ContractionSpec) -> Tuple[Lowering, ...]:
    """All registered lowerings whose capability covers the spec."""
    _ensure_registered()
    return tuple(low for low in LOWERINGS.values()
                 if low.kind == spec.kind and low.supports(spec))


def dispatch(spec: ContractionSpec, *,
             strategy: Optional[str] = None) -> Lowering:
    """Choose THE lowering for a spec: explicit > env > auto.

    * explicit — ``strategy`` names a registered lowering; it must support
      the spec (hard error otherwise — an explicit choice is a contract).
    * env — ``REPRO_GEMM_STRATEGY`` is honored only when it names a
      lowering of the spec's kind that supports the spec (a dense override
      never re-routes grouped contractions, and vice versa).
    * auto — the cheapest supporting lowering by registered cost hint
      (ties broken by name for determinism).
    """
    _ensure_registered()

    def _upgraded(low: Lowering) -> Optional[Lowering]:
        """A named lowering, or its declared more-capable sibling."""
        if low.supports(spec):
            return low
        name = low.upgrade(spec) if low.upgrade is not None else None
        if name is not None and LOWERINGS[name].supports(spec):
            return LOWERINGS[name]
        return None

    if strategy is not None and strategy != "auto":
        low = LOWERINGS.get(strategy)
        if low is None:
            raise KeyError(f"unknown lowering {strategy!r}; one of "
                           f"{sorted(LOWERINGS)}")
        if low.kind == spec.kind:
            chosen = _upgraded(low)
            if chosen is not None:
                return chosen
        raise ValueError(
            f"lowering {strategy!r} does not support {spec.describe()}")
    env = os.environ.get(_ENV_STRATEGY)
    if env and env != "auto":
        low = LOWERINGS.get(env)
        if low is None:
            # Same hard error as an unknown explicit strategy=: a typo'd
            # env override must not silently fall through to auto.
            raise KeyError(f"unknown lowering {env!r} ({_ENV_STRATEGY}); "
                           f"one of {sorted(LOWERINGS)}")
        if low.kind == spec.kind:
            chosen = _upgraded(low)
            if chosen is not None:
                return chosen
    cands = lowerings_for(spec)
    if not cands:
        raise ValueError(f"no registered lowering supports {spec.describe()}")
    return min(cands, key=lambda lw: (lw.cost(spec), lw.name))


def fallback_chain(spec: ContractionSpec,
                   chosen: Lowering) -> Tuple[Lowering, ...]:
    """The guarded-dispatch degradation order for ``spec``.

    ``chosen`` (the dispatch winner) first, then every other supporting
    lowering ordered by ``(cost, name)`` — the explicit-only comparison
    lowerings (``COMPARISON_COST``) excluded — bottoming out at the kind's
    always-supporting jnp reference lowering. The chain is what
    :func:`run_guarded` walks when a lowering fails under env/auto
    dispatch.
    """
    _ensure_registered()
    ref_name = REFERENCE_LOWERINGS.get(spec.kind)
    others = sorted(
        (lw for lw in lowerings_for(spec)
         if lw.name not in (chosen.name, ref_name)
         and lw.cost(spec) < COMPARISON_COST),
        key=lambda lw: (lw.cost(spec), lw.name))
    chain = [chosen] + others
    if ref_name is not None and ref_name != chosen.name:
        chain.append(LOWERINGS[ref_name])
    return tuple(chain)


def run_guarded(spec: ContractionSpec, chain: Tuple[Lowering, ...],
                run_one: Callable[[Lowering], jnp.ndarray]) -> jnp.ndarray:
    """Execute ``run_one(lowering)`` down a fallback chain (env/auto only).

    A failing lowering is classified (``health.classify_failure``), the
    degradation recorded in the health registry, and the next chain entry
    tried; with the opt-in numerics guard armed, a NaN/Inf output degrades
    the same way (eager execution only — tracer outputs are not checked).
    The LAST chain entry is never degraded past: its failure propagates, so
    genuine contract violations (operand mismatches) still surface.
    """
    last = len(chain) - 1
    for i, low in enumerate(chain):
        try:
            out = run_one(low)
        except Exception as exc:  # noqa: BLE001 — classify, then degrade
            if i == last:
                raise
            health.record_degradation(
                spec.describe(), low.name, health.classify_failure(exc),
                chain[i + 1].name, detail=f"{type(exc).__name__}: {exc}")
            continue
        if i < last and health.numerics_guard_enabled() \
                and health.has_nonfinite(out):
            health.record_degradation(
                spec.describe(), low.name, "numerics", chain[i + 1].name,
                detail="non-finite values in output")
            continue
        return out
    raise AssertionError("unreachable: empty fallback chain")


def check_explicit_numerics(spec: ContractionSpec, low: Lowering,
                            out) -> None:
    """The explicit-strategy side of the numerics guard: an explicit choice
    never degrades, so a non-finite output RAISES under the guard."""
    if health.numerics_guard_enabled() and health.has_nonfinite(out):
        raise health.NumericsError(
            f"non-finite values in output of explicit lowering "
            f"{low.name!r} for {spec.describe()} "
            f"({health.ENV_NUMERICS_GUARD})")


def dispatch_table(specs) -> Dict[str, str]:
    """``{spec.describe(): dispatch(spec).name}`` — the golden-test and
    serving-report view of the dispatch surface."""
    return {spec.describe(): dispatch(spec).name for spec in specs}
