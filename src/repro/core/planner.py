"""Block-size planner — the paper's constraint system (Eq. 1-7) re-derived for
the TPU memory hierarchy.

The paper's macro algorithm reads L1/L2/L3 sizes from LLVM's target tables and
solves:            kc from L1, mc from L2, nc from L3, all rounded to register
tile multiples (mr, kr, nr) chosen from the matrix-engine geometry.

On TPU the hierarchy collapses to a single software-managed VMEM with Pallas
double-buffering the HBM streams, and the register tile becomes the MXU tile:

  (C1)  working set fits VMEM (every buffer the kernel asks Mosaic for):
        dbuf*(bm*bk + bk*bn)*itemsize      double-buffered A and B streams
          + bm*bn*acc_itemsize             the revolving accumulator
          + dbuf*bm*bn*acc_itemsize        the output block (f32 at widest)
          <= vmem_budget  (derived from the chip's declared VMEM limit)
  (C2)  MXU feeding geometry:  bm % sublane == 0, bn % lane == 0, bk % lane == 0
  (C3)  accumulator grid:      bm, bn multiples of the 128x128 MXU tile when
        possible (VAccs = bm/128, HAccs = bn/128 — paper Fig. 3 generalized)
  (C5-7) padded problem dims are multiples of (bm, bk, bn) — guaranteed by the
        packer's zero-fill rather than constraining the problem.

Heuristic order is the paper's: maximize the contraction depth bk first (their
kc), then bm (their mc), then bn (their nc) — deep K amortizes the accumulator
setup exactly like MMA's kr maximizes in-accumulator operations.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core import dtypes as mdt
from repro.core.tile_format import ScaleSpec, TileFormat, is_dequant_pair
from repro.roofline.hw import TpuTarget, current_target


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    bm: int
    bk: int
    bn: int
    dtype: str
    acc_dtype: str
    layout_a: str = "row"
    layout_b: str = "row"
    double_buffer: int = 2
    vmem_budget: int = dataclasses.field(
        default_factory=lambda: current_target().vmem_bytes)
    # B-operand element dtype when it differs from the compute dtype —
    # int8/int4 weight streams (dequant-in-epilogue) halve/quarter the
    # resident B footprint, so the byte accounting below is per-operand.
    b_dtype: Optional[str] = None
    # Scale granularity of a quantized B: "tile" (per-(Kb,Nb), applied per
    # K-step) or "col" (per-Nb column, hoisted into the store epilogue).
    b_scale: str = "tile"

    @property
    def vaccs(self) -> int:
        return max(self.bm // current_target().mxu_dim, 1)

    @property
    def haccs(self) -> int:
        return max(self.bn // current_target().mxu_dim, 1)

    @property
    def b_format(self) -> TileFormat:
        """The packed-B tile format this plan implies — the single descriptor
        the pack layer, kernels, and weight pytrees consume. A narrow integer
        ``b_dtype`` under a float compute dtype marks the format quantized
        (per-tile f32 scales, dequant fused into the kernel)."""
        bdt = self.b_dtype or self.dtype
        quant = is_dequant_pair(self.dtype, bdt)
        scale = ScaleSpec(granularity=self.b_scale) if quant else None
        return TileFormat(bk=self.bk, bn=self.bn, layout=self.layout_b,
                          dtype=bdt, scale=scale)

    def vmem_working_set(self) -> int:
        item = mdt.info(self.dtype).itemsize
        acc_item = jnp.dtype(self.acc_dtype).itemsize
        a_stream = self.double_buffer * self.bm * self.bk * item
        # B streams at the tile format's bytes (narrow int8 B tiles carry a
        # per-tile scale — counted, though it is noise next to the tile).
        b_stream = self.double_buffer * self.b_format.tile_bytes()
        acc_and_out = (1 + self.double_buffer) * self.bm * self.bn * acc_item
        return a_stream + b_stream + acc_and_out

    def validate(self, target: TpuTarget | None = None) -> None:
        sub, lane = mdt.alignment(self.dtype, target or current_target())
        if self.vmem_working_set() > self.vmem_budget:
            raise ValueError(
                f"plan {self} exceeds VMEM budget: "
                f"{self.vmem_working_set()} > {self.vmem_budget}")
        for name, val, mult in (("bm", self.bm, sub), ("bn", self.bn, lane),
                                ("bk", self.bk, lane)):
            if val % mult and val >= mult:
                raise ValueError(f"{name}={val} not aligned to {mult}")

    def kwargs(self) -> dict:
        return dict(bm=self.bm, bk=self.bk, bn=self.bn)


def _round_down(x: int, mult: int) -> int:
    return max((x // mult) * mult, mult)


def plan_gemm(m: int, k: int, n: int, dtype="float32", *,
              b_dtype: str | None = None,
              target: TpuTarget | None = None,
              vmem_budget: int | None = None,
              double_buffer: int = 2,
              layout_a: str = "row",
              layout_b: str = "row",
              scale_granularity: str = "tile") -> GemmPlan:
    """Solve the TPU-translated constraint system for a concrete problem.

    ``b_dtype`` is the B-operand element dtype when it differs from the
    compute dtype (int8/int4 dequant-in-epilogue weights): the (C1) byte
    terms are per-operand, so a narrow B stream — 0.5 bytes/element for
    nibble-packed int4 — buys deeper bk / wider bn before the budget binds,
    and the emitted plan's ``b_format`` is quantized.
    ``scale_granularity`` picks the quantized format's scale convention
    ("tile" per-(Kb,Nb), "col" per-Nb-column store-only dequant).
    """
    target = target or current_target()
    d = mdt.info(jnp.dtype(dtype).name if not isinstance(dtype, str) else dtype)
    b_item = (mdt.info(jnp.dtype(b_dtype).name).itemsize if b_dtype
              else d.itemsize)
    budget = vmem_budget or target.vmem_bytes
    sub, lane = target.sublane(d.itemsize), target.lane
    acc_item = jnp.dtype(d.acc_dtype).itemsize
    mxu = target.mxu_dim
    # Per-tile scale stream of a QUANTIZED B (one scale per resident tile) —
    # shares the quantized-ness rule and scale dtype with GemmPlan.b_format,
    # so the solver and vmem_working_set() agree about the working set.
    scale_bytes = (double_buffer * ScaleSpec().itemsize
                   if is_dequant_pair(d.name, b_dtype) else 0)

    # Clip targets to the (padded) problem.
    def clipped(value: int, dim: int, mult: int) -> int:
        dim_padded = -(-dim // mult) * mult
        return min(value, dim_padded)

    # Start from the MXU-native accumulator tile (paper: one ACC = 4x4; here
    # one MXU tile = 128x128) and the paper's 2x4 VAccs x HAccs arrangement.
    bm = clipped(2 * mxu, m, sub)
    bn = clipped(4 * mxu, n, lane)

    # (C1) maximize bk first — the paper's "larger kc" insight (Eq. 1).
    def acc_and_out(bm_: int, bn_: int) -> int:
        return (1 + double_buffer) * bm_ * bn_ * acc_item

    def max_bk(bm_: int, bn_: int) -> int:
        avail = budget - acc_and_out(bm_, bn_) - scale_bytes
        # per_k may be fractional (sub-byte b_item): floor to int k-steps.
        per_k = double_buffer * (bm_ * d.itemsize + bn_ * b_item)
        return max(int(avail / per_k), lane)

    bk = clipped(_round_down(max_bk(bm, bn), lane), k, lane)

    # Then grow bm (paper Eq. 3: mc from L2), then bn (Eq. 4: nc from L3),
    # re-checking the budget after each growth step.
    def fits(bm_, bk_, bn_):
        ws = (double_buffer * (bm_ * bk_ * d.itemsize + bk_ * bn_ * b_item)
              + acc_and_out(bm_, bn_) + scale_bytes)
        return ws <= budget

    for cand in (8 * mxu, 4 * mxu, 2 * mxu):
        c = clipped(cand, m, sub)
        if c > bm and fits(c, bk, bn):
            bm = c
            break
    for cand in (8 * mxu, 6 * mxu, 4 * mxu):
        c = clipped(cand, n, lane)
        if c > bn and fits(bm, bk, c):
            bn = c
            break

    # Small problems: shrink to the aligned problem envelope.
    bm = min(bm, _round_down(-(-m // sub) * sub, sub))
    bn = min(bn, _round_down(-(-n // lane) * lane, lane))
    bk = min(bk, _round_down(-(-k // lane) * lane, lane))

    while not fits(bm, bk, bn) and bk > lane:
        bk = _round_down(bk // 2, lane)
    while not fits(bm, bk, bn) and bn > lane:
        bn = _round_down(bn // 2, lane)
    while not fits(bm, bk, bn) and bm > sub:
        bm = _round_down(bm // 2, sub)

    # Balance the K split: a budget-capped bk that does not divide K would
    # zero-fill most of a last K tile (up to ~2x the B stream); the same
    # number of K steps at the shallowest lane-aligned depth covers K with
    # under one lane of fill. Never deeper than before, so (C1) still holds.
    k_steps = -(-k // bk)
    bk = -(-k // (k_steps * lane)) * lane

    plan = GemmPlan(bm=bm, bk=bk, bn=bn, dtype=d.name, acc_dtype=d.acc_dtype,
                    layout_a=layout_a, layout_b=layout_b,
                    double_buffer=double_buffer, vmem_budget=budget,
                    b_dtype=b_dtype, b_scale=scale_granularity)
    plan.validate(target)
    return plan


def plan_grouped_gemm(e: int, m: int, k: int, n: int, dtype="float32", *,
                      b_dtype: str | None = None,
                      target: TpuTarget | None = None,
                      n_b_streams: int = 1,
                      double_buffer: int = 2,
                      layout_b: str = "row",
                      scale_granularity: str = "tile") -> GemmPlan:
    """Plan for the grouped kernel: one expert's [m,k,n] problem at a time.

    The expert axis is the outermost grid dimension, so only one expert's
    tiles are VMEM-resident per grid step and the per-expert tile constraints
    are exactly the 2-D system's — but the expert-loop stream adds working
    set when the kernel carries extra B operands (``n_b_streams=2`` for the
    fused silu-gate pair: a second double-buffered B stream plus a second
    revolving accumulator share VMEM with the first). The budget is solved
    with that reservation subtracted, then re-validated.
    """
    target = target or current_target()
    d = mdt.info(jnp.dtype(dtype).name if not isinstance(dtype, str) else dtype)
    acc_item = jnp.dtype(d.acc_dtype).itemsize

    def extra_for(plan: GemmPlan) -> int:
        # The second stream carries the partner stack's tiles (at the tile
        # format's bytes — int8 silu-gate pairs reserve narrow) + a second
        # revolving accumulator.
        return (n_b_streams - 1) * (
            double_buffer * plan.b_format.tile_bytes()
            + plan.bm * plan.bn * acc_item)

    plan = plan_gemm(m, k, n, dtype, b_dtype=b_dtype, target=target,
                     double_buffer=double_buffer, layout_b=layout_b,
                     scale_granularity=scale_granularity)
    if n_b_streams > 1 and (plan.vmem_working_set() + extra_for(plan)
                            > target.vmem_bytes):
        # Re-solve with an even budget split. Each extra stream's reservation
        # is a strict subset of one plan's working-set terms (a B stream + an
        # accumulator, no A stream), so a plan solved within budget/streams
        # always fits n_b_streams-fold.
        plan = plan_gemm(m, k, n, dtype, b_dtype=b_dtype, target=target,
                         double_buffer=double_buffer, layout_b=layout_b,
                         scale_granularity=scale_granularity,
                         vmem_budget=target.vmem_bytes // n_b_streams)
        assert plan.vmem_working_set() + extra_for(plan) <= target.vmem_bytes
    return plan


def should_pack(m: int, k: int, n: int, dtype="float32", *,
                b_dtype: str | None = None,
                target: TpuTarget | None = None, fused: bool = False,
                group: int = 1, occupancy: float = 1.0) -> bool:
    """Strategy heuristic from the paper's own results: packing pays off once
    operands exceed the fast-memory envelope (Figs. 4-6: Tiling wins small,
    Tiling+Packing wins medium/large).

    ``fused=True`` models the pack-free-A pipeline (``tiling_packing_fused``):
    A is never copied, so the per-call packing bill is only B's one tile-major
    copy, amortized over every M-block that re-streams B. Two conditions:
    (a) there must BE more than one M-block — with m inside the planner's
    largest bm (8*mxu) each B tile is read exactly once and a per-call copy
    buys nothing (decode-shaped GEMMs stay on ``tiling``; load-time-packed
    weights bypass this function entirely via ``weights_prepacked``); and
    (b) B is more than a small slice of VMEM, so it can't stay resident next
    to the double-buffered A stream and the accumulator — each M-block then
    re-reads it from HBM, and the contiguous tile-major stream beats the
    strided gather. Together these move the crossover well before the paper's
    Figs. 4-6 whole-working-set spill point.

    ``group=E`` (> 1) models the grouped kernel over a stacked [E,K,N] B:
    ``m`` is the PER-EXPERT row count. B is resident per-expert rather than
    per-call — the expert loop streams the full E-times-larger stack through
    VMEM once per call regardless of M-blocking — so condition (b) is tested
    against the whole stack, and condition (a) collapses to "is there at
    least one full sublane block of rows per expert": a decode-shaped
    per-expert M (a handful of capacity slots) cannot amortize the grouped
    kernel's padded-envelope A stream and stays on the einsum fallback.

    ``occupancy`` (grouped only) is the expected fraction of per-expert rows
    that carry real tokens — a GShard capacity dispatch at
    ``capacity_factor=f`` fills at most ``1/f`` of its slots, and routing
    skew fills less. Condition (a) is tested against the EXPECTED rows
    ``m * occupancy``, not the padded envelope ``m``: a skewed decode-ish
    dispatch whose padded capacity looks prefill-shaped but whose occupied
    rows fit a sublane block makes the einsum call, not the kernel call.
    """
    target = target or current_target()
    item = mdt.info(jnp.dtype(dtype).name if not isinstance(dtype, str)
                    else dtype).itemsize
    # B's resident/streamed bytes are counted at B's OWN dtype: an int8
    # dequant-in-epilogue weight stream is half/quarter the compute dtype's
    # footprint, so it stays VMEM-resident longer and the pack crossover
    # moves out accordingly.
    b_item = (mdt.info(jnp.dtype(b_dtype).name).itemsize if b_dtype else item)
    if group > 1:
        m_expected = m * min(max(occupancy, 0.0), 1.0)
        return (m_expected > target.sublane(item)
                and group * k * n * b_item > target.vmem_bytes // 32)
    if fused:
        return (m > 8 * target.mxu_dim
                and k * n * b_item > target.vmem_bytes // 32)
    total = (m * k + m * n) * item + k * n * b_item
    return total > target.vmem_bytes


def choose_grouped_strategy(e: int, m: int, k: int, n: int, dtype="float32",
                            *, b_dtype: str | None = None,
                            target: TpuTarget | None = None,
                            counts_known: bool = False,
                            occupancy: float = 1.0) -> str:
    """Grouped analogue of :func:`choose_strategy` — the planner's cost model
    for the batched-expert contraction (backend-agnostic; the dispatch layer
    gates it on the kernel target).

    The kernel crossover is :func:`should_pack`'s ``group=E`` form: B
    resident per expert, condition (a) tested against the EXPECTED occupied
    rows ``m * occupancy``. With ``counts_known`` the crossover lands on the
    ragged variant (the counts strictly add information: all-padding grid
    steps early-out); below the crossover the batched einsum is the right
    library lowering.
    """
    if should_pack(m, k, n, dtype, b_dtype=b_dtype, target=target,
                   fused=True, group=e, occupancy=occupancy):
        return "grouped_packed_ragged" if counts_known else "grouped_packed"
    return "grouped_einsum"


def choose_strategy(m: int, k: int, n: int, dtype="float32", *,
                    b_dtype: str | None = None,
                    target: TpuTarget | None = None,
                    weights_prepacked: bool = False) -> str:
    """Pick the kernel strategy for a problem signature.

    With the fused-A kernel available, per-call A-packing is never worth it:
    the auto path chooses between plain ``tiling`` (small: everything streams
    fine unpacked) and ``tiling_packing_fused`` (medium/large: B tile-major,
    A pack-free). ``weights_prepacked`` (PackedWeight) always takes the fused
    kernel — B's packing cost was already paid at load time.
    """
    if weights_prepacked:
        return "tiling_packing_fused"
    if should_pack(m, k, n, dtype, b_dtype=b_dtype, target=target, fused=True):
        return "tiling_packing_fused"
    return "tiling"
