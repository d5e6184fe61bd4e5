"""LayeredGemm — the paper's contribution as a composable JAX module.

Bundles planner + packing + micro kernel + epilogue into one reusable object
(the "compiler pass" as a library citizen). Also provides
:class:`PackedWeight`, a beyond-paper extension natural to frameworks: model
weights are static across calls, so the macro-level packing can be *hoisted to
load time* and amortized over every step — something a per-call library (or
per-loop compiler rewrite) cannot do.

``PackedWeight`` is registered as a JAX pytree node (the packed buffer and the
optional per-tile scale grid are the leaves; (k, n, plan) are static aux
data), so packed weights can live inside jit'd/scanned model parameter trees:
the serving engine packs every dense weight once at load time and each layer's
slice flows through ``jax.lax.scan`` like any other array. Its :meth:`matmul`
runs the pack-free-A fused kernel (``gemm_packed_fused_a``): A streams from
its natural layout, and bias + activation are applied in the kernel's final
grid step.

:class:`GroupedPackedWeight` extends the same idea one dimension: a stacked
expert weight [E, K, N] (MoE) is packed per-expert into one tile-major stack
and contracted by ``gemm_grouped_packed`` with the expert axis outermost on
the kernel grid — including the fused silu-gate pair for MoE gate/up.

Both pytrees share one packing/plan/format core (:class:`_PackedCommon`):
the tile format they pack to, carry, and hand the kernels is the plan's
``b_format`` — a single :class:`repro.core.tile_format.TileFormat`
descriptor. ``quantize="int8"`` at pack time selects the quantized format:
weights are stored as int8 tiles + per-(Kb,Nb)-tile f32 scales (halving HBM
traffic vs bf16 at serving time), and every matmul path — dense fused-A,
grouped, ragged, and the jnp fallbacks — dequantizes per tile on the f32
accumulator ahead of the fused epilogues. ``quantize="int4"`` stores
nibble-packed int4 tiles (two values per byte — 0.25x bf16 B traffic,
widened to i8 in-kernel via shift/mask); a ``":col"`` suffix on either
("int8:col" / "int4:col") switches the scale convention from per-tile to
per-Nb-column, hoisting the dequant multiply out of the K loop into the
store epilogue.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import contraction as ctr
from repro.core import dtypes as mdt
from repro.core import strategy as strat
from repro.core.contraction import ContractionSpec, default_backend
from repro.core.epilogue import apply_epilogue, as_epilogue_spec
from repro.core.planner import (GemmPlan, choose_strategy, plan_gemm,
                                plan_grouped_gemm)
from repro.core.tile_format import TileFormat, normalize_packed
from repro.kernels import pack as pack_mod
from repro.kernels import ref
from repro.kernels.gemm_grouped import (gemm_grouped_packed,
                                        gemm_grouped_packed_ragged)
from repro.kernels.gemm_packed import gemm_packed_fused_a
from repro.testing import faults


@dataclasses.dataclass
class LayeredGemm:
    """Plan-once, run-many layered GEMM for a fixed problem signature."""

    m: int
    k: int
    n: int
    dtype: str = "float32"
    strategy: Optional[str] = None        # None -> fused size heuristic
    backend: Optional[str] = None
    epilogue: str = "none"
    plan: Optional[GemmPlan] = None

    def __post_init__(self):
        self.plan = self.plan or plan_gemm(self.m, self.k, self.n, self.dtype)
        if self.strategy is None:
            self.strategy = choose_strategy(self.m, self.k, self.n, self.dtype)
        self.backend = self.backend or default_backend()

    def __call__(self, a, b, c=None, *, alpha=1.0, beta=0.0, bias=None,
                 out_dtype=None):
        assert a.shape == (self.m, self.k) and b.shape == (self.k, self.n), (
            a.shape, b.shape, (self.m, self.k, self.n))
        # epilogue/bias ride inside the lowering (kernel strategies fuse them
        # into the final grid step; jnp strategies let XLA fuse them).
        return strat.run(self.strategy, a, b, c, alpha=alpha, beta=beta,
                         plan=self.plan, backend=self.backend,
                         out_dtype=out_dtype, bias=bias,
                         epilogue=self.epilogue)


def _parse_quantize(quantize: Optional[str]):
    """``quantize`` string -> (b_dtype, scale_granularity).

    Accepted: None, "int8", "int4", and either with a ":col" suffix
    selecting per-column (store-only-dequant) scales, e.g. "int4:col".
    """
    if quantize is None:
        return None, "tile"
    base, _, gran = quantize.partition(":")
    if base not in ("int8", "int4") or (gran and gran != "col"):
        raise ValueError(
            f"unsupported quantize={quantize!r} (accepted: 'int8', 'int4', "
            f"optionally suffixed ':col')")
    return base, (gran or "tile")


class _PackedCommon:
    """Shared plan/format/packing core of the two packed-weight pytrees.

    Everything format-shaped lives here once: the plan's TileFormat is the
    single source of truth for packing (dense or grouped, float or
    quantized), the runtime M-block clamp, and the quantization pairing
    rules — the dense and grouped classes only differ in operand rank.

    ``weight_kind`` is the declarative classification the dispatch layer
    keys on (``repro.core.contraction.weight_kind``) — the registry probes
    this attribute, never the concrete class.
    """

    weight_kind = "packed"

    @property
    def fmt(self) -> TileFormat:
        """The packed buffer's tile format (from the plan — single source)."""
        return self.plan.b_format

    @staticmethod
    def _check_quantize_plan(plan: GemmPlan, quantize: Optional[str]) -> None:
        if quantize is not None and not plan.b_format.is_quantized:
            raise ValueError(
                f"quantize={quantize!r} needs a plan with b_dtype set "
                f"(got {plan})")

    @staticmethod
    def _pack_pair(w: jnp.ndarray, fmt: TileFormat, backend: str,
                   grouped: bool):
        """One (packed, scales-or-None) pair via the format-driven packers:
        the Pallas packer on the kernel backend (on a TPU a packing failure
        raises — no reference packer stands in for the device), the jnp
        reference packer elsewhere."""
        if grouped:
            packer = (pack_mod.pack_b_grouped if backend == "pallas"
                      else ref.pack_b_grouped_ref)
        else:
            packer = pack_mod.pack_b if backend == "pallas" else ref.pack_b_ref
        return normalize_packed(packer(w, fmt), fmt)

    def _clamp_bm(self, rows: int, dtype) -> int:
        # The plan's bm reflects the pack-time m_hint; the packed B buffer is
        # independent of it, so clamp the M-block to the *runtime* row count
        # (aligned up to the sublane) — a decode step with 4 rows must not be
        # padded to a 1024-row macro tile.
        sub, _ = mdt.alignment(dtype)
        return min(self.plan.bm, max(-(-rows // sub) * sub, sub))

    def _check_k(self, k_got: int) -> None:
        if k_got != self.k:
            # Padded tile envelopes can coincide for different K, so the
            # kernels cannot catch this — check the true K here.
            raise ValueError(
                f"contraction mismatch: a has K={k_got}, weight was "
                f"packed with K={self.k}")


@dataclasses.dataclass
class PackedWeight(_PackedCommon):
    """A weight matrix stored pre-packed in tile-major order (load-time
    packing); ``scales`` is the per-tile dequant grid of a quantized format
    (None for float tiles)."""

    packed: jnp.ndarray     # [Nb, Kb, bk, bn] (row) per pack_b
    k: int
    n: int
    plan: GemmPlan
    scales: Optional[jnp.ndarray] = None   # [Nb, Kb] f32 ([Nb] for :col)

    @classmethod
    def pack(cls, w: jnp.ndarray, *, m_hint: int = 1024,
             plan: Optional[GemmPlan] = None,
             backend: Optional[str] = None,
             quantize: Optional[str] = None) -> "PackedWeight":
        """w: [K, N], or [L, K, N] for scan-stacked layers (packed per layer
        under vmap so ``jax.lax.scan`` can slice the leading axis).
        ``quantize``: "int8" stores int8 tiles + per-tile f32 scales (the
        dequant runs fused in the kernel epilogue at every matmul); "int4"
        stores nibble-packed tiles (two values/byte); a ":col" suffix on
        either switches to per-column [Nb] scales applied once in the store
        epilogue instead of per K-step."""
        assert w.ndim in (2, 3), w.shape
        k, n = w.shape[-2:]
        b_dtype, gran = _parse_quantize(quantize)
        plan = plan or plan_gemm(m_hint, k, n, w.dtype, b_dtype=b_dtype,
                                 scale_granularity=gran)
        cls._check_quantize_plan(plan, quantize)
        fmt = plan.b_format
        be = backend or default_backend()
        if w.ndim == 3:
            # Load-time packing of the whole layer stack, one layer per
            # vmapped packer call.
            packed, scales = jax.vmap(
                lambda wl: cls._pack_pair(wl, fmt, be, grouped=False))(w)
        else:
            packed, scales = cls._pack_pair(w, fmt, be, grouped=False)
        return cls(packed=packed, k=k, n=n, plan=plan, scales=scales)

    def matmul(self, a: jnp.ndarray, *, bias=None, epilogue="none",
               out_dtype=None, backend: Optional[str] = None) -> jnp.ndarray:
        """epilogue(a[M,K] @ W + bias) via the pack-free-A fused pipeline.

        A spec facade: builds the :class:`ContractionSpec` for this packed
        contraction and routes it through the one dispatch point
        (``repro.core.gemm.contract``). ``epilogue`` is an
        :class:`EpilogueSpec` (legacy name strings keep working).
        """
        from repro.core.gemm import contract  # late: gemm imports this module
        spec = ContractionSpec.dense(
            a.shape[0], a.shape[1], self.n, a.dtype, w=self,
            epilogue=as_epilogue_spec(epilogue), bias=bias is not None,
            out_dtype=out_dtype)
        return contract(spec, a, self, bias=bias, backend=backend)

    def _matmul_impl(self, a: jnp.ndarray, *, bias, epilogue: str,
                     out_dtype, backend: Optional[str]) -> jnp.ndarray:
        """The registered lowering body (``packed_weight``).

        B's packing cost was paid once at load time; A is consumed directly
        from its natural layout (no pack_a materialization on any backend),
        and bias + activation are fused into the store epilogue — with the
        per-tile dequant ahead of them when the weight is quantized.
        """
        self._check_k(a.shape[1])
        faults.maybe_fail("kernel_compile")
        be = backend or default_backend()
        bm = self._clamp_bm(a.shape[0], a.dtype)
        scales = faults.corrupt("scale_grid", self.scales)
        if be == "pallas":
            out = gemm_packed_fused_a(a, self.packed, self.n, bm=bm,
                                      layout_b=self.plan.layout_b,
                                      b_scales=scales, bias=bias,
                                      epilogue=epilogue,
                                      b_format=self.fmt,
                                      out_dtype=out_dtype or a.dtype)
            faults.maybe_fail("kernel_run")
            return out
        acc = ref.fused_packed_acc_ref(a, self.packed, self.n,
                                       layout_b=self.plan.layout_b,
                                       bm=bm, b_scales=scales,
                                       fmt=self.fmt)
        if bias is not None:
            acc = acc + bias.astype(acc.dtype)
        out = apply_epilogue(epilogue, acc)
        out = out.astype(out_dtype or a.dtype)
        faults.maybe_fail("kernel_run")
        return out


def _packed_weight_flatten(pw: PackedWeight):
    return (pw.packed, pw.scales), (pw.k, pw.n, pw.plan)


def _packed_weight_unflatten(aux, children):
    k, n, plan = aux
    return PackedWeight(packed=children[0], k=k, n=n, plan=plan,
                        scales=children[1])


jax.tree_util.register_pytree_node(PackedWeight, _packed_weight_flatten,
                                   _packed_weight_unflatten)


@dataclasses.dataclass
class GroupedPackedWeight(_PackedCommon):
    """A stacked expert weight [E, K, N] stored pre-packed tile-major.

    The grouped extension of :class:`PackedWeight`: every expert's matrix is
    packed with the same plan into one [E, Nb, Kb, bk, bn] buffer, paid once
    at load time and consumed by ``gemm_grouped_packed`` with the expert axis
    as the outermost grid dimension. Registered as a pytree node (the packed
    stack and the optional [E, Nb, Kb] scale grid are the leaves), so
    scan-stacked MoE layers ([L, E, K, N] at rest) slice through
    ``jax.lax.scan`` like any other parameter leaf.

    ``n_b_streams=2`` at pack time reserves VMEM for the fused silu-gate
    kernel's second B stream + accumulator — use it for gate/up pairs so
    both weights share one silu-gate-feasible plan. ``quantize="int8"``
    stores int8 tiles + per-tile scales; all three serving contractions
    (matmul, silu-gate, and their ragged counts forms) dequantize in-kernel.
    """

    packed: jnp.ndarray     # [E, Nb, Kb, bk, bn] (+ leading stack dims)
    e: int
    k: int
    n: int
    plan: GemmPlan
    scales: Optional[jnp.ndarray] = None   # [E, Nb, Kb] / [E, Nb] for :col
                                           # (+ leading stack dims)

    @classmethod
    def pack(cls, w: jnp.ndarray, *, m_hint: int = 1024,
             plan: Optional[GemmPlan] = None,
             n_b_streams: int = 1,
             backend: Optional[str] = None,
             quantize: Optional[str] = None) -> "GroupedPackedWeight":
        """w: [E, K, N], or [L, E, K, N] for scan-stacked MoE layers."""
        assert w.ndim in (3, 4), w.shape
        e, k, n = w.shape[-3:]
        b_dtype, gran = _parse_quantize(quantize)
        plan = plan or plan_grouped_gemm(
            e, m_hint, k, n, jnp.dtype(w.dtype).name,
            n_b_streams=n_b_streams, b_dtype=b_dtype,
            scale_granularity=gran)
        cls._check_quantize_plan(plan, quantize)
        fmt = plan.b_format
        be = backend or default_backend()
        if w.ndim == 4:
            # Load-time packing of the whole layer stack, one layer per
            # vmapped packer call.
            packed, scales = jax.vmap(
                lambda wl: cls._pack_pair(wl, fmt, be, grouped=True))(w)
        else:
            packed, scales = cls._pack_pair(w, fmt, be, grouped=True)
        return cls(packed=packed, e=e, k=k, n=n, plan=plan, scales=scales)

    def _check(self, a: jnp.ndarray) -> None:
        if self.packed.ndim != 5:
            raise ValueError(
                f"grouped matmul needs a per-layer packed stack "
                f"[E,Nb,Kb,bk,bn]; got ndim={self.packed.ndim} (still "
                f"scan-stacked?)")
        if a.ndim != 3 or a.shape[0] != self.e or a.shape[2] != self.k:
            raise ValueError(
                f"grouped operand mismatch: a={a.shape}, weight stack is "
                f"E={self.e}, K={self.k}")

    def _use_kernel(self, a: jnp.ndarray, backend: Optional[str]) -> bool:
        # Decode-shaped per-expert M (a single sublane block of capacity
        # slots) stays on the jnp fallback: the padded-envelope A stream and
        # grid overheads cannot amortize over so few rows.
        be = backend or default_backend()
        sub, _ = mdt.alignment(a.dtype)
        return be == "pallas" and a.shape[1] > sub

    def _check_pair(self, up: "GroupedPackedWeight") -> None:
        if self.plan != up.plan or self.packed.shape != up.packed.shape:
            raise ValueError("silu_gate pair must share plan and geometry "
                             f"({self.plan} vs {up.plan})")
        if (self.scales is None) != (up.scales is None):
            raise ValueError("silu_gate pair must be quantized together")

    def _check_ragged(self, a: jnp.ndarray, counts: jnp.ndarray) -> None:
        if a.ndim != 4 or a.shape[0] != self.e or a.shape[3] != self.k:
            raise ValueError(
                f"ragged grouped operand mismatch: a={a.shape} must be "
                f"[E={self.e}, S, C, K={self.k}]")
        if counts.shape != a.shape[:2]:
            raise ValueError(
                f"counts {counts.shape} must match a's [E, S]={a.shape[:2]}")

    def _ragged(self, a, counts, *, b2=None, bias=None,
                epilogue="none", out_dtype=None, backend=None):
        """Dispatch the ragged contraction: a [E, S, C, K], counts [E, S].

        ``b2`` is the silu-gate partner WEIGHT (GroupedPackedWeight), so its
        packed stack and scale grid travel together. On the pallas backend
        (TPU target), prefill-shaped segments run the scalar-prefetch
        kernel, whose grid early-outs every all-padding (segment, m-block)
        step; decode-shaped segments (C inside one sublane block) have at
        most one block to skip and keep the masked fallback. On the jnp
        backend the ragged contract lowers to the masked batched einsum:
        XLA:CPU's monolithic batched GEMM outruns any runtime-skipping
        control flow at serving shapes (measured — see
        benchmarks/bench_moe_grouped.py), so the CPU path keeps padded-GEMM
        speed and the ragged *semantics* (zeroed tails). The cond-guarded
        CPU lowering of the skipping algorithm lives in the strategy
        registry (``run_grouped("grouped_packed_ragged", backend="jnp")``)
        as a comparison lowering, like the paper's slower codegen variants.
        """
        if (epilogue == "silu_gate") != (b2 is not None):
            raise ValueError("epilogue='silu_gate' requires the partner "
                             "stack (use silu_gate(), not matmul())")
        faults.maybe_fail("kernel_compile")
        e, s, c, k = a.shape
        be = backend or default_backend()
        bm = self._clamp_bm(c, a.dtype)
        scales = faults.corrupt("scale_grid", self.scales)
        sub, _ = mdt.alignment(a.dtype)
        if be == "pallas" and c > sub:
            out = gemm_grouped_packed_ragged(
                a, self.packed, self.n, counts,
                b2_packed=b2.packed if b2 is not None else None,
                bm=bm, layout_b=self.plan.layout_b, b_scales=scales,
                b2_scales=b2.scales if b2 is not None else None, bias=bias,
                epilogue=epilogue, b_format=self.fmt,
                out_dtype=out_dtype or a.dtype)
            faults.maybe_fail("kernel_run")
            return out
        b_full = ref.unpack_b_grouped_ref(self.packed, self.k, self.n,
                                          self.plan.layout_b,
                                          scales=scales, fmt=self.fmt)
        b2_full = (ref.unpack_b_grouped_ref(b2.packed, self.k, self.n,
                                            self.plan.layout_b,
                                            scales=b2.scales, fmt=self.fmt)
                   if b2 is not None else None)
        epi = (None if epilogue in ("none", "silu_gate")
               else lambda x: apply_epilogue(epilogue, x))
        out = ref.grouped_ragged_ref(a, b_full, counts, b2=b2_full,
                                     bias=bias, epilogue_fn=epi,
                                     out_dtype=out_dtype or a.dtype)
        faults.maybe_fail("kernel_run")
        return out

    def _spec(self, a3, *, epilogue, bias, counts, out_dtype):
        return ContractionSpec.grouped(
            self.e, a3.shape[1], self.k, self.n, a3.dtype, w=self,
            epilogue=epilogue, bias=bias is not None, counts=counts,
            out_dtype=out_dtype)

    def matmul(self, a: jnp.ndarray, *, counts=None, bias=None,
               epilogue="none", out_dtype=None,
               backend: Optional[str] = None) -> jnp.ndarray:
        """out[e] = epilogue(a[e] @ W[e] + bias[e]); a: [E, M, K].

        A spec facade over the one dispatch point (the operands arrive
        already expert-major, so this calls ``dispatch`` directly on the
        folded form), with the guarded-degradation runner around the chosen
        lowering (env/auto choices degrade down the fallback chain on
        failure; see ``repro.core.contraction.run_guarded``). With
        ``counts`` ([E, S] int32) the call is RAGGED: ``a`` must be
        [E, S, C, K] (S capacity segments of C rows per expert) and rows
        at/past ``counts[e, s]`` are padding — skipped by the kernel grid
        and zero in the [E, S, C, N] output.
        """
        epi = as_epilogue_spec(epilogue)
        if epi.gate_mul:
            # Contract violation, not a lowering failure: reject before
            # dispatch so the guarded chain never swallows it.
            raise ValueError("epilogue='silu_gate' requires the partner "
                             "stack (use silu_gate(), not matmul())")
        if counts is not None:
            self._check_ragged(a, counts)
            a3 = a.reshape(self.e, -1, self.k)
        else:
            self._check(a)
            a3 = a
        spec = self._spec(a3, epilogue=epi, bias=bias,
                          counts=counts is not None, out_dtype=out_dtype)
        out = ctr.run_guarded(
            spec, ctr.fallback_chain(spec, ctr.dispatch(spec)),
            lambda lw: lw.run(spec, a3, self, bias=bias, counts=counts,
                              backend=backend))
        return out.reshape(a.shape[:-1] + (self.n,))

    def silu_gate(self, up: "GroupedPackedWeight", a: jnp.ndarray, *,
                  counts=None, out_dtype=None,
                  backend: Optional[str] = None) -> jnp.ndarray:
        """silu(a @ self) * (a @ up) — the fused MoE gate/up pair.

        One pass over the gate accumulator: the kernel streams both packed
        stacks against a single A read and applies silu*mul in VMEM before
        the one HBM store. ``counts`` selects the ragged form exactly as in
        :meth:`matmul` — both packed streams skip the padding blocks.
        """
        self._check_pair(up)
        if counts is not None:
            self._check_ragged(a, counts)
            up._check_ragged(a, counts)
            a3 = a.reshape(self.e, -1, self.k)
        else:
            self._check(a)
            up._check(a)
            a3 = a
        spec = self._spec(a3, epilogue=as_epilogue_spec("silu_gate"),
                          bias=None, counts=counts is not None,
                          out_dtype=out_dtype)
        out = ctr.run_guarded(
            spec, ctr.fallback_chain(spec, ctr.dispatch(spec)),
            lambda lw: lw.run(spec, a3, self, w2=up, counts=counts,
                              backend=backend))
        return out.reshape(a.shape[:-1] + (self.n,))

    def _matmul_impl(self, a, *, bias, epilogue: str, out_dtype,
                     backend) -> jnp.ndarray:
        """Non-ragged lowering body: every expert's B tiles stream
        contiguously from the load-time-packed stack; A is consumed from
        its natural [E, M, K] layout. Decode-shaped per-expert M keeps the
        jnp reference contraction (see :meth:`_use_kernel`)."""
        faults.maybe_fail("kernel_compile")
        bm = self._clamp_bm(a.shape[1], a.dtype)
        scales = faults.corrupt("scale_grid", self.scales)
        if self._use_kernel(a, backend):
            out = gemm_grouped_packed(a, self.packed, self.n, bm=bm,
                                      layout_b=self.plan.layout_b,
                                      b_scales=scales, bias=bias,
                                      epilogue=epilogue,
                                      b_format=self.fmt,
                                      out_dtype=out_dtype or a.dtype)
            faults.maybe_fail("kernel_run")
            return out
        acc = ref.grouped_fused_acc_ref(a, self.packed, self.n,
                                        layout_b=self.plan.layout_b,
                                        bm=bm, b_scales=scales,
                                        fmt=self.fmt)
        out = strat.grouped_epilogue(acc, None, bias, epilogue,
                                     out_dtype or a.dtype)
        faults.maybe_fail("kernel_run")
        return out

    def _silu_gate_impl(self, up: "GroupedPackedWeight", a, *, out_dtype,
                        backend) -> jnp.ndarray:
        faults.maybe_fail("kernel_compile")
        bm = self._clamp_bm(a.shape[1], a.dtype)
        scales = faults.corrupt("scale_grid", self.scales)
        if self._use_kernel(a, backend):
            out = gemm_grouped_packed(a, self.packed, self.n,
                                      b2_packed=up.packed, bm=bm,
                                      layout_b=self.plan.layout_b,
                                      b_scales=scales,
                                      b2_scales=up.scales,
                                      epilogue="silu_gate",
                                      b_format=self.fmt,
                                      out_dtype=out_dtype or a.dtype)
            faults.maybe_fail("kernel_run")
            return out
        gate = ref.grouped_fused_acc_ref(a, self.packed, self.n,
                                         layout_b=self.plan.layout_b,
                                         bm=bm, b_scales=scales,
                                         fmt=self.fmt)
        up_acc = ref.grouped_fused_acc_ref(a, up.packed, up.n,
                                           layout_b=up.plan.layout_b,
                                           bm=bm, b_scales=up.scales,
                                           fmt=up.fmt)
        out = strat.grouped_epilogue(gate, up_acc, None, "silu_gate",
                                     out_dtype or a.dtype)
        faults.maybe_fail("kernel_run")
        return out


def _grouped_weight_flatten(gw: GroupedPackedWeight):
    return (gw.packed, gw.scales), (gw.e, gw.k, gw.n, gw.plan)


def _grouped_weight_unflatten(aux, children):
    e, k, n, plan = aux
    return GroupedPackedWeight(packed=children[0], e=e, k=k, n=n, plan=plan,
                               scales=children[1])


jax.tree_util.register_pytree_node(GroupedPackedWeight,
                                   _grouped_weight_flatten,
                                   _grouped_weight_unflatten)


# ---------------------------------------------------------------------------
# Capability registration: the load-time-packed weight lowerings
# ---------------------------------------------------------------------------

def _run_packed_weight(spec, a, w, *, w2=None, c=None, bias=None, counts=None,
                       alpha=1.0, beta=0.0, plan=None, backend=None,
                       interpret=None):
    if c is not None or alpha != 1.0 or beta != 0.0:
        raise ValueError(
            "PackedWeight matmul supports the linear-layer epilogue only "
            "(no c/alpha/beta)")
    return w._matmul_impl(a, bias=bias, epilogue=spec.epilogue.kernel_name,
                          out_dtype=spec.resolved_out_dtype(a),
                          backend=backend)


def _run_grouped_packed_weight(spec, a, w, *, w2=None, c=None, bias=None,
                               counts=None, alpha=1.0, beta=0.0, plan=None,
                               backend=None, interpret=None):
    # Operands arrive folded: a [E, M, K], counts [E, S] (M = S*C). The
    # kernel-vs-reference choice per backend/shape lives in the impls —
    # the registry records the CAPABILITY, the impl owns the execution.
    w._check(a)
    if w2 is not None:
        w._check_pair(w2)
    out_dtype = spec.resolved_out_dtype(a)
    epi = spec.epilogue.kernel_name
    if counts is not None:
        s = counts.shape[1]
        a4 = a.reshape(w.e, s, -1, a.shape[-1])
        out = w._ragged(a4, counts, b2=w2, bias=bias, epilogue=epi,
                        out_dtype=out_dtype, backend=backend)
        return out.reshape(w.e, a.shape[1], w.n)
    if w2 is not None:
        return w._silu_gate_impl(w2, a, out_dtype=out_dtype, backend=backend)
    return w._matmul_impl(a, bias=bias, epilogue=epi, out_dtype=out_dtype,
                          backend=backend)


ctr.register_lowering(
    "packed_weight", "dense",
    supports=lambda spec: spec.weight == "packed",
    cost=lambda spec: 0.0,   # load-time packing already paid: always the pick
    run=_run_packed_weight)
ctr.register_lowering(
    "grouped_packed_weight", "grouped",
    supports=lambda spec: spec.weight == "packed",
    cost=lambda spec: 0.0,
    run=_run_grouped_packed_weight)
