"""Grouped (batched-expert) GEMM over load-time-packed weights.

One Pallas kernel contracts a stacked activation tensor A[E, M, K] against a
stack of tile-major-packed expert weights B[E, Nb, Kb, bk, bn] — the MoE
expert contraction (``models/moe.py``) expressed as the paper's layered
pipeline grown one dimension: the expert axis becomes the outermost grid
dimension and the same micro kernel is composed across the whole batch of
expert problems (the "compiler-composed nanokernel" direction of Library
Liberation, applied to grouped GEMM).

A streams pack-free from its natural [E, M, K] layout exactly as in
``gemm_packed_fused_a`` — the BlockSpec index maps simply gain a leading
expert coordinate — and every expert's B tiles arrive as contiguous
HBM→VMEM DMAs from the load-time-packed buffer (``pack.pack_b_grouped``).

Epilogues are fused into the final K-step as in the 2-D kernels, plus one
grouped-only fusion: ``epilogue="silu_gate"`` takes a *second* packed weight
stack and computes ``silu(A@Bg) * (A@Bu)`` with two revolving accumulators
sharing a single A stream — the MoE gate/up einsum pair collapses into one
pass over the gate accumulator (one kernel, one A read, one HBM store).

``gemm_grouped_packed_ragged`` is the occupancy-aware variant: the capacity
dimension of a GShard-style dispatch is padded (capacity C per expert), so a
skewed router leaves whole stretches of all-zero rows in A. The ragged kernel
takes a scalar-prefetched per-segment valid-row count
(``PrefetchScalarGridSpec``) and (a) early-outs the K-loop of every
(segment, m-block) grid step that is entirely padding — the count-aware A/B
index maps also pin the DMA indices of skipped steps, so runs of skipped
steps re-reference already-resident tiles instead of fetching new ones — and
(b) clamps the final partial m-block with an iota row mask, so dropped-token
slots are stored as zeros and never carry garbage back to HBM. The micro
kernel (the dot per grid step) is byte-identical to the padded kernel's;
only the outer layers learned the data shape, per the paper's layering.

``gemm_grouped_packed_ragged_jnp`` is the matching jnp lowering (runs
natively on CPU): the same (segment, m-block) decomposition expressed as a
``lax.cond``-guarded block loop, so XLA executes — not merely masks — only
the occupied blocks at run time. It is a COMPARISON lowering (the strategy
registry's CPU expression of the skipping algorithm, parity-tested against
the kernel): XLA:CPU's monolithic batched GEMM beats any serialized
control-flow skipping at serving shapes, so the production jnp fallback in
``core.layered`` keeps the masked batched einsum instead.

Counts contract (shared by both lowerings): ``counts[e, s]`` is the number of
valid leading rows of segment ``s`` of expert ``e``, int32, ``0 <= counts <=
C``; rows at or past the count are treated as padding regardless of content,
and are zero in the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tile_format import TileFormat
from repro.kernels.common import (KERNEL_EPILOGUES, GemmRefs, acc_dtype_for,
                                  b_tile_spec, cdiv, col_scaled,
                                  contract_tile, default_interpret, pad2d,
                                  pallas_kwargs, scale_operand,
                                  scale_spec, tile_scale)


def _grouped_scales(r: GemmRefs, fmt: TileFormat, **coords):
    """(gate scale, up scale) of the current B tile(s) from the SMEM grids
    (None when unquantized)."""
    if r.scale is None:
        return None, None
    s2 = (tile_scale(r.scale2, fmt, **coords) if r.scale2 is not None
          else None)
    return tile_scale(r.scale, fmt, **coords), s2


def _grouped_store(r: GemmRefs, fmt: TileFormat, epilogue: str, scales):
    """The grouped kernels' fused store epilogue on the VMEM accumulators:
    (hoisted col-scale dequant,) bias, then activation or silu-gate."""
    col = col_scaled(fmt)
    out = r.acc[...]
    if col and scales[0] is not None:  # hoisted dequant, ahead of the rest
        out = out * scales[0].astype(out.dtype)
    if r.bias is not None:
        out = out + r.bias[0].astype(out.dtype)     # [1,bn] broadcast
    if r.acc2 is None:
        return KERNEL_EPILOGUES[epilogue](out)
    # silu(gate) * up on the VMEM accumulators — the MoE pair fusion.
    up = r.acc2[...]
    if col and scales[1] is not None:
        up = up * scales[1].astype(up.dtype)
    return KERNEL_EPILOGUES["silu"](out) * up


def _grouped_kernel(*refs, k_steps, n_blocks, fmt, epilogue, has_bias,
                    has_scale, has_gate):
    r = GemmRefs(refs, n_lead=2, has_gate=has_gate, has_scale=has_scale,
                 has_bias=has_bias)
    a_ref, b_ref = r.lead
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        r.acc[...] = jnp.zeros_like(r.acc)
        if has_gate:
            r.acc2[...] = jnp.zeros_like(r.acc2)

    a = a_ref[0]       # [bm, bk] strided block of the NATURAL [E, M, K] layout
    # Quantized stacks dequantize per K-step (per-tile scale on the f32
    # accumulator path, gate and up each with their own scale grid).
    # Col-granularity scales are K-invariant: the store epilogue applies
    # them once (store-only dequant).
    scales = _grouped_scales(r, fmt, nb=n_blocks, kb=k_steps,
                             e=pl.program_id(0), j=pl.program_id(2), kk=kk)
    step = (None, None) if col_scaled(fmt) else scales
    r.acc[...] += contract_tile(a, b_ref[0, 0, 0], step[0], fmt, r.acc.dtype)
    if has_gate:
        r.acc2[...] += contract_tile(a, r.b2[0, 0, 0], step[1], fmt,
                                     r.acc2.dtype)

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        r.out[0] = _grouped_store(r, fmt, epilogue, scales).astype(r.out.dtype)


def _append_scales(in_specs, operands, fmt, grid_enk, b_shape, b_scales,
                   b2_scales):
    """Append the gate (and up) scale grids as flat SMEM operands."""
    if b_scales is None:
        return
    e, nb, kb = grid_enk
    want = (e, nb) if col_scaled(fmt) else (e, nb, kb)
    for sc in (b_scales, b2_scales):
        if sc is None:
            continue
        assert sc.shape == want, (sc.shape, b_shape, want)
        in_specs.append(scale_spec())
        operands.append(scale_operand(sc))


def gemm_grouped_packed(a: jnp.ndarray,
                        b_packed: jnp.ndarray,
                        n: int,
                        *,
                        b2_packed: jnp.ndarray | None = None,
                        bm: int = 128,
                        layout_b: str = "row",
                        b_scales: jnp.ndarray | None = None,
                        b2_scales: jnp.ndarray | None = None,
                        out_dtype=None,
                        epilogue: str = "none",
                        bias: jnp.ndarray | None = None,
                        b_format: TileFormat | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Grouped pack-free-A GEMM: out[e] = epilogue(A[e] @ unpack(B[e]) + bias[e]).

    a:        [E, M, K] activations in their natural layout (streamed
              block-by-block per expert — no tile-major copy of A, ever).
    b_packed: [E, Nb, Kb, bk, bn] (row) / [E, Nb, Kb, bn, bk] (col), from
              ``pack.pack_b_grouped`` (typically once, at weight-load time).
    bias:     optional per-expert bias [E, N].
    epilogue: a name from ``KERNEL_EPILOGUES``, or ``"silu_gate"`` — then
              ``b2_packed`` (same packed geometry) must be given and the
              kernel returns ``silu(A@B) * (A@B2)`` computed in one pass.
    b_scales / b2_scales: f32 scale grids for quantized stacks (from a
              quantized ``pack_b_grouped``): per-tile [E, Nb, Kb] dequant
              is fused per K-step ahead of every store epilogue; per-column
              [E, Nb] (``granularity="col"``) dequant hoists into the store
              epilogue itself — either way bias / activation / silu-gate
              work quantized unchanged.
    b_format: the authoritative :class:`TileFormat` — REQUIRED for
              nibble-packed int4 stacks and col-granularity scales (neither
              is inferable from the buffer); inferred when omitted.

    Returns [E, M, n].
    """
    if interpret is None:
        interpret = default_interpret()
    has_gate = epilogue == "silu_gate"
    if has_gate != (b2_packed is not None):
        raise ValueError("epilogue='silu_gate' requires b2_packed (and only "
                         "silu_gate takes it)")
    has_scale = b_scales is not None
    if has_gate and has_scale != (b2_scales is not None):
        raise ValueError("quantized silu_gate needs BOTH scale grids")
    fmt = b_format if b_format is not None else TileFormat.from_packed(
        b_packed, layout_b, has_scales=has_scale)
    e, m, k = a.shape
    eb, nb, kb = b_packed.shape[:3]
    assert eb == e, (a.shape, b_packed.shape)
    bk, bn = fmt.bk, fmt.bn
    assert cdiv(k, bk) == kb, (a.shape, b_packed.shape, bk)
    if has_gate:
        assert b2_packed.shape == b_packed.shape, (b2_packed.shape,
                                                   b_packed.shape)
    out_dtype = out_dtype or a.dtype
    acc_dtype = acc_dtype_for(a.dtype)
    a_p = jax.vmap(lambda ae: pad2d(ae, bm, bk))(a)   # [E, Mp, Kp]
    mb = cdiv(m, bm)

    grid = (e, mb, nb, kb)  # expert outermost; K innermost (revolving acc)
    b_map = lambda ee, i, j, kk: (ee, j, kk, 0, 0)
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda ee, i, j, kk: (ee, i, kk)),
        b_tile_spec(fmt, b_map, lead=3),
    ]
    operands = [a_p, b_packed]
    if has_gate:
        in_specs.append(b_tile_spec(fmt, b_map, lead=3))
        operands.append(b2_packed)
    _append_scales(in_specs, operands, fmt, (e, nb, kb), b_packed.shape,
                   b_scales, b2_scales if has_gate else None)
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (e, n), (bias.shape, (e, n))
        in_specs.append(
            pl.BlockSpec((1, 1, bn), lambda ee, i, j, kk: (ee, 0, j)))
        operands.append(jax.vmap(
            lambda be: pad2d(be.reshape(1, n), 1, bn))(bias))
    scratch = [pltpu.VMEM((bm, bn), acc_dtype)]
    if has_gate:
        scratch.append(pltpu.VMEM((bm, bn), acc_dtype))

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, k_steps=kb, n_blocks=nb, fmt=fmt,
                          epilogue=epilogue, has_bias=has_bias,
                          has_scale=has_scale, has_gate=has_gate),
        name="gemm_grouped_packed",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda ee, i, j, kk: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, mb * bm, nb * bn), out_dtype),
        scratch_shapes=scratch,
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(*operands)
    return out[:, :m, :n]


# ---------------------------------------------------------------------------
# Ragged (occupancy-aware) grouped GEMM
# ---------------------------------------------------------------------------

def _ragged_kernel(*refs, k_steps, n_blocks, segments, bm, fmt, epilogue,
                   has_bias, has_scale, has_gate):
    r = GemmRefs(refs, n_lead=3, has_gate=has_gate, has_scale=has_scale,
                 has_bias=has_bias)
    counts_ref, a_ref, b_ref = r.lead

    g = pl.program_id(0)
    i = pl.program_id(1)
    kk = pl.program_id(3)
    # Valid rows of THIS m-block: whole blocks below the count contribute bm,
    # the partial block gets the remainder, blocks past the count get 0.
    bc = jnp.clip(counts_ref[g] - i * bm, 0, bm)
    live = bc > 0
    last_k = kk == k_steps - 1
    scales = _grouped_scales(r, fmt, nb=n_blocks, kb=k_steps,
                             e=g // segments, j=pl.program_id(2), kk=kk)
    step = (None, None) if col_scaled(fmt) else scales

    @pl.when(live & (kk == 0))
    def _init():
        r.acc[...] = jnp.zeros_like(r.acc)
        if has_gate:
            r.acc2[...] = jnp.zeros_like(r.acc2)

    # Zero-work early-out: an all-padding block skips the dot(s) entirely —
    # the grid still visits the step, but the MXU never fires.
    @pl.when(live)
    def _acc():
        r.acc[...] += contract_tile(a_ref[0], b_ref[0, 0, 0], step[0], fmt,
                                    r.acc.dtype)
        if has_gate:
            r.acc2[...] += contract_tile(a_ref[0], r.b2[0, 0, 0], step[1],
                                         fmt, r.acc2.dtype)

    @pl.when(live & last_k)
    def _epilogue():
        out = _grouped_store(r, fmt, epilogue, scales)
        # Masked store: rows at/past the count are written as zeros, so
        # dropped-token slots never carry garbage (or a bias image) to HBM.
        rows = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        r.out[0] = jnp.where(rows < bc, out, 0).astype(r.out.dtype)

    # All-padding block: one cheap zero store (no accumulator touch, no
    # epilogue) — the output block must still be written, it just never
    # carries data.
    @pl.when(jnp.logical_not(live) & last_k)
    def _store_zeros():
        r.out[0] = jnp.zeros_like(r.out[0])


def gemm_grouped_packed_ragged(a: jnp.ndarray,
                               b_packed: jnp.ndarray,
                               n: int,
                               counts: jnp.ndarray,
                               *,
                               b2_packed: jnp.ndarray | None = None,
                               bm: int = 128,
                               layout_b: str = "row",
                               b_scales: jnp.ndarray | None = None,
                               b2_scales: jnp.ndarray | None = None,
                               out_dtype=None,
                               epilogue: str = "none",
                               bias: jnp.ndarray | None = None,
                               b_format: TileFormat | None = None,
                               interpret: bool | None = None) -> jnp.ndarray:
    """Occupancy-aware grouped GEMM over a scalar-prefetched count vector.

    a:        [E, S, C, K] — per-expert activations in S equal capacity
              segments of C rows each (the MoE path's [G, E, C, d] dispatch
              tensor, expert-major; S=1 for a plain [E, M, K] problem).
    counts:   [E, S] int32, ``counts[e, s] <= C`` — valid leading rows per
              segment. Prefetched to SMEM before the grid runs, so both the
              index maps and the kernel body can branch on it.
    b_packed: [E, Nb, Kb, bk, bn] from ``pack.pack_b_grouped`` (load time).
    b_scales / b2_scales: f32 scale grids (quantized stacks): per-tile
              [E, Nb, Kb] or per-column [E, Nb] (``granularity="col"``,
              dequant hoisted into the store epilogue), resident in SMEM
              and read at the B tile coordinates of live steps only.
    b_format: authoritative :class:`TileFormat` (REQUIRED for int4 /
              col-scale stacks; inferred from the buffer when omitted).

    Returns [E, S, C, n]; rows at/past ``counts[e, s]`` are zero. Up to the
    masked tail rows, the result is identical to ``gemm_grouped_packed`` on
    the same operands with the padding rows zeroed.
    """
    if interpret is None:
        interpret = default_interpret()
    has_gate = epilogue == "silu_gate"
    if has_gate != (b2_packed is not None):
        raise ValueError("epilogue='silu_gate' requires b2_packed (and only "
                         "silu_gate takes it)")
    has_scale = b_scales is not None
    if has_gate and has_scale != (b2_scales is not None):
        raise ValueError("quantized silu_gate needs BOTH scale grids")
    fmt = b_format if b_format is not None else TileFormat.from_packed(
        b_packed, layout_b, has_scales=has_scale)
    e, s, c, k = a.shape
    eb, nb, kb = b_packed.shape[:3]
    assert eb == e, (a.shape, b_packed.shape)
    if counts.shape != (e, s):
        raise ValueError(f"counts must be [E, S]={e, s}; got {counts.shape}")
    bk, bn = fmt.bk, fmt.bn
    assert cdiv(k, bk) == kb, (a.shape, b_packed.shape, bk)
    if has_gate:
        assert b2_packed.shape == b_packed.shape, (b2_packed.shape,
                                                   b_packed.shape)
    out_dtype = out_dtype or a.dtype
    acc_dtype = acc_dtype_for(a.dtype)
    grp = e * s
    bm = min(bm, -(-c // 8) * 8)  # never block beyond the segment envelope
    a3 = a.reshape(grp, c, k)
    a_p = jax.vmap(lambda ae: pad2d(ae, bm, bk))(a3)   # [E*S, Cp, Kp]
    mb = cdiv(c, bm)
    counts_flat = jnp.clip(counts.reshape(grp), 0, c).astype(jnp.int32)

    grid = (grp, mb, nb, kb)  # segment outermost; K innermost (revolving acc)

    def live(cnt, g, i):
        return cnt[g] > i * bm

    # Count-aware index maps: a skipped (g, i) step pins its A/B indices to
    # the block-0 coordinates, so a run of skipped steps issues no new DMAs
    # (Pallas elides the copy when consecutive indices coincide).
    def a_map(g, i, j, kk, cnt):
        ok = live(cnt, g, i)
        return (g, jnp.where(ok, i, 0), jnp.where(ok, kk, 0))

    def b_map(g, i, j, kk, cnt):
        return (g // s, j, jnp.where(live(cnt, g, i), kk, 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, bm, bk), a_map),
        b_tile_spec(fmt, b_map, lead=3),
    ]
    operands = [a_p, b_packed]
    if has_gate:
        in_specs.append(b_tile_spec(fmt, b_map, lead=3))
        operands.append(b2_packed)
    _append_scales(in_specs, operands, fmt, (e, nb, kb), b_packed.shape,
                   b_scales, b2_scales if has_gate else None)
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (e, n), (bias.shape, (e, n))
        in_specs.append(
            pl.BlockSpec((1, 1, bn), lambda g, i, j, kk, cnt: (g // s, 0, j)))
        operands.append(jax.vmap(
            lambda be: pad2d(be.reshape(1, n), 1, bn))(bias))
    scratch = [pltpu.VMEM((bm, bn), acc_dtype)]
    if has_gate:
        scratch.append(pltpu.VMEM((bm, bn), acc_dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda g, i, j, kk, cnt: (g, i, j)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, k_steps=kb, n_blocks=nb,
                          segments=s, bm=bm, fmt=fmt, epilogue=epilogue,
                          has_bias=has_bias, has_scale=has_scale,
                          has_gate=has_gate),
        name="gemm_grouped_packed_ragged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((grp, mb * bm, nb * bn), out_dtype),
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(counts_flat, *operands)
    return out[:, :c, :n].reshape(e, s, c, n)


def unpack_b_grouped(b_packed: jnp.ndarray, k: int, n: int,
                     layout_b: str = "row",
                     scales: jnp.ndarray | None = None,
                     fmt: TileFormat | None = None) -> jnp.ndarray:
    """Tile-major [E, Nb, Kb, bk, bn] -> natural [E, K, N] view (one copy).

    ``scales`` ([E, Nb, Kb] per-tile / [E, Nb] per-column, quantized
    stacks) dequantizes each tile before the reshape — the natural view is
    then float. ``fmt`` is required for nibble-packed int4 stacks (the
    buffer is widened to i8 first).
    """
    if fmt is not None and fmt.sub_byte:
        from repro.core.tile_format import unpack_nibbles
        b_packed = unpack_nibbles(b_packed)
    if scales is not None:
        extra = b_packed.ndim - scales.ndim
        b_packed = (b_packed.astype(scales.dtype)
                    * scales[(...,) + (None,) * extra])
    if layout_b == "col":
        b_packed = b_packed.transpose(0, 1, 2, 4, 3)
    e, nb, kb, bk, bn = b_packed.shape
    full = b_packed.transpose(0, 2, 3, 1, 4).reshape(e, kb * bk, nb * bn)
    return full[:, :k, :n]


def gemm_grouped_packed_ragged_jnp(a: jnp.ndarray,
                                   b_packed: jnp.ndarray,
                                   n: int,
                                   counts: jnp.ndarray,
                                   *,
                                   b2_packed: jnp.ndarray | None = None,
                                   bm: int = 16,
                                   layout_b: str = "row",
                                   b_scales: jnp.ndarray | None = None,
                                   b2_scales: jnp.ndarray | None = None,
                                   out_dtype=None,
                                   epilogue: str = "none",
                                   bias: jnp.ndarray | None = None,
                                   b_format: TileFormat | None = None,
                                   ) -> jnp.ndarray:
    """jnp lowering of :func:`gemm_grouped_packed_ragged` (CPU-native).

    Same contract and (segment, m-block) decomposition; the early-out is a
    ``lax.cond`` per block, which XLA executes as a real branch — occupied
    blocks run a full-width [bm, K] x [K, N] dot in f32, padding blocks run
    nothing. The packed stack is unpacked to a natural [E, K, N] view once
    per call (a reshape-transpose, trivial next to the dots) so the block
    dots hit the backend's fast GEMM path instead of a tile-by-tile einsum.

    This is the strategy registry's comparison lowering, not the serving
    fallback: the block loop is serialized by construction, and XLA:CPU's
    batched GEMM wins back more through parallel packing/blocking than the
    skipped padding saves at serving shapes (the masked einsum in
    ``core.layered`` is the production CPU path). It exists to express — and
    property-test — the exact skipping semantics of the kernel in portable
    jnp, and to measure the algorithm where a serialized backend is honest
    about it.
    """
    has_gate = epilogue == "silu_gate"
    if has_gate != (b2_packed is not None):
        raise ValueError("epilogue='silu_gate' requires b2_packed (and only "
                         "silu_gate takes it)")
    e, s, c, k = a.shape
    if counts.shape != (e, s):
        raise ValueError(f"counts must be [E, S]={e, s}; got {counts.shape}")
    out_dtype = out_dtype or a.dtype
    grp = e * s
    bm = max(8, min(bm, -(-c // 8) * 8))
    mb = cdiv(c, bm)
    cp = mb * bm
    b_full = unpack_b_grouped(b_packed, k, n, layout_b,
                              scales=b_scales,
                              fmt=b_format).astype(jnp.float32)
    b2_full = (unpack_b_grouped(b2_packed, k, n, layout_b,
                                scales=b2_scales,
                                fmt=b_format).astype(jnp.float32)
               if has_gate else None)
    a3 = a.reshape(grp, c, k).astype(jnp.float32)
    if cp != c:
        a3 = jnp.pad(a3, ((0, 0), (0, cp - c), (0, 0)))
    counts_flat = jnp.clip(counts.reshape(grp), 0, c).astype(jnp.int32)

    segs = []
    for g in range(grp):           # static unroll: E*S segments
        eg = g // s                # static expert index -> static B slice
        ag, be = a3[g], b_full[eg]
        b2e = b2_full[eg] if has_gate else None
        bias_e = (bias[eg].astype(jnp.float32) if bias is not None else None)
        cnt = counts_flat[g]

        def body(i, out, ag=ag, be=be, b2e=b2e, bias_e=bias_e, cnt=cnt):
            bc = jnp.clip(cnt - i * bm, 0, bm)

            def compute():
                blk = jax.lax.dynamic_slice_in_dim(ag, i * bm, bm, 0)
                acc = blk @ be
                if bias_e is not None:
                    acc = acc + bias_e
                if has_gate:
                    return KERNEL_EPILOGUES["silu"](acc) * (blk @ b2e)
                return KERNEL_EPILOGUES[epilogue](acc)

            blk_out = jax.lax.cond(bc > 0, compute,
                                   lambda: jnp.zeros((bm, n), jnp.float32))
            rows = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 0)
            blk_out = jnp.where(rows < bc, blk_out, 0)
            return jax.lax.dynamic_update_slice_in_dim(out, blk_out,
                                                       i * bm, 0)

        segs.append(jax.lax.fori_loop(0, mb, body,
                                      jnp.zeros((cp, n), jnp.float32)))
    out = jnp.stack(segs)[:, :c]
    return out.reshape(e, s, c, n).astype(out_dtype)
