"""Pallas packing kernels — the paper's macro-level data reorganization (§3.1).

``pack_a`` copies A[M,K] into a tile-major buffer [Mb, Kb, bm, bk] whose tiles
lie in memory in row-of-tiles order — the exact order the micro kernel consumes
them (paper Fig. 2b). ``pack_b`` produces [Nb, Kb, bk, bn] in column-of-tiles
order. Remainder tiles are zero-filled (paper: "the remainder elements are
filled with zeroes in the packing buffers").

The B-side geometry is :class:`repro.core.tile_format.TileFormat`-driven
(legacy ``(bk, bn, layout)`` ints normalize to a format): ``layout`` chooses
the element order *within* each tile ("row" | "col"), mirroring the paper's
flexible per-target tile layout (MMA wants col-major A, row-major B). On TPU
the packed buffer makes every grid step's HBM→VMEM DMA a single contiguous
block instead of a strided gather.

A QUANTIZED format (int8 elements + a ScaleSpec) makes ``pack_b`` /
``pack_b_grouped`` return ``(packed, scales)``: the per-(Kb,Nb)-tile absmax
scales are computed in jnp (packing is a load-time pass; the absmax reduction
is trivial next to the copy) and the int8 values then take the same Pallas
tile-major copy as float packing — one packer, every element dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.tile_format import (TileFormat, as_tile_format,
                                    pack_nibbles, quantize_tiles)
from repro.kernels.common import cdiv, default_interpret, pad2d, pallas_kwargs
from repro.roofline.hw import current_target
from repro.testing import faults


def _pack_kernel(x_ref, o_ref, *, transpose: bool):
    tile = x_ref[...]
    if transpose:
        tile = tile.T
    o_ref[0, 0] = tile


def _sub_rows(b0: int, b1: int, itemsize: int) -> int:
    """Rows of a tile's leading dim one pack step copies. A whole tile when
    it is small; otherwise the largest lane-aligned divisor of ``b0`` whose
    [rows, b1] block keeps the step's four VMEM buffers (input and output,
    double-buffered) within half the declared VMEM limit — a weight tile the
    planner sized for a GEMM's whole budget never has to fit four times."""
    cap = current_target().vmem_limit_bytes // 8
    lane = current_target().lane
    if b0 * b1 * itemsize <= cap or b0 % lane:
        return b0
    fits = [r for r in range(lane, b0 + 1, lane)
            if b0 % r == 0 and r * b1 * itemsize <= cap]
    return max(fits) if fits else lane


def _pack(x: jnp.ndarray, b0: int, b1: int, *, grid_order: str, layout: str,
          interpret: bool | None):
    """Shared packer. grid_order 'row': out [G0, G1, ...] = [dim0-tiles, dim1-tiles]
    (A's row-of-tiles order); 'col': out [G1, G0, ...] (B's column-of-tiles order).
    Each tile is copied in ``rows``-high slabs of its leading (dim-0) extent.
    """
    if interpret is None:
        interpret = default_interpret()
    transpose = layout == "col"
    x_p = pad2d(x, b0, b1)
    g0, g1 = cdiv(x.shape[0], b0), cdiv(x.shape[1], b1)
    rows = _sub_rows(b0, b1, x.dtype.itemsize)
    steps = b0 // rows
    t0, t1 = (b1, b0) if transpose else (b0, b1)
    out_block = (1, 1, b1, rows) if transpose else (1, 1, rows, b1)

    def tile_ids(p, q):  # grid (p, q) -> (dim-0 tile, dim-1 tile)
        return (p, q) if grid_order == "row" else (q, p)

    def out_index(p, q, r):
        return (p, q, 0, r) if transpose else (p, q, r, 0)

    def in_index(p, q, r):
        i, j = tile_ids(p, q)
        return (i * steps + r, j)

    grid = (g0, g1, steps) if grid_order == "row" else (g1, g0, steps)
    out_shape = (grid[0], grid[1], t0, t1)
    return pl.pallas_call(
        functools.partial(_pack_kernel, transpose=transpose),
        name="pack",
        grid=grid,
        in_specs=[pl.BlockSpec((rows, b1), in_index)],
        out_specs=pl.BlockSpec(out_block, out_index),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        **pallas_kwargs(interpret=interpret,
                        dimension_semantics=("parallel",) * 3),
    )(x_p)


def _quantize_natural(b: jnp.ndarray, fmt: TileFormat):
    """Float B[K,N] -> (int8 natural-layout values, scales).

    The scales come from the shared ``quantize_b_tiles_ref`` contract
    (absmax/qmax per tile [Nb, Kb] or per column [Nb], zero groups -> 1.0);
    the quantized values (int4's stay UNPACKED i8 in [-7, 7] here) are
    scattered back to the natural layout so the Pallas tile-major copy
    below stays the single packing code path — sub-byte nibble packing is
    the caller's final storage step after that copy.
    """
    assert jnp.issubdtype(b.dtype, jnp.floating), (
        f"quantized packing consumes float weights; got {b.dtype}")
    b_p = pad2d(b, fmt.bk, fmt.bn)
    kb, nb = b_p.shape[0] // fmt.bk, b_p.shape[1] // fmt.bn
    tiles = b_p.reshape(kb, fmt.bk, nb, fmt.bn).transpose(2, 0, 1, 3)
    q, scales = quantize_tiles(tiles, fmt)            # [Nb,Kb,bk,bn], [Nb,Kb]
    q_nat = q.transpose(1, 2, 0, 3).reshape(b_p.shape)
    return q_nat, scales


def pack_a(a: jnp.ndarray, bm: int, bk: int, layout: str = "row",
           interpret: bool | None = None) -> jnp.ndarray:
    """A[M,K] -> [Mb, Kb, bm, bk] ("row") or [Mb, Kb, bk, bm] ("col")."""
    faults.maybe_fail("pack")
    return _pack(a, bm, bk, grid_order="row", layout=layout, interpret=interpret)


def pack_b(b: jnp.ndarray, bk, bn: int | None = None, layout: str = "row",
           interpret: bool | None = None):
    """B[K,N] -> [Nb, Kb, bk, bn] ("row") or [Nb, Kb, bn, bk] ("col").

    ``bk`` may be a :class:`TileFormat` (then ``bn``/``layout`` are unused);
    a quantized format returns ``(packed, scales)``.
    """
    faults.maybe_fail("pack")
    fmt = as_tile_format(bk, bn, layout=layout, dtype=b.dtype)
    scales = None
    if fmt.is_quantized:
        b, scales = _quantize_natural(b, fmt)
    packed = _pack(b, fmt.bk, fmt.bn, grid_order="col", layout=fmt.layout,
                   interpret=interpret)
    if fmt.sub_byte:
        packed = pack_nibbles(packed)  # final storage step: 2 values/byte
    return (packed, scales) if fmt.is_quantized else packed


def pack_b_grouped(b: jnp.ndarray, bk, bn: int | None = None,
                   layout: str = "row", interpret: bool | None = None):
    """B[E,K,N] -> [E, Nb, Kb, bk, bn] ("row") / [E, Nb, Kb, bn, bk] ("col").

    The grouped packer for stacked expert weights: each expert's matrix gets
    the same column-of-tiles treatment as :func:`pack_b`, with the expert
    index as the outermost grid dimension — the packed stack is what
    ``gemm_grouped_packed`` consumes (typically packed once at weight-load).
    ``bk`` may be a :class:`TileFormat`; quantized formats return
    ``(packed, scales)`` with per-expert scale grids [E, Nb, Kb].
    """
    faults.maybe_fail("pack")
    fmt = as_tile_format(bk, bn, layout=layout, dtype=b.dtype)
    if interpret is None:
        interpret = default_interpret()
    scales = None
    if fmt.is_quantized:
        b, scales = jax.vmap(lambda be: _quantize_natural(be, fmt))(b)
    transpose = fmt.layout == "col"
    e = b.shape[0]
    b_p = jax.vmap(lambda be: pad2d(be, fmt.bk, fmt.bn))(b)
    kb, nb = cdiv(b.shape[1], fmt.bk), cdiv(b.shape[2], fmt.bn)
    t0, t1 = fmt.tile_shape
    rows = _sub_rows(fmt.bk, fmt.bn, b.dtype.itemsize)
    steps = fmt.bk // rows
    out_block = ((1, 1, 1, fmt.bn, rows) if transpose
                 else (1, 1, 1, rows, fmt.bn))

    def out_index(ee, j, i, r):
        return (ee, j, i, 0, r) if transpose else (ee, j, i, r, 0)

    packed = pl.pallas_call(
        functools.partial(_pack_kernel_grouped, transpose=transpose),
        name="pack_b_grouped",
        grid=(e, nb, kb, steps),
        in_specs=[pl.BlockSpec((1, rows, fmt.bn),
                               lambda ee, j, i, r: (ee, i * steps + r, j))],
        out_specs=pl.BlockSpec(out_block, out_index),
        out_shape=jax.ShapeDtypeStruct((e, nb, kb, t0, t1), b.dtype),
        **pallas_kwargs(interpret=interpret,
                        dimension_semantics=("parallel",) * 4),
    )(b_p)
    if fmt.sub_byte:
        packed = pack_nibbles(packed)  # final storage step: 2 values/byte
    return (packed, scales) if fmt.is_quantized else packed


def _pack_kernel_grouped(x_ref, o_ref, *, transpose: bool):
    tile = x_ref[0]
    if transpose:
        tile = tile.T
    o_ref[0, 0, 0] = tile
