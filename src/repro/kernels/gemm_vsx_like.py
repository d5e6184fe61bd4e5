"""Generic (vector-unit-only) micro-kernel lowering — the paper's **VSX** baseline.

The paper contrasts the MMA-specific lowering of ``llvm.matrix.multiply`` with
LLVM's generic lowering, which on POWER10 emulates each outer product with
*splat + element-wise multiply-add* VSX instructions (§2: "In processors with
one-dimensional vector instructions, the outer products are emulated using a
combination of splatting and element-wise multiply-add instructions").

TPU analogue: compute the block product as a sequence of rank-1 updates using
only VPU-shaped ops (broadcast + FMA), never issuing an MXU contraction. This
kernel exists to quantify the matrix-engine speedup structurally (roofline:
VPU peak ≈ 1/32 of MXU bf16 peak on v5e) and to validate that both lowerings
compute identical results — the paper's Fig. 10b experiment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tile_format import TileFormat
from repro.kernels.common import (acc_dtype_for, b_tile_spec, cdiv,
                                  default_interpret, pad2d, pallas_kwargs)


def _vsx_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps, bk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(acc_ref.dtype)  # [bm, bk]
    b = b_ref[...].astype(acc_ref.dtype)  # [bk, bn]

    def rank1_update(kk, acc):
        a_col = jax.lax.dynamic_slice_in_dim(a, kk, 1, axis=1)  # splat source
        b_row = jax.lax.dynamic_slice_in_dim(b, kk, 1, axis=0)
        return acc + a_col * b_row  # broadcast-multiply-add on the VPU

    acc_ref[...] = jax.lax.fori_loop(0, bk, rank1_update, acc_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _vsx_packed_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps, bk,
                       layout_b):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(acc_ref.dtype)  # [bm, bk] strided (natural layout)
    b = b_ref[0, 0]                       # pre-packed tile, contiguous DMA
    if layout_b == "col":
        b = b.T
    b = b.astype(acc_ref.dtype)           # [bk, bn]

    def rank1_update(kk, acc):
        a_col = jax.lax.dynamic_slice_in_dim(a, kk, 1, axis=1)
        b_row = jax.lax.dynamic_slice_in_dim(b, kk, 1, axis=0)
        return acc + a_col * b_row

    acc_ref[...] = jax.lax.fori_loop(0, bk, rank1_update, acc_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_vsx_like(a: jnp.ndarray,
                    b: jnp.ndarray,
                    *,
                    bm: int = 128,
                    bk: int = 128,
                    bn: int = 128,
                    out_dtype=None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """A @ B via rank-1 VPU updates (no matrix engine)."""
    if interpret is None:
        interpret = default_interpret()
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    acc_dtype = acc_dtype_for(a.dtype)
    a_p, b_p = pad2d(a, bm, bk), pad2d(b, bk, bn)
    mb, kb, nb = cdiv(m, bm), cdiv(k, bk), cdiv(n, bn)

    out = pl.pallas_call(
        functools.partial(_vsx_kernel, k_steps=kb, bk=bk),
        name="matmul_vsx_like",
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mb * bm, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a_p, b_p)
    return out[:m, :n]


def matmul_vsx_like_packed(a: jnp.ndarray,
                           b_packed: jnp.ndarray,
                           n: int,
                           *,
                           bm: int = 128,
                           layout_b: str = "row",
                           out_dtype=None,
                           interpret: bool | None = None) -> jnp.ndarray:
    """A @ unpack(B) via rank-1 VPU updates over a tile-major-packed B.

    The ROADMAP "fused packing for the vsx lowering" item: B arrives
    pre-packed from ``pack.pack_b`` and is consumed via the same BlockSpec
    index maps as ``gemm_packed_fused_a`` — each grid step's B DMA is one
    contiguous [bk,bn] tile instead of a strided gather — while the micro
    kernel stays the generic splat+FMA emulation (no matrix engine).
    """
    if interpret is None:
        interpret = default_interpret()
    fmt = TileFormat.from_packed(b_packed, layout_b)
    m, k = a.shape
    nb, kb = b_packed.shape[:2]
    bk, bn = fmt.bk, fmt.bn
    assert cdiv(k, bk) == kb, (a.shape, b_packed.shape, bk)
    out_dtype = out_dtype or a.dtype
    acc_dtype = acc_dtype_for(a.dtype)
    a_p = pad2d(a, bm, bk)
    mb = cdiv(m, bm)

    out = pl.pallas_call(
        functools.partial(_vsx_packed_kernel, k_steps=kb, bk=bk,
                          layout_b=layout_b),
        name="matmul_vsx_like_packed",
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            b_tile_spec(fmt, lambda i, j, kk: (j, kk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mb * bm, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a_p, b_packed)
    return out[:m, :n]
