"""Blocked GEMM Pallas kernels over PACKED operands — the paper's
**"Tiling+Packing"** strategy (§3.1 + §3.2 combined, Algorithm 1 in full).

Operands come from ``repro.kernels.pack`` in tile-major order, so every grid
step's HBM→VMEM DMA is one contiguous [bm,bk] / [bk,bn] block (unit-stride
stream), the TPU analogue of the paper's packed-buffer locality win (on CPU the
win was cache/TLB behaviour; on TPU it is strided-vs-contiguous DMA).

Supports the paper's per-target intra-tile layouts: layout_a="col" stores A
tiles transposed (MMA's preferred A layout) and the micro kernel contracts
accordingly without any in-VMEM transpose.

Two kernels:

  * :func:`gemm_packed` — both operands pre-packed (the paper's per-call
    pipeline: pack_a + pack_b + this kernel).
  * :func:`gemm_packed_fused_a` — B pre-packed, A consumed *directly from its
    natural [M,K] layout* via the BlockSpec index map (BLIS-style stream
    packing fused into the macro loop). This removes pack_a's full HBM
    read+write of A per call — the right pipeline when A is a per-step
    activation and B is a load-time-packed weight (see core/layered.py's
    ``PackedWeight``).

Both kernels fuse the full epilogue (alpha/beta, ``bias``, activation from
``KERNEL_EPILOGUES``) into the final grid step: one HBM store, no post-kernel
elementwise ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tile_format import TileFormat
from repro.kernels.common import (GemmRefs, acc_dtype_for, b_tile_spec,
                                  bias_spec_and_operand, c_spec_and_operand,
                                  cdiv, col_scaled, contract_tile,
                                  default_interpret, finalize_gemm, pad2d,
                                  pallas_kwargs, scale_operand, scale_spec,
                                  tile_scale)


def _packed_kernel(*refs, alpha, beta, k_steps, layout_a, fmt,
                   epilogue="none", has_c=False, has_bias=False):
    r = GemmRefs(refs, n_lead=2, has_c=has_c, has_bias=has_bias)
    a_ref, b_ref = r.lead
    acc_ref = r.acc

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0, 0]  # [bm,bk] ("row") or [bk,bm] ("col")
    b = b_ref[0, 0]  # [bk,bn] ("row") or [bn,bk] ("col")
    lhs_contract = 1 if layout_a == "row" else 0
    # Result is [bm, bn] for every layout combination (contraction over bk).
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((lhs_contract,), (fmt.rhs_contract,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        finalize_gemm(acc_ref, r.c, r.bias, r.out, alpha=alpha, beta=beta,
                      epilogue=epilogue)


def _fused_a_kernel(*refs, alpha, beta, k_steps, n_blocks, fmt,
                    epilogue="none", has_c=False, has_bias=False,
                    has_scale=False):
    r = GemmRefs(refs, n_lead=2, has_c=has_c, has_scale=has_scale,
                 has_bias=has_bias)
    a_ref, b_ref = r.lead
    acc_ref = r.acc
    j, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]   # [bm,bk] strided block of the NATURAL [M,K] operand
    b = b_ref[0, 0]  # [bk,bn] ("row") or [bn,bk] ("col") pre-packed tile
    # Quantized B dequantizes per K-step on the f32 accumulator (the tile's
    # scalar scale is read from the SMEM grid at B's tile coordinates),
    # ahead of the store epilogue. A col-granularity scale is K-invariant
    # and hoists out of the K loop entirely: finalize_gemm applies it once
    # to the finished accumulator (store-only dequant).
    scale = (tile_scale(r.scale, fmt, nb=n_blocks, kb=k_steps, j=j, kk=kk)
             if has_scale else None)
    col = col_scaled(fmt)
    acc_ref[...] += contract_tile(a, b, None if col else scale, fmt,
                                  acc_ref.dtype)

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        finalize_gemm(acc_ref, r.c, r.bias, r.out, alpha=alpha, beta=beta,
                      epilogue=epilogue, scale=scale if col else None)


def gemm_packed(a_packed: jnp.ndarray,
                b_packed: jnp.ndarray,
                m: int,
                n: int,
                c: jnp.ndarray | None = None,
                *,
                alpha: float = 1.0,
                beta: float = 0.0,
                layout_a: str = "row",
                layout_b: str = "row",
                out_dtype=None,
                epilogue: str = "none",
                bias: jnp.ndarray | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """C[:m,:n] <- epilogue(alpha * unpack(A)@unpack(B) + beta * C + bias).

    a_packed: [Mb, Kb, bm, bk] (row) / [Mb, Kb, bk, bm] (col)
    b_packed: [Nb, Kb, bk, bn] (row) / [Nb, Kb, bn, bk] (col)
    """
    if interpret is None:
        interpret = default_interpret()
    fmt = TileFormat.from_packed(b_packed, layout_b)
    mb, kb = a_packed.shape[:2]
    nb, kb2 = b_packed.shape[:2]
    assert kb == kb2, (a_packed.shape, b_packed.shape)
    if layout_a == "row":
        bm, bk = a_packed.shape[2:]
    else:
        bk, bm = a_packed.shape[2:]
    bn = fmt.bn
    assert bk == fmt.bk, (a_packed.shape, b_packed.shape)
    out_dtype = out_dtype or (c.dtype if c is not None else a_packed.dtype)
    acc_dtype = acc_dtype_for(a_packed.dtype)

    grid = (mb, nb, kb)  # K innermost: revolving accumulator, one HBM store
    ta = a_packed.shape[2:]
    in_specs = [
        pl.BlockSpec((1, 1) + ta, lambda i, j, kk: (i, kk, 0, 0)),
        b_tile_spec(fmt, lambda i, j, kk: (j, kk, 0, 0)),
    ]
    operands = [a_packed, b_packed]
    has_c = c is not None
    if has_c:
        spec, op = c_spec_and_operand(c, m, n, bm, bn)
        in_specs.append(spec)
        operands.append(op)
    has_bias = bias is not None
    if has_bias:
        spec, op = bias_spec_and_operand(bias, n, bn)
        in_specs.append(spec)
        operands.append(op)
    out = pl.pallas_call(
        functools.partial(_packed_kernel, alpha=alpha, beta=beta, k_steps=kb,
                          layout_a=layout_a, fmt=fmt, epilogue=epilogue,
                          has_c=has_c, has_bias=has_bias),
        name="gemm_packed",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mb * bm, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out[:m, :n]


def gemm_packed_fused_a(a: jnp.ndarray,
                        b_packed: jnp.ndarray,
                        n: int,
                        c: jnp.ndarray | None = None,
                        *,
                        bm: int = 128,
                        alpha: float = 1.0,
                        beta: float = 0.0,
                        layout_b: str = "row",
                        b_scales: jnp.ndarray | None = None,
                        out_dtype=None,
                        epilogue: str = "none",
                        bias: jnp.ndarray | None = None,
                        b_format: TileFormat | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Pack-free-A GEMM: C[:m,:n] <- epilogue(alpha*A@unpack(B) + beta*C + bias).

    A arrives in its natural [M,K] layout and is streamed block-by-block via
    the BlockSpec index map (a strided HBM→VMEM DMA per grid step) — no
    tile-major copy of A is ever materialized. B must be pre-packed with
    ``pack_b`` (typically once, at weight-load time).

    ``b_scales`` (f32, from a quantized ``pack_b``) marks B as
    dequant-in-epilogue: [Nb, Kb] per-tile scales sit whole in SMEM, are
    read at B's tile coordinates and multiply each K-step's partial product
    on the f32 accumulator; [Nb] per-column scales (``granularity="col"``)
    multiply the finished accumulator once in the store epilogue, ahead of
    bias/activation. ``b_format`` is the authoritative :class:`TileFormat`
    of the packed stack — REQUIRED for nibble-packed int4 buffers (an int4
    stack is physically int8 with a halved trailing dim, so
    ``from_packed`` inference cannot see it) and for col-granularity
    scales; when omitted the format is inferred from the buffer.
    """
    if interpret is None:
        interpret = default_interpret()
    fmt = b_format if b_format is not None else TileFormat.from_packed(
        b_packed, layout_b, has_scales=b_scales is not None)
    m, k = a.shape
    nb, kb = b_packed.shape[:2]
    bk, bn = fmt.bk, fmt.bn
    assert cdiv(k, bk) == kb, (a.shape, b_packed.shape, bk)
    out_dtype = out_dtype or (c.dtype if c is not None else a.dtype)
    acc_dtype = acc_dtype_for(a.dtype)
    a_p = pad2d(a, bm, bk)
    mb = cdiv(m, bm)

    grid = (mb, nb, kb)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        b_tile_spec(fmt, lambda i, j, kk: (j, kk, 0, 0)),
    ]
    operands = [a_p, b_packed]
    has_c = c is not None
    if has_c:
        spec, op = c_spec_and_operand(c, m, n, bm, bn)
        in_specs.append(spec)
        operands.append(op)
    has_scale = b_scales is not None
    if has_scale:
        want = (nb,) if col_scaled(fmt) else (nb, kb)
        assert b_scales.shape == want, (b_scales.shape, b_packed.shape, want)
        in_specs.append(scale_spec())
        operands.append(scale_operand(b_scales))
    has_bias = bias is not None
    if has_bias:
        spec, op = bias_spec_and_operand(bias, n, bn)
        in_specs.append(spec)
        operands.append(op)
    out = pl.pallas_call(
        functools.partial(_fused_a_kernel, alpha=alpha, beta=beta, k_steps=kb,
                          n_blocks=nb, fmt=fmt, epilogue=epilogue, has_c=has_c,
                          has_bias=has_bias, has_scale=has_scale),
        name="gemm_packed_fused_a",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mb * bm, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out[:m, :n]
