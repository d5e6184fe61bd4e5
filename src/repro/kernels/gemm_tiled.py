"""Blocked GEMM Pallas kernel over naturally-laid-out (strided) operands.

This is the paper's **"Tiling"** strategy: macro-level blocking chosen by the
planner, micro kernel behind the matrix intrinsic, but NO packing — every
HBM→VMEM block DMA is a strided gather from the row-major operand, exactly as
loadTile() reads the unpacked matrices in Algorithm 1 without lines 3/5.

Micro-level faithfulness (paper §3.2, Algorithm 2):
  * the accumulator tile lives in VMEM scratch for the whole K loop and is
    stored to HBM exactly once — "no accumulator spills" (constraint 5);
  * `jax.lax.dot_general(..., preferred_element_type)` is the
    `llvm.matrix.multiply` analogue, lowered by Mosaic to MXU passes; the
    (bm/128)×(bn/128) MXU-tile grid inside the block is the VAccs×HAccs
    accumulator arrangement;
  * the full epilogue (alpha/beta, then ``bias``, then the activation from the
    shared ``KERNEL_EPILOGUES`` registry) is fused into the final grid step
    (Alg. 1 lines 15-21 extended): everything is applied to the f32
    accumulator while it is still VMEM-resident, so the output takes exactly
    one HBM store and no post-kernel elementwise ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (KERNEL_EPILOGUES, GemmRefs, acc_dtype_for,
                                  bias_spec_and_operand, c_spec_and_operand,
                                  cdiv, default_interpret, finalize_gemm,
                                  pad2d, pallas_kwargs)

_EPILOGUES = KERNEL_EPILOGUES  # back-compat alias (tests import this name)


def _gemm_kernel(*refs, alpha, beta, k_steps, epilogue="none", has_c=False,
                 has_bias=False):
    r = GemmRefs(refs, n_lead=2, has_c=has_c, has_bias=has_bias)
    a_ref, b_ref = r.lead
    acc_ref = r.acc

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        finalize_gemm(acc_ref, r.c, r.bias, r.out, alpha=alpha, beta=beta,
                      epilogue=epilogue)


def gemm_tiled(a: jnp.ndarray,
               b: jnp.ndarray,
               c: jnp.ndarray | None = None,
               *,
               alpha: float = 1.0,
               beta: float = 0.0,
               bm: int = 128,
               bk: int = 128,
               bn: int = 128,
               out_dtype=None,
               epilogue: str = "none",
               bias: jnp.ndarray | None = None,
               interpret: bool | None = None) -> jnp.ndarray:
    """C <- epilogue(alpha * A@B + beta * C + bias) with (bm,bk,bn) blocking."""
    if interpret is None:
        interpret = default_interpret()
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or (c.dtype if c is not None else a.dtype)
    acc_dtype = acc_dtype_for(a.dtype)
    a_p = pad2d(a, bm, bk)
    b_p = pad2d(b, bk, bn)
    mb, kb, nb = cdiv(m, bm), cdiv(k, bk), cdiv(n, bn)
    grid = (mb, nb, kb)  # K innermost: revolving VMEM accumulator

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [a_p, b_p]
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
        functools.partial(_gemm_kernel, alpha=alpha, beta=beta, k_steps=kb,
                          epilogue=epilogue, has_c=has_c,
                          has_bias=has_bias),
        name="gemm_tiled",
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
