"""Blocked online-softmax attention Pallas kernel (flash attention).

The long-context shapes (prefill_32k, long_500k) make attention the dominant
non-GEMM hot spot; this kernel applies the paper's discipline to it: VMEM block
residency (q block + running max/denominator/accumulator scratch persist across
the KV grid dimension — "no accumulator spills") and MXU contraction for both
the QK^T and PV products.

Supports causal masking, sliding windows (Mixtral/Hymba) and GQA (KV-head
sharing via the index map, no materialized repeat).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret, pallas_kwargs

_NEG_INF = -1e30  # finite sentinel: avoids (-inf) - (-inf) NaNs in rescaling


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, sq, skv, bq, bkv, kv_steps):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32)  # [bq, D]
    k = k_ref[0, :, 0, :].astype(jnp.float32)  # [bkv, D]
    v = v_ref[0, :, 0, :].astype(jnp.float32)  # [bkv, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # Right-aligned query positions (decode: queries sit at the end of the KV).
    q_pos = (iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
             + (skv - sq))
    k_pos = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = k_pos < skv  # zero-padded KV tail
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ikv == kv_steps - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padding) rows -> 0
        o_ref[0, :, 0, :] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray,
                    k: jnp.ndarray,
                    v: jnp.ndarray,
                    *,
                    causal: bool = True,
                    window: int | None = None,
                    scale: float | None = None,
                    bq: int = 128,
                    bkv: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q:[B,Sq,H,D], k/v:[B,Skv,Hkv,D] -> [B,Sq,H,D]. GQA via index mapping."""
    if interpret is None:
        interpret = default_interpret()
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))

    bq_ = min(bq, sq)
    bkv_ = min(bkv, skv)
    pq = (-sq) % bq_
    pkv = (-skv) % bkv_
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0))) if pkv else k
    vp = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0))) if pkv else v
    q_steps, kv_steps = cdiv(sq, bq_), cdiv(skv, bkv_)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, sq=sq, skv=skv, bq=bq_, bkv=bkv_,
                          kv_steps=kv_steps),
        name="flash_attention",
        grid=(b * h, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq_, 1, d),
                         lambda bh, i, j: (bh // h, i, bh % h, 0)),
            pl.BlockSpec((1, bkv_, 1, d),
                         lambda bh, i, j: (bh // h, j, (bh % h) // group, 0)),
            pl.BlockSpec((1, bkv_, 1, d),
                         lambda bh, i, j: (bh // h, j, (bh % h) // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, 1, d),
                               lambda bh, i, j: (bh // h, i, bh % h, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq + pq, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        **pallas_kwargs(
            interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qp, kp, vp)
    return out[:, :sq]
