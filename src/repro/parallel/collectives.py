"""Manual collective programs (shard_map) for patterns the auto-partitioner
lowers poorly.

``sp_decode_attention``: flash-decode over a KV cache sharded along the
SEQUENCE dim (sequence-parallel serving). Each shard attends over its local
KV slice, then the shards combine with the numerically-stable flash rescaling:

    m   = pmax(m_local)                      (global running max)
    l   = psum(l_local * exp(m_local - m))   (corrected denominator)
    out = psum(o_local * exp(m_local - m)) / l

One pmax + two psums of [B, H, D]-sized values replace the auto-partitioner's
all-gather of the whole KV stream — the SP decode pattern from DESIGN.md §4.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -1e30


def _local_flash(q, k, v, k_positions, q_positions, window):
    """Unnormalized local attention. q:[B,H,D]; k/v:[B,S_loc,Hkv,D].

    Returns (o_unnorm [B,H,D], l [B,H], m [B,H]).
    """
    b, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, group, d)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale   # [B,Hkv,G,S_loc]
    mask = k_positions[:, None, None, :] <= q_positions[:, None, None, None]
    mask &= k_positions[:, None, None, :] >= 0
    if window is not None:
        mask &= (q_positions[:, None, None, None]
                 - k_positions[:, None, None, :]) < window
    logits = jnp.where(mask, logits, _NEG)
    m = jnp.max(logits, axis=-1)                          # [B,Hkv,G]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return (o.reshape(b, h, d), l.reshape(b, h), m.reshape(b, h))


def sp_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray, k_positions: jnp.ndarray,
                        q_positions: jnp.ndarray, *,
                        mesh: Mesh, seq_axis: str = "model",
                        window: Optional[int] = None) -> jnp.ndarray:
    """One-token attention with the KV cache sharded on seq over ``seq_axis``.

    q: [B,H,D]; k/v_cache: [B,S,Hkv,D]; k_positions: [B,S] absolute positions
    (-1 => invalid slot); q_positions: [B]. Returns [B,H,D].
    """
    def kernel(q_l, k_l, v_l, kpos_l, qpos):
        o, l, m = _local_flash(q_l, k_l, v_l, kpos_l, qpos, window)
        m_glob = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * corr, seq_axis)
        o_glob = jax.lax.psum(o * corr[..., None], seq_axis)
        denom = jnp.where(l_glob == 0.0, 1.0, l_glob)
        return (o_glob / denom[..., None]).astype(q_l.dtype)

    in_specs = (P(), P(None, seq_axis), P(None, seq_axis),
                P(None, seq_axis), P())
    mapped = jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
        axis_names={seq_axis},  # partial-manual: other axes stay automatic
    )
    return mapped(q, k_cache, v_cache, k_positions, q_positions)


def ref_decode_attention(q, k_cache, v_cache, k_positions, q_positions,
                         window=None):
    """Single-device oracle for sp_decode_attention."""
    o, l, m = _local_flash(q, k_cache, v_cache, k_positions, q_positions,
                           window)
    denom = jnp.where(l == 0.0, 1.0, l)
    return (o / denom[..., None]).astype(q.dtype)
