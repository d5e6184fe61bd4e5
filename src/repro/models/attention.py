"""Attention blocks: GQA / MQA / MHA, sliding windows, qk-norm, RoPE, KV caches.

Projections go through ``repro.core.gemm.linear`` (the paper's layered GEMM);
the score/value contractions use the memory-bounded chunked lowering from
``layers.chunked_attention`` (TPU fast path: ``repro.kernels.flash_attention``,
same oracle).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.core import gemm
from repro.models.layers import (apply_rope, chunked_attention, dense_param,
                                 resolve_weight)
from repro.parallel.mesh import shard


def attn_params(cfg: ModelConfig, key, cross: bool = False) -> dict:
    d = cfg.d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "wq": dense_param(k1, d, cfg.q_dim),
        "wk": dense_param(k2, d, cfg.kv_dim),
        "wv": dense_param(k3, d, cfg.kv_dim),
        "wo": dense_param(k4, cfg.q_dim, d),
    }
    if cfg.use_bias:
        p.update(bq=jnp.zeros((cfg.q_dim,), jnp.float32),
                 bk=jnp.zeros((cfg.kv_dim,), jnp.float32),
                 bv=jnp.zeros((cfg.kv_dim,), jnp.float32),
                 bo=jnp.zeros((d,), jnp.float32))
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def _rms(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def project_qkv(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                positions: Optional[jnp.ndarray],
                rope: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: [B,S,d] -> q [B,S,H,D], k/v [B,S,Hkv,D] (rope + qk-norm applied)."""
    b, s, _ = x.shape
    q = gemm.linear(x, resolve_weight(p["wq"], x.dtype), p.get("bq"))
    k = gemm.linear(x, resolve_weight(p["wk"], x.dtype), p.get("bk"))
    v = gemm.linear(x, resolve_weight(p["wv"], x.dtype), p.get("bv"))
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    heads_ax = "model" if cfg.shard_attention else None
    q = shard(q, "batch", None, heads_ax)
    if "q_norm" in p:
        q = _rms(q, p["q_norm"])
        k = _rms(k, p["k_norm"])
    if rope and cfg.pos_embedding == "rope" and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def self_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                   positions: jnp.ndarray, *, causal: bool = True,
                   prefix_len: int = 0, return_kv: bool = False,
                   epilogue_shard: bool = True):
    """Full-sequence self attention (training / prefill).

    ``epilogue_shard=False`` leaves the wo output as a TP-partial sum so the
    caller can fuse it with another partial before ONE collective (used by
    parallel blocks — §Perf H5).
    """
    window = cfg.sliding_window if cfg.attention_type == "sliding_window" else None
    q, k, v = project_qkv(cfg, p, x, positions)
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            prefix_len=prefix_len,
                            scale=cfg.attention_multiplier)
    out = out.reshape(*x.shape[:-1], cfg.q_dim)
    heads_ax = "model" if cfg.shard_attention else None
    out = shard(out, "batch", None, heads_ax)
    out = gemm.linear(out, resolve_weight(p["wo"], x.dtype), p.get("bo"))
    if epilogue_shard:
        # Megatron-SP epilogue: the wo contraction is TP-partial; demanding a
        # seq-sharded output reduce-scatters it into the residual stream.
        # Saved under remat so backward reuses the post-collective value.
        out = checkpoint_name(shard(out, "batch", "seq"), "mixer_out")
    if return_kv:
        return out, (k, v)
    return out


@jax.named_scope("kv_write")
def cache_from_prefill(cfg: ModelConfig, k: jnp.ndarray, v: jnp.ndarray,
                       max_len: int, dtype) -> dict:
    """Build the decode ring-buffer cache from full-prefill K/V [B,S,Hkv,D].

    Ring invariant: slot s holds the latest position congruent to s (mod
    slots). For full caches (slots >= S) this is the identity layout; for SWA
    the last `window` positions land at slot = pos % slots.
    """
    b, s, hkv, d = k.shape
    window = cfg.sliding_window if cfg.attention_type == "sliding_window" else None
    slots = min(max_len, window) if window else max_len
    if slots >= s:
        pad = ((0, 0), (0, slots - s), (0, 0), (0, 0))
        return {"k": jnp.pad(k, pad).astype(dtype),
                "v": jnp.pad(v, pad).astype(dtype)}
    slot_ids = jnp.arange(slots)
    src = (s - 1) - ((s - 1 - slot_ids) % slots)   # position held by slot s
    return {"k": k[:, src].astype(dtype), "v": v[:, src].astype(dtype)}


def cross_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    enc_k: jnp.ndarray, enc_v: jnp.ndarray) -> jnp.ndarray:
    """Decoder cross-attention against precomputed encoder K/V [B,Se,Hkv,D]."""
    b, s, _ = x.shape
    q = gemm.linear(x, resolve_weight(p["wq"], x.dtype), p.get("bq"))
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    out = chunked_attention(q, enc_k, enc_v, causal=False)
    out = out.reshape(b, s, cfg.q_dim)
    return gemm.linear(out, resolve_weight(p["wo"], x.dtype), p.get("bo"))


def encode_kv(cfg: ModelConfig, p: dict, enc_out: jnp.ndarray):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    b, se, _ = enc_out.shape
    k = gemm.linear(enc_out, resolve_weight(p["wk"], enc_out.dtype), p.get("bk"))
    v = gemm.linear(enc_out, resolve_weight(p["wv"], enc_out.dtype), p.get("bv"))
    return (k.reshape(b, se, cfg.num_kv_heads, cfg.head_dim),
            v.reshape(b, se, cfg.num_kv_heads, cfg.head_dim))


# ---------------------------------------------------------------------------
# Decode path (single query token against a KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype) -> dict:
    """Cache for one layer. SWA archs keep a ring buffer of `window` slots."""
    window = cfg.sliding_window if cfg.attention_type == "sliding_window" else None
    slots = min(max_len, window) if window else max_len
    shape = (batch, slots, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@dataclasses.dataclass(frozen=True)
class PagedKV:
    """A block pool of K/V, read one layer at a time by the batched decode.

    ``read(layer)`` returns that layer's ``(k, v)``, each ``[B, max_len,
    Hkv, D]`` in the compute dtype: every row's blocks in table order,
    bitwise the dense cache of that row. ``layer`` is bound inside the
    decode layer scan. Decoding through a ``PagedKV`` writes nothing back:
    each layer returns only the position it wrote, for the caller to put
    into the pool.
    """

    read: Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]
    layer: Optional[jnp.ndarray] = None


def decode_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                     cache, pos: jnp.ndarray) -> Tuple[jnp.ndarray, dict]:
    """One-token self attention. x: [B,1,d]; pos: [B] absolute position.

    The cache is a ring buffer of ``slots`` positions: slot s holds absolute
    position  p(s) = pos - ((pos - s) mod slots)  (the most recent position
    congruent to s). Masking reconstructs absolute positions from slot ids, so
    sliding windows need no rolls — the paper's "packing" discipline applied
    to the KV stream: write once, contiguous layout, no data motion.

    ``cache`` is a dense ``{"k", "v"}`` ring ``[B, slots, Hkv, D]``, returned
    with the new position written; or a :class:`PagedKV` bound to a layer,
    read here, for which only the written ``{"k", "v"}`` ``[B, Hkv, D]`` is
    returned. Both put the new position in place by the same select, so a
    paged row attends over exactly the values its dense cache would hold.
    """
    b = x.shape[0]
    window = cfg.sliding_window if cfg.attention_type == "sliding_window" else None
    q, k_new, v_new = project_qkv(cfg, p, x, pos[:, None])
    paged = isinstance(cache, PagedKV)
    k_old, v_old = cache.read(cache.layer) if paged else (cache["k"],
                                                          cache["v"])
    k_new, v_new = k_new.astype(k_old.dtype), v_new.astype(v_old.dtype)
    slots = k_old.shape[1]
    slot_ids = jnp.arange(slots)[None, :]                      # [1, slots]
    at = (slot_ids == (pos % slots)[:, None])[:, :, None, None]

    @jax.named_scope("kv_write")
    def write(buf, new):
        return jnp.where(at, new, buf)

    k_cache = shard(write(k_old, k_new), "batch", "kv_seq")
    v_cache = shard(write(v_old, v_new), "batch", "kv_seq")

    posb = pos[:, None]
    k_positions = posb - ((posb - slot_ids) % slots)           # [B, slots]
    kv_valid = k_positions >= 0
    if window is not None:
        kv_valid &= (posb - k_positions) < window

    out = chunked_attention(q, k_cache, v_cache, causal=True,
                            q_positions=pos[:, None],
                            k_positions=k_positions,
                            kv_valid=kv_valid, chunk=1,
                            scale=cfg.attention_multiplier)
    out = out.reshape(b, 1, cfg.q_dim)
    out = gemm.linear(out, resolve_weight(p["wo"], x.dtype), p.get("bo"))
    if paged:
        return out, {"k": k_new[:, 0], "v": v_new[:, 0]}
    return out, {"k": k_cache, "v": v_cache}
