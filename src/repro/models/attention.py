"""Attention blocks: GQA / MQA / MHA, sliding windows, qk-norm, RoPE, KV caches.

Projections go through ``repro.core.gemm.linear`` (the paper's layered GEMM);
the score/value contractions use the memory-bounded chunked lowering from
``layers.chunked_attention`` (TPU fast path: ``repro.kernels.flash_attention``,
same oracle). One-token decode over a full-attention cache, dense or paged,
goes through the blocked decode attention of
``repro.kernels.paged_attention``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.core import gemm
from repro.core.contraction import kernel_backend
from repro.kernels import paged_attention
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


def dequantize_kv(q, scale, dtype):
    """Elementwise ``q * scale`` into the compute dtype (scale broadcasts
    over the trailing [Hkv, D] axes): an int8 KV pool's one read formula."""
    return (q.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


@dataclasses.dataclass(frozen=True)
class PagedKV:
    """A block pool of K/V, read one layer at a time by the batched decode.

    ``k``/``v``: the pool leaves ``[L, blocks, bs, Hkv, D]``; ``tables``:
    each row's block ids ``[B, MB]``; ``layer``: bound inside the decode
    layer scan. An int8 pool carries its per-position scales ``[L, blocks,
    bs]`` and the compute ``dtype`` it dequantizes into. Decoding through a
    ``PagedKV`` writes nothing back: each layer returns only the position
    it wrote, for the caller to put into the pool.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    tables: jnp.ndarray
    scale_k: Optional[jnp.ndarray] = None
    scale_v: Optional[jnp.ndarray] = None
    dtype: Optional[str] = None
    layer: Optional[jnp.ndarray] = None

    @jax.named_scope("kv_gather")
    def dense(self) -> dict:
        """An int8 pool's layer as dense ``{"k", "v"}`` ``[B, max_len, Hkv,
        D]`` in ``dtype``: each row's blocks in table order, dequantized
        elementwise, bitwise the view ``PagedKVCache.gather_slot`` gives."""
        def leaf(pool, scales):
            g = dequantize_kv(pool[self.layer, self.tables],
                              scales[self.layer, self.tables], self.dtype)
            return g.reshape(self.tables.shape[0], -1, *pool.shape[3:])

        return {"k": leaf(self.k, self.scale_k),
                "v": leaf(self.v, self.scale_v)}


def blocked_decode_attention(q, k_new, v_new, pool_k, pool_v, layer, tables,
                             lens, *, scale: float):
    """One-token attention of each row over positions ``0 .. lens - 1`` of
    its pool blocks, then its new position, in the reduction order of
    :mod:`repro.kernels.paged_attention`: the Pallas kernel on a TPU
    (outside a device mesh), its jnp twin elsewhere. q: [B, H, D]."""
    fn = (paged_attention.paged_decode_attention if kernel_backend()
          else paged_attention.paged_decode_attention_jnp)
    with jax.named_scope("attention"):
        return fn(q, k_new, v_new, pool_k, pool_v, layer, tables, lens,
                  scale=scale)


def decode_attention(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                     cache, pos: jnp.ndarray) -> Tuple[jnp.ndarray, dict]:
    """One-token self attention. x: [B,1,d]; pos: [B] absolute position.

    ``cache`` is a dense ``{"k", "v"}`` cache ``[B, slots, Hkv, D]``,
    returned with the new position written; or a :class:`PagedKV` bound to
    a layer, for which only the written ``{"k", "v"}`` ``[B, Hkv, D]`` is
    returned.

    Full attention reads positions ``0 .. pos - 1`` and the new position
    through :func:`blocked_decode_attention`, from the pool's blocks or
    from the dense cache viewed as blocks
    (``paged_attention.dense_tables``), so a paged row attends in exactly
    the order and over exactly the values of its dense cache. A sliding
    window keeps a ring of ``window`` slots: slot s holds absolute position
    p(s) = pos - ((pos - s) mod slots) (the most recent position congruent
    to s), and masking reconstructs absolute positions from slot ids, so
    the window needs no rolls — the paper's "packing" discipline applied to
    the KV stream: write once, contiguous layout, no data motion.
    """
    b = x.shape[0]
    window = cfg.sliding_window if cfg.attention_type == "sliding_window" else None
    q, k_new, v_new = project_qkv(cfg, p, x, pos[:, None])
    if window:
        return _ring_attention(cfg, p, x, q, k_new, v_new, cache, pos,
                               window)
    paged = isinstance(cache, PagedKV)
    if paged and cache.scale_k is None:
        pool_k, pool_v = cache.k, cache.v
        layer, tables = cache.layer, cache.tables
    else:
        dense = cache.dense() if paged else cache
        max_len = dense["k"].shape[1]
        bs, tables = paged_attention.dense_tables(b, max_len)
        pool_k, pool_v = (dense[n].reshape(1, -1, bs, *dense[n].shape[2:])
                          for n in ("k", "v"))
        layer = 0
    k_new = k_new[:, 0].astype(pool_k.dtype)
    v_new = v_new[:, 0].astype(pool_v.dtype)
    scale = cfg.attention_multiplier
    out = blocked_decode_attention(
        q[:, 0], k_new, v_new, pool_k, pool_v, layer, tables, pos,
        scale=1.0 / math.sqrt(cfg.head_dim) if scale is None else scale)
    out = out.reshape(b, 1, cfg.q_dim)
    out = gemm.linear(out, resolve_weight(p["wo"], x.dtype), p.get("bo"))
    if paged:
        return out, {"k": k_new, "v": v_new}
    at = (jnp.arange(max_len)[None, :] == pos[:, None])[:, :, None, None]

    @jax.named_scope("kv_write")
    def write(buf, new):
        return jnp.where(at, new[:, None], buf)

    return out, {"k": shard(write(cache["k"], k_new), "batch", "kv_seq"),
                 "v": shard(write(cache["v"], v_new), "batch", "kv_seq")}


def _ring_attention(cfg: ModelConfig, p: dict, x, q, k_new, v_new, cache,
                    pos, window: int):
    """A sliding window's decode over its dense ring ``[B, slots, Hkv, D]``
    (see :func:`decode_attention`): the new position written by a select,
    then attention over every slot, masked to the window."""
    b = x.shape[0]
    k_old, v_old = cache["k"], cache["v"]
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
    kv_valid = (k_positions >= 0) & ((posb - k_positions) < window)
    out = chunked_attention(q, k_cache, v_cache, causal=True,
                            q_positions=pos[:, None],
                            k_positions=k_positions,
                            kv_valid=kv_valid, chunk=1,
                            scale=cfg.attention_multiplier)
    out = out.reshape(b, 1, cfg.q_dim)
    out = gemm.linear(out, resolve_weight(p["wo"], x.dtype), p.get("bo"))
    return out, {"k": k_cache, "v": v_cache}
