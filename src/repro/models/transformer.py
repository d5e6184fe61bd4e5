"""Decoder-only transformer assembly for the dense / moe / hybrid / ssm / vlm
families. Layers are stacked pytrees consumed by ``jax.lax.scan`` (compact HLO
for the 512-device dry-run; per-layer remat policy applied inside the scan).

A layer-typed stack (``cfg.layer_types``: Granite-4.0-H's mamba and
attention layers, one mixer each) has its own assembly at the end of this
module; ``forward``, ``prefill``, ``init_caches`` and ``decode`` hand it on.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ATTENTION, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, embed_params,
                                 embed_tokens, lm_logits, mlp_params,
                                 norm_params)
from repro.parallel.mesh import shard


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def layer_params(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 8)
    p: dict = {"norm1": norm_params(cfg, keys[0])}
    if cfg.has_attention:
        p["attn"] = attn.attn_params(cfg, keys[1])
    if cfg.has_ssm:
        p["ssm"] = ssm_mod.ssm_params(cfg, keys[2])
    if cfg.d_ff > 0:
        p["norm2"] = norm_params(cfg, keys[3])
        if cfg.is_moe:
            p["moe"] = moe_mod.moe_params(cfg, keys[4])
        else:
            p["mlp"] = mlp_params(cfg, keys[5])
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    k_embed, k_layers = jax.random.split(key)
    params = embed_params(cfg, k_embed)
    if cfg.layer_typed:
        params.update(typed_layer_stacks(cfg, k_layers))
        params["final_norm"] = norm_params(cfg, jax.random.fold_in(key, 7))
        return params
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params["layers"] = jax.vmap(lambda k: layer_params(cfg, k))(layer_keys)
    params["final_norm"] = norm_params(cfg, jax.random.fold_in(key, 7))
    return params


# ---------------------------------------------------------------------------
# Layer body (full-sequence: train / prefill)
# ---------------------------------------------------------------------------

def block(cfg: ModelConfig, p: dict, x: jnp.ndarray, positions: jnp.ndarray,
          prefix_len: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One transformer block. Returns (x, moe_aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    x = shard(x, "batch", "seq")
    h = apply_norm(cfg, p["norm1"], x)
    if cfg.parallel_block:
        # Cohere-style: x + attn(h) + mlp(h), single pre-norm. Both mixer
        # outputs are TP-partial sums over the SAME axis: summing them first
        # fuses two all-reduces into one (§Perf H5).
        combined = (attn.self_attention(cfg, p["attn"], h, positions,
                                        prefix_len=prefix_len,
                                        epilogue_shard=False)
                    + apply_mlp(cfg, p["mlp"], h, epilogue_shard=False))
        x = x + checkpoint_name(shard(combined, "batch", "seq"), "mixer_out")
        return x, aux
    if cfg.family == "hybrid":
        # Hymba: parallel attention + SSM heads over the same normed input,
        # outputs averaged (per-path fusion simplified; see DESIGN.md).
        x = x + 0.5 * (attn.self_attention(cfg, p["attn"], h, positions,
                                           prefix_len=prefix_len)
                       + ssm_mod.apply_ssm(cfg, p["ssm"], h))
    elif cfg.has_ssm:
        x = x + ssm_mod.apply_ssm(cfg, p["ssm"], h)
    elif cfg.has_attention:
        x = x + attn.self_attention(cfg, p["attn"], h, positions,
                                    prefix_len=prefix_len)
    if cfg.d_ff > 0:
        h2 = apply_norm(cfg, p["norm2"], x)
        if cfg.is_moe:
            out, aux, _ = moe_mod.apply_moe(cfg, p["moe"], h2)
            x = x + out
        else:
            x = x + apply_mlp(cfg, p["mlp"], h2)
    return x, aux


def cast_layer_params(cfg: ModelConfig, layers: dict) -> dict:
    """Cast matrix weights to the compute dtype ONCE, outside the layer scan.

    The FSDP all-gather of scan-invariant weights is hoisted out of the loop
    by XLA; gathering f32 masters doubles both the gathered-buffer memory and
    the gather traffic vs casting first (measured — EXPERIMENTS.md §Perf).
    1-D/scalar leaves (norm scales, A_log, dt_bias, D) stay f32 for stability.
    """
    compute = jnp.dtype(cfg.compute_dtype)

    def cast(w):
        if w.ndim >= 2 and w.dtype == jnp.float32:
            return w.astype(compute)
        if w.dtype == jnp.int8:
            # int8 serving weights: streamed narrow from HBM, widened to the
            # compute dtype at use (per-layer slice). Scale factors are fused
            # into the adjacent norms in a production quantizer; the dry-run
            # measures the memory/collective structure (§Perf H9).
            return w.astype(compute)
        return w

    return jax.tree.map(cast, layers)


def run_layers(cfg: ModelConfig, layers: dict, x: jnp.ndarray,
               positions: jnp.ndarray, prefix_len: int = 0,
               remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    layers = cast_layer_params(cfg, layers)
    body = functools.partial(block, cfg, prefix_len=prefix_len)

    # Remat policy: recompute everything EXCEPT the post-all-reduce mixer
    # outputs — saving them costs 2 seq-sharded tensors per layer but lets
    # the backward pass skip re-running the TP collectives (§Perf H4).
    policy = jax.checkpoint_policies.save_only_these_names("mixer_out")

    def scan_fn(carry, lp):
        fn = (jax.checkpoint(
                  lambda c, q: body(q, c, positions=positions),
                  policy=policy)
              if remat else (lambda c, q: body(q, c, positions=positions)))
        new_x, aux = fn(carry, lp)
        return new_x, aux

    x, auxes = jax.lax.scan(scan_fn, x, layers)
    return x, jnp.sum(auxes)


# ---------------------------------------------------------------------------
# Full forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: dict, tokens: jnp.ndarray, *,
            prefix_embeds: Optional[jnp.ndarray] = None,
            remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: [B,S] -> (logits [B, S(+P), V] fp32, moe_aux).

    ``prefix_embeds`` ([B,P,d]): precomputed modality embeddings (VLM stub)
    prepended with a bidirectional prefix-LM mask.
    """
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(cfg, params, tokens, compute)
    prefix_len = 0
    if prefix_embeds is not None:
        prefix_len = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(compute), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if cfg.layer_typed:
        x, aux = typed_forward(cfg, params, x, positions, remat), jnp.zeros(
            (), jnp.float32)
    else:
        x, aux = run_layers(cfg, params["layers"], x, positions,
                            prefix_len=prefix_len, remat=remat)
    x = apply_norm(cfg, params["final_norm"], x)
    return lm_logits(cfg, params, x), aux


# ---------------------------------------------------------------------------
# Prefill (forward + decode-cache construction)
# ---------------------------------------------------------------------------

def prefill_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                  positions: jnp.ndarray, prefix_len: int, max_len: int,
                  cache_dtype) -> Tuple[jnp.ndarray, dict]:
    """Like :func:`block` but also emits this layer's decode cache."""
    cache: dict = {}
    x = shard(x, "batch", "seq")
    h = apply_norm(cfg, p["norm1"], x)
    if cfg.parallel_block:
        a_out, (k, v) = attn.self_attention(cfg, p["attn"], h, positions,
                                            prefix_len=prefix_len,
                                            return_kv=True)
        cache["kv"] = attn.cache_from_prefill(cfg, k, v, max_len, cache_dtype)
        x = x + a_out + apply_mlp(cfg, p["mlp"], h)
        return x, cache
    if cfg.family == "hybrid":
        a_out, (k, v) = attn.self_attention(cfg, p["attn"], h, positions,
                                            prefix_len=prefix_len,
                                            return_kv=True)
        cache["kv"] = attn.cache_from_prefill(cfg, k, v, max_len, cache_dtype)
        s_out, cache["ssm"] = ssm_mod.apply_ssm(cfg, p["ssm"], h,
                                                return_state=True)
        x = x + 0.5 * (a_out + s_out)
    elif cfg.has_ssm:
        s_out, cache["ssm"] = ssm_mod.apply_ssm(cfg, p["ssm"], h,
                                                return_state=True)
        x = x + s_out
    elif cfg.has_attention:
        a_out, (k, v) = attn.self_attention(cfg, p["attn"], h, positions,
                                            prefix_len=prefix_len,
                                            return_kv=True)
        cache["kv"] = attn.cache_from_prefill(cfg, k, v, max_len, cache_dtype)
        x = x + a_out
    if cfg.d_ff > 0:
        h2 = apply_norm(cfg, p["norm2"], x)
        if cfg.is_moe:
            out, _, _ = moe_mod.apply_moe(cfg, p["moe"], h2)
            x = x + out
        else:
            x = x + apply_mlp(cfg, p["mlp"], h2)
    return x, cache


def prefill(cfg: ModelConfig, params: dict, tokens: jnp.ndarray, *,
            prefix_embeds: Optional[jnp.ndarray] = None,
            max_len: Optional[int] = None,
            cache_dtype=None) -> Tuple[jnp.ndarray, dict]:
    """Prompt processing: returns (last-position logits [B,V], decode caches)."""
    compute = jnp.dtype(cfg.compute_dtype)
    cache_dtype = cache_dtype or compute
    x = embed_tokens(cfg, params, tokens, compute)
    prefix_len = 0
    if prefix_embeds is not None:
        prefix_len = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(compute), x], axis=1)
    b, s, _ = x.shape
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if cfg.layer_typed:
        x, caches = typed_prefill(cfg, params, x, positions, max_len,
                                  cache_dtype)
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(cfg, params, x[:, -1:])[:, 0], caches

    def scan_fn(carry, lp):
        new_x, cache = prefill_block(cfg, lp, carry, positions, prefix_len,
                                     max_len, cache_dtype)
        return new_x, cache

    x, caches = jax.lax.scan(scan_fn, x,
                             cast_layer_params(cfg, params["layers"]))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params, x[:, -1:])
    return logits[:, 0], caches


# ---------------------------------------------------------------------------
# Decode (one token against caches)
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """Stacked per-layer caches [L, ...] (a layer-typed stack's: see
    :func:`typed_caches`)."""
    if cfg.layer_typed:
        return typed_caches(cfg, batch, max_len, dtype)

    def one_layer(_):
        c = {}
        if cfg.has_attention:
            c["kv"] = attn.init_kv_cache(cfg, batch, max_len, dtype)
        if cfg.has_ssm:
            c["ssm"] = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return c

    return jax.vmap(one_layer)(jnp.arange(cfg.num_layers))


def decode_block(cfg: ModelConfig, p: dict, cache: dict, x: jnp.ndarray,
                 pos: jnp.ndarray) -> Tuple[jnp.ndarray, dict]:
    new_cache = dict(cache)
    h = apply_norm(cfg, p["norm1"], x)
    if cfg.parallel_block:
        a_out, new_cache["kv"] = attn.decode_attention(cfg, p["attn"], h,
                                                       cache["kv"], pos)
        x = x + a_out + apply_mlp(cfg, p["mlp"], h)
        return x, new_cache
    if cfg.family == "hybrid":
        a_out, new_cache["kv"] = attn.decode_attention(cfg, p["attn"], h,
                                                       cache["kv"], pos)
        s_out, new_cache["ssm"] = ssm_mod.decode_ssm(cfg, p["ssm"], h,
                                                     cache["ssm"])
        x = x + 0.5 * (a_out + s_out)
    elif cfg.has_ssm:
        s_out, new_cache["ssm"] = ssm_mod.decode_ssm(cfg, p["ssm"], h,
                                                     cache["ssm"])
        x = x + s_out
    elif cfg.has_attention:
        a_out, new_cache["kv"] = attn.decode_attention(cfg, p["attn"], h,
                                                       cache["kv"], pos)
        x = x + a_out
    if cfg.d_ff > 0:
        h2 = apply_norm(cfg, p["norm2"], x)
        if cfg.is_moe:
            out, _, _ = moe_mod.apply_moe(cfg, p["moe"], h2)
            x = x + out
        else:
            x = x + apply_mlp(cfg, p["mlp"], h2)
    return x, new_cache


def decode(cfg: ModelConfig, params: dict, caches, token: jnp.ndarray,
           pos: jnp.ndarray) -> Tuple[jnp.ndarray, dict]:
    """token: [B,1]; pos: [B] -> (logits [B,1,V], new caches).

    ``caches`` is the stacked per-layer dense caches, or an
    :class:`~repro.models.attention.PagedKV` that each layer reads its own
    K/V from; then the new caches are only the written positions,
    ``{"kv": {"k", "v"}}`` of ``[L, B, Hkv, D]``. A layer-typed stack takes
    and returns its own caches (:func:`typed_decode`).
    """
    compute = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(cfg, params, token, compute)
    if cfg.layer_typed:
        x, new_caches = typed_decode(cfg, params, caches, x, pos)
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(cfg, params, x), new_caches
    paged = isinstance(caches, attn.PagedKV)

    def scan_fn(carry, layer_in):
        lp, lc = layer_in
        if paged:
            lc = {"kv": dataclasses.replace(caches, layer=lc)}
        new_x, new_c = decode_block(cfg, lp, lc, carry, pos)
        return new_x, new_c

    per_layer = jnp.arange(cfg.num_layers) if paged else caches
    x, new_caches = jax.lax.scan(
        scan_fn, x, (cast_layer_params(cfg, params["layers"]), per_layer))
    x = apply_norm(cfg, params["final_norm"], x)
    return lm_logits(cfg, params, x), new_caches


# ---------------------------------------------------------------------------
# Layer-typed stacks (cfg.layer_types): one mixer a layer, two kinds in order
# ---------------------------------------------------------------------------
#
# Weights are stacked by kind: ``params["mamba_layers"]`` [Lm] and
# ``params["attn_layers"]`` [La], each layer {norm1, mixer ("ssm" or
# "attn"), norm2, mlp}. The stack runs as ONE scan over its mamba layers;
# each step first runs the attention layer that precedes its mamba layer, if
# there is one (a ``lax.cond`` on a per-step table; the attention weights are
# read by index inside it), then the mamba layer: the layers run in their
# published order in one compiled loop, with no per-layer copy of a stacked
# weight. Each sublayer is ``x + residual_multiplier * mixer(norm(x))``.
#
# Caches: ``{"kv": {"k", "v"} [La, B, max_len, Hkv, D], "ssm": {"state" [Lm,
# B, H, P, N] f32, "conv" [Lm, B, W - 1, C] f32}}``. The recurrent state is
# the scan's input and output: each mamba step reads its layer's state as a
# scan slice and emits the new state as the scan's output, under the scopes
# ``ssm_state`` and ``ssm_conv`` (the decode update and its conv window;
# ``models.ssm`` names the same scopes and the prefill's chunked scan
# ``ssm_scan``). Only the attention layers' caches ride in the carry.

def attention_before(cfg: ModelConfig) -> np.ndarray:
    """For each mamba layer, the index of the attention layer just before
    it in ``layer_types``, or -1 (``ModelConfig`` has checked that every
    attention layer is followed by a mamba layer)."""
    out, a, pending = [], 0, None
    for kind in cfg.layer_types:
        if kind == ATTENTION:
            pending, a = a, a + 1
        else:
            out.append(-1 if pending is None else pending)
            pending = None
    return np.asarray(out, np.int32)


def _typed_layer(cfg: ModelConfig, key, mixer: str) -> dict:
    keys = jax.random.split(key, 4)
    make = attn.attn_params if mixer == "attn" else ssm_mod.ssm_params
    return {"norm1": norm_params(cfg, keys[0]), mixer: make(cfg, keys[1]),
            "norm2": norm_params(cfg, keys[2]),
            "mlp": mlp_params(cfg, keys[3])}


def typed_layer_stacks(cfg: ModelConfig, key) -> dict:
    k_ssm, k_attn = jax.random.split(key)
    stack = (lambda mixer, k, n: jax.vmap(
        lambda kk: _typed_layer(cfg, kk, mixer))(jax.random.split(k, n)))
    return {"mamba_layers": stack("ssm", k_ssm, cfg.num_ssm_layers),
            "attn_layers": stack("attn", k_attn, cfg.num_attention_layers)}


def _residual(cfg: ModelConfig, x, out):
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def _typed_stack(cfg: ModelConfig, params: dict, x, state, attn_mixer,
                 ssm_mixer, ssm_in=None, remat: bool = False):
    """x through every layer in order -> (x, state, ssm_out).
    ``attn_mixer(p, h, a, state)`` gives attention layer ``a``'s output and
    the new ``state`` (the attention caches, carried through the scan);
    ``ssm_mixer(p, h, j, s)`` gives mamba layer ``j``'s output and what the
    scan emits for it, stacked into ``ssm_out`` [Lm, ...], given ``s``, its
    slice of ``ssm_in`` [Lm, ...]."""
    mamba = cast_layer_params(cfg, params["mamba_layers"])
    attn_layers = cast_layer_params(cfg, params["attn_layers"])
    before = jnp.asarray(attention_before(cfg))

    def sublayers(p, mixer, kind, idx, x, arg):
        out, res = mixer(p[kind], apply_norm(cfg, p["norm1"], x), idx, arg)
        x = _residual(cfg, x, out)
        x = _residual(cfg, x, apply_mlp(cfg, p["mlp"],
                                        apply_norm(cfg, p["norm2"], x)))
        return x, res

    def attention_layer(x, state, a):
        p = jax.tree.map(lambda w: w[a], attn_layers)
        return sublayers(p, attn_mixer, "attn", a, x, state)

    def unit(carry, xs):
        x, state = carry
        p, j, s = xs
        a = before[j]
        x, state = jax.lax.cond(a >= 0, attention_layer,
                                lambda x, state, a: (x, state), x, state, a)
        x, out = sublayers(p, ssm_mixer, "ssm", j, x, s)
        return (x, state), out

    if remat:
        unit = jax.checkpoint(unit)
    (x, state), ssm_out = jax.lax.scan(
        unit, (x, state), (mamba, jnp.arange(cfg.num_ssm_layers), ssm_in))
    return x, state, ssm_out


def typed_forward(cfg: ModelConfig, params: dict, x, positions,
                  remat: bool = True):
    def attn_mixer(p, h, a, state):
        return attn.self_attention(cfg, p, h, positions), state

    def ssm_mixer(p, h, j, s):
        return ssm_mod.apply_ssm(cfg, p, h), None

    x, _, _ = _typed_stack(cfg, params, x, (), attn_mixer, ssm_mixer,
                           remat=remat)
    return x


def _stack_kv(cfg: ModelConfig, one: dict) -> dict:
    """Zeroed ``[La, ...]`` attention caches shaped like ``one``'s leaves."""
    return jax.tree.map(lambda a: jnp.zeros((cfg.num_attention_layers,
                                             *a.shape), a.dtype), one)


def typed_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    ssm = ssm_mod.init_ssm_cache(cfg, batch, jnp.float32)
    return {"kv": _stack_kv(cfg, attn.init_kv_cache(cfg, batch, max_len,
                                                    dtype)),
            "ssm": jax.tree.map(lambda a: jnp.zeros((cfg.num_ssm_layers,
                                                      *a.shape), a.dtype),
                                ssm)}


def typed_prefill(cfg: ModelConfig, params: dict, x, positions, max_len: int,
                  cache_dtype):
    b = x.shape[0]

    def attn_mixer(p, h, a, state):
        out, (k, v) = attn.self_attention(cfg, p, h, positions,
                                          return_kv=True)
        kv = attn.cache_from_prefill(cfg, k, v, max_len, cache_dtype)
        return out, jax.tree.map(lambda buf, new: buf.at[a].set(new),
                                 state, kv)

    def ssm_mixer(p, h, j, s):
        return ssm_mod.apply_ssm(cfg, p, h, return_state=True)

    kv = _stack_kv(cfg, attn.init_kv_cache(cfg, b, max_len, cache_dtype))
    x, kv, ssm = _typed_stack(cfg, params, x, kv, attn_mixer, ssm_mixer)
    return x, {"kv": kv, "ssm": ssm}


def typed_decode(cfg: ModelConfig, params: dict, caches: dict, x, pos):
    """One token of every row against ``caches``: dense ``{"kv", "ssm"}``
    (returned with the position written), or with ``caches["kv"]`` a
    :class:`~repro.models.attention.PagedKV` read one attention layer at a
    time, for which the new ``"kv"`` holds only the written positions ``[La,
    B, Hkv, D]``. The new ``"ssm"`` is always the whole new state."""
    b = x.shape[0]
    paged = isinstance(caches["kv"], attn.PagedKV)

    def attn_mixer(p, h, a, state):
        if paged:
            lc = dataclasses.replace(caches["kv"], layer=a)
        else:
            lc = jax.tree.map(lambda buf: buf[a], state)
        out, new = attn.decode_attention(cfg, p, h, lc, pos)
        return out, jax.tree.map(lambda buf, n: buf.at[a].set(n), state, new)

    def ssm_mixer(p, h, j, s):
        return ssm_mod.decode_ssm(cfg, p, h, s)

    if paged:
        pool = caches["kv"]
        dtype = pool.k.dtype if pool.scale_k is None else pool.dtype
        kv = {name: jnp.zeros((cfg.num_attention_layers, b,
                               *pool.k.shape[3:]), dtype)
              for name in ("k", "v")}
    else:
        kv = caches["kv"]
    x, kv, ssm = _typed_stack(cfg, params, x, kv, attn_mixer, ssm_mixer,
                              caches["ssm"])
    return x, {"kv": kv, "ssm": ssm}
