"""Plain float32 forward of the pre-norm dense decoders the benchmark serves.

Written from the published descriptions, in straightforward ``jax.numpy``,
with no kernels, cache or batching tricks, and importing nothing of the
program under test:

* OLMo-1B (arXiv:2402.00838; ``allenai/OLMo-1B-hf``): LayerNorm without
  scale or bias (eps 1e-5), rotary embeddings on q and k (theta 10000,
  rotate-half pairing), causal multi-head attention, SwiGLU MLP, no biases,
  output head tied to the embedding.
* Phi-3-mini (arXiv:2404.14219; ``microsoft/Phi-3-mini-4k-instruct``):
  the same block with RMSNorm (eps 1e-5, learned scale) and an untied
  output head. The published checkpoint fuses q/k/v and gate/up into one
  projection each; separate matrices compute the same products. Its
  sliding window (2047) exceeds every context served here, so attention
  is full.

Every matmul runs at ``precision="highest"``. The forward goes one layer at
a time: each layer's weights are drawn from the seed (``chipbench.weights``)
just before use and dropped after, so the reference fits beside nothing
larger than one layer and the activations.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
EPS = 1e-5


def _norm(arch: dict, x, scale=None):
    if arch["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def _rope(x, theta: float):
    """x: [T, H, D], rotate-half pairing (dimension i with i + D/2)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]   # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(arch: dict, q, k, v):
    """One sequence: q [T, H, D], k/v [T, Hkv, D] -> [T, H * D]."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h * d)


def _f32(w):
    return w.astype(jnp.float32)


@partial(jax.jit, static_argnames=("arch_items",))
def _layer(x, key, layer, arch_items):
    """One decoder block over x [N, T, d], weights drawn from the seed."""
    arch = dict(arch_items)
    w = jax.tree.map(_f32, W.layer_weights(key, arch, layer))
    hd, h, hkv = (arch["head_dim"], arch["num_attention_heads"],
                  arch["num_key_value_heads"])

    def attend(xs):
        a = _norm(arch, xs, w.get("norm1"))
        t = xs.shape[0]
        q = jnp.dot(a, w["wq"], precision=HI).reshape(t, h, hd)
        k = jnp.dot(a, w["wk"], precision=HI).reshape(t, hkv, hd)
        v = jnp.dot(a, w["wv"], precision=HI).reshape(t, hkv, hd)
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
        return jnp.dot(_attention(arch, q, k, v), w["wo"], precision=HI)

    x = x + jax.lax.map(attend, x)
    m = _norm(arch, x, w.get("norm2"))
    gate = jnp.dot(m, w["w_gate"], precision=HI)
    up = jnp.dot(m, w["w_up"], precision=HI)
    return x + jnp.dot(jax.nn.silu(gate) * up, w["w_down"], precision=HI)


@partial(jax.jit, static_argnames=("arch_items",))
def _embed(tokens, key, arch_items):
    arch = dict(arch_items)
    return _f32(W.leaf(key, "embed", (arch["vocab_size"],
                                      arch["hidden_size"])))[tokens]


@partial(jax.jit, static_argnames=("arch_items",))
def _gaps(x, key, targets, probe_ids, arch_items):
    """Per position: the reference's best logit minus its logit of the
    target token, the best token, and the logits at ``probe_ids``.
    x [N, T, d]; targets [N, T]; probe_ids [K]."""
    arch = dict(arch_items)
    g = jax.tree.map(_f32, W.global_weights(key, arch))
    head = g["embed"] if arch["tie_word_embeddings"] else g["head"]
    x = _norm(arch, x, g.get("final_norm"))

    def one(args):
        xs, ts = args
        logits = jnp.dot(xs, head.T, precision=HI)          # [T, V]
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, ts[:, None], -1)[:, 0]
        return (best - got, jnp.argmax(logits, -1).astype(jnp.int32),
                logits[:, probe_ids])

    return jax.lax.map(one, (x, targets))


def arch_items(arch: dict) -> tuple:
    return tuple(sorted(arch.items()))


def final_hidden(arch: dict, seed: int, tokens) -> jnp.ndarray:
    """Hidden states after the last block, x [N, T, d], layer by layer."""
    items = arch_items(arch)
    key = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens, jnp.int32), key, items)
        for layer in range(arch["num_hidden_layers"]):
            x = _layer(x, key, jnp.uint32(layer), items)
    return x


@partial(jax.jit, static_argnames=("arch_items",))
def _logits(x, key, arch_items):
    arch = dict(arch_items)
    g = jax.tree.map(_f32, W.global_weights(key, arch))
    head = g["embed"] if arch["tie_word_embeddings"] else g["head"]
    return jnp.dot(_norm(arch, x, g.get("final_norm")), head.T, precision=HI)


def logits(arch: dict, seed: int, tokens) -> jnp.ndarray:
    """Every position's logits [N, T, V] (for small sizes: tests)."""
    x = final_hidden(arch, seed, tokens)
    with jax.default_matmul_precision("highest"):
        return _logits(x, W.root_key(seed), arch_items(arch))


def token_gaps(arch: dict, seed: int, tokens, targets, probe_ids):
    """For each position of each sequence, how far the reference's logit of
    ``targets`` lies below its best logit (0 where the target is its
    argmax), its argmax, and its logits at ``probe_ids`` [N, T, K].
    ``tokens``/``targets``: [N, T] int32, where ``targets[:, t]`` is the
    token served after position ``t``."""
    x = final_hidden(arch, seed, tokens)
    with jax.default_matmul_precision("highest"):
        return _gaps(x, W.root_key(seed), jnp.asarray(targets, jnp.int32),
                     jnp.asarray(probe_ids, jnp.int32), arch_items(arch))
