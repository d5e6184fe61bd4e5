"""The dense decoder: pre-norm blocks of full causal attention and a SwiGLU
MLP, the architecture of every configuration in ``configs/`` so far. Its
configuration keys and the program's config, its parameter tree and leaves,
its plain reference and its operation and KV counts (the contract is in
``chipbench/arch/__init__.py``).

The reference is a plain float32 forward of the pre-norm dense decoders the
benchmark serves. Written from the published descriptions, in
straightforward ``jax.numpy``, with no kernels, cache or batching tricks,
and importing nothing of the program under test:

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

import dataclasses
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp

from chipbench import weights as W

# ----- the configuration and the program -------------------------------------


def arch_of(config: dict) -> dict:
    """What the program and the reference need of a configuration file:
    its sizes, ``norm`` (the reference's ``layernorm`` or ``rmsnorm``) and
    ``program_norm_type`` (the same norm by the program's name)."""
    keys = ("registry", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "vocab_size", "tie_word_embeddings", "rope_theta", "norm",
            "program_norm_type")
    arch = {k: config[k] for k in keys}
    arch["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    return arch


def program_config(arch: dict):
    """The program's ``ModelConfig``: the registry entry with every size
    taken from the benchmark's configuration file."""
    from repro.configs import get_config
    base = get_config(arch["registry"])
    cfg = dataclasses.replace(
        base, num_layers=arch["num_hidden_layers"],
        d_model=arch["hidden_size"], num_heads=arch["num_attention_heads"],
        num_kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        d_ff=arch["intermediate_size"], vocab_size=arch["vocab_size"],
        tie_embeddings=arch["tie_word_embeddings"],
        rope_theta=arch["rope_theta"])
    want = {"norm_type": arch["program_norm_type"], "mlp_type": "swiglu",
            "family": "dense", "attention_type": "full", "use_bias": False,
            "qk_norm": False, "parallel_block": False,
            "pos_embedding": "rope", "compute_dtype": "bfloat16"}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{arch['registry']}: program config {got} is not "
                         f"the architecture the reference computes {want}")
    return cfg


def param_tree(key, arch: dict) -> dict:
    """The program's parameter layout, filled from the benchmark's leaves."""
    L, d = arch["num_hidden_layers"], arch["hidden_size"]
    shapes = layer_shapes(arch)

    def st(name):
        return stacked(key, name, shapes[name], L)

    def norm(name):
        if not parametric_norm(arch):
            return {}
        return {"scale": stacked(key, name, (d,), L).astype(jnp.float32)}

    g = global_weights(key, arch)
    params = {
        "embed": {"table": g["embed"]},
        "layers": {
            "norm1": norm("norm1"),
            "attn": {"wq": st("wq"), "wk": st("wk"), "wv": st("wv"),
                     "wo": st("wo")},
            "norm2": norm("norm2"),
            "mlp": {"wg": st("w_gate"), "wu": st("w_up"), "wo": st("w_down")},
        },
        "final_norm": ({"scale": g["final_norm"].astype(jnp.float32)}
                       if "final_norm" in g else {}),
    }
    if "head" in g:
        params["head"] = {"table": g["head"]}
    return params


# ----- leaves ----------------------------------------------------------------

# Leaf ids are part of the seed derivation: never renumber.
LEAF_IDS = {"embed": 1, "head": 2, "final_norm": 3, "norm1": 10, "norm2": 11,
            "wq": 20, "wk": 21, "wv": 22, "wo": 23,
            "w_gate": 30, "w_up": 31, "w_down": 32}
NORM_LEAVES = ("norm1", "norm2", "final_norm")


def layer_shapes(arch: dict) -> dict:
    """Per-layer matrix shapes [in, out] of the dense decoder block."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hd = arch["head_dim"]
    q, kv = arch["num_attention_heads"] * hd, arch["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _kind(name: str) -> str:
    return W.NORM_SCALE if name in NORM_LEAVES else W.MATRIX


def leaf(key, name: str, shape, layer: int | None = None):
    """One leaf (one layer's slice for per-layer leaves), in bfloat16."""
    return W.leaf(key, LEAF_IDS[name], shape, layer, _kind(name))


def stacked(key, name: str, shape, num_layers: int):
    """``[L, *shape]``: every layer's slice, equal to ``leaf(..., layer=i)``.
    """
    return W.stacked(key, LEAF_IDS[name], shape, num_layers, _kind(name))


def parametric_norm(arch: dict) -> bool:
    return arch["norm"] == "rmsnorm"


def layer_weights(key, arch: dict, layer: int) -> dict:
    """One layer's weights by benchmark name (the reference's unit of work)."""
    out = {n: leaf(key, n, s, layer) for n, s in layer_shapes(arch).items()}
    if parametric_norm(arch):
        for n in ("norm1", "norm2"):
            out[n] = leaf(key, n, (arch["hidden_size"],), layer)
    return out


def global_weights(key, arch: dict) -> dict:
    """Embedding, final norm and (untied) output head."""
    v, d = arch["vocab_size"], arch["hidden_size"]
    out = {"embed": leaf(key, "embed", (v, d))}
    if parametric_norm(arch):
        out["final_norm"] = leaf(key, "final_norm", (d,))
    if not arch["tie_word_embeddings"]:
        out["head"] = leaf(key, "head", (v, d))
    return out


# ----- the reference ---------------------------------------------------------

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
    w = jax.tree.map(_f32, layer_weights(key, arch, layer))
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
    return _f32(leaf(key, "embed", (arch["vocab_size"],
                                    arch["hidden_size"])))[tokens]


@partial(jax.jit, static_argnames=("arch_items",))
def _gaps(x, key, targets, probe_ids, arch_items):
    """Per position: the reference's best logit minus its logit of the
    target token, the best token, and the logits at ``probe_ids``.
    x [N, T, d]; targets [N, T]; probe_ids [K]."""
    arch = dict(arch_items)
    g = jax.tree.map(_f32, global_weights(key, arch))
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
    g = jax.tree.map(_f32, global_weights(key, arch))
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


# ----- operations, weights and KV elements -----------------------------------


def weight_gemms(arch: dict, rows: int,
                 head_rows: int | None = None) -> List[Tuple[int, int, int]]:
    """Every weight GEMM of one model step over ``rows`` token rows: seven
    per layer and the LM head over ``head_rows`` rows (a prefill takes the
    logits of its last position only), as ``(M, K, N)``."""
    per_layer = [(rows, k, n) for k, n in layer_shapes(arch).values()]
    head = (rows if head_rows is None else head_rows, arch["hidden_size"],
            arch["vocab_size"])
    return per_layer * arch["num_hidden_layers"] + [head]


def weight_shapes(arch: dict) -> List[Tuple[int, int]]:
    """``(K, N)`` of every weight GEMM of the model (the LM head's too)."""
    return list(layer_shapes(arch).values()) + [
        (arch["hidden_size"], arch["vocab_size"])]


def layer_params(arch: dict) -> int:
    """Weights each token multiplies by in the layers."""
    per_layer = sum(k * n for k, n in layer_shapes(arch).values())
    return arch["num_hidden_layers"] * per_layer


def matmul_params(arch: dict) -> int:
    """Weights a decoded token multiplies by: the layers and the LM head."""
    return layer_params(arch) + arch["hidden_size"] * arch["vocab_size"]


def attention_flops(arch: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    q_dim = arch["num_attention_heads"] * arch["head_dim"]
    return 4 * arch["num_hidden_layers"] * q_dim * context


def decode_token_flops(arch: dict, position: int) -> int:
    """Model operations of one token decoded at ``position`` (it attends to
    ``position + 1`` keys, itself included)."""
    return 2 * matmul_params(arch) + attention_flops(arch, position + 1)


def prefill_flops(arch: dict, length: int) -> int:
    """Model operations of a causal prompt of ``length`` tokens, with the
    logits of its last position."""
    causal_keys = length * (length + 1) // 2
    q_dim = arch["num_attention_heads"] * arch["head_dim"]
    return (2 * layer_params(arch) * length
            + 2 * arch["hidden_size"] * arch["vocab_size"]
            + 4 * arch["num_hidden_layers"] * q_dim * causal_keys)


def kv_elements(arch: dict, serving: dict) -> Tuple[int, int]:
    """Elements of one leaf of the paged KV pool ``[L, blocks + 1, block,
    Hkv, D]`` and of the dense view ``[L, max_live, max_len, Hkv, D]`` the
    batched step gathers from it."""
    per_position = (arch["num_hidden_layers"] * arch["num_key_value_heads"]
                    * arch["head_dim"])
    blocks = serving["max_live"] * serving["max_len"] // serving["block_size"]
    return ((blocks + 1) * serving["block_size"] * per_position,
            serving["max_live"] * serving["max_len"] * per_position)
