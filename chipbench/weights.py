"""Seeded model weights, defined by the benchmark and not by the program.

Every leaf is a function of ``(seed, leaf name, layer)`` alone, so the served
program (all layers in one jitted call) and the plain reference (one layer at
a time, after the window) draw the same values. Matrices are normal with
the source configs' ``initializer_range`` (0.02) as their standard
deviation, drawn in float32 and rounded once to bfloat16, the type the
weights are served in; the reference upcasts those bfloat16 values to
float32. Normal and not uniform: a uniform draw has no tails, so an int8
tile scaled by its absolute maximum (the program's int8 path) loses little
more than bfloat16 does, and the comparison could not tell the precision
below the served one from the served one. Norm scales are ``1 + U[-0.25,
0.25)`` from raw threefry bits through exact float operations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Standard deviation of the matrices: both source configs'
# ``initializer_range``.
STD = 0.02
NORM_HALF_WIDTH = 0.25   # norm scales: 1 + U[-0.25, 0.25), rounded to bf16

# Leaf ids are part of the seed derivation: never renumber.
LEAF_IDS = {"embed": 1, "head": 2, "final_norm": 3, "norm1": 10, "norm2": 11,
            "wq": 20, "wk": 21, "wv": 22, "wo": 23,
            "w_gate": 30, "w_up": 31, "w_down": 32}
LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def root_key(seed: int):
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, np.uint32(part & 0xFFFFFFFF))
    return key


def layer_shapes(arch: dict) -> dict:
    """Per-layer matrix shapes [in, out] of the dense decoder block."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hd = arch["head_dim"]
    q, kv = arch["num_attention_heads"] * hd, arch["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _uniform_bf16(key, shape, half_width: float, offset: float = 0.0):
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type((bits >> 9) | np.uint32(0x3F800000),
                                           jnp.float32)       # [1, 2) exact
    sym = (one_two - 1.0) * 2.0 - 1.0                          # [-1, 1) exact
    return (sym * half_width + offset).astype(jnp.bfloat16)


def _normal_bf16(key, shape, std: float):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def leaf(key, name: str, shape, layer: int | None = None):
    """One leaf (one layer's slice for per-layer leaves), in bfloat16."""
    k = jax.random.fold_in(key, LEAF_IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    if name in ("norm1", "norm2", "final_norm"):
        return _uniform_bf16(k, shape, NORM_HALF_WIDTH, 1.0)
    return _normal_bf16(k, shape, STD)


def stacked(key, name: str, shape, num_layers: int):
    """``[L, *shape]``: every layer's slice, equal to ``leaf(..., layer=i)``."""
    return jax.vmap(lambda i: leaf(key, name, shape, i))(
        jnp.arange(num_layers, dtype=jnp.uint32))


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
