"""Seeded model weights, defined by the benchmark and not by the program.

Every leaf is a function of ``(seed, leaf id, layer)`` alone, so the served
program (all layers in one jitted call) and the plain reference (one layer at
a time, after the window) draw the same values. An architecture's module
(``chipbench/arch/<name>.py``) gives each of its leaves an id and a draw
kind. Matrices (``MATRIX``) are normal with the source configs'
``initializer_range`` (0.02) as their standard deviation, drawn in float32
and rounded once to bfloat16, the type the weights are served in; the
reference upcasts those bfloat16 values to float32. Normal and not uniform:
a uniform draw has no tails, so an int8 tile scaled by its absolute maximum
(the program's int8 path) loses little more than bfloat16 does, and the
comparison could not tell the precision below the served one from the
served one. Norm scales (``NORM_SCALE``) are ``1 + U[-0.25, 0.25)`` from raw
threefry bits through exact float operations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Standard deviation of the matrices: the source configs'
# ``initializer_range``.
STD = 0.02
NORM_HALF_WIDTH = 0.25   # norm scales: 1 + U[-0.25, 0.25), rounded to bf16

# Draw kinds.
MATRIX = "matrix"
NORM_SCALE = "norm_scale"


def root_key(seed: int):
    """A key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, np.uint32(part & 0xFFFFFFFF))
    return key


def uniform_bf16(key, shape, half_width: float, offset: float = 0.0):
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type((bits >> 9) | np.uint32(0x3F800000),
                                           jnp.float32)       # [1, 2) exact
    sym = (one_two - 1.0) * 2.0 - 1.0                          # [-1, 1) exact
    return (sym * half_width + offset).astype(jnp.bfloat16)


def normal_bf16(key, shape, std: float):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def leaf(key, leaf_id: int, shape, layer: int | None = None,
         kind: str = MATRIX):
    """One leaf (one layer's slice for per-layer leaves), in bfloat16."""
    k = jax.random.fold_in(key, leaf_id)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    if kind == NORM_SCALE:
        return uniform_bf16(k, shape, NORM_HALF_WIDTH, 1.0)
    if kind != MATRIX:
        raise ValueError(f"unknown draw kind {kind!r}")
    return normal_bf16(k, shape, STD)


def stacked(key, leaf_id: int, shape, num_layers: int, kind: str = MATRIX):
    """``[L, *shape]``: every layer's slice, equal to ``leaf(..., layer=i)``."""
    return jax.vmap(lambda i: leaf(key, leaf_id, shape, i, kind))(
        jnp.arange(num_layers, dtype=jnp.uint32))
