"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def compat_make_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis in auto-sharding mode (the sharding
    rules annotate; GSPMD partitions). ``devices`` defaults to all."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) single pod (256 chips) or (2,16,16) two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (tests / CPU examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return compat_make_mesh((n // model_parallel, model_parallel),
                            ("data", "model"))
