"""Paged decode attention (``kernels/paged_attention.py``): the Pallas kernel
(interpret mode) and its jnp twin against ``chunked_attention`` over the
dense view, and against each other; the batched decode step built on them
holds no dense ``[B, max_len, Hkv, D]`` view; a sliding window keeps its
ring; the scheduler counts the blocks its attention visits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import health
from repro.kernels import paged_attention as pa
from repro.models import attention, build
from repro.models.layers import chunked_attention
from repro.serve import (ContinuousConfig, ContinuousScheduler, Engine,
                         Request, ServeConfig, VirtualClock)
from repro.testing import faults


def _case(rng, *, heads, kv_heads, d, bs, max_len, lens, layers=3):
    """A pool whose rows hold ``lens`` positions in blocks drawn out of
    order (no block shared by two rows; a row of length 0 keeps an
    all-null table), with queries and new positions, all float32."""
    b, mb = len(lens), max_len // bs
    nb = b * mb + 1
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pool_k, pool_v = f(layers, nb, bs, kv_heads, d), f(layers, nb, bs,
                                                       kv_heads, d)
    free = iter(rng.permutation(np.arange(1, nb)))
    tables = np.zeros((b, mb), np.int32)
    for row, n in enumerate(lens):
        for j in range(-(-n // bs)):
            tables[row, j] = next(free)
    return (f(b, heads, d), f(b, kv_heads, d), f(b, kv_heads, d), pool_k,
            pool_v, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))


def _dense_reference(q, k_new, v_new, pool_k, pool_v, layer, tables, lens,
                     scale):
    """``chunked_attention`` over the dense view: each row's blocks in
    table order, the new position written at ``lens``."""
    b = q.shape[0]
    view = [p[layer, tables].reshape(b, -1, *p.shape[3:])
            for p in (pool_k, pool_v)]
    slots = view[0].shape[1]
    at = (jnp.arange(slots)[None] == lens[:, None])[:, :, None, None]
    k, v = (jnp.where(at, new[:, None], old)
            for new, old in zip((k_new, v_new), view))
    return chunked_attention(
        q[:, None], k, v, causal=True, q_positions=lens[:, None],
        kv_valid=jnp.arange(slots)[None] <= lens[:, None], chunk=1,
        scale=scale)[:, 0]


# (q heads, KV heads, head dim): olmo-1b's MHA head shape and granite's
# 4:1 GQA, each with its block size. Over 288 positions a row folds up to
# three chunks; the lengths cross every block and chunk edge.
@pytest.mark.parametrize("heads,kv_heads,d,bs,scale,layer", [
    (4, 4, 128, 8, None, 2),
    (8, 2, 64, 16, 1 / 64, 1),
])
def test_kernel_and_twin_match_dense_attention(rng, heads, kv_heads, d, bs,
                                               scale, layer):
    chunk = pa.CHUNK
    max_len = 2 * chunk + 32
    lens = [0, 1, 15, 16, 17, chunk - 1, chunk + 1, 2 * chunk, max_len - 1]
    args = _case(rng, heads=heads, kv_heads=kv_heads, d=d, bs=bs,
                 max_len=max_len, lens=lens)
    q, k_new, v_new, pool_k, pool_v, tables, lens_a = args
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    layer = jnp.int32(layer)
    kernel = pa.paged_decode_attention(q, k_new, v_new, pool_k, pool_v,
                                       layer, tables, lens_a, scale=scale,
                                       interpret=True)
    twin = pa.paged_decode_attention_jnp(q, k_new, v_new, pool_k, pool_v,
                                         layer, tables, lens_a, scale=scale)
    want = _dense_reference(q, k_new, v_new, pool_k, pool_v, layer, tables,
                            lens_a, scale)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(twin), np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(twin), np.asarray(kernel), **tol)
    # A row of length 0 attends to its new position alone.
    np.testing.assert_allclose(
        np.asarray(twin[0]),
        np.repeat(np.asarray(v_new[0]), heads // kv_heads, axis=0), **tol)


# ---------------------------------------------------------------------------
# The batched step and the scheduler built on it
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(reduced_config("olmo-1b"),
                              compute_dtype="float32")
    model = build(cfg)
    return Engine(model, model.init(jax.random.PRNGKey(0)),
                  ServeConfig(max_len=256))


def _tensor(*dims):
    return "tensor<" + "x".join(map(str, dims)) + "x"


def test_batched_step_builds_no_dense_view(engine):
    """The olmo-shaped batched step lowers with no ``[B, max_len, Hkv, D]``
    view of a layer (nor its transpose, nor the ``[B, MB, bs, Hkv, D]``
    gather it was reshaped from): attention reads the pool's blocks."""
    b, max_len, bs = 2, 256, 16
    cs = ContinuousScheduler(engine, ContinuousConfig(max_live=b,
                                                      block_size=bs))
    kv = cs.kv
    text = cs._jit_step.lower(
        engine.params, kv.pool["k"], kv.pool["v"], kv.device_tables(),
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32)).as_text()
    cfg = engine.model.cfg
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    for dims in ((b, max_len, hkv, d), (max_len, b, hkv, d),
                 (b, max_len // bs, bs, hkv, d)):
        assert _tensor(*dims) not in text, dims
    assert _tensor(*kv.pool["k"].shape) in text


def test_sliding_window_keeps_the_ring(monkeypatch):
    """Full attention decodes through the blocked function; a sliding
    window's dense ring decodes through the select and windowed mask."""
    seen = []
    for name in ("blocked_decode_attention", "_ring_attention"):
        real = getattr(attention, name)
        monkeypatch.setattr(attention, name,
                            lambda *a, _n=name, _f=real, **k:
                            seen.append(_n) or _f(*a, **k))
    for arch, window, want in (("olmo-1b", None, "blocked_decode_attention"),
                               ("mixtral-8x22b", 8, "_ring_attention")):
        cfg = dataclasses.replace(reduced_config(arch),
                                  compute_dtype="float32",
                                  sliding_window=window)
        assert (cfg.attention_type == "sliding_window") == bool(window)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        token = jnp.zeros((2, 1), jnp.int32)
        caches = model.init_decode_state(params, {"tokens": token},
                                         max_len=16, dtype=jnp.float32)
        seen.clear()
        jax.eval_shape(model.decode, params, caches, token,
                       jnp.zeros((2,), jnp.int32))
        assert seen and set(seen) == {want}, (arch, seen)


def test_engagement_counter_counts_live_blocks(engine, monkeypatch):
    """Each clean batched step adds the blocks its attention visited (each
    row's blocks below its write position) and those the dense view would
    have read (``max_live`` x blocks per row)."""
    monkeypatch.delenv(faults.ENV_FAULT, raising=False)
    monkeypatch.delenv(health.ENV_NUMERICS_GUARD, raising=False)
    faults.reset()
    health.clear_serve()
    clock = VirtualClock()
    bs, max_live = 16, 3
    cs = ContinuousScheduler(engine, ContinuousConfig(
        queue_capacity=8, max_live=max_live, block_size=bs),
        clock=clock, sleep=clock.sleep)
    prompts, budget = (5, 16, 40), 6
    for rid, n in enumerate(prompts):
        cs.submit(Request(request_id=rid, tokens=np.arange(n) % 60 + 1,
                          max_new_tokens=budget))
    cs.drain()
    # Every request is admitted in the first tick and its first token
    # comes from prefill; the batched steps write positions n .. n+budget-2.
    steps = budget - 1
    visited = sum(-(-pos // bs) for n in prompts
                  for pos in range(n, n + steps))
    s = cs.stats()
    assert s["kv_blocks_visited"] == visited
    assert s["kv_blocks_dense"] == steps * max_live * (256 // bs)
    health.clear_serve()
