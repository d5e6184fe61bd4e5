"""A layer-typed hybrid (Granite-4.0-H's stack of Mamba-2 and attention
layers) on the serving path: the recurrent store beside the paged KV pool,
the batched step that carries it, and every continuous-scheduler contract
the dense models keep (bitwise parity with the batch-1 front-end, preempt
and resume, bisection), at a small size on the CPU: five layers ``[m, m,
a, m, m]``, width 64, Mamba 4 heads of 16 with state 16 and chunk 8,
attention 4 query / 2 KV heads of 16, vocabulary 256."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.core import health
from repro.models import build
from repro.models.transformer import attention_before
from repro.serve import (ContinuousConfig, ContinuousScheduler, Engine,
                         Request, ServeConfig, StreamConfig, StreamFrontend,
                         VirtualClock)
from repro.serve.kv_cache import PagedKVCache
from repro.testing import faults

LAYERS = ("mamba", "mamba", "attention", "mamba", "mamba")


def small_hybrid(**kw):
    return dataclasses.replace(
        get_config("granite-4.0-h-micro"), num_layers=5, layer_types=LAYERS,
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=256, ssm_state_size=16, ssm_head_dim=16, ssm_expand=1,
        ssm_chunk=8, compute_dtype="float32", **kw)


@pytest.fixture(scope="module")
def engine():
    model = build(small_hybrid())
    # temperature > 0: the bitwise claims must hold for sampled streams.
    return Engine(model, model.init(jax.random.PRNGKey(0)),
                  ServeConfig(max_len=32, temperature=0.7, seed=3))


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    monkeypatch.delenv(faults.ENV_FAULT, raising=False)
    monkeypatch.delenv(health.ENV_NUMERICS_GUARD, raising=False)
    faults.reset()
    health.clear_serve()
    health.clear_health()
    yield
    faults.reset()
    health.clear_serve()
    health.clear_health()


def _requests(n, seed=1):
    r = np.random.default_rng(seed)
    return [Request(request_id=i,
                    tokens=r.integers(0, 64, int(r.choice((4, 6, 8))))
                    .astype(np.int32),
                    max_new_tokens=int(r.choice((2, 3, 4, 6))))
            for i in range(n)]


def _batch1(engine, reqs):
    clock = VirtualClock()
    fe = StreamFrontend(engine, StreamConfig(queue_capacity=64, max_live=2),
                        clock=clock, sleep=clock.sleep)
    for r in reqs:
        fe.submit(r)
    fe.drain()
    health.clear_serve()
    return {rid: res.tokens.copy() for rid, res in fe.results.items()}


def _serve(engine, reqs, **kw):
    clock = VirtualClock()
    cs = ContinuousScheduler(engine, ContinuousConfig(
        **{"queue_capacity": 32, "max_live": 3, "block_size": 8, **kw}),
        clock=clock, sleep=clock.sleep)
    for r in reqs:
        cs.submit(r)
    cs.drain(max_ticks=20_000)
    assert cs.kv.alloc.free_count == cs.kv.alloc.capacity
    return cs


# ----- configuration --------------------------------------------------------


def test_granite_h_micro_is_served_and_counted_by_layer_kind():
    cfg = get_config("granite-4.0-h-micro")
    assert "granite-4.0-h-micro" not in ARCH_IDS   # not the assignment's
    assert (cfg.num_layers, cfg.num_ssm_layers,
            cfg.num_attention_layers) == (40, 36, 4)
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert (cfg.d_inner, cfg.ssm_num_heads, cfg.kv_dim) == (4096, 64, 512)
    # By hand: attention 4 x 10485760, mamba 36 x 25847232 (in_proj 2048 x
    # 8512, out_proj 4096 x 2048, conv 4 x 4352 + bias, A_log, dt_bias, D,
    # gated norm), MLP and two norms 40 x 50335744, tied vocabulary
    # 100352 x 2048: about 3.19B, as published.
    assert cfg.num_params() == (4 * 10485760 + 36 * 25847232
                                + 40 * 50335744 + 100352 * 2048)
    assert cfg.num_params() == 3191394048


@pytest.mark.parametrize("kinds", [("mamba", "attention"),
                                   ("attention", "attention", "mamba"),
                                   ("mamba", "moe")])
def test_layer_types_are_checked(kinds):
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(small_hybrid(), num_layers=len(kinds),
                            layer_types=kinds)


def test_attention_layers_run_before_their_mamba_layer():
    assert attention_before(small_hybrid()).tolist() == [-1, -1, 0, -1]
    cfg = get_config("granite-4.0-h-micro")
    before = attention_before(cfg)
    assert [j for j, a in enumerate(before) if a >= 0] == [5, 14, 23, 32]
    assert sorted(before[before >= 0].tolist()) == [0, 1, 2, 3]


def test_reduced_granite_keeps_both_kinds():
    cfg = reduced_config("granite-4.0-h-micro")
    assert cfg.layer_types == ("attention", "mamba")
    logits, _ = build(cfg).forward(
        build(cfg).init(jax.random.PRNGKey(0)),
        {"tokens": jnp.zeros((1, 4), jnp.int32)})
    assert logits.shape == (1, 4, cfg.vocab_size)


# ----- the recurrent store beside the paged pool ----------------------------


def test_pool_pages_the_attention_layers_and_holds_the_state(engine):
    cfg = engine.model.cfg
    kv = PagedKVCache(cfg, max_live=3, max_len=32, block_size=8,
                      num_blocks=8)
    assert kv.pool["k"].shape == (1, 9, 8, 2, 16)
    assert kv.ssm["state"].shape == (4, 3, 4, 16, 16)
    assert kv.ssm["conv"].shape == (4, 3, 3, 64 + 2 * 16)
    state = 4 * 3 * (4 * 16 * 16 + 3 * 96) * 4
    assert kv.state_bytes() == state
    assert kv.pool_bytes() == 2 * kv.pool["k"].nbytes + state
    assert kv.bytes_per_block() == 2 * kv.pool["k"].nbytes // 9


@pytest.mark.parametrize("name", ["mamba2-130m", "hymba-1.5b",
                                  "granite-4.0-h-micro"])
def test_pool_refuses_what_it_cannot_hold(name):
    cfg = reduced_config(name)
    if cfg.layer_typed:          # a layer-typed stack with no attention layer
        cfg = dataclasses.replace(cfg, layer_types=("mamba", "mamba"))
    with pytest.raises(ValueError, match="not pageable"):
        PagedKVCache(cfg, max_live=2, max_len=32, block_size=8,
                     num_blocks=8)


def test_each_batched_row_is_its_batch1_decode(engine):
    """One batched step, rows read from the pool and the store, against
    each row's batch-1 decode of its gathered caches: the same sampled
    token bit for bit, the same logits and new state to float32 rounding
    (XLA on the CPU orders a GEMM's sums by its row count, so a row of 3
    and a row of 1 differ in the last bits: the dense models' rows differ
    by about 1e-7 too; the token streams are what the contracts pin)."""
    clock = VirtualClock()
    cs = ContinuousScheduler(engine, ContinuousConfig(
        queue_capacity=8, max_live=3, block_size=8), clock=clock,
        sleep=clock.sleep)
    for r in _requests(3, seed=5):
        cs.submit(dataclasses.replace(r, max_new_tokens=8))
    cs.step()                                  # admit all three, step once
    kv, B = cs.kv, cs.cfg.max_live
    tokens = np.zeros((B, 1), np.int32)
    pos = np.zeros((B,), np.int32)
    rids = np.zeros((B,), np.int32)
    steps = np.zeros((B,), np.int32)
    for row, slot in cs._live.items():
        tokens[row, 0] = slot.emitted[-1]
        pos[row] = slot.req.tokens.shape[0] + len(slot.emitted) - 1
        rids[row], steps[row] = slot.req.request_id, len(slot.emitted)
        assert kv.grow(row, int(pos[row]) + 1)
    out = cs._jit_step(engine.params, kv.pool["k"], kv.pool["v"],
                       kv.ssm["state"], kv.ssm["conv"], kv.device_tables(),
                       jnp.asarray(tokens), jnp.asarray(pos))
    assert len(out) == 5
    batched = np.asarray(engine.sample_tokens(out[0], rids, steps))
    for row in sorted(cs._live):
        raw, new = engine.decode_request(
            kv.gather_slot(row), jnp.asarray(tokens[row:row + 1]),
            int(pos[row]))
        alone = engine.sample_tokens(raw[:, 0], rids[row:row + 1],
                                     steps[row:row + 1])
        assert int(alone[0]) == int(batched[row])
        np.testing.assert_allclose(out[0][row], raw[0, 0], rtol=0,
                                   atol=1e-6)
        for i, name in ((3, "state"), (4, "conv")):
            np.testing.assert_allclose(out[i][:, row], new["ssm"][name][:, 0],
                                       rtol=1e-5, atol=1e-6)


# ----- the scheduler's contracts --------------------------------------------


def test_continuous_matches_batch1_bitwise(engine):
    ref = _batch1(engine, _requests(8))
    cs = _serve(engine, _requests(8))
    assert cs.stats()["completed"] == 8 and cs.stats()["preempted"] == 0
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)


@pytest.mark.parametrize("kv_quantize", [None, "int8"])
def test_preempt_and_resume_rebuild_the_state_bitwise(engine, kv_quantize):
    """A pool of 3 blocks for 3 rows preempts; each resumed row replays its
    prefix through the batch-1 decode, which rebuilds its recurrent state
    by the recurrence the batched step runs, and serves what an
    uninterrupted run serves."""
    ref = _serve(engine, _requests(8), kv_quantize=kv_quantize)
    health.clear_serve()
    cs = _serve(engine, _requests(8), num_kv_blocks=3,
                kv_quantize=kv_quantize)
    s = cs.stats()
    assert s["completed"] == 8 and s["preempted"] >= 1
    assert s["resumed"] == s["preempted"]
    for rid, res in ref.results.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, res.tokens)


def test_bisection_hands_each_row_its_state(engine):
    """The batched step fails past its retries: every row re-runs alone on
    its gathered caches (the row's blocks and state), commits its state,
    and stays bitwise; one row whose re-run fails is evicted alone."""
    ref = _batch1(engine, _requests(8))
    with faults.inject("batch_step", nth=(1, 2)):
        cs = _serve(engine, _requests(8), max_retries=1)
    assert cs.stats()["completed"] == 8
    for rid, toks in ref.items():
        np.testing.assert_array_equal(cs.results[rid].tokens, toks)
    health.clear_serve()
    with faults.inject("batch_step", nth=(1, 2, 3)):
        cs = _serve(engine, _requests(8), max_retries=1)
    evicted = [r for r, res in cs.results.items() if res.status == "evicted"]
    assert len(evicted) == 1
    for rid, toks in ref.items():
        got = cs.results[rid].tokens
        np.testing.assert_array_equal(
            got, toks[:len(got)] if rid in evicted else toks)


# ----- what the batched step compiles to ------------------------------------


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_hybrid_step_carries_no_copy_of_the_store(engine):
    """The recurrent store is the layer scan's input and output: no
    zero-filled buffer of the store's shape is built, and the attention
    layers' ``cond`` carries only the activations and the KV writes."""
    clock = VirtualClock()
    cs = ContinuousScheduler(engine, ContinuousConfig(
        queue_capacity=8, max_live=3, block_size=8), clock=clock,
        sleep=clock.sleep)
    kv = cs.kv
    jaxpr = cs._jit_step.trace(
        engine.params, kv.pool["k"], kv.pool["v"], kv.ssm["state"],
        kv.ssm["conv"], kv.device_tables(), jnp.zeros((3, 1), jnp.int32),
        jnp.zeros((3,), jnp.int32)).jaxpr.jaxpr
    store = {kv.ssm["state"].shape, kv.ssm["conv"].shape}
    eqns = list(_eqns(jaxpr))
    conds = [e for e in eqns if e.primitive.name == "cond"]
    assert conds
    for e in conds:
        assert not store & {tuple(v.aval.shape) for v in e.invars}
    assert not [e for e in eqns if e.primitive.name == "broadcast_in_dim"
                and store & {tuple(v.aval.shape) for v in e.outvars}]
    scans = [e for e in eqns if e.primitive.name == "scan"
             and store <= {tuple(v.aval.shape) for v in e.outvars}]
    assert len(scans) == 1


# The dense batched step's jaxpr (reduced olmo-1b, two rows, float and int8
# pools, with and without packed weights), as built since its attention reads
# the pool's blocks (the blocked decode attention's jnp twin, off the TPU):
# the layer-typed stack leaves the olmo cells' program as it is.
DENSE_STEP_SHA256 = {
    (False, None):
        "3b69f55bc44d1cde269685f294e3d92db2e3269b8c7ed40ab39fd07d6c247d87",
    (False, "int8"):
        "fed0874e0dcfe625ef1233f20555938cc6393ab1632244020892c709891d77f5",
    (True, None):
        "4bcebf572cbc2dc92fbb8e5446d2ae8a416b83abb3c616e8a9672b8ff2dd7d73",
    (True, "int8"):
        "bae77cbb89a331b46c6d0845a0b546ae0f3e87c62fff083cb9da550041acc526",
}


@pytest.mark.parametrize("pack,kv_quantize", sorted(DENSE_STEP_SHA256,
                                                    key=str))
def test_dense_batched_step_is_unchanged(pack, kv_quantize):
    import hashlib
    model = build(reduced_config("olmo-1b"))
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 ServeConfig(max_len=16, pack_weights=pack))
    cs = ContinuousScheduler(eng, ContinuousConfig(
        max_live=2, block_size=8, kv_quantize=kv_quantize))
    kv = cs.kv
    scales = [kv.scales["k"], kv.scales["v"]] if kv_quantize else []
    text = str(cs._jit_step.trace(
        eng.params, kv.pool["k"], kv.pool["v"], *scales, kv.device_tables(),
        jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32)).jaxpr)
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == DENSE_STEP_SHA256[(pack, kv_quantize)]
