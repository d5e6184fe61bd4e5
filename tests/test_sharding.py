"""Sharding rules: logical-axis resolution, divisibility fallbacks, per-arch
TP policy, and end-to-end pjit equivalence on the host mesh."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced_config
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.parallel import sharding as rules
from repro.parallel.mesh import logical_spec, use_mesh


def _mesh22():
    # a synthetic (data=1, model=1) host mesh is enough to resolve specs;
    # divisibility tests use abstract meshes below.
    return make_host_mesh(1)


def _abstract_mesh(shape, names):
    # Mesh over repeated devices is invalid; use jax.sharding.AbstractMesh.
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(names))


def test_logical_spec_divisibility_fallback():
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    with use_mesh(None):
        # 96 heads over model=16 -> divisible; 25 heads -> replicated
        assert logical_spec((32, 96), (None, "model"), mesh) == P(None, "model")
        assert logical_spec((32, 25), (None, "model"), mesh) == P(None, None)
        # batch over (pod,data) only when divisible by the product
        mesh3 = _abstract_mesh((2, 16, 16), ("pod", "data", "model"))
        assert logical_spec((64, 8), ("batch", None), mesh3) == \
            P(("pod", "data"), None)
        assert logical_spec((1, 8), ("batch", None), mesh3) == P(None, None)


def test_param_specs_dense_arch():
    cfg = get_config("olmo-1b")
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = rules.param_specs(cfg, params, mesh)
    attn = specs["layers"]["attn"]
    assert attn["wq"] == P(None, "data", "model")   # FSDP x TP
    assert attn["wo"] == P(None, "model", "data")
    mlp = specs["layers"]["mlp"]
    assert mlp["wg"] == P(None, "data", "model")
    assert mlp["wo"] == P(None, "model", "data")
    assert specs["embed"]["table"] == P("model", "data")


def test_param_specs_awkward_heads_replicate_attention():
    cfg = get_config("hymba-1.5b")  # 25 heads, shard_attention=False
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = rules.param_specs(cfg, params, mesh)
    assert specs["layers"]["attn"]["wq"] == P(None, "data", None)
    # but the FFN still gets TP (5504 % 16 == 0)
    assert specs["layers"]["mlp"]["wg"] == P(None, "data", "model")


def test_param_specs_moe_ep_vs_tp():
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    # llama4: 16 experts % 16 == 0 -> expert-parallel
    cfg = get_config("llama4-scout-17b-a16e")
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    specs = rules.param_specs(cfg, params, mesh)
    assert specs["layers"]["moe"]["wg"] == P(None, "model", "data", None)
    # mixtral: 8 experts % 16 != 0 -> TP over d_ff
    cfg = get_config("mixtral-8x22b")
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    specs = rules.param_specs(cfg, params, mesh)
    assert specs["layers"]["moe"]["wg"] == P(None, None, "data", "model")


def test_vocab_sharding_falls_back_when_odd():
    cfg = get_config("whisper-base")  # vocab 51865 odd
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    specs = rules.param_specs(cfg, params, mesh)
    assert specs["embed"]["table"][0] is None  # not sharded over model


def test_cache_specs_sequence_parallel():
    cfg = get_config("qwen3-4b")
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    model = build(cfg)
    kv = {"kv": {"k": jax.ShapeDtypeStruct((36, 128, 32768, 8, 128),
                                           jnp.bfloat16),
                 "v": jax.ShapeDtypeStruct((36, 128, 32768, 8, 128),
                                           jnp.bfloat16)}}
    specs = rules.cache_specs(cfg, kv, mesh)
    assert specs["kv"]["k"] == P(None, "data", "model", None, None)


def test_pjit_forward_matches_single_device(rng):
    """Sharded execution must be numerically identical on a 1-device mesh."""
    cfg = reduced_config("olmo-1b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                                   jnp.int32)}
    plain, _ = model.forward(params, batch, remat=False)
    mesh = make_host_mesh(1)
    with use_mesh(mesh):
        sharded, _ = jax.jit(
            lambda p, b: model.forward(p, b, remat=False))(params, batch)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(sharded),
                               rtol=1e-5, atol=1e-5)


def test_init_sharded_creates_state_on_the_mesh():
    """Parameters and AdamW state come out of ONE jit already carrying the
    sharding rules' shardings (nothing is built on one device first)."""
    from repro.launch.train import init_sharded
    cfg = reduced_config("olmo-1b")
    model = build(cfg)
    mesh = make_host_mesh(1)
    params, opt_state = init_sharded(model, mesh, jax.random.PRNGKey(0))
    want = rules.named_shardings(cfg, params, mesh)
    for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    for leaf, sh in zip(jax.tree.leaves(opt_state["mu"]), jax.tree.leaves(want)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    ref = model.init(jax.random.PRNGKey(0))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


def test_kernels_are_auto_dispatched_only_outside_a_mesh(monkeypatch):
    from repro.core.contraction import kernel_backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_backend()
    with use_mesh(make_host_mesh(1)):
        assert not kernel_backend()
