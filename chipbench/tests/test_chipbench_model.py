"""The reference and the set-up path on the CPU, at a reduced size.

* The plain float32 reference computes the same mathematics as the
  program's own model code run in float32.
* Weights drawn layer by layer equal the stacked draw the program gets.
* The served tree (drawn, laid out and packed in one jitted call) serves the
  same tokens, bitwise, as ``Engine(pack_weights=True)`` packing the same
  bfloat16 weights itself.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import serve
from chipbench import weights as W
from chipbench.arch import dense
from chipbench.tests.tinycell import tiny_spec


def tiny_arch(model_type):
    """The configuration file's architecture with every size cut."""
    arch = tiny_spec(model_type).arch
    arch.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                vocab_size=256)
    return arch


SEED = 2 ** 32 + 17


@pytest.mark.parametrize("model_type", ["olmo", "phi3"])
def test_reference_agrees_with_the_program_model_in_float32(model_type):
    from repro.models import build
    arch = tiny_arch(model_type)
    cfg = dataclasses.replace(dense.program_config(arch),
                              compute_dtype="float32")
    model = build(cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          dense.param_tree(W.root_key(SEED), arch))
    tokens = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": jnp.asarray(tokens)},
                               remat=False)
    want = dense.logits(arch, SEED, tokens)
    scale = float(jnp.abs(want).max())
    err = float(jnp.abs(got - want).max())
    assert err <= 1e-4 * scale, (err, scale)


def test_layer_draws_equal_the_stacked_draw():
    arch = tiny_arch("phi3")
    key = W.root_key(SEED)
    tree = jax.jit(lambda k: dense.param_tree(k, arch))(key)
    for layer in range(arch["num_hidden_layers"]):
        one = dense.layer_weights(key, arch, layer)
        assert np.array_equal(np.asarray(tree["layers"]["attn"]["wq"][layer]),
                              np.asarray(one["wq"]))
        assert np.array_equal(np.asarray(tree["layers"]["mlp"]["wo"][layer]),
                              np.asarray(one["w_down"]))
        assert np.array_equal(
            np.asarray(tree["layers"]["norm2"]["scale"][layer]),
            np.asarray(one["norm2"].astype(jnp.float32)))


def test_seeds_beyond_32_bits_draw_different_weights():
    a = dense.leaf(W.root_key(5), "wq", (8, 8))
    b = dense.leaf(W.root_key(5 + 2 ** 32), "wq", (8, 8))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert a.dtype == jnp.bfloat16


@pytest.mark.parametrize("model_type", ["olmo", "phi3"])
def test_served_tree_serves_what_engine_packing_serves(model_type):
    from repro.models import build
    from repro.serve.engine import Engine, ServeConfig
    arch = tiny_arch(model_type)
    program = build(dense.program_config(arch))
    raw = jax.jit(lambda k: dense.param_tree(k, arch))(W.root_key(SEED))
    ours = Engine(program, serve.build_params(dense, program, arch, SEED),
                  ServeConfig(max_len=32, pack_weights=False))
    theirs = Engine(program, raw, ServeConfig(max_len=32, pack_weights=True))
    batch = {"tokens": jnp.asarray(np.random.default_rng(1).integers(
        0, 256, (2, 12)).astype(np.int32))}
    a = ours.generate(batch, max_new_tokens=6)
    b = theirs.generate(batch, max_new_tokens=6)
    assert np.array_equal(a, b)
