"""A hybrid stack of Mamba-2 and attention layers, one mixer a layer, in a
published order (``layer_types``): Granite-4.0-H (``model_type``
``granitemoehybrid`` without experts). Its configuration keys and the
program's config, its parameter tree and leaves, its plain reference and its
operation, KV and recurrent-state counts (the contract is in
``chipbench/arch/__init__.py``; ``ssm_state_bytes`` and
``ssm_state_flops`` are this module's own, for ``ssm_roofline.decode``).

The reference is a plain float32 forward written from the published
descriptions, in straightforward ``jax.numpy``, with no kernels, cache,
chunking or batching tricks, and importing nothing of the program under
test:

* Granite-4.0-H-Micro (``ibm-granite/granite-4.0-h-micro``
  ``config.json``; the ``GraniteMoeHybrid`` layer of Hugging Face
  ``transformers``): every layer is ``x + m * mixer(rmsnorm(x))`` then
  ``x + m * mlp(rmsnorm(x))`` with ``m`` = ``residual_multiplier``; the
  mixer is the layer's kind. The embedding is scaled by
  ``embedding_multiplier``, the logits divided by ``logits_scaling``, the
  output head tied to the embedding; RMSNorm eps ``rms_norm_eps``; the MLP
  is SwiGLU at ``shared_intermediate_size`` (``num_local_experts`` 0).
* Attention: causal GQA with no positional embedding
  (``position_embedding_type`` ``nope``), scores scaled by
  ``attention_multiplier`` (not 1/sqrt(head_dim)), no biases.
* Mamba-2 (arXiv:2405.21060), one group: ``in_proj`` gives ``z``, ``xBC``
  and ``dt`` in that order; a causal depthwise conv of width ``d_conv``
  with bias over ``xBC``, then SiLU, split into ``x`` (``H`` heads of
  ``P``), ``B`` and ``C`` (``N`` each); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the recurrence run token by token,
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = C_t h_t + D x_t``
  (not the chunked dual form the program's prefill runs); then the gated
  RMSNorm ``rmsnorm(y * silu(z)) * scale`` over the whole inner width and
  ``out_proj``. No projection bias (``mamba_proj_bias`` false).

Every matmul runs at ``precision="highest"``. The forward goes one layer at
a time: each layer's weights are drawn from the seed just before use and
dropped after.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp

from chipbench import weights as W

MAMBA, ATTENTION = "mamba", "attention"

# ----- the configuration and the program -------------------------------------

# Published keys whose values the program and the reference assume.
FIXED = {"attention_bias": False, "hidden_act": "silu",
         "mamba_n_groups": 1, "mamba_proj_bias": False,
         "mamba_conv_bias": True, "normalization_function": "rmsnorm",
         "num_local_experts": 0, "position_embedding_type": "nope"}


def arch_of(config: dict) -> dict:
    """What the program and the reference need of a configuration file."""
    for key, want in FIXED.items():
        if config[key] != want:
            raise ValueError(f"{config['name']}: {key} is {config[key]!r}; "
                             f"this architecture computes {want!r}")
    keys = ("registry", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "tie_word_embeddings", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
            "mamba_expand", "mamba_chunk_size", "rms_norm_eps")
    arch = {k: config[k] for k in keys}
    arch["layer_types"] = tuple(config["layer_types"])
    arch["intermediate_size"] = config["shared_intermediate_size"]
    arch["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    arch["d_inner"] = config["mamba_expand"] * config["hidden_size"]
    if arch["d_inner"] != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise ValueError(f"{config['name']}: mamba_n_heads x mamba_d_head "
                         f"is not mamba_expand x hidden_size")
    if len(arch["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(f"{config['name']}: layer_types has "
                         f"{len(arch['layer_types'])} layers")
    if not arch["tie_word_embeddings"]:
        raise ValueError(f"{config['name']}: the head is tied here")
    return arch


def program_config(arch: dict):
    """The program's ``ModelConfig``: the registry entry with every size and
    scaling taken from the benchmark's configuration file."""
    from repro.configs import get_config
    base = get_config(arch["registry"])
    cfg = dataclasses.replace(
        base, num_layers=arch["num_hidden_layers"],
        layer_types=arch["layer_types"], d_model=arch["hidden_size"],
        num_heads=arch["num_attention_heads"],
        num_kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        d_ff=arch["intermediate_size"], vocab_size=arch["vocab_size"],
        tie_embeddings=True,
        attention_multiplier=arch["attention_multiplier"],
        embedding_multiplier=arch["embedding_multiplier"],
        residual_multiplier=arch["residual_multiplier"],
        logits_scaling=arch["logits_scaling"],
        ssm_state_size=arch["mamba_d_state"],
        ssm_head_dim=arch["mamba_d_head"], ssm_expand=arch["mamba_expand"],
        ssm_chunk=arch["mamba_chunk_size"],
        ssm_conv_width=arch["mamba_d_conv"])
    want = {"norm_type": "rmsnorm", "mlp_type": "swiglu", "family": "hybrid",
            "attention_type": "full", "use_bias": False, "qk_norm": False,
            "parallel_block": False, "pos_embedding": "none",
            "compute_dtype": "bfloat16", "num_experts": 0,
            "ssm_num_heads": arch["mamba_n_heads"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{arch['registry']}: program config {got} is not "
                         f"the architecture the reference computes {want}")
    return cfg


def layers_of(arch: dict, kind: str) -> List[int]:
    """The published indices of the layers of one kind, in order."""
    return [i for i, t in enumerate(arch["layer_types"]) if t == kind]


def param_tree(key, arch: dict) -> dict:
    """The program's parameter layout (layers stacked by kind), filled from
    the benchmark's leaves; each layer's slice is drawn at its published
    index."""
    shapes = layer_shapes(arch)

    def st(name, layers):
        return stacked(key, name, shapes[name], layers)

    def f32(name, layers):
        return st(name, layers).astype(jnp.float32)

    def common(layers):
        return {"norm1": {"scale": f32("norm1", layers)},
                "norm2": {"scale": f32("norm2", layers)},
                "mlp": {"wg": st("w_gate", layers), "wu": st("w_up", layers),
                        "wo": st("w_down", layers)}}

    m, a = layers_of(arch, MAMBA), layers_of(arch, ATTENTION)
    ssm = {"in_proj": st("in_proj", m), "conv_w": f32("conv_w", m),
           "conv_b": f32("conv_b", m), "A_log": f32("A_log", m),
           "dt_bias": f32("dt_bias", m), "D": f32("D", m),
           "norm": f32("ssm_norm", m), "out_proj": st("out_proj", m)}
    attn = {"wq": st("wq", a), "wk": st("wk", a), "wv": st("wv", a),
            "wo": st("wo", a)}
    g = global_weights(key, arch)
    return {"embed": {"table": g["embed"]},
            "mamba_layers": dict(common(m), ssm=ssm),
            "attn_layers": dict(common(a), attn=attn),
            "final_norm": {"scale": g["final_norm"].astype(jnp.float32)}}


# ----- leaves ----------------------------------------------------------------

# Leaf ids are part of the seed derivation: never renumber.
LEAF_IDS = {"embed": 1, "final_norm": 3, "norm1": 10, "norm2": 11,
            "wq": 20, "wk": 21, "wv": 22, "wo": 23,
            "w_gate": 30, "w_up": 31, "w_down": 32,
            "in_proj": 40, "out_proj": 41, "conv_w": 42, "conv_b": 43,
            "A_log": 44, "dt_bias": 45, "D": 46, "ssm_norm": 47}

# Draws besides ``weights``' matrices and norm scales, each uniform
# ``offset + U[-half, half)`` from raw bits through exact float operations
# (``weights.uniform_bf16``), rounded to bfloat16, as Mamba-2's own
# initialisation ranges them: ``A_log = log A`` with ``A`` log-uniform in
# [1, 16] (so ``A_log`` uniform in [0, ln 16)), and ``dt_bias`` uniform
# over [softplus^-1(0.001), softplus^-1(0.1)), the inverse softplus of the
# initial step range, so that some heads decay by a factor near 1 a token
# (a long memory) and others in a few tokens; the conv's weight and bias
# uniform in +/- 1/sqrt(d_conv) (PyTorch's default for a depthwise conv of
# width 4). ``D`` and the gated norm's scale are norm scales (1 +/- 0.25).
LN16 = math.log(16.0)
DT_BIAS_LO, DT_BIAS_HI = -6.907255, -2.252168   # softplus^-1(.001), (.1)
UNIFORM = {"A_log": (LN16 / 2, LN16 / 2),
           "dt_bias": ((DT_BIAS_HI - DT_BIAS_LO) / 2,
                       (DT_BIAS_HI + DT_BIAS_LO) / 2),
           "conv_w": (0.5, 0.0), "conv_b": (0.5, 0.0)}
NORM_LEAVES = ("norm1", "norm2", "final_norm", "D", "ssm_norm")


def layer_shapes(arch: dict) -> dict:
    """Per-layer leaf shapes of both kinds of layer ([in, out] for
    matrices)."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hd = arch["head_dim"]
    q, kv = arch["num_attention_heads"] * hd, arch["num_key_value_heads"] * hd
    di, n, h = arch["d_inner"], arch["mamba_d_state"], arch["mamba_n_heads"]
    conv = di + 2 * n
    return {"norm1": (d,), "norm2": (d,),
            "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
            "in_proj": (d, 2 * di + 2 * n + h), "out_proj": (di, d),
            "conv_w": (arch["mamba_d_conv"], conv), "conv_b": (conv,),
            "A_log": (h,), "dt_bias": (h,), "D": (h,), "ssm_norm": (di,)}


MATRICES = {ATTENTION: ("wq", "wk", "wv", "wo"),
            MAMBA: ("in_proj", "out_proj")}
MLP = ("w_gate", "w_up", "w_down")


def leaf(key, name: str, shape, layer=None):
    """One leaf (one layer's slice for per-layer leaves), in bfloat16."""
    if name not in UNIFORM:
        kind = W.NORM_SCALE if name in NORM_LEAVES else W.MATRIX
        return W.leaf(key, LEAF_IDS[name], shape, layer, kind)
    k = jax.random.fold_in(key, LEAF_IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    half, offset = UNIFORM[name]
    return W.uniform_bf16(k, shape, half, offset)


def stacked(key, name: str, shape, layers):
    """``[len(layers), *shape]``: the slices of the published layers
    ``layers``, each equal to ``leaf(..., layer=i)``."""
    return jax.vmap(lambda i: leaf(key, name, shape, i))(
        jnp.asarray(layers, jnp.uint32))


def layer_leaves(kind: str) -> Tuple[str, ...]:
    """The per-layer leaves of a layer of kind ``kind``."""
    names = ("norm1", "norm2") + MLP + MATRICES[kind]
    if kind == MAMBA:
        names += ("conv_w", "conv_b", "A_log", "dt_bias", "D", "ssm_norm")
    return names


def global_weights(key, arch: dict) -> dict:
    """Embedding (the tied head) and final norm."""
    v, d = arch["vocab_size"], arch["hidden_size"]
    return {"embed": leaf(key, "embed", (v, d)),
            "final_norm": leaf(key, "final_norm", (d,))}


# ----- the reference ---------------------------------------------------------

HI = jax.lax.Precision.HIGHEST


def _f32(w):
    return w.astype(jnp.float32)


def _rms(arch: dict, x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch["rms_norm_eps"]) * scale


def _mlp(arch: dict, w, x):
    m = _rms(arch, x, w["norm2"])
    gate = jnp.dot(m, w["w_gate"], precision=HI)
    up = jnp.dot(m, w["w_up"], precision=HI)
    return x + arch["residual_multiplier"] * jnp.dot(
        jax.nn.silu(gate) * up, w["w_down"], precision=HI)


def _attention(arch: dict, w, xs):
    """One sequence xs [T, d]: causal GQA, no positions, scaled by
    ``attention_multiplier``."""
    t = xs.shape[0]
    hd, h, hkv = (arch["head_dim"], arch["num_attention_heads"],
                  arch["num_key_value_heads"])
    a = _rms(arch, xs, w["norm1"])
    q = jnp.dot(a, w["wq"], precision=HI).reshape(t, h, hd)
    k = jnp.dot(a, w["wk"], precision=HI).reshape(t, hkv, hd)
    v = jnp.dot(a, w["wv"], precision=HI).reshape(t, hkv, hd)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) \
        * arch["attention_multiplier"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h * hd)
    return jnp.dot(o, w["wo"], precision=HI)


def _mamba(arch: dict, w, x):
    """Every sequence x [N, T, d] through one Mamba-2 mixer, token by
    token."""
    n_seq, t, _ = x.shape
    di, ns, h = arch["d_inner"], arch["mamba_d_state"], arch["mamba_n_heads"]
    p, width = arch["mamba_d_head"], arch["mamba_d_conv"]
    a = _rms(arch, x, w["norm1"])
    proj = jnp.dot(a, w["in_proj"], precision=HI)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * ns], \
        proj[..., 2 * di + 2 * ns:]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * w["conv_w"][i] for i in range(width))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xh = xbc[..., :di].reshape(n_seq, t, h, p)
    b, c = xbc[..., di:di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [N, T, H]
    decay_rate = -jnp.exp(w["A_log"])                        # [H]

    def step(state, inp):                                    # [N, H, P, Ns]
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * decay_rate)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, jnp.einsum("nhps,ns->nhp", state, c_t, precision=HI)

    seq = [jnp.moveaxis(v, 1, 0) for v in (xh, b, c, dt)]
    _, y = jax.lax.scan(step, jnp.zeros((n_seq, h, p, ns), jnp.float32), seq)
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * xh         # [N, T, H, P]
    y = y.reshape(n_seq, t, di) * jax.nn.silu(z)
    y = _rms(arch, y, w["ssm_norm"])
    return arch["residual_multiplier"] * jnp.dot(y, w["out_proj"],
                                                 precision=HI)


@partial(jax.jit, static_argnames=("arch_items", "kind"))
def _layer(x, key, layer, arch_items, kind):
    """One layer of kind ``kind`` over x [N, T, d], weights drawn from the
    seed at published index ``layer``."""
    arch = dict(arch_items)
    shapes = layer_shapes(arch)
    w = {n: _f32(leaf(key, n, shapes[n], layer)) for n in layer_leaves(kind)}
    if kind == MAMBA:
        x = x + _mamba(arch, w, x)
    else:
        x = x + arch["residual_multiplier"] * jax.lax.map(
            lambda xs: _attention(arch, w, xs), x)
    return _mlp(arch, w, x)


@partial(jax.jit, static_argnames=("arch_items",))
def _embed(tokens, key, arch_items):
    arch = dict(arch_items)
    table = _f32(leaf(key, "embed", (arch["vocab_size"],
                                     arch["hidden_size"])))
    return table[tokens] * arch["embedding_multiplier"]


def _head(arch: dict, key, x):
    g = jax.tree.map(_f32, global_weights(key, arch))
    return _rms(arch, x, g["final_norm"]), g["embed"]


@partial(jax.jit, static_argnames=("arch_items",))
def _gaps(x, key, targets, probe_ids, arch_items):
    """Per position: the reference's best logit minus its logit of the
    target token, the best token, and the logits at ``probe_ids``.
    x [N, T, d]; targets [N, T]; probe_ids [K]."""
    arch = dict(arch_items)
    x, head = _head(arch, key, x)

    def one(args):
        xs, ts = args
        logits = jnp.dot(xs, head.T, precision=HI) / arch["logits_scaling"]
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, ts[:, None], -1)[:, 0]
        return (best - got, jnp.argmax(logits, -1).astype(jnp.int32),
                logits[:, probe_ids])

    return jax.lax.map(one, (x, targets))


@partial(jax.jit, static_argnames=("arch_items",))
def _logits(x, key, arch_items):
    arch = dict(arch_items)
    x, head = _head(arch, key, x)
    return jnp.dot(x, head.T, precision=HI) / arch["logits_scaling"]


def arch_items(arch: dict) -> tuple:
    return tuple(sorted(arch.items()))


def final_hidden(arch: dict, seed: int, tokens) -> jnp.ndarray:
    """Hidden states after the last layer, x [N, T, d], layer by layer."""
    items = arch_items(arch)
    key = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens, jnp.int32), key, items)
        for layer, kind in enumerate(arch["layer_types"]):
            x = _layer(x, key, jnp.uint32(layer), items, kind)
    return x


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


# ----- operations, weights, KV elements and recurrent state ------------------


def _layer_gemms(arch: dict, kind: str) -> List[Tuple[int, int]]:
    shapes = layer_shapes(arch)
    return [shapes[n] for n in MATRICES[kind] + MLP]


def weight_gemms(arch: dict, rows: int,
                 head_rows: int | None = None) -> List[Tuple[int, int, int]]:
    """Every weight GEMM of one model step over ``rows`` token rows, layer
    by layer in published order (a mamba layer's ``in_proj`` and
    ``out_proj``, an attention layer's four projections, each layer's three
    MLP matrices), and the LM head over ``head_rows`` rows, as ``(M, K,
    N)``."""
    out = [(rows, k, n) for kind in arch["layer_types"]
           for k, n in _layer_gemms(arch, kind)]
    return out + [(rows if head_rows is None else head_rows,
                   arch["hidden_size"], arch["vocab_size"])]


def weight_shapes(arch: dict) -> List[Tuple[int, int]]:
    """``(K, N)`` of every weight GEMM of the model (the LM head's too)."""
    return sorted(set(_layer_gemms(arch, MAMBA) + _layer_gemms(arch, ATTENTION)
                      + [(arch["hidden_size"], arch["vocab_size"])]))


def layer_params(arch: dict) -> int:
    """Weights each token multiplies by in the layers."""
    return sum(k * n for kind in arch["layer_types"]
               for k, n in _layer_gemms(arch, kind))


def attention_flops(arch: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every attention layer."""
    q_dim = arch["num_attention_heads"] * arch["head_dim"]
    return 4 * len(layers_of(arch, ATTENTION)) * q_dim * context


def _state_elements(arch: dict) -> int:
    return arch["mamba_n_heads"] * arch["mamba_d_head"] * arch["mamba_d_state"]


def _conv_channels(arch: dict) -> int:
    return arch["d_inner"] + 2 * arch["mamba_d_state"]


def ssm_token_flops(arch: dict) -> int:
    """Operations of one token's recurrent update, in every mamba layer: the
    state decayed, the input's outer product scaled by dt and added (4 a
    state element), its readout by C (2 a state element), and the conv
    window (2 a tap and channel)."""
    per_layer = (6 * _state_elements(arch)
                 + 2 * arch["mamba_d_conv"] * _conv_channels(arch))
    return len(layers_of(arch, MAMBA)) * per_layer


def ssd_flops(arch: dict, length: int) -> int:
    """Operations of the chunked SSD scan over a prompt of ``length``
    tokens, in every mamba layer, counted over the real tokens: within each
    chunk, each causal pair's ``C_t . B_s`` and its weighting of ``x_s``;
    for each token, its part of the chunk's state and its readout of the
    state before the chunk (4 a state element); and the conv."""
    q = arch["mamba_chunk_size"]
    full, rest = divmod(length, q)
    pairs = full * q * (q + 1) // 2 + rest * (rest + 1) // 2
    per_pair = 2 * arch["mamba_d_state"] + 2 * arch["d_inner"]
    per_token = (4 * _state_elements(arch)
                 + 2 * arch["mamba_d_conv"] * _conv_channels(arch))
    return len(layers_of(arch, MAMBA)) * (pairs * per_pair
                                          + length * per_token)


def decode_token_flops(arch: dict, position: int) -> int:
    """Model operations of one token decoded at ``position``: its weight
    GEMMs and the LM head, attention over ``position + 1`` keys in each
    attention layer, and the recurrent update of each mamba layer."""
    head = arch["hidden_size"] * arch["vocab_size"]
    return (2 * (layer_params(arch) + head)
            + attention_flops(arch, position + 1) + ssm_token_flops(arch))


def prefill_flops(arch: dict, length: int) -> int:
    """Model operations of a causal prompt of ``length`` tokens, with the
    logits of its last position."""
    causal_keys = length * (length + 1) // 2
    q_dim = arch["num_attention_heads"] * arch["head_dim"]
    return (2 * layer_params(arch) * length
            + 2 * arch["hidden_size"] * arch["vocab_size"]
            + 4 * len(layers_of(arch, ATTENTION)) * q_dim * causal_keys
            + ssd_flops(arch, length))


def kv_elements(arch: dict, serving: dict) -> Tuple[int, int]:
    """Elements of one leaf of the paged KV pool ``[La, blocks + 1, block,
    Hkv, D]`` and of the dense view ``[La, max_live, max_len, Hkv, D]``,
    over the attention layers alone."""
    per_position = (len(layers_of(arch, ATTENTION))
                    * arch["num_key_value_heads"] * arch["head_dim"])
    blocks = serving["max_live"] * serving["max_len"] // serving["block_size"]
    return ((blocks + 1) * serving["block_size"] * per_position,
            serving["max_live"] * serving["max_len"] * per_position)


def ssm_store_elements(arch: dict, serving: dict) -> Tuple[int, int]:
    """Elements of the recurrent store's two leaves, the state ``[Lm,
    max_live, H, P, N]`` and the conv window ``[Lm, max_live, d_conv - 1,
    C]``."""
    rows = len(layers_of(arch, MAMBA)) * serving["max_live"]
    return (rows * _state_elements(arch),
            rows * (arch["mamba_d_conv"] - 1) * _conv_channels(arch))


F32 = 4


def ssm_state_bytes(arch: dict, rows: int) -> int:
    """Bytes of recurrent state ``rows`` rows read and write in one step:
    each mamba layer's float32 state ``[H, P, N]`` and conv window ``[d_conv
    - 1, C]``, read once and written once."""
    per_row = (_state_elements(arch)
               + (arch["mamba_d_conv"] - 1) * _conv_channels(arch))
    return 2 * F32 * rows * len(layers_of(arch, MAMBA)) * per_row


def ssm_state_flops(arch: dict, rows: int) -> int:
    """Operations of ``rows`` rows' recurrent update in one step."""
    return rows * ssm_token_flops(arch)
