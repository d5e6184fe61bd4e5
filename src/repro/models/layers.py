"""Shared model building blocks: norms, RoPE, MLPs, embeddings, chunked attention.

Every dense contraction routes through ``repro.core.gemm.linear`` — the
paper's layered GEMM is the framework's single matmul entry point. Weights may
be raw ``[K,N]`` arrays (training) or :class:`repro.core.PackedWeight` (tile-
major, packed once at load time by :func:`pack_model_params`): the packed form
routes through the pack-free-A fused kernel with bias and activation applied
in the kernel's store epilogue, so the serving path has no per-call packing
and no post-kernel elementwise ops.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.core import (EPILOGUE_SPECS, EpilogueSpec, GroupedPackedWeight,
                        PackedWeight, as_compute_weight, gemm)
from repro.parallel.mesh import shard

Init = jax.nn.initializers.normal(stddev=0.02)


def dense_param(key, in_dim: int, out_dim: int, dtype=jnp.float32):
    return Init(key, (in_dim, out_dim), dtype)


def resolve_weight(w, dtype):
    """Dense-weight accessor: packed weights pass through (packed in the
    compute dtype at load time); raw arrays are cast to the compute dtype.
    Weight-kind classification lives in core (no isinstance probes here)."""
    return as_compute_weight(w, dtype)


# Dense [K,N] weight names eligible for load-time packing, across every
# architecture family (attention/mlp/ssm). MoE expert stacks ([E,K,N], same
# key names inside the "moe" subtree) pack separately as GroupedPackedWeight.
DENSE_WEIGHT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "wg", "wu", "wi", "in_proj", "out_proj"})

# Stacked [E,K,N] expert-weight names inside a "moe" subtree, packed grouped
# tile-major at load time. The gate/up pair shares a silu-gate-capable plan
# (n_b_streams=2) so the fused grouped kernel can stream both stacks.
GROUPED_WEIGHT_KEYS = frozenset({"wg", "wu", "wo"})
_GATE_PAIR_KEYS = frozenset({"wg", "wu"})


def _pack_dense(w: jnp.ndarray, compute, quantize=None) -> PackedWeight:
    """Pack one dense weight (2-D, or [L,K,N] scan-stacked) tile-major.

    Runs once at load time on the execution backend's packer (the Pallas
    packer on a TPU, the jnp reference elsewhere; identical buffer
    layouts). Stacking and ``quantize`` ("int8"/"int4", optional ":col" —
    quantized tiles + a scale grid that scan-slices alongside the packed
    buffer) are handled inside ``PackedWeight.pack``.
    """
    return PackedWeight.pack(w.astype(compute), quantize=quantize)


def _pack_grouped(w: jnp.ndarray, compute, key: str,
                  quantize=None) -> GroupedPackedWeight:
    """Pack one expert stack ([E,K,N], or [L,E,K,N] scan-stacked) grouped
    tile-major in the compute dtype (backend packer; load-time, runs once)."""
    w = w.astype(compute)
    return GroupedPackedWeight.pack(
        w, n_b_streams=2 if key in _GATE_PAIR_KEYS else 1, quantize=quantize)


def pack_model_params(cfg: ModelConfig, params: dict, *, dtype=None,
                      quantize=None) -> dict:
    """Load-time packing pass: replace every dense weight with a PackedWeight
    and every MoE expert stack with a GroupedPackedWeight.

    Returns a new params tree in which each ``DENSE_WEIGHT_KEYS`` leaf (float
    dtypes only — pre-quantized int8 streams keep their narrow-HBM path) is
    tile-major packed in the compute dtype, each ``GROUPED_WEIGHT_KEYS`` leaf
    inside a "moe" subtree is grouped-packed per expert, and ``head_packed``
    holds the packed LM head ([d_model, vocab], from the tied embedding or
    the separate head table). Serving engines call this once at weight-load;
    every subsequent prefill/decode step then runs the pack-free-A fused
    kernels (dense and grouped), with the MoE gate/up pair fused into one
    silu-gate kernel pass.

    ``quantize`` quantizes every packed weight — dense projections, the LM
    head, and all three MoE expert stacks. ``"int8"``: int8 tiles +
    per-(Kb,Nb)-tile f32 scales (narrow-HBM serving: B traffic halves vs
    bf16); ``"int4"``: nibble-packed tiles (two values/byte, 0.25x bf16 B
    traffic); a ``":col"`` suffix selects per-Nb-column scales applied once
    in the store epilogue instead of per K-step. The kernels dequantize on
    the f32 accumulator ahead of the fused epilogues, so the serving
    numerics match a dequantized-weight run to quantization error.
    """
    compute = jnp.dtype(dtype or cfg.compute_dtype)

    def walk(tree, in_moe=False):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, val in tree.items():
            is_float = (hasattr(val, "ndim")
                        and jnp.issubdtype(val.dtype, jnp.floating))
            if (in_moe and key in GROUPED_WEIGHT_KEYS and is_float
                    and val.ndim in (3, 4)):
                # [E,K,N] expert stack (+leading L when scan-stacked).
                out[key] = _pack_grouped(val, compute, key, quantize)
            elif (not in_moe and key in DENSE_WEIGHT_KEYS and is_float
                    and val.ndim in (2, 3)):
                out[key] = _pack_dense(val, compute, quantize)
            else:
                out[key] = walk(val, in_moe or key == "moe")
        return out

    out = walk(params)
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["head"]["table"])
    out["head_packed"] = _pack_dense(jnp.asarray(table).T, compute, quantize)
    if not cfg.tie_embeddings:
        # lm_logits always prefers head_packed; keeping the raw untied table
        # would hold the model's largest matrix in memory twice.
        out.pop("head", None)
    return out


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_params(cfg: ModelConfig, key, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    if cfg.norm_type == "nonparametric_ln":
        return {}
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm" and cfg.use_bias:
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, p: dict, x: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        out = xf * p["scale"]
    else:  # layernorm / nonparametric_ln
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = xf
        if "scale" in p:
            out = out * p["scale"]
        if "bias" in p:
            out = out + p["bias"]
    return out.astype(x.dtype)


def rms_norm_gated(x: jnp.ndarray, z: jnp.ndarray, scale: jnp.ndarray,
                   eps: float = 1e-5) -> jnp.ndarray:
    """Mamba2's gated RMSNorm: norm(x * silu(z)) * scale."""
    xf = (x * jax.nn.silu(z)).astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [B, S] (absolute)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(seq_len: int, d_model: int) -> jnp.ndarray:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    emb = jnp.zeros((seq_len, d_model), jnp.float32)
    emb = emb.at[:, 0::2].set(jnp.sin(angle))
    emb = emb.at[:, 1::2].set(jnp.cos(angle))
    return emb


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_params(cfg: ModelConfig, key) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    gated = cfg.mlp_type in ("swiglu", "geglu")
    # Gate and up projections are SEPARATE tensors (not a fused [d, 2f]):
    # splitting a fused projection across the TP-sharded 2f dim costs a
    # collective-permute per layer (measured in the dry-run; see DESIGN.md).
    if gated:
        p = {"wg": dense_param(k1, d, f), "wu": dense_param(k3, d, f),
             "wo": dense_param(k2, f, d)}
    else:
        p = {"wi": dense_param(k1, d, f), "wo": dense_param(k2, f, d)}
    if cfg.use_bias:
        p["bi"] = jnp.zeros((f,), jnp.float32)
        p["bo"] = jnp.zeros((d,), jnp.float32)
    return p


@jax.named_scope("mlp")
def apply_mlp(cfg: ModelConfig, p: dict, x: jnp.ndarray,
              epilogue_shard: bool = True) -> jnp.ndarray:
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = EpilogueSpec(activation="silu" if cfg.mlp_type == "swiglu"
                           else "gelu")
        # The activation rides as the GEMM's declared epilogue chain
        # (in-kernel on the Pallas path; XLA-fused on the jnp path).
        gate = gemm.linear(x, resolve_weight(p["wg"], x.dtype), p.get("bi"),
                           epilogue=act)
        up = gemm.linear(x, resolve_weight(p["wu"], x.dtype))
        h = gate * up
    else:
        h = gemm.linear(x, resolve_weight(p["wi"], x.dtype), p.get("bi"),
                        epilogue=EPILOGUE_SPECS["gelu"])
    h = shard(h, "batch", None, "model")
    out = gemm.linear(h, resolve_weight(p["wo"], x.dtype), p.get("bo"))
    if not epilogue_shard:
        return out  # TP-partial: caller fuses before one collective (H5)
    # Megatron-SP epilogue (see attention.self_attention): reduce-scatter the
    # TP-partial down-projection into the seq-sharded residual stream; saved
    # under remat so backward skips re-running the TP collective (§Perf H4).
    return checkpoint_name(shard(out, "batch", "seq"), "mixer_out")


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_params(cfg: ModelConfig, key) -> dict:
    p = {"embed": {"table": Init(key, (cfg.vocab_size, cfg.d_model),
                                 jnp.float32)}}
    if not cfg.tie_embeddings:
        p["head"] = {"table": Init(jax.random.fold_in(key, 1),
                                   (cfg.vocab_size, cfg.d_model), jnp.float32)}
    return p


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                 compute_dtype) -> jnp.ndarray:
    # Annotate the casted lookup table vocab-sharded with d REPLICATED before
    # the gather: the f32 master is (model, fsdp)-sharded, and gathering from
    # a d-over-data table forces GSPMD into an involuntary full
    # rematerialization of the [B, S, d] gather output when it reshards to
    # the batch-sharded residual layout (measured on the 512-device dry run).
    # With d replicated, the vocab-sharded gather's masked partial rows
    # all-reduce over "model" straight into the batch-sharded layout.
    table = shard(params["embed"]["table"].astype(compute_dtype),
                  "model", None)
    x = table[tokens]
    if cfg.family == "vlm":  # gemma-style scaled embeddings
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    if cfg.embedding_multiplier != 1.0:   # Granite's scaled embeddings
        x = x * jnp.asarray(cfg.embedding_multiplier, compute_dtype)
    return shard(x, "batch")


@jax.named_scope("lm_head")
def lm_logits(cfg: ModelConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    head = params.get("head_packed")  # load-time-packed LM head (serving)
    if head is None:
        table = (params["embed"]["table"] if cfg.tie_embeddings
                 else params["head"]["table"])
        # Megatron vocab-parallel head layout: [d, V] with d REPLICATED and
        # vocab over "model". Without the annotation the head inherits the
        # master table's d-over-data sharding and GSPMD contracts x@head by
        # fully rematerializing the batch-sharded [B, S, d] stream (the
        # bf16 [2,4096,2048] full-remat on the 512-device dry run); with it
        # the contraction keeps x batch-sharded and emits logits already in
        # the ("batch", None, "model") layout pinned below.
        head = shard(table.T.astype(x.dtype), None, "model")
    # logits keep a full-precision cross-shard reduce (softmax sensitivity)
    logits = gemm.linear(x, head, accum="f32").astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard(logits, "batch", None, "model")


# ---------------------------------------------------------------------------
# Chunked exact attention (memory-bounded jnp lowering)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, window: Optional[int] = None,
                      prefix_len: int = 0, q_offset: int = 0,
                      q_positions: Optional[jnp.ndarray] = None,
                      kv_valid: Optional[jnp.ndarray] = None,
                      k_positions: Optional[jnp.ndarray] = None,
                      chunk: int = 512,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Exact attention, scanned over query chunks to bound peak memory.

    q: [B,Sq,H,D]; k/v: [B,Skv,Hkv,D]. Query position i maps to absolute
    position q_offset + i unless ``q_positions`` ([B,Sq]) is given (decode).
    ``k_positions`` ([B,Skv] absolute, for rotated SWA caches) defaults to
    arange. ``kv_valid``: [B,Skv] bool for ragged caches. Attention pattern:
    causal (+ sliding window) with an optional bidirectional prefix
    (prefix-LM, used by the VLM family). ``scale`` multiplies the scores
    (default ``1/sqrt(D)``).
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    group = h // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(skv)[None], (b, skv))
    # K/V stay in their storage dtype; the contractions below request f32
    # accumulation via preferred_element_type (native on the MXU). An explicit
    # astype here would materialize an f32 copy of the whole KV stream.
    kf, vf = k, v

    chunk = min(chunk, sq)
    pad = (-sq) % chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    if q_positions is None:
        q_positions = q_offset + jnp.arange(sq)[None]  # [1, Sq]
    qpos_all = jnp.broadcast_to(q_positions, (b, sq))
    if pad:
        qpos_all = jnp.pad(qpos_all, ((0, 0), (0, pad)))
    n_chunks = qp.shape[1] // chunk

    def one_chunk(ci):
        qs = jax.lax.dynamic_slice_in_dim(qp, ci * chunk, chunk, 1)
        qpos = jax.lax.dynamic_slice_in_dim(qpos_all, ci * chunk, chunk, 1)
        # [B, Hkv, group, chunk, Skv]
        qg = qs.reshape(b, chunk, hkv, group, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf,
                            preferred_element_type=jnp.float32) * scale
        qpb = qpos[:, :, None]                          # [B, chunk, 1]
        kpb = k_positions[:, None, :]                   # [B, 1, Skv]
        mask = jnp.ones((b, chunk, skv), bool)
        if causal:
            mask &= qpb >= kpb
        if window is not None:
            mask &= (qpb - kpb) < window
        if prefix_len:
            mask |= (qpb < prefix_len) & (kpb < prefix_len)
        if kv_valid is not None:
            mask &= kv_valid[:, None, :]
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vf.dtype), vf,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, chunk, h, d).astype(q.dtype)

    out = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_chunks * chunk, h, d)
    return out[:, :sq]
