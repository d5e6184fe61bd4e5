"""Mamba2 SSD (state-space duality) mixer — chunked matmul ("dual") form.

The SSD algorithm re-expresses the selective-state-space recurrence as blocked
matrix products (arXiv:2405.21060, Listing 1), which is precisely the shape of
computation the paper's layered-GEMM discipline targets: within-chunk terms
are dense GEMMs; only the small chunk-state recurrence is sequential.

Layout: x [B, L, H, P] heads, B/C shared across heads (ngroups=1) [B, L, N],
A scalar per head, dt per (token, head).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import gemm
from repro.models.layers import dense_param, resolve_weight, rms_norm_gated
from repro.parallel.mesh import shard


def ssm_params(cfg: ModelConfig, key) -> dict:
    d, di, n, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state_size, cfg.ssm_num_heads
    conv_ch = di + 2 * n
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        # in_proj -> [z(di), x(di), B(n), C(n), dt(nh)]
        "in_proj": dense_param(k1, d, 2 * di + 2 * n + nh),
        "conv_w": jax.nn.initializers.normal(0.02)(
            k2, (cfg.ssm_conv_width, conv_ch), jnp.float32),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "dt_bias": jnp.full((nh,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "D": jnp.ones((nh,), jnp.float32),
        "norm": jnp.ones((di,), jnp.float32),
        "out_proj": dense_param(k3, di, d),
    }


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """[..., T] -> [..., T, T] lower-triangular segment sums (log-decay)."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x, dt, a, b, c, chunk: int,
                initial_state: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD dual form. x:[B,L,H,P] dt:[B,L,H] a:[H] b,c:[B,L,N].

    Returns (y [B,L,H,P], final_state [B,H,P,N]).
    """
    bsz, length, nh, p = x.shape
    n = b.shape[-1]
    pad = (-length) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk

    xb = x.reshape(bsz, nc, chunk, nh, p).astype(jnp.float32)
    dtb = dt.reshape(bsz, nc, chunk, nh).astype(jnp.float32)
    bb = b.reshape(bsz, nc, chunk, n).astype(jnp.float32)
    cb = c.reshape(bsz, nc, chunk, n).astype(jnp.float32)

    da = dtb * (-jnp.exp(a.astype(jnp.float32)))[None, None, None, :]
    da = jnp.moveaxis(da, -1, 1)                  # [B, H, nc, Q]
    da_cs = jnp.cumsum(da, axis=-1)               # within-chunk cumsum
    xdt = xb * dtb[..., None]                     # [B,nc,Q,H,P]

    # 1) intra-chunk (dense GEMMs over the chunk):
    decay = jnp.exp(_segsum(da))                  # [B,H,nc,Q,Q]
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", cb, bb, decay, xdt)

    # 2) chunk boundary states:
    decay_states = jnp.exp(da_cs[..., -1:] - da_cs)           # [B,H,nc,Q]
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", bb, decay_states, xdt)

    # 3) inter-chunk recurrence over nc chunk states (the only sequential part):
    if initial_state is None:
        initial_state = jnp.zeros((bsz, nh, p, n), jnp.float32)
    chunk_decay = jnp.exp(da_cs[..., -1])         # [B,H,nc]

    def step(carry, inp):
        st, dec = inp                             # st: [B,H,P,N]; dec: [B,H]
        new = carry * dec[..., None, None] + st
        return new, carry                         # emit the PRE-chunk state

    states_seq = jnp.moveaxis(states, 1, 0)                   # [nc,B,H,P,N]
    decay_seq = jnp.moveaxis(chunk_decay, -1, 0)              # [nc,B,H]
    final_state, prev_states = jax.lax.scan(
        step, initial_state.astype(jnp.float32), (states_seq, decay_seq))
    prev_states = jnp.moveaxis(prev_states, 0, 1)             # [B,nc,H,P,N]

    # 4) inter-chunk contribution:
    state_decay_out = jnp.exp(da_cs)              # [B,H,nc,Q]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", cb, prev_states,
                       state_decay_out)

    y = (y_diag + y_off).reshape(bsz, nc * chunk, nh, p)[:, :length]
    return y.astype(x.dtype), final_state


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv. x: [B,L,C]; w: [W,C]."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(width))
    return out + b[None, None, :]


def apply_ssm(cfg: ModelConfig, p: dict, x: jnp.ndarray,
              return_state: bool = False):
    """Full-sequence Mamba2 block. x: [B,S,d] -> [B,S,d] (+ decode cache)."""
    bsz, s, _ = x.shape
    di, n, nh, hp = (cfg.d_inner, cfg.ssm_state_size, cfg.ssm_num_heads,
                     cfg.ssm_head_dim)
    proj = gemm.linear(x, resolve_weight(p["in_proj"], x.dtype))
    z, xin, b, c, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n],
                                 axis=-1)
    conv_in = jnp.concatenate([xin, b, c], axis=-1)
    with jax.named_scope("ssm_conv"):
        conv_out = jax.nn.silu(_causal_conv(conv_in.astype(jnp.float32),
                                            p["conv_w"], p["conv_b"]))
    xin, b, c = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    xh = xin.reshape(bsz, s, nh, hp)
    with jax.named_scope("ssm_scan"):
        y, final_state = ssd_chunked(xh, dt, p["A_log"], b, c, cfg.ssm_chunk)
    y = y + p["D"][None, None, :, None] * xh          # skip connection
    y = y.reshape(bsz, s, di)
    y = rms_norm_gated(y, z.astype(jnp.float32), p["norm"])
    y = shard(y, "batch", None, "model")
    out = gemm.linear(y.astype(x.dtype), resolve_weight(p["out_proj"], x.dtype))
    if return_state:
        w = cfg.ssm_conv_width - 1
        tail = conv_in.astype(jnp.float32)[:, -w:]
        if s < w:  # prompt shorter than the conv receptive field
            tail = jnp.pad(tail, ((0, 0), (w - s, 0), (0, 0)))
        return out, {"state": final_state, "conv": tail}
    return out


# ---------------------------------------------------------------------------
# Decode path (O(1) per token — the reason SSM archs run long_500k)
# ---------------------------------------------------------------------------

def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    di, n = cfg.d_inner, cfg.ssm_state_size
    return {
        "state": jnp.zeros((batch, cfg.ssm_num_heads, cfg.ssm_head_dim, n),
                           jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, di + 2 * n),
                          jnp.float32),
    }


def decode_ssm(cfg: ModelConfig, p: dict, x: jnp.ndarray,
               cache: dict) -> Tuple[jnp.ndarray, dict]:
    """One-token SSD recurrence. x: [B,1,d]."""
    bsz = x.shape[0]
    di, n, nh, hp = (cfg.d_inner, cfg.ssm_state_size, cfg.ssm_num_heads,
                     cfg.ssm_head_dim)
    proj = gemm.linear(x[:, 0], resolve_weight(p["in_proj"], x.dtype))
    z, xin, b, c, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n],
                                 axis=-1)
    conv_in = jnp.concatenate([xin, b, c], axis=-1).astype(jnp.float32)
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([cache["conv"], conv_in[:, None]], axis=1)
        conv_out = jax.nn.silu((window * p["conv_w"][None]).sum(1)
                               + p["conv_b"])
        new_conv = window[:, 1:]
    xin, b, c = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])     # [B,nh]
    xh = xin.reshape(bsz, nh, hp)
    with jax.named_scope("ssm_state"):
        da = jnp.exp(dt * (-jnp.exp(p["A_log"])))                    # [B,nh]
        # state <- decay * state + dt * x (outer) B
        new_state = (cache["state"] * da[..., None, None]
                     + jnp.einsum("bhp,bn,bh->bhpn", xh, b, dt))
        y = (jnp.einsum("bhpn,bn->bhp", new_state, c)
             + p["D"][None, :, None] * xh)
    y = y.reshape(bsz, di)
    y = rms_norm_gated(y, z.astype(jnp.float32), p["norm"])
    out = gemm.linear(y.astype(x.dtype), resolve_weight(p["out_proj"], x.dtype))
    return out[:, None], {"state": new_state, "conv": new_conv}
