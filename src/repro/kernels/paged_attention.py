"""Paged decode attention: one query token per row, read from a block pool.

The batched decode step keeps K/V in a pool ``[L, blocks, bs, Hkv, D]``;
each row maps its positions onto blocks through a table ``[B, MB]``. This
kernel attends each row's query over ITS live positions, reading the blocks
straight from the pool by DMA (the layer index and the tables are scalar
prefetch), so no per-layer ``[B, max_len, Hkv, D]`` view is written and no
empty slot is attended over. The row's new position ``(k_new, v_new)`` is
folded in last: it reaches the pool only through the caller's scatter.

Reduction order (shared with :func:`paged_decode_attention_jnp`, the jnp
lowering): the row's positions ``0 .. lens[b] - 1`` in chunks of ``CHUNK``
positions, folded in order into an online softmax (f32 max, sum and
accumulator; K/V in their storage dtype, probabilities cast to it for the
value contraction), then the new position. The order depends on positions
only, never on the pool's block size, so a dense cache viewed as blocks
(:func:`dense_tables`) sums in exactly the order its paged twin does.

Kernel layout: a ``(row, chunk)`` grid. Each step takes ``CHUNK // bs``
blocks of K and of V through as many BlockSpecs, indexed by the
scalar-prefetched layer and a fetch order derived from the tables
(:func:`_fetch_order`): a block past the row's length repeats the index the
input held at the step before, so Pallas's pipeline, which skips the copy
of an unchanged block, fetches only live blocks, double-buffered across
steps and rows; a step past the row's length computes nothing. All heads
of a chunk go through the MXU at once: the scores of every query head
against every (position, KV head) row of the chunk, of which the mask keeps
the pairs of a KV head and its query group. The chunk's blocks are joined
into one matrix first, so the kernel's order, too, is the same for any
block size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret, pallas_kwargs

CHUNK = 128   # positions folded per online-softmax step


def dense_tables(batch: int, max_len: int):
    """A dense cache ``[B, max_len, Hkv, D]`` as a one-layer pool: its block
    size (the largest that divides both ``max_len`` and ``CHUNK``) and the
    tables ``[B, max_len // bs]`` that map row ``b`` onto its own blocks.
    ``cache.reshape(1, batch * MB, bs, Hkv, D)`` is the pool."""
    bs = math.gcd(max_len, CHUNK)
    mb = max_len // bs
    return bs, (jnp.arange(batch, dtype=jnp.int32)[:, None] * mb
                + jnp.arange(mb, dtype=jnp.int32)[None, :])


def _fold_new(q, k_new, v_new, m, l, acc, scale, vdtype):
    """Fold the new position into the running softmax; q/k_new/v_new are
    f32 ``[..., D]`` with one KV vector per query head."""
    s = jnp.sum(q * k_new, axis=-1, keepdims=True) * scale
    m_new = jnp.maximum(m, s)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + p
    acc = alpha * acc + p.astype(vdtype).astype(jnp.float32) * v_new
    return acc / l


def paged_decode_attention_jnp(q, k_new, v_new, pool_k, pool_v, layer,
                               tables, lens, *, scale: float):
    """jnp lowering of :func:`paged_decode_attention`: the same chunks in
    the same order. Each chunk gathers every row's ``CHUNK`` positions from
    the pool; a row past its length keeps its state unchanged."""
    b, h, d = q.shape
    hkv = k_new.shape[1]
    group = h // hkv
    bs = pool_k.shape[2]
    qg = q.reshape(b, hkv, group, d)
    n = cdiv(lens, CHUNK)

    def body(c, carry):
        m, l, acc = carry
        p_idx = c * CHUNK + jnp.arange(CHUNK)
        blk = jnp.take(tables, p_idx // bs, axis=1, mode="clip")   # [B, C]
        k = pool_k[layer, blk, p_idx % bs]                      # [B,C,hkv,D]
        v = pool_v[layer, blk, p_idx % bs]
        valid = p_idx[None, :] < lens[:, None]                  # [B, C]
        s = jnp.einsum("bhgd,bchd->bhgc", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        v = jnp.where(valid[:, :, None, None], v, jnp.zeros((), v.dtype))
        acc_new = alpha * acc + jnp.einsum(
            "bhgc,bchd->bhgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        live = (c < n)[:, None, None, None]
        return (jnp.where(live, m_new, m), jnp.where(live, l_new, l),
                jnp.where(live, acc_new, acc))

    init = (jnp.full((b, hkv, group, 1), -jnp.inf, jnp.float32),
            jnp.zeros((b, hkv, group, 1), jnp.float32),
            jnp.zeros((b, hkv, group, d), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, jnp.max(n), body, init)
    f32 = jnp.float32
    out = _fold_new(qg.astype(f32), k_new.astype(f32)[:, :, None],
                    v_new.astype(f32)[:, :, None], m, l, acc, scale,
                    pool_v.dtype)
    return out.reshape(b, h, d).astype(q.dtype)


def _fetch_order(tables, lens, bs: int, per: int):
    """The block each of the kernel's ``per`` K (and V) inputs loads at each
    grid step ``(row, chunk)``: the table's block where it lies below the
    row's length, else the block that input held at the step before (or,
    before its first live block, that block), so that Pallas, which skips
    the copy of an unchanged block index, fetches no block twice and none
    past a row's length."""
    b, mb = tables.shape
    nc = cdiv(mb, per)
    pad = nc * per - mb
    t = jnp.pad(tables, ((0, 0), (0, pad)))
    live = (jnp.arange(nc * per)[None, :] < cdiv(lens, bs)[:, None])
    # [per, steps]: input i's block at step (row, chunk), in grid order.
    t = t.reshape(b, nc, per).transpose(2, 0, 1).reshape(per, b * nc)
    live = live.reshape(b, nc, per).transpose(2, 0, 1).reshape(per, b * nc)
    step = jnp.arange(b * nc)
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=1)
    first = jnp.min(jnp.where(live, step, b * nc), axis=1, keepdims=True)
    src = jnp.where(last >= 0, last, jnp.minimum(first, b * nc - 1))
    return jnp.take_along_axis(t, src, axis=1)             # [per, B * nc]


def _kernel(layer_ref, fetch_ref, lens_ref, q_ref, kn_ref, vn_ref, *refs,
            scale, group, per):
    k_refs, v_refs = refs[:per], refs[per:2 * per]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * per:]
    row, c = pl.program_id(0), pl.program_id(1)
    bs, hkv, d = k_refs[0].shape
    h = q_ref.shape[1]
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    left = lens_ref[row] - c * CHUNK          # live positions from here on

    @pl.when(left > 0)
    def _():
        # The chunk's (position, KV head) rows as one matrix, whatever the
        # block size.
        def rows(refs):
            return jnp.concatenate([ref[...].reshape(bs * hkv, d)
                                    for ref in refs])

        k, v = rows(k_refs), rows(v_refs)
        r = CHUNK * hkv
        col = jax.lax.broadcasted_iota(jnp.int32, (h, r), 1)
        ok = ((col % hkv) == jax.lax.broadcasted_iota(jnp.int32, (h, r), 0)
              // group) & (col // hkv < left)
        s = jax.lax.dot_general(q_ref[0].astype(k.dtype), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        s = jnp.where(ok, s, -jnp.inf)
        m = jnp.maximum(m_ref[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_ref[...] - m)
        p = jnp.exp(s - m)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        row_pos = jax.lax.broadcasted_iota(jnp.int32, (r, d), 0) // hkv
        v = jnp.where(row_pos < left, v, jnp.zeros((), v.dtype))
        p = p.astype(v.dtype)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        m_ref[...] = m

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        out = _fold_new(q_ref[0].astype(f32), kn_ref[0].astype(f32),
                        vn_ref[0].astype(f32), m_ref[...], l_ref[...],
                        acc_ref[...], scale, v_refs[0].dtype)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, layer, tables,
                           lens, *, scale: float,
                           interpret: bool | None = None):
    """One-token attention of every row over its pool blocks.

    q: [B, H, D]; k_new/v_new: [B, Hkv, D], the row's new position;
    pool_k/pool_v: [L, blocks, bs, Hkv, D]; layer: int32 scalar; tables:
    [B, MB] int32 block ids; lens: [B] int32, the positions already in the
    pool (a row of length 0 reads no block of its own). Returns [B, H, D]
    in q's dtype. The block size must divide ``CHUNK``.
    """
    if interpret is None:
        interpret = default_interpret()
    b, h, d = q.shape
    hkv = k_new.shape[1]
    bs = pool_k.shape[2]
    if CHUNK % bs:
        raise ValueError(f"the pool's block size {bs} does not divide the "
                         f"chunk of {CHUNK} positions")
    group, per = h // hkv, CHUNK // bs
    nc = cdiv(tables.shape[1], per)
    fetch = _fetch_order(tables.astype(jnp.int32), lens.astype(jnp.int32),
                         bs, per)
    # One KV vector per query head for the new position's fold.
    kn, vn = (jnp.repeat(x, group, axis=1) for x in (k_new, v_new))

    def block(i):
        return pl.BlockSpec(
            (None, None, bs, hkv, d),
            lambda r, c, layer, fetch, lens: (layer[0], fetch[i, r * nc + c],
                                              0, 0, 0))

    row_block = pl.BlockSpec((1, h, d), lambda r, c, *_: (r, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nc),
        in_specs=[row_block, row_block, row_block]
        + [block(i) for i in range(per)] * 2,
        out_specs=row_block,
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), group=group,
                          per=per),
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        **pallas_kwargs(interpret=interpret,
                        dimension_semantics=("arbitrary", "arbitrary")),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), fetch,
      lens.astype(jnp.int32), q, kn, vn, *[pool_k] * per,
      *[pool_v] * per)
