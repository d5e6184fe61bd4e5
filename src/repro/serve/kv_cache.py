"""Paged/block KV cache for the continuous-batching scheduler.

The paper's packing discipline applied to the KV stream one level up: instead
of reserving a dense ``max_len`` cache per slot (the batch-1 front-end's
layout), K/V live in a global pool of fixed-size BLOCKS and each slot maps its
positions onto blocks through a per-slot block table — sequence LENGTH is
decoupled from ALLOCATION, so a batch of mostly-short requests no longer pays
for the longest request's worst case.

Block-accounting contract
=========================

* The pool holds ``num_blocks + 1`` blocks per layer; **block 0 is the NULL
  block** — it backs every unallocated table entry, absorbs the batched
  step's padding-row writes, and is NEVER validly read: any position it
  backs lies beyond the owning slot's current length, which the decode
  attention never fetches or masks exactly (probability exactly zero, the
  value zeroed before the value contraction; proven in
  ``tests/test_serve_continuous.py``). Block 0 is never allocated and never
  freed.
* :class:`BlockAllocator` hands out blocks lowest-id-first (deterministic
  layouts for bitwise replay tests) and detects double-free. **Exhaustion is
  a typed backpressure signal**: :meth:`BlockAllocator.try_alloc` returns
  ``None`` when the pool is short — it never raises for load. The armed
  ``kv_alloc`` fault site (class ``resource``) fires inside ``try_alloc`` to
  stand in for allocator failure.
* **No leaks**: every block allocated to a slot is returned by
  :meth:`PagedKVCache.release` (completion, eviction, deadline miss, or
  preemption), and released blocks are SCRUBBED to zero before reuse — a NaN
  parked in a recycled block would otherwise leak through the masked value
  contraction (0 · NaN = NaN). After a full drain
  ``allocator.free_count == allocator.capacity`` (property-swept in tests).
* ``max_len % block_size == 0`` is required so a fully-tabled slot holds
  EXACTLY the dense ``max_len`` cache the batch-1 programs use — the batched
  step's attention over a row's blocks and the batch-1 attention over its
  dense cache are the same blocked decode attention, folding the same
  positions in the same order, which is what makes the batched step
  bitwise-equal to the batch-1 path (the bisection and preempt-resume
  contracts ride on this).

Supported families: decoder-only token LMs with full attention (dense / moe /
parallel-block), and layer-typed hybrids (``ModelConfig.layer_types``:
mamba and attention layers, one mixer each). Sliding-window rings,
encoder-decoder and VLM caches, models with no attention layer and hybrids
that run both mixers in every layer are not paged here (the ring wrap and the other
state break the block mapping); constructing a :class:`PagedKVCache` for
one raises ``ValueError``.

Recurrent state (layer-typed hybrids)
=====================================

Only the attention layers are paged (``L`` is their count). Beside the pool
lives a per-row recurrent store, ``ssm["state"]: [Lm, max_live, H, P, N]``
and ``ssm["conv"]: [Lm, max_live, W - 1, C]`` (float32, what the model's
decode reads and writes; ``Lm`` mamba layers). A row's state is written
whole, never in part: by ``insert_dense`` (the state prefill or a replay
produced), by ``write_position`` (a batch-1 decode's new state, with its
written position) and by the batched step, which reads every row's state
and returns every row's new state. A dead row's state is bitwise inert:
admission overwrites it before a live row reads it, and rows never mix, so
``release`` leaves it. The store's bytes count in ``pool_bytes``
(``state_bytes``), one memory account with the blocks.

Quantized pool (``quantize="int8"``)
====================================

The pool leaves store int8 values plus per-POSITION f32 scale leaves
``scales[name]: [L, num_blocks + 1, block_size]`` — one absmax/127 scale per
(layer, position) over that position's ``[Hkv, D]`` vector, the KV analogue of
the weight pipeline's scale-operand convention. Halved KV bytes per resident
token ≈ 2x concurrent users per block budget. The contract clauses above hold
unchanged, plus:

* **Quantize exactly once per position.** Every write path — ``insert_dense``
  scatter, ``write_position`` commit, the batched step's scatter, and resume
  replay — quantizes a position's vector with the same formula at write time
  and never re-quantizes it (re-quantizing a dequantized vector is NOT
  idempotent: absmax drifts by the rounding error, which would break the
  bitwise preempt/resume contract). Reads dequantize ``q * scale`` into the
  compute dtype.
* Per-position (not per-block) scales for the same reason: appending a
  position to a block must not touch its neighbours' already-committed bytes.
* The null block's scales are 1.0 (dequant of its zeros is exactly zero);
  ``release`` scrubs a slot's scale entries back to 1.0 alongside the zeroed
  values.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import dequantize_kv
from repro.models.ssm import init_ssm_cache
from repro.testing import faults

# The two paged leaves of a decoder-only attention cache.
_KV_LEAVES = ("k", "v")


# Module-level jit'd pool helpers: the compile cache is keyed on the function
# object, so hoisting them out of the instance shares compiles across every
# PagedKVCache of the same pool shape (per-instance jits re-compiled the full
# helper set for every new scheduler — pure overhead on the serving path).

@jax.jit
def _scatter_blocks(pool, row, blocks):
    return pool.at[:, row].set(blocks)


# The scrubs write the pool in place (donated): release runs for every
# finished slot of a tick before anything waits on the chip, and a copying
# scrub per slot and leaf would keep that many whole pools alive at once.

@functools.partial(jax.jit, donate_argnums=0)
def _scrub_row(pool, row):
    zeros = jnp.zeros((pool.shape[0], row.shape[0], *pool.shape[2:]),
                      pool.dtype)
    return pool.at[:, row].set(zeros)


# A row of the recurrent store is written in place (donated): the store is
# whole-model sized, and a copying write per admission would hold two.

@functools.partial(jax.jit, donate_argnums=0)
def _put_state_row(store, row, leaf):
    return store.at[:, row].set(leaf[:, 0])


@jax.jit
def _state_row(store, row):
    return jax.lax.dynamic_slice_in_dim(store, row, 1, axis=1)


@jax.jit
def _gather_row(pool, row):
    g = pool[:, row]                     # [L, MB, bs, h, d]
    return g.reshape(g.shape[0], 1, row.shape[0] * pool.shape[2],
                     *g.shape[3:])


@jax.jit
def _write_pos(pool, dest, written):
    flat = pool.reshape(pool.shape[0], -1, *pool.shape[3:])
    return flat.at[:, dest].set(written).reshape(pool.shape)


# Quantized-pool helpers. ``quantize_kv_position`` is the ONE quantization
# formula (shared by every write path, inside and outside jit, so replayed
# writes are bitwise the live writes); the rest mirror the float helpers with
# a scale leaf riding along.

def quantize_kv_position(x):
    """``x: [..., Hkv, D]`` float -> (int8 values, f32 per-position scales
    ``[...]``). absmax/127 per position; an all-zero position gets scale 1.0
    (its zeros stay exactly zero through the round trip)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]), -127, 127)
    return q.astype(jnp.int8), scale


@jax.jit
def _scatter_blocks_q(pool, scales, row, leaf):
    bs = pool.shape[2]
    q, s = quantize_kv_position(leaf[:, 0])      # [L, max_len(, h, d)]
    qb = q.reshape(q.shape[0], row.shape[0], bs, *q.shape[2:])
    sb = s.reshape(s.shape[0], row.shape[0], bs)
    return pool.at[:, row].set(qb), scales.at[:, row].set(sb)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scrub_row_q(pool, scales, row):
    zeros = jnp.zeros((pool.shape[0], row.shape[0], *pool.shape[2:]),
                      pool.dtype)
    ones = jnp.ones((scales.shape[0], row.shape[0], scales.shape[2]),
                    scales.dtype)
    return pool.at[:, row].set(zeros), scales.at[:, row].set(ones)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _gather_row_q(pool, scales, row, *, dtype):
    g = dequantize_kv(pool[:, row], scales[:, row], dtype)  # [L,MB,bs,h,d]
    return g.reshape(g.shape[0], 1, row.shape[0] * pool.shape[2],
                     *g.shape[3:])


@jax.jit
def _write_pos_q(pool, scales, dest, written):
    q, s = quantize_kv_position(written)         # [L, h, d] -> [L]
    flat = pool.reshape(pool.shape[0], -1, *pool.shape[3:])
    sflat = scales.reshape(scales.shape[0], -1)
    return (flat.at[:, dest].set(q).reshape(pool.shape),
            sflat.at[:, dest].set(s).reshape(scales.shape))


class BlockAllocator:
    """Deterministic fixed-size block allocator (ids ``1..capacity``).

    Lowest-id-first allocation order, double-free detection, and typed
    backpressure: ``try_alloc`` returns ``None`` on real exhaustion (the
    caller preempts or waits — it never crashes), and raises
    :class:`~repro.testing.faults.InjectedFault` only when the ``kv_alloc``
    fault site is armed for the hit.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"need at least one KV block, got {capacity}")
        self.capacity = int(capacity)
        self._free: List[int] = list(range(1, capacity + 1))  # sorted asc
        self._used: set = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._used)

    def try_alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks (lowest ids first) or return ``None`` if the
        pool cannot satisfy the request — exhaustion is backpressure, not an
        exception. Fault site ``kv_alloc`` fires here when armed."""
        faults.maybe_fail("kv_alloc")
        if n < 0:
            raise ValueError(f"negative allocation {n}")
        if n > len(self._free):
            return None
        blocks, self._free = self._free[:n], self._free[n:]
        self._used.update(blocks)
        return blocks

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if b not in self._used:
                raise ValueError(f"double free / foreign block {b}")
            self._used.discard(b)
        self._free = sorted(self._free + list(blocks))


class PagedKVCache:
    """The block-pooled KV store behind the continuous scheduler's slots.

    Device state is two pooled leaves per layer stack —
    ``pool[name]: [L, num_blocks + 1, block_size, Hkv, D]`` for ``name`` in
    ``("k", "v")`` — plus a HOST block table ``tables: [max_live,
    blocks_per_slot] int32`` mapping each slot's position range onto pool
    blocks (0 = null block). The batched decode step's attention reads each
    row's live blocks of a layer straight from the pool
    (``models.attention.PagedKV``, ``kernels.paged_attention``) and the
    step scatters back only the one position each row wrote, ``[L, B, Hkv,
    D]``; no dense ``[B, max_len, Hkv, D]`` view is built (an int8 pool is
    read as one, dequantized, a layer at a time).

    ``quantize="int8"`` stores the pool as int8 values + per-position f32
    scale leaves (see the module docstring's quantized-pool contract);
    reads dequantize into ``cache_dtype``, writes quantize exactly once.
    """

    def __init__(self, model_cfg, *, max_live: int, max_len: int,
                 block_size: int, num_blocks: int, cache_dtype="float32",
                 quantize: Optional[str] = None):
        if model_cfg.is_encoder_decoder or model_cfg.family == "vlm" \
                or model_cfg.num_attention_layers == 0 \
                or model_cfg.attention_type == "sliding_window" \
                or (model_cfg.has_ssm and not model_cfg.layer_typed):
            raise ValueError(
                f"{model_cfg.name!r} is not pageable (family "
                f"{model_cfg.family!r}, attention "
                f"{model_cfg.attention_type!r}, encoder-decoder "
                f"{model_cfg.is_encoder_decoder}, state-space layers "
                f"{'in every layer' if model_cfg.has_ssm else 'none'}): "
                "paged KV holds decoder-only full-attention token LMs and "
                "layer-typed hybrids (mamba and attention layers)")
        if max_len % block_size != 0:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"block_size={block_size} (a slot's blocks must "
                             "equal the dense batch-1 cache exactly)")
        if quantize not in (None, "int8"):
            raise ValueError(
                f"unsupported KV quantize={quantize!r} (only 'int8')")
        self.max_live = int(max_live)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        self.blocks_per_slot = max_len // block_size
        self.alloc = BlockAllocator(num_blocks)
        self.quantize = quantize
        self.compute_dtype = jnp.dtype(cache_dtype)
        dtype = jnp.dtype(jnp.int8) if quantize else self.compute_dtype
        L = model_cfg.num_attention_layers
        pool_shape = (L, num_blocks + 1, block_size,
                      model_cfg.num_kv_heads, model_cfg.head_dim)
        self.pool: Dict[str, jnp.ndarray] = {
            name: jnp.zeros(pool_shape, dtype) for name in _KV_LEAVES}
        # Per-position dequant scales (quantized pools only): 1.0 everywhere
        # at rest — the null block's zeros dequantize to exactly zero.
        self.scales: Optional[Dict[str, jnp.ndarray]] = None
        if quantize:
            self.scales = {name: jnp.ones(pool_shape[:3], jnp.float32)
                           for name in _KV_LEAVES}
        # The recurrent store (layer-typed hybrids; None otherwise): each
        # row's state, as the model's decode caches hold it.
        self.ssm: Optional[Dict[str, jnp.ndarray]] = None
        if model_cfg.layer_typed:
            one = jax.eval_shape(
                lambda: init_ssm_cache(model_cfg, max_live, jnp.float32))
            self.ssm = {name: jnp.zeros((model_cfg.num_ssm_layers,
                                         *leaf.shape), leaf.dtype)
                        for name, leaf in one.items()}
        # Host-side: per-slot block lists (allocation order == position
        # order) and the dense table the jit'd step consumes.
        self._slot_blocks: List[List[int]] = [[] for _ in range(max_live)]
        self.tables = np.zeros((max_live, self.blocks_per_slot), np.int32)
        self._tables_dev = None  # device mirror, invalidated on table edits

    # ----- accounting -----------------------------------------------------

    def blocks_for(self, length: int) -> int:
        """Blocks needed to back positions ``0 .. length - 1``."""
        return max(0, -(-length // self.block_size))

    def slot_block_count(self, slot: int) -> int:
        return len(self._slot_blocks[slot])

    def accounting_consistent(self) -> bool:
        """Every table entry's block is either null or owned by exactly one
        slot, and used/free counts close against capacity."""
        owned = [b for blocks in self._slot_blocks for b in blocks]
        return (len(owned) == len(set(owned))
                and set(owned) == self.alloc._used
                and self.alloc.free_count + self.alloc.used_count
                == self.alloc.capacity)

    def _kv_bytes(self) -> int:
        total = sum(leaf.size * leaf.dtype.itemsize
                    for leaf in self.pool.values())
        if self.scales is not None:
            total += sum(s.size * s.dtype.itemsize
                         for s in self.scales.values())
        return total

    def state_bytes(self) -> int:
        """Device bytes of the recurrent store (0 without one)."""
        if self.ssm is None:
            return 0
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in self.ssm.values())

    def pool_bytes(self) -> int:
        """Device bytes resident in the KV pool: value leaves plus, for a
        quantized pool, the per-position scale leaves, plus the recurrent
        store (the honest total a memory budget must cover)."""
        return self._kv_bytes() + self.state_bytes()

    def bytes_per_block(self) -> int:
        """KV bytes per (layer-stacked) block — the per-token KV cost is
        this divided by ``block_size``."""
        return self._kv_bytes() // (self.alloc.capacity + 1)

    # ----- allocation / release -------------------------------------------

    def grow(self, slot: int, length: int) -> bool:
        """Ensure ``slot`` has blocks backing positions ``0 .. length - 1``.
        True on success; False on real pool exhaustion (typed backpressure —
        caller preempts or waits). Raises ``InjectedFault`` only when the
        ``kv_alloc`` site is armed."""
        have = len(self._slot_blocks[slot])
        need = self.blocks_for(length) - have
        if need <= 0:
            return True
        got = self.alloc.try_alloc(need)
        if got is None:
            return False
        for i, b in enumerate(got):
            self.tables[slot, have + i] = b
        self._slot_blocks[slot].extend(got)
        self._tables_dev = None
        return True

    def release(self, slot: int) -> None:
        """Return the slot's blocks to the pool, scrubbing them to zero first
        (a NaN left in a recycled block would leak through the masked value
        contraction: 0 · NaN = NaN), and reset its table row to null."""
        blocks = self._slot_blocks[slot]
        if blocks:
            # Scrub the FULL fixed-shape table row (null entries re-zero the
            # already-zero null block): one compiled shape regardless of how
            # many blocks the slot held. Quantized pools reset the scale
            # entries to 1.0 alongside (scrubbed zeros dequantize to zero).
            row = jnp.asarray(self.tables[slot])
            for name in _KV_LEAVES:
                if self.quantize:
                    self.pool[name], self.scales[name] = _scrub_row_q(
                        self.pool[name], self.scales[name], row)
                else:
                    self.pool[name] = _scrub_row(self.pool[name], row)
            self.alloc.free(blocks)
        self._slot_blocks[slot] = []
        self.tables[slot, :] = 0
        self._tables_dev = None

    # ----- data movement --------------------------------------------------

    def insert_dense(self, slot: int, caches) -> None:
        """Scatter a batch-1 dense cache (``caches["kv"]`` leaves
        ``[L, 1, max_len, Hkv, D]`` from ``Engine.prefill_request`` /
        ``decode_request``) into the slot's blocks. Table entries still null
        receive the dense cache's zero padding, so the null block stays
        zero — one compiled scatter regardless of how many blocks are live.
        A quantized pool quantizes each position here, exactly once (zero
        padding rounds to zero values with scale 1.0). A recurrent store
        takes the row's state from ``caches["ssm"]``."""
        self._put_state(slot, caches)
        row = jnp.asarray(self.tables[slot])
        for name in _KV_LEAVES:
            leaf = caches["kv"][name]
            if self.quantize:
                self.pool[name], self.scales[name] = _scatter_blocks_q(
                    self.pool[name], self.scales[name], row, leaf)
                continue
            blocks = leaf.reshape(leaf.shape[0], self.blocks_per_slot,
                                  self.block_size, *leaf.shape[3:])
            self.pool[name] = _scatter_blocks(self.pool[name], row, blocks)

    def write_position(self, slot: int, pos: int, caches) -> None:
        """Commit ONE written position from a batch-1 decode's new caches
        into the slot's block (the bisection path's per-row commit), and
        its new recurrent state into the slot's row."""
        block = self.tables[slot, pos // self.block_size]
        if block == 0:
            raise ValueError(f"slot {slot} position {pos} not backed by an "
                             "allocated block")
        self._put_state(slot, caches)
        dest = int(block) * self.block_size + pos % self.block_size
        for name in _KV_LEAVES:
            written = caches["kv"][name][:, 0, pos]     # [L, Hkv, D]
            if self.quantize:
                self.pool[name], self.scales[name] = _write_pos_q(
                    self.pool[name], self.scales[name], jnp.int32(dest),
                    written)
            else:
                self.pool[name] = _write_pos(self.pool[name], jnp.int32(dest),
                                             written)

    def _put_state(self, slot: int, caches) -> None:
        if self.ssm is not None:
            for name, leaf in caches["ssm"].items():
                self.ssm[name] = _put_state_row(self.ssm[name],
                                                jnp.int32(slot), leaf)

    def gather_slot(self, slot: int) -> dict:
        """The slot's dense batch-1 cache view ``{"kv": {"k", "v"}}`` (with
        ``"ssm"``, the row's recurrent state, where there is a store) —
        bitwise the cache the batch-1 programs would hold (bisection re-runs
        and tests read through this). Quantized pools dequantize into the
        compute dtype — elementwise ``q * scale``, so the view is bitwise
        what the batched step's per-layer read gives that row."""
        row = jnp.asarray(self.tables[slot])
        if self.quantize:
            dt = self.compute_dtype.name
            out = {"kv": {name: _gather_row_q(self.pool[name],
                                              self.scales[name], row,
                                              dtype=dt)
                          for name in _KV_LEAVES}}
        else:
            out = {"kv": {name: _gather_row(self.pool[name], row)
                          for name in _KV_LEAVES}}
        if self.ssm is not None:
            out["ssm"] = {name: _state_row(leaf, jnp.int32(slot))
                          for name, leaf in self.ssm.items()}
        return out

    def device_tables(self) -> jnp.ndarray:
        """The block table as a device operand for the jit'd batched step
        (cached on device; table edits invalidate the mirror, so steady-state
        ticks skip the host->device transfer)."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables)
        return self._tables_dev
