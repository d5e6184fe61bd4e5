"""TileFormat — the packed-tile format as a first-class compile-time object.

The paper's layered design hinges on a clean interface between the packing
layer and the micro kernel: the *format* of the packed buffer (block shape,
grid-major order, intra-tile element layout, element dtype) is what lets a new
data layout retarget the whole stack at once. Related compiler-composed-
nanokernel work (Library Liberation) and Exo's micro-kernel generation make
the same argument: format metadata should be a single compile-time object, not
a convention duplicated per kernel.

:class:`TileFormat` is that object for the B operand's tile-major stack
(``[Nb, Kb, t0, t1]``, grown to ``[E, Nb, Kb, t0, t1]`` for grouped expert
stacks). It is consumed by

  * the pack layer (``kernels/pack.py`` and the jnp packers in
    ``kernels/ref.py``) — geometry, zero-fill envelope, and (for quantized
    formats) the per-tile scale emission;
  * the kernel BlockSpec/index-map builders (``kernels/common.py``) — tile
    block shapes and the contraction-dim position;
  * the planner (``core/planner.py``) — per-tile and per-buffer byte
    accounting (``GemmPlan.b_format`` derives the format from a plan);
  * both weight pytrees (``core/layered.py``) — packing, the scale leaf, and
    the jnp fallbacks.

A :class:`ScaleSpec` on the format marks it QUANTIZED: tile elements are a
narrow integer dtype and a dense scale tensor rides alongside the packed
stack. Two granularities are defined:

  * ``granularity="tile"`` (default): one scale per (Kb, Nb) tile — a
    ``[Nb, Kb]`` (grouped: ``[E, Nb, Kb]``) grid. ``scale[j, kk]``
    dequantizes tile (j, kk) as ``tile * scale``; the kernels consume it
    through a BlockSpec mirroring B's index map and apply it to each
    K-step's partial product on the VMEM f32 accumulator — before the store
    epilogue (bias/activation/silu-gate), so every fused epilogue works on
    quantized stacks unchanged.
  * ``granularity="col"``: one scale per Nb column block — a ``[Nb]``
    (grouped: ``[E, Nb]``) vector shared by every Kb tile of that column.
    Because the scale is K-invariant, dequantization hoists OUT of the
    K loop entirely: the kernel accumulates raw integer products and
    multiplies the finished accumulator by the column scale ONCE in the
    store epilogue, ahead of bias/activation/gate in the ``EpilogueSpec``
    chain (a true store-only dequant step; cheaper per K-step, coarser
    error envelope than per-tile scales).

SUB-BYTE formats: ``dtype="int4"`` stores TWO values per byte — nibble-packed
along the trailing (minor) tile axis of length ``t``, element ``i`` in the
LOW nibble and element ``i + t/2`` in the HIGH nibble of stored byte ``i``
(see :func:`pack_nibbles`). The physical buffer dtype is int8 with the
trailing tile dim halved (``storage_tile_shape``); kernels widen the VMEM
tile back to i8 via shift/mask (:func:`unpack_nibbles`) inside the tile
load — the two halves are lane-contiguous, so the widen is a concatenation
the TPU compiler lowers, not a lane interleave — and HBM→VMEM B traffic is
0.25x bf16. Quantized int4 values live in [-7, 7]
(``scale = absmax/7``).

Both descriptors are frozen/hashable — safe as pytree-static aux data, jit
cache keys, and plan fields.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pack_nibbles(q: jnp.ndarray) -> jnp.ndarray:
    """Nibble-pack an int stack along its trailing axis (two values/byte).

    Of a trailing axis of length ``t``, element ``i`` lands in the LOW
    nibble and element ``i + t/2`` in the HIGH nibble of output byte ``i`` —
    THE sub-byte storage convention of ``dtype="int4"`` formats. Values must
    fit in [-8, 7]; the trailing dim must be even (the pack layer's
    zero-fill envelope guarantees this for ragged K/N edges).
    """
    if q.shape[-1] % 2:
        raise ValueError(f"nibble pack needs an even trailing dim, "
                         f"got {q.shape}")
    q = q.astype(jnp.int8)
    half = q.shape[-1] // 2
    lo, hi = q[..., :half], q[..., half:]
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.int8)


def unpack_nibbles(p: jnp.ndarray) -> jnp.ndarray:
    """Invert :func:`pack_nibbles`: int8 nibble-pairs -> sign-extended i8.

    Pure shift/mask arithmetic on 32-bit lanes (``(x << 28) >> 28``
    sign-extends the low nibble, ``(x << 24) >> 28`` the high one — the TPU
    vector unit has no 8-bit shifts), so it runs unchanged on a VMEM tile
    inside a kernel body — the in-register widen of the sub-byte tile load.
    Output trailing dim is 2x the input's: the low nibbles, then the high.
    """
    p = p.astype(jnp.int32)
    lo = jnp.left_shift(p, 28) >> 28
    hi = jnp.left_shift(p, 24) >> 28
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


@dataclasses.dataclass(frozen=True)
class ScaleSpec:
    """Dequantization-scale spec for a quantized tile format.

    ``granularity="tile"``: one scale per (Kb, Nb) tile, applied per K-step.
    ``granularity="col"``: one scale per Nb column block, hoisted out of the
    K loop into the store epilogue (see module docstring).
    """

    dtype: str = "float32"
    granularity: str = "tile"

    def __post_init__(self):
        if self.granularity not in ("tile", "col"):
            raise ValueError(
                f"unsupported scale granularity {self.granularity!r} "
                "(defined: per-(Kb,Nb)-'tile', per-Nb-'col')")

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class TileFormat:
    """Descriptor of one tile-major packed-B buffer ``[*, Nb, Kb, t0, t1]``.

    ``bk``/``bn`` are the block (tile) sizes along the contraction and output
    dims; ``layout`` picks the intra-tile element order (``"row"``: tiles are
    ``[bk, bn]``; ``"col"``: ``[bn, bk]`` — the matrix engine's preferred B
    layouts, paper §3.1). ``dtype`` is the tile *element* dtype; a
    :class:`ScaleSpec` marks the format quantized (see module docstring).
    """

    bk: int
    bn: int
    layout: str = "row"
    dtype: str = "float32"
    scale: Optional[ScaleSpec] = None

    def __post_init__(self):
        if self.layout not in ("row", "col"):
            raise ValueError(f"bad layout {self.layout!r}")
        if self.scale is not None and not jnp.issubdtype(
                jnp.dtype(self.dtype), jnp.integer):
            raise ValueError(
                f"per-tile scales go with integer tile elements; got "
                f"dtype={self.dtype!r}")
        if self.sub_byte and self.tile_shape[-1] % 2:
            raise ValueError(
                f"int4 tiles nibble-pack pairs along the trailing tile dim, "
                f"which must be even; got tile {self.tile_shape}")

    # -- geometry -----------------------------------------------------------

    @property
    def tile_shape(self) -> Tuple[int, int]:
        """Shape of one stored tile: [bk, bn] ("row") / [bn, bk] ("col")."""
        return (self.bn, self.bk) if self.layout == "col" else (self.bk,
                                                                self.bn)

    @property
    def rhs_contract(self) -> int:
        """Contraction dim of one stored tile (for dot_general)."""
        return 0 if self.layout == "row" else 1

    @property
    def sub_byte(self) -> bool:
        """True when tiles store two elements per byte (nibble-packed)."""
        return self.dtype == "int4"

    @property
    def storage_dtype(self) -> str:
        """Physical buffer dtype: int8 carries int4 nibble pairs."""
        return "int8" if self.sub_byte else self.dtype

    @property
    def storage_tile_shape(self) -> Tuple[int, int]:
        """Shape of one stored tile AS BUFFERED: trailing dim halves for
        nibble-packed formats (two logical elements per stored byte)."""
        t0, t1 = self.tile_shape
        return (t0, t1 // 2) if self.sub_byte else (t0, t1)

    def grid(self, k: int, n: int) -> Tuple[int, int]:
        """(Nb, Kb) tile grid covering a [K, N] operand (zero-fill envelope)."""
        return cdiv(n, self.bn), cdiv(k, self.bk)

    def packed_shape(self, k: int, n: int) -> Tuple[int, int, int, int]:
        """Physical buffer shape (storage tiles; halved minor dim for int4)."""
        return self.grid(k, n) + self.storage_tile_shape

    def scale_shape(self, k: int, n: int) -> Tuple[int, ...]:
        """Scale tensor shape: [Nb, Kb] per-tile, [Nb] per-column."""
        nb, kb = self.grid(k, n)
        if self.scale is not None and self.scale.granularity == "col":
            return (nb,)
        return (nb, kb)

    # -- byte accounting (planner) -----------------------------------------

    @property
    def itemsize(self) -> float:
        """Bytes per LOGICAL element (0.5 for nibble-packed int4)."""
        return 0.5 if self.sub_byte else jnp.dtype(self.dtype).itemsize

    @property
    def is_quantized(self) -> bool:
        return self.scale is not None

    def tile_bytes(self) -> int:
        """HBM bytes of one resident tile (elements + its per-tile scale)."""
        b = self.bk * self.bn * self.itemsize
        if self.scale is not None and self.scale.granularity == "tile":
            b += self.scale.itemsize
        return math.ceil(b)

    def packed_bytes(self, k: int, n: int) -> int:
        """Total bytes of the packed stack (+scales) for a [K, N] operand."""
        nb, kb = self.grid(k, n)
        total = nb * kb * self.tile_bytes()
        if self.scale is not None and self.scale.granularity == "col":
            total += nb * self.scale.itemsize
        return total

    # -- construction helpers ----------------------------------------------

    @classmethod
    def from_packed(cls, packed, layout: str = "row",
                    has_scales: bool = False) -> "TileFormat":
        """Recover the format of an existing packed buffer (trailing two dims
        are the tile; any number of leading grid/stack dims).

        CANNOT detect sub-byte formats: an int4 buffer is physically int8
        with a halved trailing dim, indistinguishable from a narrow int8
        format. Callers holding an int4 (or col-scaled) stack must pass the
        authoritative format explicitly (the kernels' ``b_format=`` kwarg);
        this inference is the legacy fallback for self-describing buffers.
        """
        t0, t1 = packed.shape[-2:]
        bk, bn = (t1, t0) if layout == "col" else (t0, t1)
        return cls(bk=bk, bn=bn, layout=layout,
                   dtype=jnp.dtype(packed.dtype).name,
                   scale=ScaleSpec() if has_scales else None)


def is_dequant_pair(compute_dtype, b_dtype) -> bool:
    """THE quantized-ness rule, in one place: a format is dequant-in-epilogue
    (int tiles + per-tile scales) exactly when B's element dtype is a narrow
    integer under a non-integer compute dtype. Used by ``GemmPlan.b_format``
    and the planner's byte terms, so solver and plan always agree."""
    if b_dtype is None:
        return False
    return (jnp.issubdtype(jnp.dtype(b_dtype), jnp.integer)
            and not jnp.issubdtype(jnp.dtype(compute_dtype), jnp.integer))


def normalize_packed(out, fmt: TileFormat):
    """Normalize a packer's polymorphic return to ``(packed, scales-or-None)``
    — quantized formats already return the pair, float formats a bare array."""
    return out if fmt.is_quantized else (out, None)


def quantize_tiles(t: jnp.ndarray, fmt: TileFormat):
    """Row-layout tile stack [..., Nb, Kb, bk, bn] (float) -> (int tiles,
    scales) — THE quantization contract of a scaled format.

    ``scale = absmax/qmax`` with qmax 127 (int8) / 7 (int4); 1.0 for all-zero
    reduction groups, so zero-fill remainder tiles stay exact. Values
    round-to-nearest-even, clipped to [-qmax, qmax]. The reduction group is
    the scale granularity: one tile (``"tile"`` -> [..., Nb, Kb] scales) or
    one whole tile-column (``"col"`` -> [..., Nb] scales, absmax over every
    Kb tile of column j). Dequantization is ``tile * scale`` — per K-step on
    the f32 accumulator for "tile", once in the store epilogue for "col".

    int4 tiles are returned UNPACKED as int8 values in [-7, 7] (the natural
    layout the pack pipeline scatters); nibble packing is the pack layer's
    final storage step (:func:`pack_nibbles`).
    """
    qmax = 7.0 if fmt.sub_byte else 127.0
    if fmt.scale.granularity == "col":
        absmax = jnp.max(jnp.abs(t), axis=(-3, -2, -1))
        bcast = (..., None, None, None)
    else:
        absmax = jnp.max(jnp.abs(t), axis=(-2, -1))
        bcast = (..., None, None)
    scales = jnp.where(absmax > 0, absmax / qmax, 1.0)
    scales = scales.astype(fmt.scale.dtype)
    q = jnp.round(t / scales[bcast]).clip(-qmax, qmax)
    return q.astype(jnp.dtype(fmt.storage_dtype)), scales


def as_tile_format(fmt, bn: Optional[int] = None, *, layout: str = "row",
                   dtype=None) -> TileFormat:
    """Normalize the pack layer's legacy ``(bk, bn, layout)`` int arguments to
    a :class:`TileFormat` — the single code path for both calling styles."""
    if isinstance(fmt, TileFormat):
        return fmt
    if bn is None:
        raise TypeError("pack needs a TileFormat or explicit (bk, bn) ints")
    return TileFormat(bk=int(fmt), bn=int(bn), layout=layout,
                      dtype=jnp.dtype(dtype or "float32").name)
