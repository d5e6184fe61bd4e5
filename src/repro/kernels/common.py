"""Shared Pallas utilities: compiler params, padding, interpret policy, and
the TileFormat-driven BlockSpec builders every packed GEMM kernel uses.

The packed-B geometry (tile block shapes, the scale operand's layout and
in-kernel indexing, the ref-splitting convention for optional operands)
lives HERE, keyed by :class:`repro.core.tile_format.TileFormat` — the dense
and grouped kernels consume these builders instead of re-deriving
``[Nb, Kb, bk, bn]`` layout constants per kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl  # noqa: F401  (re-exported)
from jax.experimental.pallas import tpu as pltpu

from repro.core.tile_format import (TileFormat,  # noqa: F401  (re-exported)
                                    unpack_nibbles)
from repro.roofline.hw import current_target


def default_interpret() -> bool:
    """Pallas TPU kernels run compiled on a TPU and in interpret mode on
    every other backend (CPU CI)."""
    return jax.default_backend() != "tpu"


def tpu_compiler_params(dimension_semantics) -> pltpu.CompilerParams:
    """Mosaic compiler params: the grid's dimension semantics plus the
    scoped-VMEM limit of the chip the kernel is compiled for — the same
    number the planner's VMEM budget is derived from (``roofline.hw``)."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=current_target().vmem_limit_bytes)


def pallas_kwargs(*, interpret: bool, dimension_semantics) -> dict:
    """kwargs for pl.pallas_call: compiler params when compiled, none under
    interpret. Interpret mode on an attached TPU is refused: it would run a
    main-path kernel on the host while the chip idles."""
    if not interpret:
        return {"interpret": False,
                "compiler_params": tpu_compiler_params(dimension_semantics)}
    if jax.default_backend() == "tpu":
        raise RuntimeError("Pallas interpret mode requested on a TPU backend")
    return {"interpret": True}


# In-kernel epilogue table shared by every GEMM kernel: applied to the f32
# accumulator tile in VMEM during the final grid step, before the single HBM
# store. Must stay in sync with repro.core.epilogue.ACTIVATIONS (tested) —
# an EpilogueSpec chain lowers onto this table via its ``kernel_name`` (the
# bias stage lowers to the kernels' bias operand, the dequant stage to the
# scale operand), which is why a new composite epilogue in
# ``repro.core.epilogue.EPILOGUE_SPECS`` reaches every kernel with zero
# per-kernel edits.
KERNEL_EPILOGUES = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0),
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "tanh": jnp.tanh,
}


def kernel_epilogue_name(epilogue) -> str:
    """Normalize an ``EpilogueSpec | str`` to the in-kernel epilogue name
    (kernels speak the lowered string form; specs carry the chain)."""
    name = getattr(epilogue, "kernel_name", epilogue)
    if name not in KERNEL_EPILOGUES and name != "silu_gate":
        raise KeyError(f"unknown kernel epilogue {name!r}")
    return name


class GemmRefs:
    """A GEMM kernel's refs, split once by the shared operand convention.

    Every GEMM kernel (dense, fused-A, grouped, ragged) orders its refs as
    ``<lead operands>, c?, b2?, scale?, scale2?, bias?, out, acc, acc2?`` —
    this is the single splitter replacing the per-kernel index arithmetic.
    The optional-operand flags mirror the EpilogueSpec chain (``has_c`` =
    the dense beta*C term, ``has_bias`` = the bias stage, ``has_gate`` = the
    gate-mul stage, ``has_scale`` = the implied dequant stage of a quantized
    TileFormat).
    """

    def __init__(self, refs, *, n_lead: int, has_c: bool = False,
                 has_gate: bool = False, has_scale: bool = False,
                 has_bias: bool = False):
        it = iter(refs)
        self.lead = tuple(next(it) for _ in range(n_lead))
        self.c = next(it) if has_c else None
        self.b2 = next(it) if has_gate else None
        self.scale = next(it) if has_scale else None
        self.scale2 = next(it) if (has_scale and has_gate) else None
        self.bias = next(it) if has_bias else None
        self.out = next(it)
        self.acc = next(it)
        self.acc2 = next(it) if has_gate else None
        leftover = tuple(it)
        assert not leftover, f"unconsumed kernel refs: {len(leftover)}"


def b_tile_spec(fmt: TileFormat, index_map, *, lead: int = 2):
    """BlockSpec for one packed-B tile of a ``[*lead-grid, t0, t1]`` stack
    (``lead=2`` dense [Nb,Kb,...], ``lead=3`` grouped [E,Nb,Kb,...]).
    Blocks are STORAGE tiles: nibble-packed int4 streams the halved-minor
    int8 buffer (0.25x bf16 HBM->VMEM traffic) and widens in-kernel."""
    return pl.BlockSpec((1,) * lead + fmt.storage_tile_shape, index_map)


def scale_spec():
    """BlockSpec for a scale operand: the whole flattened f32 grid resident
    in SMEM (a few scalars per tile column — Mosaic's (8, 128) block rule
    has no VMEM block of one scalar), read by :func:`tile_scale`."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def scale_operand(scales: jnp.ndarray) -> jnp.ndarray:
    """The scale grid ([Nb, Kb] / [Nb], grouped with a leading E) as the
    flat f32 vector :func:`scale_spec` places in SMEM."""
    return scales.reshape(-1).astype(jnp.float32)


def col_scaled(fmt: TileFormat) -> bool:
    """Whether the format's scales are K-invariant (per Nb column): their
    multiply hoists out of the K loop into the store epilogue."""
    return fmt.scale is not None and fmt.scale.granularity == "col"


def tile_scale(scale_ref, fmt: TileFormat, *, nb: int, kb: int, j, kk, e=0):
    """The dequant scale of B tile ``(e, j, kk)`` from the flat SMEM grid —
    the same tile coordinates B's index map fetched. A col-granularity grid
    has one scale per ``(e, j)`` column."""
    if col_scaled(fmt):
        return scale_ref[e * nb + j]
    return scale_ref[(e * nb + j) * kb + kk]


def contract_tile(a, b_tile, scale, fmt: TileFormat, acc_dtype):
    """One micro-kernel step over a packed-B tile: widen a sub-byte tile to
    i8 via shift/mask on the VMEM block (nibble-packed int4), cast a
    quantized tile up to the activation dtype (int tiles stream narrow from
    HBM; the MXU pass runs in the compute dtype), contract per the format's
    intra-tile layout, and dequantize the partial product with the tile's
    scalar ``scale`` (None: no per-step dequant — an unquantized format, or
    col-granularity scales, which multiply the finished accumulator once in
    :func:`finalize_gemm` or the grouped kernels' inline epilogues)."""
    if fmt.sub_byte:
        b_tile = unpack_nibbles(b_tile)
    if fmt.is_quantized and b_tile.dtype != a.dtype:
        b_tile = b_tile.astype(a.dtype)
    partial = jax.lax.dot_general(
        a, b_tile, (((1,), (fmt.rhs_contract,)), ((), ())),
        preferred_element_type=acc_dtype)
    if scale is None:
        return partial
    return partial * scale.astype(partial.dtype)


def bias_spec_and_operand(bias, n, bn):
    """BlockSpec + padded [1, N] operand for a fused bias vector (3-D grid)."""
    assert bias.shape == (n,), (bias.shape, n)
    spec = pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))
    return spec, pad2d(bias.reshape(1, n), 1, bn)


def c_spec_and_operand(c, m, n, bm, bn):
    """BlockSpec + padded operand for the dense beta*C input (3-D grid)."""
    assert c.shape == (m, n), (c.shape, (m, n))
    return pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)), pad2d(c, bm, bn)


def finalize_gemm(acc_ref, c_ref, bias_ref, o_ref, *, alpha, beta, epilogue,
                  scale=None):
    """Shared fused store epilogue for every GEMM kernel: (col-scale
    dequant,) alpha/beta, then bias, then activation — the EpilogueSpec
    chain order, applied to the VMEM-resident f32 accumulator, then the
    single cast-and-store to HBM. ``scale`` is the hoisted col-granularity
    dequant scale (one scalar per Nb column), the store-only dequant step
    that runs ahead of bias/activation for K-invariant scales. ``c_ref`` is
    None when the call has no C operand (beta*C vanishes). ``epilogue`` is
    an in-kernel name or an EpilogueSpec (normalized)."""
    out = acc_ref[...]
    if scale is not None:
        out = out * scale.astype(out.dtype)
    out = alpha * out
    if c_ref is not None and beta != 0:
        out = out + beta * c_ref[...].astype(acc_ref.dtype)
    if bias_ref is not None:
        out = out + bias_ref[...].astype(acc_ref.dtype)  # [1,bn] broadcast
    out = KERNEL_EPILOGUES[kernel_epilogue_name(epilogue)](out)
    o_ref[...] = out.astype(o_ref.dtype)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad2d(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    """Zero-pad a 2-D array to multiples of (m0, m1) — paper's remainder fill."""
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def acc_dtype_for(dtype) -> jnp.dtype:
    """Accumulator dtype (paper Table 1: i32 for integer inputs, f32 else)."""
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.int32
    return jnp.float32
