"""TPU hardware model, keyed by the device kind JAX reports.

Sources: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s, 128 MiB of VMEM per TensorCore), plus the
assignment's ~50 GB/s/link ICI constant. Everything here is a parameter —
the planner reads these the way the paper's macro algorithm reads LLVM's
cache-size tables.

VMEM comes from ONE number per chip, ``vmem_limit_bytes``: the scoped-VMEM
limit every ``pallas_call`` declares to the Mosaic compiler (whose own
default on v5e is 16 MiB). The planner's working-set budget
(:attr:`TpuTarget.vmem_bytes`) is derived from it, leaving a margin for
what the plan's byte model does not count (bias blocks, Mosaic's internal
scratch), so a plan within budget compiles within the declared limit.
"""
from __future__ import annotations

import dataclasses

MiB = 1024 ** 2


@dataclasses.dataclass(frozen=True)
class TpuTarget:
    name: str = "tpu-v5e"

    # Compute.
    peak_bf16_flops: float = 197e12      # per chip, bf16 on the MXU
    peak_f32_flops: float = 197e12 / 4   # f32 passes cost ~4x on the MXU
    peak_int8_ops: float = 393e12        # published int8 peak
    peak_vpu_flops: float = 197e12 / 32  # VPU-only (the "VSX lowering" ceiling)

    # Memory.
    hbm_bytes: int = 16 * 1024**3        # 16 GiB
    hbm_bw: float = 819e9                # bytes/s
    vmem_capacity: int = 128 * MiB       # physical VMEM per TensorCore
    vmem_limit_bytes: int = 64 * MiB     # scoped limit every kernel declares
    vmem_bw: float = 11.4e12             # ~VREG-side bandwidth (approx)

    # Interconnect.
    ici_link_bw: float = 50e9            # bytes/s per link (assignment constant)
    ici_links_per_chip: int = 4          # 2D torus on v5e

    # MXU geometry.
    mxu_dim: int = 128                   # 128x128 systolic array
    lane: int = 128                      # vector lane count (last-dim tile)
    sublane_bytes: int = 32              # second-minor tile = 32 bytes / lane

    @property
    def vmem_bytes(self) -> int:
        """The planner's VMEM working-set budget: three quarters of the
        declared limit (the rest is the margin for unmodelled buffers)."""
        return self.vmem_limit_bytes * 3 // 4

    def sublane(self, itemsize: float) -> int:
        """Second-minor tiling multiple for a dtype (8 f32 / 16 bf16 / 32 i8 /
        64 nibble-packed i4; ``itemsize`` may be a fraction of a byte)."""
        return max(int(self.sublane_bytes / itemsize), 1)


V5E = TpuTarget()

# device_kind (as ``jax.devices()[0].device_kind`` reports it) -> target.
TARGETS = {
    "TPU v5 lite": V5E,
}


def target_for(device_kind: str) -> TpuTarget:
    """The hardware model of one TPU kind; an unknown kind is an error (its
    VMEM and peaks are not v5e's, and guessing them miscompiles kernels)."""
    try:
        return TARGETS[device_kind]
    except KeyError:
        raise KeyError(f"no hardware model for TPU kind {device_kind!r}; "
                       f"known: {sorted(TARGETS)}") from None


def current_target() -> TpuTarget:
    """The target kernels are planned and compiled for in this process: the
    attached TPU's, by device kind. Off-TPU (CPU tests, interpret mode,
    ahead-of-time compiles for a described chip) it is the v5e design
    target."""
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        return V5E
    return target_for(device.device_kind)


def peak_flops(dtype: str, target: TpuTarget = V5E) -> float:
    peaks = {
        "bfloat16": target.peak_bf16_flops,
        "float16": target.peak_bf16_flops,
        "float32": target.peak_f32_flops,
        "int8": target.peak_int8_ops,
    }
    if str(dtype) not in peaks:
        raise KeyError(f"no peak for dtype {dtype!r}; known: {sorted(peaks)}")
    return peaks[str(dtype)]
