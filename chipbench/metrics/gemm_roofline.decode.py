"""Kernel layer (``kernels/gemm_packed.py`` et al.): the weight GEMMs of the
batched decode step against their roofline.

The least time the chip could take for one step's weight GEMMs (the
architecture's ``weight_gemms`` at the ``max_live`` rows the step computes:
for each, the larger of operations over the bf16 peak and bytes over the
HBM bandwidth; ``chipbench.flops``), times the traced steps, over the
device time in which those steps' weight GEMMs ran (``Context.gemm_time_s``):
the packed-weight GEMM kernels, told apart from other kernels by their
weight operand's tile grid, and the ops that stage each kernel's weight
tiles for it. Inside the layer loop XLA slices each layer's tiles out of the
stacked weights into the kernel's memory before the kernel runs, so the
kernel alone never reads its weights from HBM and its own time is not the
GEMM's. Moves ``tpot_ms``.
"""
from chipbench import flops

UNIT, LAYER, MOVES = "%", "kernels", "tpot_ms"


def read(ctx):
    steps = [m for m, _ in ctx.step_modules()]
    gemm_s = ctx.gemm_time_s(steps)
    if not steps or gemm_s <= 0:
        return None
    p = ctx.peaks
    ideal = flops.step_gemm_ideal_s(
        ctx.model.weight_gemms(ctx.arch, ctx.serving["max_live"]),
        p["bf16_flops"], p["hbm_bytes_per_s"])
    return 100.0 * ideal * len(steps) / gemm_s
