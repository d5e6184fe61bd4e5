"""Kernel layer (``kernels/gemm_packed.py`` et al.): the weight GEMMs of
the prefill programs against their roofline.

Each traced prefill's least weight-GEMM time (the architecture's
``weight_gemms`` at the prompt's rows, the LM head's at one row;
``chipbench.flops``) over the device time in which its program's weight
GEMMs ran, their weights' staging included (as ``gemm_roofline.decode``
counts it). Moves ``ttft_p95_ms``.
"""
from chipbench import flops

UNIT, LAYER, MOVES = "%", "kernels", "ttft_p95_ms"


def read(ctx):
    pre = ctx.prefill_modules()
    gemm_s = ctx.gemm_time_s([m for m, _ in pre])
    if not pre or gemm_s <= 0:
        return None
    p = ctx.peaks
    ideal = sum(flops.step_gemm_ideal_s(
        ctx.model.weight_gemms(ctx.arch, n, head_rows=1), p["bf16_flops"],
        p["hbm_bytes_per_s"]) for _, n in pre)
    return 100.0 * ideal / gemm_s
