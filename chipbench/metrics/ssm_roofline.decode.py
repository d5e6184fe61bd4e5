"""SSM state layer: the batched decode step's recurrent update against its
roofline.

The update's least time over the device time it took
(``ssm_state_ms.decode``): for each traced batched step, the larger of the
bytes of recurrent state its live rows read and write (the architecture's
``ssm_state_bytes``: each mamba layer's float32 state and conv window, read
once and written once) over the HBM bandwidth and the update's operations
(``ssm_state_flops``) over the bf16 peak, summed over the steps. Padding
rows do no useful work and count nothing. An architecture without a
recurrent state has nothing to read. Moves ``tpot_ms``.
"""
from chipbench import flops, program as P
from chipbench.cell import metric_reader

UNIT, LAYER, MOVES = "%", "ssm state", "tpot_ms"


def read(ctx, trace_dir=P.TRACE_DIR):
    if not hasattr(ctx.model, "ssm_state_bytes"):
        return None
    prog = P.load(ctx, trace_dir)
    if prog is None:
        return None
    ms = metric_reader("ssm_state_ms.decode").step_ms(ctx, prog.op_scopes)
    steps = ctx.step_modules()
    if ms is None or not steps:
        return None
    p = ctx.peaks
    ideal = sum(flops.ideal_s(ctx.model.ssm_state_flops(ctx.arch, len(pairs)),
                              ctx.model.ssm_state_bytes(ctx.arch, len(pairs)),
                              p["bf16_flops"], p["hbm_bytes_per_s"])
                for _, pairs in steps)
    return 100.0 * ideal / (ms / 1e3 * len(steps))
