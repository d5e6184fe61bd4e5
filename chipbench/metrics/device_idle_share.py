"""Device layer: share of the traced window in which no operation runs on
the chip (1 minus the union of device-op intervals). Moves ``tpot_ms``."""
from chipbench import trace as T

UNIT, LAYER, MOVES = "%", "device", "tpot_ms"


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - T.busy_s(ctx.trace) / ctx.trace.window_s)
