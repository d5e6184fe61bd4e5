"""Scheduler layer (``serve/scheduler.py``): host time per tick.

Mean over the traced scheduler ticks (``sched.step`` spans) of the tick's
wall time during which no operation ran on the device. Moves ``tpot_ms``.
"""
from chipbench import trace as T

UNIT, LAYER, MOVES = "ms", "scheduler", "tpot_ms"


def read(ctx):
    ticks = ctx.traced_ticks()
    if not ticks:
        return None
    busy = T.Coverage([(o[0], o[1]) for o in ctx.trace.ops])
    idle = [(e - s) - busy.covered(s, e) for s, e, _ in ticks]
    return sum(idle) / len(idle) / 1e6
