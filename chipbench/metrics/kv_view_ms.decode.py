"""KV cache layer (``serve/scheduler.py`` batched step, ``serve/kv_cache.py``,
``models/attention.py``): device time of the batched decode step's KV view.

Mean over the traced batched steps of the device time in which the step
gathers the rows' blocks into the dense ``[L, max_live, max_len, Hkv, D]``
view, blends each row's new position into it and scatters it back into the
pool: the ops the program scopes ``kv_gather``, ``kv_write`` and
``kv_scatter``, and the unscoped ops that produce a whole pool or view (the
pool's copies, the scan's stacking of the blended cache;
``program.step_scope_ms``). The scopes come from the traced run's
``.xplane.pb`` (``program.load``); a program that names no KV scope has
nothing to read. Also prints the step's time by scope and the ticks' idle
time by phase. Moves ``tpot_ms``.
"""
from chipbench import program as P

UNIT, LAYER, MOVES = "ms", "kv cache", "tpot_ms"


def read(ctx, trace_dir=P.TRACE_DIR):
    prog = P.load(ctx, trace_dir)
    if prog is None:
        return None
    for line in P.describe(ctx, prog):
        print(line, flush=True)
    ms = P.step_scope_ms(ctx, prog.op_scopes)
    return None if ms is None else ms["kv_view"]
