"""SSM state layer (``models/ssm.py`` decode, ``models/transformer.py``'s
layer-typed stack, ``serve/kv_cache.py``'s recurrent store): device time of
the batched decode step's recurrent update.

Mean over the traced batched steps of the device time of the ops the
program scopes ``ssm_state`` (each mamba layer's state read, decayed and
updated) or ``ssm_conv`` (its conv window), and of the ops of no named
scope that produce a whole leaf of the recurrent store (the architecture's
``ssm_store_elements``): the layer scan writes each layer's new state into
its stacked output in one fusion with the update, and that fusion carries
the scan's ``op_name``, as ``program.step_scope_ms`` counts the KV view's.
The union of their intervals. An op with no scope of its own takes that of
the op holding it (a loop's body takes the loop's); ops that hold others
are left out, so no time counts twice. The scopes come from the traced
run's ``.xplane.pb`` (``program.load``); a program with no recurrent state
names neither, and has nothing to read. Moves ``tpot_ms``.
"""
import bisect
import math

from chipbench import program as P, trace as T

UNIT, LAYER, MOVES = "ms", "ssm state", "tpot_ms"
SCOPES = frozenset(("ssm_state", "ssm_conv"))


def step_ms(ctx, scopes):
    """Device ms per traced batched step under ``SCOPES``, or None where no
    op of the steps has one. ``scopes``: aligned with ``ctx.trace.ops``."""
    steps = [m for m, _ in ctx.step_modules()]
    if not steps or len(scopes) != len(ctx.trace.ops):
        return None
    starts = [o[0] for o in ctx.trace.ops]
    store = set(ctx.model.ssm_store_elements(ctx.arch, ctx.serving))
    ivs = []
    for m in steps:
        lo = bisect.bisect_left(starts, m[0])
        hi = bisect.bisect_left(starts, m[1])
        ops, own = ctx.trace.ops[lo:hi], scopes[lo:hi]
        up = P.holders(ops)
        held = {h for h in up if h is not None}
        for i, o in enumerate(ops):
            if i in held:
                continue
            j, scope = i, own[i]
            while not scope and up[j] is not None:
                j = up[j]
                scope = own[j]
            names = set(scope.split("/"))
            if SCOPES & names or (
                    not names & set(P.SCOPES)
                    and math.prod(P.result_shape(o[2])) in store):
                ivs.append(o[:2])
    if not ivs:
        return None
    return sum(e - s for s, e in T.union(ivs)) / 1e6 / len(steps)


def read(ctx, trace_dir=P.TRACE_DIR):
    if not hasattr(ctx.model, "ssm_store_elements"):
        return None
    prog = P.load(ctx, trace_dir)
    return None if prog is None else step_ms(ctx, prog.op_scopes)
