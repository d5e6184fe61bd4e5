"""Model-step layer (``serve/engine.py``, ``models/``): the prefill
programs' share of the chip's bf16 peak.

Model operations of the traced prompts (the architecture's
``prefill_flops``: causal attention, logits of the last position) over the
device time of their prefill programs times the peak. Moves
``ttft_p95_ms``.
"""
UNIT, LAYER, MOVES = "%", "model step", "ttft_p95_ms"


def read(ctx):
    pre = ctx.prefill_modules()
    ops = sum(ctx.model.prefill_flops(ctx.arch, n) for _, n in pre)
    device_s = sum(m[1] - m[0] for m, _ in pre) / 1e9
    if ops == 0 or device_s <= 0:
        return None
    return 100.0 * ops / (device_s * ctx.peaks["bf16_flops"])
