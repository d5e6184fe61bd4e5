"""Model-step layer (``serve/engine.py``, ``models/``): the batched decode
step's share of the chip's bf16 peak.

Model operations of the tokens the traced batched steps produced for live
rows (weights, and attention over each row's real context; the
architecture's ``decode_token_flops``), over the device time of those
steps' programs times the peak. Padding rows do no useful work and count
nothing. Moves ``tpot_ms``.
"""
UNIT, LAYER, MOVES = "%", "model step", "tpot_ms"


def read(ctx):
    steps = ctx.step_modules()
    ops = sum(ctx.model.decode_token_flops(ctx.arch, ctx.prompt_len[r] + s - 1)
              for _, pairs in steps for r, s in pairs if s >= 1)
    device_s = sum(m[1] - m[0] for m, _ in steps) / 1e9
    if ops == 0 or device_s <= 0:
        return None
    return 100.0 * ops / (device_s * ctx.peaks["bf16_flops"])
