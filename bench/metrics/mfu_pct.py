"""Whole step: FLOPs the model needs for every token processed in the traced
window (output tokens, and prompt tokens of the prefill chunks run before
those steps) over the window's length times the chip's peak FLOP/s."""


def read(rec):
    if rec.trace is None or rec.peak is None:
        return None
    steps = rec.steps_in(rec.trace.start, rec.trace.end)
    if not steps:
        return None
    flops, _, _ = rec.decode_work(steps)
    flops += rec.prefill_flops(steps)
    return 100.0 * flops / (rec.trace.window_s * rec.peak["bf16_flops_per_s"])
