"""Kernels (the whole decode step, whose attention and append are XLA
operations): the least time the chip needs for the decode steps of the
window — each the larger of its needed FLOPs over peak FLOP/s and its needed
bytes (every weight once, each sequence's own live K/V, the new K/V) over
peak bandwidth — as a share of their device time in the trace."""


def read(rec):
    if rec.trace is None or rec.peak is None:
        return None
    device = sum(rec.trace.program_times("decode_step"))
    steps = rec.steps_in(rec.trace.start, rec.trace.end)
    if not device or not steps:
        return None
    _, _, least = rec.decode_work(steps)
    return 100.0 * least / device
