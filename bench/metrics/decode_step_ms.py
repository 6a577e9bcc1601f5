"""Model step: mean device time of the ``decode_step`` program per call,
from the trace of the window (a mean, so a stall counts)."""


def read(rec):
    if rec.trace is None:
        return None
    times = rec.trace.program_times("decode_step")
    return sum(times) / len(times) * 1e3 if times else None
