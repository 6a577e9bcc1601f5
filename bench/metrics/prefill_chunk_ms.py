"""Model step: mean device time of the ``prefill_chunk`` program per call,
from the trace of the window."""


def read(rec):
    if rec.trace is None:
        return None
    times = rec.trace.program_times("prefill_chunk")
    return sum(times) / len(times) * 1e3 if times else None
