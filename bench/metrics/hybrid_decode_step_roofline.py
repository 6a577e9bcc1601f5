"""Kernels of a hybrid Mamba-2 / attention stack with held experts (the whole
decode step): the least time the chip needs for the decode steps of the
window — each the larger of its needed FLOPs over peak FLOP/s and its needed
bytes (every weight held here once, each active sequence's Mamba state read
and written once, its own live K/V and the new K/V; ``harness/work_hybrid.py``)
over peak bandwidth — as a share of their device time in the trace, as
``decode_step_roofline`` reads a dense stack."""
from harness import work, work_hybrid


def read(rec):
    if rec.trace is None or rec.peak is None or "m_heads" not in rec.sizes:
        return None
    device = sum(rec.trace.program_times("decode_step"))
    steps = rec.steps_in(rec.trace.start, rec.trace.end)
    if not device or not steps:
        return None
    least = sum(work.least_time(*work_hybrid.decode_step(rec.sizes, s.ctxs), rec.peak)
                for s in steps)
    return 100.0 * least / device
