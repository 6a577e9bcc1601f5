"""Whole step of a hybrid Mamba-2 / attention stack with held experts: FLOPs
the model needs (``harness/work_hybrid.py``) for every token processed in
the traced window — output tokens, and prompt tokens of the prefill chunks
run before those steps — over the window's length times the chip's peak
FLOP/s, as ``mfu_pct`` reads a dense stack."""
from harness import work_hybrid


def read(rec):
    if rec.trace is None or rec.peak is None or "m_heads" not in rec.sizes:
        return None
    steps = rec.steps_in(rec.trace.start, rec.trace.end)
    if not steps:
        return None
    idx = {s.index for s in steps}
    flops = sum(work_hybrid.decode_step(rec.sizes, s.ctxs)[0] for s in steps)
    flops += sum(work_hybrid.prefill_chunk(rec.sizes, c.t0, c.live, c.final)
                 for c in rec.chunks if c.step in idx)
    return 100.0 * flops / (rec.trace.window_s * rec.peak["bf16_flops_per_s"])
