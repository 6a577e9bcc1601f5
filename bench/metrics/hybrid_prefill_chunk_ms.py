"""Model step of a hybrid cell: mean device time of the ``prefill_chunk``
program per call, from the trace of the window.  ``prefill_chunk_ms`` reads
the same for cells that report ``ttft_p50_ms``; the hybrid cell reports
``output_tok_s``, which its prefill chunks, most of its device time, move."""


def read(rec):
    if rec.trace is None or "m_heads" not in rec.sizes:
        return None
    times = rec.trace.program_times("prefill_chunk")
    return sum(times) / len(times) * 1e3 if times else None
