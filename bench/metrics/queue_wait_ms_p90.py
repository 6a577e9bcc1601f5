"""Scheduler: 90th percentile of the program's own submit → admission wait
(registry ``serve.queue_wait_ms``) over the requests due in the window."""
import numpy as np


def read(rec):
    waits = [rec.queue_wait_ms[r.rid] for r in rec.due_in_window() if r.rid in rec.queue_wait_ms]
    return float(np.quantile(waits, 0.90)) if waits else None
