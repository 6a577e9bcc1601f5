"""90th percentile of every gap between successive output tokens of a
request, pooled over all requests, for gaps that end inside the window."""
import numpy as np


def read(rec):
    gaps = [
        (b - a) * 1e3
        for r in rec.requests
        for a, b in zip(r.stamps, r.stamps[1:])
        if rec.in_window(b)
    ]
    return float(np.quantile(gaps, 0.90)) if gaps else None
