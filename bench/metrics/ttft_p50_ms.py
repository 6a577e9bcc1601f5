"""Median, over every request due inside the window, of the time from when
it was due to when its first token was ready on the device."""
import numpy as np


def read(rec):
    waits = [(r.first - r.due) * 1e3 for r in rec.due_in_window() if r.first is not None]
    return float(np.quantile(waits, 0.50)) if waits else None
