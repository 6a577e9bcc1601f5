"""Output tokens that became ready on the device inside the window, per
second of the window."""


def read(rec):
    n = sum(1 for r in rec.requests for t in r.stamps if rec.in_window(t))
    return n / rec.seconds if n else None
