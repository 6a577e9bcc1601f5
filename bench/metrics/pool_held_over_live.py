"""Pool: the pool's peak capacity over the peak of live tokens inside the
window (registry gauges ``pool.capacity_tokens`` and ``pool.live_tokens``,
read after every step)."""


def read(rec):
    inside = [(cap, live) for t, cap, live in rec.gauges if rec.in_window(t)]
    peak_live = max((live for _, live in inside), default=0)
    if not peak_live:
        return None
    return max(cap for cap, _ in inside) / peak_live
