"""Pool: megabytes the program copied to grow its K/V pool inside the window
(registry ``pool.copied_bytes``)."""


def read(rec):
    before, after = rec.counters["pool.copied_bytes"]
    return (after - before) / 1e6
