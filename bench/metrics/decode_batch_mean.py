"""Scheduler: sequences decoded per decode step, averaged over the decode
steps whose tokens were ready inside the window."""


def read(rec):
    steps = rec.steps_in(rec.t_open, rec.t_close)
    return sum(len(s.ctxs) for s in steps) / len(steps) if steps else None
