"""The trace reduction, checked on a small trace recorded on a TPU v5e (0.5 s
of the tiny cell of benchroot.py, run by bench/run.py with ``--trace 1``).
``data/tiny_trace.json`` holds the clock mark and window of that run and the
step programs' device times the harness read there."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from harness import tracing

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def xplane(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "tiny.xplane.pb.gz").read_bytes()))
    return str(path)


@pytest.fixture(scope="module")
def recorded(xplane):
    meta = json.loads((DATA / "tiny_trace.json").read_text())
    trace = tracing.reduce(xplane, mark_perf=meta["mark_perf"], start=meta["start"], end=meta["end"])
    return meta, trace


def _ops(meta, xplane):
    """Every device operation's interval, read straight from the file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane)
    mark = next(ev.start_ns for pl in data.planes if pl.name.startswith("/host:")
                for ln in pl.lines for ev in ln.events if ev.name == tracing.MARK)
    out = []
    for pl in data.planes:
        if pl.name.startswith("/device:TPU"):
            for ln in pl.lines:
                if ln.name == "XLA Ops":
                    out += [(meta["mark_perf"] + (ev.start_ns - mark) * 1e-9,
                             meta["mark_perf"] + (ev.end_ns - mark) * 1e-9) for ev in ln.events]
    return out


def test_program_times_match_the_run_that_recorded_them(recorded):
    meta, trace = recorded
    assert [x * 1e3 for x in trace.program_times("decode_step")] == pytest.approx(meta["decode_ms"])
    assert [x * 1e3 for x in trace.program_times("prefill_chunk")] == pytest.approx(meta["prefill_ms"])
    assert sorted(trace.modules) == meta["modules"]


def test_busy_time_is_the_union_of_device_operations(recorded, xplane):
    meta, trace = recorded
    lo, hi = meta["start"], meta["end"]
    # count covered time by sweeping sorted endpoints (+1 at a start, -1 at an end)
    edges = []
    for s, e in _ops(meta, xplane):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    depth, covered, last = 0, 0.0, None
    for t, d in edges:
        if depth > 0:
            covered += t - last
        depth += d
        last = t
    assert trace.busy_s == pytest.approx(covered, rel=1e-9)
    assert 0 < trace.busy_s < trace.window_s
    # every idle second is named by the harness span it fell in
    assert sum(s for _, s in trace.idle_gaps) == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    assert all(name.startswith("bench.") for name, _ in trace.idle_gaps)


def test_step_programs_are_found_by_name(recorded):
    _, trace = recorded
    decode = trace.program_times("decode_step")
    prefill = trace.program_times("prefill_chunk")
    assert decode and prefill
    assert all(0 < t < trace.window_s for t in decode + prefill)
    # control flow is not double counted among the heaviest operations, and
    # they add up to no more than the busy time
    assert not any(n.startswith("while") for n, _ in trace.device_ops)
    assert 0 < sum(s for _, s in trace.device_ops) <= trace.busy_s * (1 + 1e-9)


def test_op_names_and_union():
    assert tracing._op_name("%fusion.12 = bf16[4,8]{1,0:T(8,128)} fusion(%a), kind=kLoop") \
        == "fusion.12 = bf16[4,8]"
    assert tracing._op_name("%while.5 = (s32[]) while(%t), body=%b") is None
    assert tracing._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
