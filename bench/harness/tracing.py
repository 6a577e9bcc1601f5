"""Reduce a profiler trace of the window to device busy time, per-program
device time, the heaviest device operations and the idle gaps.

The trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) has one
plane per device (``/device:TPU:<n>``) with a line of program executions
(``XLA Modules``) and a line of operations (``XLA Ops``), and a host plane
whose ``python`` line holds the harness's ``bench.*`` annotations.  Times
are moved onto the host's ``perf_counter`` clock through the
``bench.mark`` annotation, whose start the harness records on both clocks.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

MARK = "bench.mark"
TOP = 10


@dataclasses.dataclass
class Trace:
    start: float  # traced window, perf_counter seconds
    end: float
    busy_s: float  # union of device operations, averaged over devices
    modules: dict[str, list[tuple[float, float]]]  # program name → (start, end)
    device_ops: list[tuple[str, float]]  # heaviest operations, seconds
    idle_gaps: list[tuple[str, float]]  # idle seconds by the host span they fell in

    @property
    def window_s(self) -> float:
        return self.end - self.start

    def program_times(self, part: str) -> list[float]:
        """Device seconds of each execution of the programs whose name holds
        ``part`` (e.g. ``decode_step``), started inside the window."""
        out = []
        for name, spans in self.modules.items():
            if part in name:
                out.extend(e - s for s, e in spans if self.start <= s < self.end)
        return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


CONTROL_FLOW = ("while", "conditional", "call")


def _op_name(text: str) -> str | None:
    """``%fusion.12 = bf16[…]{…} fusion(…)`` → ``fusion.12 = bf16[…]`` (its
    name and result shape); ``None`` for control flow, whose time is that
    of the operations inside it."""
    lhs, _, rhs = text.partition(" = ")
    lhs = lhs.lstrip("%")
    if lhs.split(".")[0] in CONTROL_FLOW:
        return None
    shape = rhs.split("{")[0].split(" ")[0]
    return f"{lhs} = {shape}" if shape else lhs


def _module_name(event) -> str:
    name = event.name
    return name.split("(")[0]


def reduce(path: str, *, mark_perf: float, start: float, end: float) -> Trace:
    """Reduce the trace at ``path`` over the window [start, end) (perf_counter
    seconds); ``mark_perf`` is the perf_counter reading taken at the start
    of the ``bench.mark`` annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans: list[tuple[float, float, str]] = []
    mark_ns = None
    devices = []
    for plane in data.planes:
        if any(line.name == "XLA Ops" for line in plane.lines):
            devices.append(plane)  # a chip (planes without operations are not)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARK and mark_ns is None:
                    mark_ns = ev.start_ns
                elif ev.name.startswith("bench."):
                    host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    if mark_ns is None:
        raise ValueError(f"{path}: no {MARK} annotation to align the clocks")
    if not devices:
        raise ValueError(f"{path}: no device plane")

    def t(ns: float) -> float:
        return mark_perf + (ns - mark_ns) * 1e-9

    spans = sorted((t(s), t(e), name) for s, e, name in host_spans)
    starts = [s for s, _, _ in spans]
    modules: dict[str, list[tuple[float, float]]] = defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: dict[str, float] = defaultdict(float)
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    modules[_module_name(ev)].append((t(ev.start_ns), t(ev.end_ns)))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s, e = t(ev.start_ns), t(ev.end_ns)
                    if e > start and s < end:
                        ops.append((s, e))
                        name = _op_name(ev.name)
                        if name is not None:
                            op_time[name] += min(e, end) - max(s, start)
        busy = _union(_clip(ops, start, end))
        busy_total += sum(e - s for s, e in busy)
        edges = [start, *[x for iv in busy for x in iv], end]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps[_host_span_at(spans, starts, (g0 + g1) / 2)] += g1 - g0
    n = len(devices)
    return Trace(
        start=start,
        end=end,
        busy_s=busy_total / n,
        modules=dict(modules),
        device_ops=sorted(((k, v / n) for k, v in op_time.items()), key=lambda kv: -kv[1])[:TOP],
        idle_gaps=sorted(((k, v / n) for k, v in gaps.items()), key=lambda kv: -kv[1])[:TOP],
    )


def _host_span_at(spans, starts, t: float) -> str:
    """The latest-started harness span still open at ``t`` (the harness's
    spans do not nest, so that is the one the host was in)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 3, -1), -1):
        if spans[j][1] > t:
            return spans[j][2]
    return "bench.none"
