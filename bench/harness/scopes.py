"""Device time of the step programs by named scope, idle gaps named by the
engine's own spans, and the work counts that turn the scopes into rooflines.

``tracing.reduce`` reads the trace through ``jax.profiler.ProfileData``,
which gives each event's name and times but not the stats of its metadata.
The serving program runs each stage of its step programs under a
``jax.named_scope`` (``repro.serving.steps.SCOPES``), and the scope reaches
the trace as an element of the ``tf_op`` stat that each ``XLA Ops`` event's
metadata carries, e.g. ``jit(decode_step)/while/body/closed_call/attend/gather``.
So this module decodes the XSpace proto itself, from a minimal schema of
the fields it reads (``tensorflow``'s generated ``xplane_pb2`` is reachable
only through ``import tensorflow``, which would load all of TensorFlow into
the process that holds the chip).

Times follow ``ProfileData``: whole nanoseconds (``offset_ps // 1000`` past
the line's ``timestamp_ns``), moved onto ``perf_counter`` through the
``bench.mark`` annotation, so busy time and idle gaps agree with
``tracing.reduce`` to the bit.  The engine's spans (``repro.obs.Tracer``)
are on ``perf_counter`` through the tracer's ``epoch``; a program whose
tracer has none gives no spans, and its gaps keep their ``bench.*`` names.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
from collections import defaultdict

from harness import tracing, work

SCOPES = ("embed", "norm", "qkv", "pool_read", "kv_append", "attend", "out_proj",
          "mlp", "pool_write", "lm_head")
UNSCOPED = "unscoped"  # operations under no scope, or with no ``tf_op``
POOL_IO = ("pool_read", "kv_append", "pool_write")


# --------------------------------------------------------------------------
# the XSpace proto
# --------------------------------------------------------------------------

# message → [(field, number, type)], ``*`` marking a repeated message; the
# numbers are tsl/profiler/protobuf/xplane.proto's.  Maps are read as their
# repeated entry messages, which is how they are encoded.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane*")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "XLine*"),
               ("event_metadata", 4, "EventMetadataEntry*"),
               ("stat_metadata", 5, "StatMetadataEntry*")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"), ("events", 4, "XEvent*")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
              ("ref_value", 7, "uint64")],
    "XEventMetadata": [("name", 2, "string"), ("stats", 5, "XStat*")],
    "XStatMetadata": [("name", 2, "string")],
}


@functools.cache
def _xspace_type():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalars = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64, "string": F.TYPE_STRING}
    proto = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                               package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = proto.message_type.add(name=msg)
        for name, number, kind in fields:
            if kind in scalars:
                m.field.add(name=name, number=number, type=scalars[kind],
                            label=F.LABEL_OPTIONAL)
            else:
                m.field.add(name=name, number=number, type=F.TYPE_MESSAGE,
                            type_name=f".bench_xplane.{kind.rstrip('*')}",
                            label=F.LABEL_REPEATED if kind.endswith("*") else F.LABEL_OPTIONAL)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def load_xspace(path: str):
    space = _xspace_type()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _event_times(line):
    """(start_ns, end_ns, metadata id) of each event, in whole nanoseconds."""
    base = line.timestamp_ns
    for ev in line.events:
        s = base + ev.offset_ps // 1000
        yield s, s + ev.duration_ps // 1000, ev.metadata_id


def scope_of(tf_op: str | None) -> str:
    """The innermost named scope on an operation's ``tf_op`` path
    (``name/…/name:type``)."""
    for part in reversed((tf_op or "").split("/")):
        if part.split(":")[0] in SCOPES:
            return part.split(":")[0]
    return UNSCOPED


# --------------------------------------------------------------------------
# engine spans
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineSpan:
    name: str
    start: float  # perf_counter seconds
    end: float
    id: int
    parent: int | None
    attrs: dict


def engine_spans(tracer, start: float, end: float) -> list[EngineSpan]:
    """The tracer's spans that overlap [start, end), on ``perf_counter``;
    none where the tracer has no ``epoch`` (its clock is unknown)."""
    epoch = getattr(tracer, "epoch", None)
    if epoch is None:
        return []
    out = []
    for sp in tracer.spans:
        a = epoch + sp.t0_us * 1e-6
        b = a + sp.dur_us * 1e-6
        if b > start and a < end:
            out.append(EngineSpan(sp.name, a, b, sp.id, sp.parent, dict(sp.attrs)))
    return out


class _Innermost:
    """The innermost engine span open at a time.  The spans nest (one host
    thread), so it is the latest-started span at or before ``t`` or, if that
    one has ended, the first of its ancestors still open."""

    def __init__(self, spans: list[EngineSpan]):
        self.spans = sorted(spans, key=lambda s: (s.start, -s.end))
        self.starts = [s.start for s in self.spans]
        self.by_id = {s.id: s for s in self.spans}

    def at(self, t: float) -> EngineSpan | None:
        i = bisect.bisect_right(self.starts, t) - 1
        sp = self.spans[i] if i >= 0 else None
        while sp is not None and sp.end <= t:
            sp = self.by_id.get(sp.parent)
        return sp


def host_step_ms(spans: list[EngineSpan], start: float, end: float) -> float | None:
    """Mean duration of the ``engine.step`` spans started in [start, end)
    that dispatched a decode step."""
    decoding = {s.parent for s in spans if s.name == "decode_step"}
    durs = [s.end - s.start for s in spans
            if s.name == "engine.step" and s.id in decoding and start <= s.start < end]
    return sum(durs) / len(durs) * 1e3 if durs else None


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ScopeTrace:
    start: float  # traced window, perf_counter seconds
    end: float
    # program name → each execution's (start, end, {scope: device seconds});
    # control flow is left out, as in tracing.reduce
    executions: dict[str, list[tuple[float, float, dict[str, float]]]]
    idle_gaps: list[tuple[str, float]]  # idle seconds by ``<bench span>[/<engine span>]``

    def _runs(self, part: str) -> list[dict[str, float]]:
        """Each execution's scopes, for the programs whose name holds
        ``part`` and the executions started inside the window."""
        return [d for name, ex in self.executions.items() if part in name
                for s, _, d in ex if self.start <= s < self.end]

    def scope_times(self, part: str, scopes) -> list[float]:
        """Device seconds in ``scopes`` of each execution of ``part``; empty
        where none ran an operation in any of them (a program without the
        scopes)."""
        runs = self._runs(part)
        if not any(k in d for d in runs for k in scopes):
            return []
        return [sum(d.get(k, 0.0) for k in scopes) for d in runs]

    def split_ms(self, part: str) -> dict[str, float]:
        """Mean device milliseconds per execution of ``part``, by scope."""
        runs = self._runs(part)
        keys = sorted({k for d in runs for k in d})
        return {k: sum(d.get(k, 0.0) for d in runs) / len(runs) * 1e3 for k in keys}


def reduce(path: str, *, mark_perf: float, start: float, end: float,
           spans: list[EngineSpan] = ()) -> ScopeTrace:
    """Reduce the trace at ``path`` over [start, end) (perf_counter seconds);
    ``mark_perf`` is the perf_counter reading at the start of the
    ``bench.mark`` annotation, ``spans`` the engine's spans."""
    space = load_xspace(path)
    mark_ns = None
    bench_spans = []
    devices = []
    for plane in space.planes:
        if any(line.name == "XLA Ops" for line in plane.lines):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for s, e, mid in _event_times(line):
                name = names.get(mid, "")
                if name == tracing.MARK and mark_ns is None:
                    mark_ns = s
                elif name.startswith("bench."):
                    bench_spans.append((s, e, name))
    if mark_ns is None:
        raise ValueError(f"{path}: no {tracing.MARK} annotation to align the clocks")
    if not devices:
        raise ValueError(f"{path}: no device plane")

    def t(ns: float) -> float:
        return mark_perf + (ns - mark_ns) * 1e-9

    host = sorted((t(s), t(e), name) for s, e, name in bench_spans)
    host_starts = [s for s, _, _ in host]
    inner = _Innermost(list(spans))
    executions: dict[str, list] = defaultdict(list)
    gaps: dict[str, float] = defaultdict(float)
    for plane in devices:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        ops_meta = {}  # metadata id → scope, None for control flow
        for entry in plane.event_metadata:
            md = entry.value
            if tracing._op_name(md.name) is None:
                ops_meta[entry.key] = None
                continue
            tf_op = None
            for st in md.stats:
                if st.metadata_id == tf_op_id:
                    tf_op = st.str_value or stat_names.get(st.ref_value)
            ops_meta[entry.key] = scope_of(tf_op)
        module_names = {e.key: e.value.name.split("(")[0] for e in plane.event_metadata}
        runs = []  # (start, end, name, {scope: s}) of this device's executions
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs += [(t(s), t(e), module_names.get(mid, ""), defaultdict(float))
                         for s, e, mid in _event_times(line)]
            elif line.name == "XLA Ops":
                ops += [(t(s), t(e), mid) for s, e, mid in _event_times(line)]
        runs.sort(key=lambda r: r[0])
        run_starts = [r[0] for r in runs]
        for s, e, mid in ops:
            scope = ops_meta.get(mid, UNSCOPED)
            i = bisect.bisect_right(run_starts, s) - 1
            if scope is not None and i >= 0 and s < runs[i][1]:
                runs[i][3][scope] += e - s
        for s, e, name, by_scope in runs:
            executions[name].append((s, e, dict(by_scope)))
        # idle gaps, exactly as tracing.reduce finds them
        busy = tracing._union(tracing._clip([(s, e) for s, e, _ in ops
                                             if e > start and s < end], start, end))
        edges = [start, *[x for iv in busy for x in iv], end]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                mid_t = (g0 + g1) / 2
                name = tracing._host_span_at(host, host_starts, mid_t)
                sp = inner.at(mid_t)
                gaps[f"{name}/{sp.name}" if sp is not None else name] += g1 - g0
    n = len(devices)
    return ScopeTrace(
        start=start, end=end, executions=dict(executions),
        idle_gaps=sorted(((k, v / n) for k, v in gaps.items()), key=lambda kv: -kv[1]),
    )


# --------------------------------------------------------------------------
# work the decode attention needs
# --------------------------------------------------------------------------

def decode_attend(s: dict, ctxs: list[int]) -> tuple[float, float]:
    """(flops, bytes) the attention of one batched decode step needs: each
    sequence's query against its own live keys and values (``ctxs``, each
    including the token being decoded), all layers.  ``s`` is
    ``reference.<arch>.sizes(config)``."""
    flops = sum(work.attention_flops(s, c) for c in ctxs)
    return float(flops), float(sum(ctxs) * work.kv_bytes_per_token(s))
