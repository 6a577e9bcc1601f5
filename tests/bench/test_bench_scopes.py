"""The reduction by named scope (``bench/harness/scopes.py``), checked on two
traces recorded on a TPU v5e by bench/run.py with ``--trace 1`` (0.5 s of
benchroot.py's tiny open-loop cell):

* ``tiny.xplane.pb.gz`` — the program before its step programs had scopes
  and its engine spans a clock (``tiny_trace.json``);
* ``scoped.xplane.pb.gz`` — the scoped program; ``scoped_trace.json`` holds
  the clock mark, the window and the engine's spans of that run, placed on
  ``perf_counter`` through the tracer's epoch.

The minimal XSpace decoder is checked against ``jax.profiler.ProfileData``
for every event's name and times, and against TensorFlow's generated
``xplane_pb2`` (loaded from its file) for every operation's ``tf_op``.
"""
from __future__ import annotations

import bisect
import gzip
import importlib.util
import json
from collections import defaultdict
from pathlib import Path

import pytest

from harness import scopes, tracing, work

DATA = Path(__file__).parent / "data"
TRACES = {"tiny": "tiny_trace.json", "scoped": "scoped_trace.json"}
ENGINE_SPANS = {"engine.step", "engine.admit", "prefill_chunk", "engine.grow",
                "engine.cow", "decode_step", "engine.finish"}


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    """name → (xplane path, meta)"""
    out = {}
    for name, meta in TRACES.items():
        path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
        path.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes()))
        out[name] = (str(path), json.loads((DATA / meta).read_text()))
    return out


def _spans(meta) -> list[scopes.EngineSpan]:
    return [scopes.EngineSpan(n, s, e, i, p, a) for n, s, e, i, p, a in meta.get("spans", [])]


def _reduce(traces, name, with_spans=True):
    path, meta = traces[name]
    return scopes.reduce(path, mark_perf=meta["mark_perf"], start=meta["start"],
                         end=meta["end"], spans=_spans(meta) if with_spans else ())


def _reference_xplane():
    """TensorFlow's generated module, loaded from its file without importing
    TensorFlow."""
    tf = importlib.util.find_spec("tensorflow")
    path = Path(tf.origin).parent / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("reference_xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", sorted(TRACES))
def test_decoder_reads_the_events_profile_data_reads(traces, name):
    from jax.profiler import ProfileData

    path, _ = traces[name]
    space = scopes.load_xspace(path)
    ours = {}
    for plane in space.planes:
        md = {e.key: e.value.name for e in plane.event_metadata}
        ours[plane.name] = [(line.name, [(md.get(m, ""), s, e) for s, e, m in scopes._event_times(line)])
                            for line in plane.lines]
    theirs = {plane.name: [(line.name, [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events])
                           for line in plane.lines]
              for plane in ProfileData.from_file(path).planes}
    assert ours == theirs
    assert sum(len(evs) for lines in ours.values() for _, evs in lines) > 10_000


@pytest.mark.parametrize("name", sorted(TRACES))
def test_decoder_reads_the_tf_op_xplane_pb2_reads(traces, name):
    pb2 = _reference_xplane()
    path, _ = traces[name]
    ref = pb2.XSpace()
    ref.ParseFromString(Path(path).read_bytes())
    ours = scopes.load_xspace(path)

    def tf_ops(space):
        out = {}
        for plane in space.planes:
            stats = {e.key: e.value.name for e in plane.stat_metadata}
            for e in plane.event_metadata:
                for st in e.value.stats:
                    if stats.get(st.metadata_id) == "tf_op":
                        out[(plane.name, e.key)] = st.str_value or stats.get(st.ref_value)
        return out

    ref_ops = {(p.name, k): next((st.str_value or p.stat_metadata[st.ref_value].name
                                  for st in md.stats
                                  if p.stat_metadata[st.metadata_id].name == "tf_op"), None)
               for p in ref.planes for k, md in p.event_metadata.items()}
    ours_ops = tf_ops(ours)
    assert ours_ops == {k: v for k, v in ref_ops.items() if v is not None}
    assert sum("jit(decode_step)" in v for v in ours_ops.values()) > 20


@pytest.mark.parametrize("name", sorted(TRACES))
def test_without_engine_spans_the_gaps_are_tracing_reduces(traces, name):
    """The same busy time and gaps as ``tracing.reduce``, and the same
    program executions, when there are no engine spans to name them by."""
    path, meta = traces[name]
    old = tracing.reduce(path, mark_perf=meta["mark_perf"], start=meta["start"], end=meta["end"])
    new = _reduce(traces, name, with_spans=False)
    assert dict(new.idle_gaps) == dict(old.idle_gaps)
    assert {k: [(s, e) for s, e, _ in v] for k, v in new.executions.items()} == old.modules


def _op_times(path, meta, pb2):
    """Reference: each decode_step execution's operations in the window,
    (start, end) → [(duration s, tf_op)], read with xplane_pb2; control flow
    left out."""
    space = pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    mark = next(line.timestamp_ns + ev.offset_ps // 1000
                for p in space.planes if p.name.startswith("/host:") for line in p.lines
                for ev in line.events if p.event_metadata[ev.metadata_id].name == tracing.MARK)
    t = lambda ns: meta["mark_perf"] + (ns - mark) * 1e-9
    dev = next(p for p in space.planes if any(line.name == "XLA Ops" for line in p.lines))
    tf_id = next(k for k, v in dev.stat_metadata.items() if v.name == "tf_op")
    runs, ops = [], []
    for line in dev.lines:
        for ev in line.events:
            md = dev.event_metadata[ev.metadata_id]
            s = line.timestamp_ns + ev.offset_ps // 1000
            e = s + ev.duration_ps // 1000
            if line.name == "XLA Modules" and md.name.startswith("jit_decode_step("):
                runs.append((t(s), t(e)))
            elif line.name == "XLA Ops" and tracing._op_name(md.name) is not None:
                tf = next((st.str_value or dev.stat_metadata[st.ref_value].name
                           for st in md.stats if st.metadata_id == tf_id), None)
                ops.append((t(s), t(e), tf))
    runs.sort()
    starts = [s for s, _ in runs]
    out = defaultdict(list)
    for s, e, tf in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1] and meta["start"] <= runs[i][0] < meta["end"]:
            out[runs[i]].append((e - s, tf))
    return out


def test_scope_sums_are_each_executions_device_time(traces):
    """Each decode step's scopes, unscoped included, add up to its
    operations' device time, and each scope's share is what the operations'
    ``tf_op`` paths say."""
    path, meta = traces["scoped"]
    ref = _op_times(path, meta, _reference_xplane())
    got = _reduce(traces, "scoped")
    assert len(ref) == len(got.scope_times("decode_step", ("attend",))) > 10
    everything = scopes.SCOPES + (scopes.UNSCOPED,)
    total = got.scope_times("decode_step", everything)
    assert total == pytest.approx([sum(d for d, _ in ops) for _, ops in sorted(ref.items())],
                                  rel=1e-9)
    for scope in everything:
        want = [sum(d for d, tf in ops
                    if any(p.split(":")[0] == scope for p in (tf or "").split("/"))
                    or (scope == scopes.UNSCOPED
                        and not any(p.split(":")[0] in scopes.SCOPES for p in (tf or "").split("/"))))
                for _, ops in sorted(ref.items())]
        assert got.scope_times("decode_step", (scope,)) == pytest.approx(want, rel=1e-9, abs=1e-12)
    # every stage ran on the device, and none of them hides most of the step
    split = got.split_ms("decode_step")
    assert set(split) == set(everything)
    assert all(v > 0 for v in split.values())
    assert split[scopes.UNSCOPED] < 0.5 * sum(split.values())


def test_unscoped_program_reads_no_scope(traces):
    """A program without the scopes (the tiny trace's) reads nothing for a
    scope, so a metric of it is left out, not read as 0."""
    got = _reduce(traces, "tiny")
    assert got.scope_times("decode_step", ("attend",)) == []
    assert set(got.split_ms("decode_step")) == {scopes.UNSCOPED}
    assert got.scope_times("decode_step", (scopes.UNSCOPED,))


def test_gaps_inside_engine_spans_are_named_by_them(traces):
    """Engine spans only refine a gap's name: the time each harness span
    holds is unchanged, and the host step's gaps fall inside engine spans."""
    plain = dict(_reduce(traces, "scoped", with_spans=False).idle_gaps)
    named = _reduce(traces, "scoped").idle_gaps
    by_bench = defaultdict(float)
    for name, s in named:
        bench, _, span = name.partition("/")
        assert bench.startswith("bench.")
        assert span == "" or span in ENGINE_SPANS
        by_bench[bench] += s
    assert dict(by_bench) == pytest.approx(plain, rel=1e-9)
    inside = sum(s for name, s in named if name.startswith("bench.step/"))
    assert inside > 0.5 * plain["bench.step"]


def test_innermost_span_follows_the_parent_chain():
    S = scopes.EngineSpan
    spans = [S("engine.step", 0.0, 10.0, 0, None, {}), S("engine.admit", 1.0, 3.0, 1, 0, {}),
             S("decode_step", 4.0, 9.0, 2, 0, {}), S("inner", 5.0, 6.0, 3, 2, {}),
             S("engine.step", 12.0, 13.0, 4, None, {})]
    at = scopes._Innermost(spans).at
    names = [getattr(at(t), "name", None) for t in (-1, 0.5, 2, 3.5, 5.5, 7, 10.5, 12.5)]
    assert names == [None, "engine.step", "engine.admit", "engine.step", "inner",
                     "decode_step", None, "engine.step"]


def test_host_step_counts_the_steps_that_decoded():
    S = scopes.EngineSpan
    spans = [S("engine.step", 1.0, 1.010, 0, None, {}), S("decode_step", 1.002, 1.008, 1, 0, {}),
             S("engine.step", 2.0, 2.002, 2, None, {}),  # admission only
             S("engine.step", 3.0, 3.030, 3, None, {}), S("decode_step", 3.01, 3.02, 4, 3, {}),
             S("engine.step", 9.0, 9.5, 5, None, {}), S("decode_step", 9.1, 9.2, 6, 5, {})]
    assert scopes.host_step_ms(spans, 0.5, 5.0) == pytest.approx(20.0)
    assert scopes.host_step_ms(spans, 4.0, 5.0) is None


def test_engine_spans_need_the_tracers_epoch():
    from repro.obs import Tracer

    ticks = iter([100.0, 100.25, 100.5, 100.75, 101.0])
    tr = Tracer(clock=lambda: next(ticks))
    with tr.span("engine.step", step=0):
        with tr.span("decode_step"):
            pass
    got = scopes.engine_spans(tr, 100.0, 101.0)
    assert [(s.name, s.start, s.end) for s in got] == [
        ("decode_step", 100.5, 100.75), ("engine.step", 100.25, 101.0)]
    assert got[0].parent == got[1].id and got[1].parent is None
    assert scopes.engine_spans(tr, 101.0, 102.0) == []

    class Clockless:  # a tracer that cannot place its spans
        spans = tr.spans

    assert scopes.engine_spans(Clockless(), 100.0, 101.0) == []


def test_decode_attend_work_counts_each_sequences_own_context():
    s = {"layers": 36, "heads": 16, "kv_heads": 2, "head_dim": 128}
    flops, nbytes = scopes.decode_attend(s, [1000, 2000])
    assert flops == 4 * 36 * 16 * 128 * 3000
    assert nbytes == 3000 * 36_864 == 3000 * work.kv_bytes_per_token(s)


def test_scoped_programs_keep_the_names_the_benchmark_reads(traces):
    """The scoped program names itself: the existing reduction finds the
    step programs, with the device times the recording run read."""
    path, meta = traces["scoped"]
    trace = tracing.reduce(path, mark_perf=meta["mark_perf"], start=meta["start"], end=meta["end"])
    assert [x * 1e3 for x in trace.program_times("decode_step")] == pytest.approx(meta["decode_ms"])
    assert [x * 1e3 for x in trace.program_times("prefill_chunk")] == pytest.approx(meta["prefill_ms"])
    assert {"jit_decode_step", "jit_prefill_chunk"} <= set(trace.modules)


def test_an_operation_takes_its_innermost_scope():
    assert scopes.scope_of("jit(decode_step)/while/body/closed_call/attend/gather:") == "attend"
    assert scopes.scope_of("jit(decode_step)/mlp/norm/mul:") == "norm"
    assert scopes.scope_of("jit(decode_step)/lm_head:") == "lm_head"
    assert scopes.scope_of("jit(decode_step)/while:") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED
