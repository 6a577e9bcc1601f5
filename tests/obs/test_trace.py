"""Span tracer: nesting, instants, counter samples, JSON + Chrome export."""
import json

import pytest

from repro.obs import ServingTimeline, Tracer


def test_spans_record_nesting_depth_and_attrs():
    tr = Tracer()
    with tr.span("outer", rid=1):
        with tr.span("inner", rid=1, chunk=0):
            pass
        with tr.span("inner", rid=1, chunk=1):
            pass
    assert [s.name for s in tr.spans] == ["inner", "inner", "outer"]
    assert [s.depth for s in tr.spans] == [1, 1, 0]
    outer = tr.spans[-1]
    inner0 = tr.spans[0]
    assert outer.t0_us <= inner0.t0_us
    assert outer.dur_us >= inner0.dur_us
    assert inner0.attrs == {"rid": 1, "chunk": 0}
    # the causal tree: ids are distinct, children name their parent
    assert len({s.id for s in tr.spans}) == 3
    assert outer.parent is None
    assert [s.parent for s in tr.spans[:2]] == [outer.id, outer.id]


def test_span_times_are_offsets_from_the_epoch():
    ticks = iter([10.0, 10.5, 11.25])
    tr = Tracer(clock=lambda: next(ticks))
    with tr.span("s"):
        pass
    (s,) = tr.spans
    assert tr.epoch == 10.0
    assert tr.epoch + s.t0_us * 1e-6 == 10.5
    assert s.dur_us == pytest.approx(0.75e6)


def test_span_records_even_when_body_raises():
    tr = Tracer()
    try:
        with tr.span("boom"):
            raise RuntimeError("x")
    except RuntimeError:
        pass
    assert [s.name for s in tr.spans] == ["boom"]


def test_events_and_samples_are_ordered():
    tr = Tracer()
    tr.event("admit", rid=0)
    tr.sample("pool.utilization", 0.5)
    tr.event("complete", rid=0)
    data = tr.to_json()
    assert [e["name"] for e in data["events"]] == ["admit", "complete"]
    assert data["events"][0]["ts_us"] <= data["events"][1]["ts_us"]
    assert data["samples"][0]["value"] == 0.5


def test_chrome_trace_structure():
    tr = Tracer()
    with tr.span("prefill_chunk", rid=3):
        pass
    tr.event("admit", rid=3)
    tr.sample("pool.utilization", 0.25)
    doc = tr.to_chrome()
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs == {"X", "i", "C"}
    dur = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert dur["name"] == "prefill_chunk" and dur["args"] == {"rid": 3}
    assert dur["dur"] >= 0 and "ts" in dur
    cnt = next(e for e in doc["traceEvents"] if e["ph"] == "C")
    assert cnt["args"] == {"value": 0.25}


def test_exports_round_trip_through_files(tmp_path):
    tl = ServingTimeline()
    tl.registry.counter("serve.admitted").inc()
    with tl.span("decode_step", step=0):
        pass
    tl.gauge_sample("pool.utilization", 0.75)
    jpath = tl.export_json(str(tmp_path / "timeline.json"))
    cpath = tl.export_chrome(str(tmp_path / "trace.json"))
    loaded = json.loads(open(jpath).read())
    assert loaded["metrics"]["counters"]["serve.admitted"] == 1
    # gauge_sample writes both surfaces: registry gauge AND timeline sample
    assert loaded["metrics"]["gauges"]["pool.utilization"]["value"] == 0.75
    assert loaded["timeline"]["samples"][0]["value"] == 0.75
    chrome = json.loads(open(cpath).read())
    assert {e["name"] for e in chrome["traceEvents"]} == {
        "decode_step", "pool.utilization"
    }


def test_chrome_trace_timestamps_are_monotonic_per_tid():
    """Perfetto importer contract (ISSUE 10 satellite): every (pid, tid)
    stream is emitted in nondecreasing ts order, complete durations are
    non-negative, and every event names a track."""
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        tr.event("mid")
        with tr.span("inner2"):
            pass
    tr.sample("pool.utilization", 0.5)
    tr.event("late")
    tr.sample("pool.utilization", 0.75)
    doc = tr.to_chrome()
    streams = {}
    for e in doc["traceEvents"]:
        assert "tid" in e and "pid" in e, f"{e['ph']} event lost its track"
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        streams.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for ts in streams.values():
        assert ts == sorted(ts), "per-tid timestamps must be monotonic"


def test_chrome_counter_samples_match_registry_series(tmp_path):
    """Every ``ph: C`` event is one recorded gauge sample, value-for-value,
    and the final sample agrees with the registry's current gauge value."""
    import json as _json

    tl = ServingTimeline()
    recorded = [0.25, 0.5, 0.125]
    for v in recorded:
        tl.gauge_sample("pool.utilization", v)
    tl.gauge_sample("pool.live_tokens", 7)
    cpath = tl.export_chrome(str(tmp_path / "trace.json"))
    te = _json.loads(open(cpath).read())["traceEvents"]
    util = [e for e in te if e["ph"] == "C" and e["name"] == "pool.utilization"]
    assert [e["args"]["value"] for e in util] == recorded
    assert [e["ts"] for e in util] == sorted(e["ts"] for e in util)
    assert util[-1]["args"]["value"] == tl.registry.gauge("pool.utilization").value()
    live = [e for e in te if e["ph"] == "C" and e["name"] == "pool.live_tokens"]
    assert [e["args"]["value"] for e in live] == [7]


def test_jax_annotation_passthrough_smoke():
    """jax_annotations=True wraps span bodies in jax.profiler.TraceAnnotation
    without changing the recorded spans."""
    tr = Tracer(jax_annotations=True)
    with tr.span("annotated"):
        pass
    assert [s.name for s in tr.spans] == ["annotated"]
