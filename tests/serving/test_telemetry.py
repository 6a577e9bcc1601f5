"""Unified telemetry (ISSUE 8): timeline ⇔ legacy stats reconciliation and
the zero-sync contract on the decode hot path.

Acceptance: a ``BatchEngine.run()`` over ≥ 8 ragged requests produces a
timeline export (JSON + Chrome trace) whose per-request dispatch times and
per-step pool gauges reconcile **exactly** with the legacy ``BatchStats``
view, and a transfer-guard test proves the instrumentation adds zero
device→host transfers to the append/decode hot path.  The step programs
carry their names and their stages' scopes into the lowered program, and
the engine's spans form one tree per step on ``time.perf_counter``.
"""
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.models import transformer
from repro.serving import engine as engine_mod
from repro.serving import steps
from repro.serving.engine import BatchEngine, Engine

from test_batch_engine import RAGGED_PROMPTS, _setup


def test_timeline_export_reconciles_with_legacy_stats(tmp_path):
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=8)
    rids = [be.submit(p, 7) for p in RAGGED_PROMPTS]
    assert len(rids) >= 8
    out = be.run()
    assert all(len(out[r]) == len(p) + 7 for r, p in zip(rids, RAGGED_PROMPTS))

    jpath = be.obs.export_json(str(tmp_path / "serve_timeline.json"))
    cpath = be.obs.export_chrome(str(tmp_path / "serve_trace.json"))
    doc = json.loads(open(jpath).read())
    spans = doc["timeline"]["spans"]
    events = doc["timeline"]["events"]
    counters = doc["metrics"]["counters"]
    gauges = doc["metrics"]["gauges"]

    # span/event counts ⇔ legacy counters
    by = lambda n: [s for s in spans if s["name"] == n]
    ev = lambda n: [e for e in events if e["name"] == n]
    assert len(by("decode_step")) == be.stats.decode_steps > 0
    assert len(by("prefill_chunk")) == be.stats.prefill_chunks > 0
    assert len(ev("submit")) == len(RAGGED_PROMPTS)
    assert len(ev("admit")) == be.stats.admitted == len(RAGGED_PROMPTS)
    assert len(ev("complete")) == be.stats.completed == len(RAGGED_PROMPTS)
    assert len(ev("first_token")) == len(RAGGED_PROMPTS)
    assert len(ev("pool_grow")) == be.stats.pool_grow_events
    assert counters["serve.decode_steps"] == be.stats.decode_steps

    # per-request dispatch times: histogram series, timeline event, and the
    # Request record all carry the same float (recorded once)
    first = be.obs.registry.histogram("serve.first_token_dispatch_ms")
    per_token = be.obs.registry.histogram("serve.decode_dispatch_ms_per_token")
    first_by_rid = {e["attrs"]["rid"]: e["attrs"]["first_token_dispatch_ms"]
                    for e in ev("first_token")}
    for rid in rids:
        req = be._requests[rid]
        assert first.values(rid=rid) == [req.first_token_dispatch * 1e3]
        assert first_by_rid[rid] == req.first_token_dispatch * 1e3
        assert req.first_token_dispatch >= req.queue_wait >= 0
        if req.generated > 1:
            assert per_token.values(rid=rid) == [req.decode_dispatch_ms_per_token]

    # per-step pool gauges ⇔ legacy peaks, and every utilization sample is
    # internally consistent (= live / capacity of the same instant)
    assert gauges["pool.live_tokens"]["hwm"] == be.stats.peak_live_tokens
    assert gauges["pool.capacity_tokens"]["hwm"] == be.stats.peak_pool_tokens
    samples = doc["timeline"]["samples"]
    series = {}
    for s in samples:
        series.setdefault(s["name"], []).append(s["value"])
    live, cap, util = (
        series["pool.live_tokens"],
        series["pool.capacity_tokens"],
        series["pool.utilization"],
    )
    assert len(live) == len(cap) == len(util)
    assert max(live) == be.stats.peak_live_tokens
    assert max(cap) == be.stats.peak_pool_tokens
    for lv, cp, u in zip(live, cap, util):
        assert u == (lv / cp if cp else 0.0)

    # Chrome trace: structurally valid, same span population
    chrome = json.loads(open(cpath).read())
    te = chrome["traceEvents"]
    assert {e["ph"] for e in te} <= {"X", "i", "C"}
    durs = [e for e in te if e["ph"] == "X"]
    assert len(durs) == len(spans)
    for e in durs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and "name" in e


def test_decode_hot_path_adds_zero_device_to_host_transfers(monkeypatch):
    """Steady-state decode (no stop token, no prefill in flight): N fully
    instrumented step() calls issue zero device→host transfers.  The spy on
    ``jax.device_get`` is the teeth (the transfer guard cannot fire on CPU);
    recorded spans prove the telemetry was live during the guarded window.
    """
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=4)
    for p in RAGGED_PROMPTS[:4]:
        be.submit(p, 30)
    # drain admission + chunked prefill so only decode remains
    while be.sched.prefilling or be.sched.pending:
        be.step()
    assert all(be.sched.phase[r.slot] == "decode"
               for r in be._slots if r is not None)

    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    spans_before = len(be.obs.tracer.spans)
    steps_before = be.stats.decode_steps
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(5):
            be.step()
    assert calls == [], "decode hot path must not read the device"
    assert be.stats.decode_steps == steps_before + 5
    new_spans = be.obs.tracer.spans[spans_before:]
    # spans are appended as they close: each step's children, then its root
    one_step = ["engine.admit", "engine.grow", "engine.cow", "decode_step",
                "engine.finish", "engine.step"]
    assert [s.name for s in new_spans] == one_step * 5


def test_host_sync_audit_counts_every_device_get(monkeypatch):
    """Satellite fix: ``stats.host_syncs`` counts ALL device→host reads —
    stop drains, the final stream/first-token drains — not just stop checks.
    A spy on ``jax.device_get`` over a whole run() must agree exactly."""
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=2, stop_token=0)
    for p in RAGGED_PROMPTS[:3]:
        be.submit(p, 5)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    be.run()
    assert be.stats.host_syncs == len(calls) > 0
    syncs = be.obs.registry.counter("serve.host_syncs")
    assert syncs.value(site="stop_drain") == be.stats.decode_steps
    assert syncs.value(site="first_token_drain") == 1
    assert syncs.value(site="stream_drain") == 1
    # the debug checker's reads are audited too
    before = syncs.total()
    be.check_free_list()
    assert syncs.value(site="free_list_debug") == syncs.total() - before > 0


def test_engine_generate_audits_token_drain(monkeypatch):
    cfg, params = _setup()
    eng = Engine(params, cfg, policy="ggarray", max_len=32)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    eng.generate([[1, 2, 3]], max_new_tokens=4)
    syncs = eng.obs.registry.counter("serve.host_syncs")
    assert syncs.value(site="token_drain") == 1
    assert len(calls) == 1, "one transfer per generation, after the loop"


def test_peak_live_tokens_sees_inflight_chunked_prefill():
    """Satellite fix: tokens already prefilled into pool slabs by in-flight
    chunks count toward the live high-water mark even while the slot's
    published length is still 0."""
    cfg, params = _setup()
    C = cfg.attention_chunk  # 32 in the reduced config
    prompt = list(range(1, 2 * C - 7))  # 2 chunks: C then C−8
    be = BatchEngine(params, cfg, max_batch=2, max_chunks_per_step=1)
    rid = be.submit(prompt, 2)
    be.step()  # admit + first chunk only — decode hasn't started
    assert be.live_tokens == 0, "published length must still be 0"
    assert be.stats.peak_live_tokens >= C, (
        f"peak {be.stats.peak_live_tokens} missed the in-flight chunk of {C}"
    )
    out = be.run()
    # ...and decode growth keeps pushing the high-water mark afterwards
    assert be.stats.peak_live_tokens >= len(prompt) + 1
    assert len(out[rid]) == len(prompt) + 2


def test_instrumented_decode_hot_path_stays_zero_sync(monkeypatch):
    """ISSUE 10 acceptance: with the device counter plane ON, steady-state
    decode still issues zero device→host transfers — counter vectors ride
    the step as device data and pend in the plane until an explicit drain."""
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=4, instrument=True)
    for p in RAGGED_PROMPTS[:4]:
        be.submit(p, 30)
    while be.sched.prefilling or be.sched.pending:
        be.step()
    be.drain_device_counters()  # flush prefill-era pends before the guard

    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    pend0 = be.devctr.pending
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(5):
            be.step()
    assert calls == [], "instrumented decode must not read the device"
    assert be.devctr.pending == pend0 + 5, "each step pends one vector"
    # the drain point works and actually saw the steps
    monkeypatch.undo()
    got = be.drain_device_counters()
    assert be.devctr.pending == 0
    assert any(v > 0 for v in got.values())


def test_instrumentation_is_bit_exact_and_counts_kernel_work():
    cfg, params = _setup()
    prompts = RAGGED_PROMPTS[:5]
    plain = BatchEngine(params, cfg, max_batch=4)
    inst = BatchEngine(params, cfg, max_batch=4, instrument=True)
    out_plain = plain.run_all(prompts, 6)
    out_inst = inst.run_all(prompts, 6)
    assert out_inst == out_plain, "counters must not perturb the tokens"
    ctr = inst.drain_device_counters()
    # the paged serving path exercises gather + attend + slab appends
    assert ctr["paged_attend.lanes"] > 0
    assert ctr["paged_gather.launches"] > 0
    assert ctr["slab_append.active_lanes"] > 0
    # drained values land in the shared registry under the device. prefix
    snap = inst.obs.snapshot()["counters"]
    assert snap["device.paged_attend.lanes"] == ctr["paged_attend.lanes"]
    # an uninstrumented engine records nothing on the plane
    assert all(v == 0 for v in plain.drain_device_counters().values())


def test_instrument_off_compiles_nothing_after_instrumented_runs():
    """The instrument flag rides the frozen config into the shared jit
    factories: an instrumented fleet must not evict or fracture the plain
    engine's traces (OFF stays provably free)."""
    import jax.monitoring

    from test_trace_count import COMPILE_EVENT

    cfg, params = _setup()
    prompts = RAGGED_PROMPTS[:3]
    kw = dict(max_batch=2, initial_slabs=32, max_pages_hint=16)
    first = BatchEngine(params, cfg, **kw).run_all(prompts, 3)
    BatchEngine(params, cfg, instrument=True, **kw).run_all(prompts, 3)

    compiles: list[str] = []

    def spy(event, duration, **attrs):
        if event == COMPILE_EVENT:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(spy)
    try:
        warm = BatchEngine(params, cfg, **kw).run_all(prompts, 3)
    finally:
        jax.monitoring.unregister_event_duration_listener(spy)
    assert warm == first
    assert not compiles, (
        f"plain engine recompiled {len(compiles)} traces after an "
        "instrumented engine ran — the instrument flag leaked into the key"
    )


def test_views_share_one_registry():
    """The legacy stats views are reads of the same registry the timeline
    snapshots — not copies that can drift."""
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=2)
    be.run_all(RAGGED_PROMPTS[:2], 3)
    snap = be.obs.snapshot()
    assert snap["counters"]["serve.admitted"] == be.stats.admitted
    assert snap["counters"]["serve.completed"] == be.stats.completed
    assert (
        snap["gauges"]["pool.live_tokens"]["hwm"] == be.stats.peak_live_tokens
    )
    assert be.stats._reg is be.obs.registry is be.sched.obs.registry


# --------------------------------------------------------------------------
# step programs: names and scopes; engine spans: one tree per step
# --------------------------------------------------------------------------

def _lowered(program: str, arch: str = "qwen2.5-3b"):
    """The engine's step program, lowered at the shapes of a small engine."""
    cfg, params = _setup(arch)
    be = BatchEngine(params, cfg, max_batch=2, initial_slabs=4)
    if program == "decode_step":
        return engine_mod._decode_step_fn(cfg).lower(
            be.params, be.cur_tok, be.caches, be.lengths, active=None)
    row = np.full((be.book.max_pages,), -1, np.int32)
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    return engine_mod._prefill_chunk_fn(cfg).lower(
        be.params, jnp.zeros((1, be.sched.C), jnp.int32), be.caches,
        i32(0), i32(be.sched.C), i32(5), jnp.asarray(row), first=False)


PROGRAMS = ["decode_step", "prefill_chunk"]


@pytest.mark.parametrize("program", PROGRAMS)
def test_step_programs_carry_their_names(program):
    """XLA names the programs ``jit_<program>``: profiler traces and the
    benchmark's trace reduction find them by these names."""
    text = _lowered(program).as_text()
    assert re.search(rf"module @jit_{program}\b", text)


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_layer_op_falls_under_exactly_one_scope(program):
    """Each stage of the step body runs under a named scope, which reaches
    the compiled program as an element of every operation's ``op_name`` (a
    profiler trace's ``tf_op``); inside the layer body (the scan's
    ``closed_call``) each operation has exactly one.  The attention stack
    and the hybrid one (Mamba mixers, held experts) use every scope
    between them."""
    seen = set()
    for arch in ("qwen2.5-3b", "granite-4.0-h-small"):
        text = _lowered(program, arch).compile().as_text()
        paths = set(re.findall(rf'op_name="(jit\({program}\)/[^"]*)"', text))
        for path in paths:
            found = [p for p in path.split("/") if p in steps.SCOPES]
            assert len(found) <= 1, path
            if "/closed_call/" in path:
                assert len(found) == 1, path
            seen.update(found)
    assert seen == set(steps.SCOPES)


def test_engine_spans_nest_under_engine_step_on_perf_counter():
    """Every span of a step descends from its ``engine.step`` root through
    parent ids, lies inside its parent, and ``epoch + t0`` places it on
    ``time.perf_counter``."""
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=4)
    for p in RAGGED_PROMPTS[:6]:
        be.submit(p, 5)
    tr = be.obs.tracer
    windows = []
    while be._has_work():
        t0 = time.perf_counter()
        be.step()
        windows.append((t0, time.perf_counter()))
    by_id = {s.id: s for s in tr.spans}
    assert len(by_id) == len(tr.spans)
    roots = sorted((s for s in tr.spans if s.parent is None), key=lambda s: s.t0_us)
    assert [s.name for s in roots] == ["engine.step"] * len(windows)
    for root, (lo, hi) in zip(roots, windows):
        start = tr.epoch + root.t0_us * 1e-6
        assert lo <= start <= start + root.dur_us * 1e-6 <= hi
    children = {"engine.admit", "prefill_chunk", "engine.grow", "engine.cow",
                "decode_step", "engine.finish"}
    for s in tr.spans:
        if s.parent is None:
            continue
        parent = by_id[s.parent]
        assert parent.name == "engine.step" and s.name in children
        assert s.depth == parent.depth + 1
        assert parent.t0_us <= s.t0_us
        assert s.t0_us + s.dur_us <= parent.t0_us + parent.dur_us
    decoded = 0
    for root in roots:
        kids = sorted((s for s in tr.spans if s.parent == root.id), key=lambda s: s.t0_us)
        names = [s.name for s in kids if s.name != "prefill_chunk"]
        assert names in (["engine.admit"], ["engine.admit", "engine.grow", "engine.cow",
                                            "decode_step", "engine.finish"])
        if "decode_step" in names:
            decoded += 1
            assert root.attrs["step"] == next(
                s for s in kids if s.name == "decode_step").attrs["step"]
    assert decoded == be.stats.decode_steps > 0
