"""Device counter plane (ISSUE 10): slot layout, tape scoping, the
zero-sync drain contract, and in-kernel counters vs their jnp oracles.

The parity tests are exact (``assert_array_equal`` on whole counter
vectors): the ops wrappers promise the in-kernel block and the ``use_ref``
oracle count the *same padded-wave geometry*, so any drift means the
instrumentation is lying about the kernel it rides.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ggarray as gg
from repro.kernels.flatten import ops as fl_ops
from repro.kernels.paged import ops as pg_ops
from repro.kernels.push_back import ops as pb_ops
from repro.obs import DeviceCounterPlane, MetricsRegistry, device

SPACES = ["vmem", "hbm"]


# --------------------------------------------------------------------------
# layout + tape
# --------------------------------------------------------------------------

def test_slot_layout_is_fixed_and_packs_round_trip():
    assert len(device.SLOTS) == device.NSLOTS <= device.CTR_LANES
    assert len(set(device.SLOTS)) == device.NSLOTS  # no duplicate names
    vec = device.pack(**{"push_back.waves": 3, "paged_attend.masked_lanes": 7})
    d = device.as_dict(vec)
    assert d["push_back.waves"] == 3.0
    assert d["paged_attend.masked_lanes"] == 7.0
    assert sum(d.values()) == 10.0  # unnamed slots stay zero
    # from_block reads row 0's leading lanes of the in-kernel block
    blk = jnp.zeros((device.CTR_ROWS, device.CTR_LANES), jnp.int32)
    blk = blk.at[0, device.SLOT_INDEX["flatten.rows_touched"]].set(11)
    assert device.as_dict(device.from_block(blk))["flatten.rows_touched"] == 11.0


def test_record_is_noop_without_a_tape_and_nests_innermost():
    device.record(device.pack(**{"push_back.waves": 99}))  # must not raise
    assert not device.recording()
    with device.tape() as outer:
        device.record(device.pack(**{"push_back.waves": 1}))
        with device.tape() as inner:
            assert device.recording()
            device.record(device.pack(**{"push_back.waves": 10}))
        device.record(device.pack(**{"push_back.waves": 2}))
    assert not device.recording()
    assert device.as_dict(outer.total())["push_back.waves"] == 3.0
    assert device.as_dict(inner.total())["push_back.waves"] == 10.0
    # an empty tape still totals to a well-formed zero vector
    with device.tape() as t:
        pass
    assert sum(device.as_dict(t.total()).values()) == 0.0


def test_plane_never_syncs_until_counters_are_read(monkeypatch):
    """add() and flush() are device-only; the single drain point is the
    registry read — same contract as ``Counter.add_lazy``."""
    reg = MetricsRegistry()
    plane = DeviceCounterPlane(reg)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    plane.add(device.pack(**{"slab_append.waves": 1, "slab_append.lanes": 128}))
    plane.add(device.pack(**{"slab_append.waves": 1, "slab_append.lanes": 128}))
    assert plane.pending == 2
    assert calls == [], "add() must be a list append"
    plane.flush()
    assert plane.pending == 0
    assert calls == [], "flush() hands device scalars to add_lazy — no sync"
    got = plane.counters()
    assert len(calls) > 0, "counters() is the drain point"
    assert got["slab_append.waves"] == 2.0
    assert got["slab_append.lanes"] == 256.0
    # drained counters live under the device. prefix in the shared registry
    assert reg.counter("device.slab_append.waves").total() == 2.0


# --------------------------------------------------------------------------
# in-kernel counters == jnp oracle, per kernel family
# --------------------------------------------------------------------------

def _fleet(rng, S, N, P, npages):
    pages = np.full((N, P), -1, np.int32)
    perm = rng.permutation(S)
    k = 0
    for i, c in enumerate(npages):
        for p in range(c):
            pages[i, p] = perm[k]
            k += 1
    return jnp.asarray(pages)


@pytest.mark.parametrize("memory_space", SPACES)
def test_push_back_counters_match_oracle(memory_space):
    rng = np.random.default_rng(3)
    nblocks, b0, m = 5, 2, 11
    arr = gg.init(nblocks, b0, nbuckets=2)
    elems = jnp.asarray(rng.standard_normal((nblocks, m)), jnp.float32)
    mask = jnp.asarray(rng.random((nblocks, m)) < 0.6)
    sizes = jnp.asarray(rng.integers(0, 5, (nblocks,)), jnp.int32)
    groups = (arr.buckets, arr.buckets)
    outs = pb_ops.push_back_fused_multi(
        groups, sizes, b0, (elems, elems), mask,
        memory_space=memory_space, instrument=True,
    )
    want = pb_ops.push_back_fused_multi(
        groups, sizes, b0, (elems, elems), mask, use_ref=True, instrument=True,
    )
    np.testing.assert_array_equal(np.asarray(outs[3]), np.asarray(want[3]))
    d = device.as_dict(outs[3])
    assert d["push_back.waves"] == 1.0
    assert d["push_back.active_lanes"] == float(jnp.sum(mask))
    assert d["push_back.lanes"] >= d["push_back.active_lanes"]
    assert d["push_back.lanes"] >= nblocks * m
    assert d["push_back.padded_lanes"] == d["push_back.lanes"] - nblocks * m
    # the data outputs are untouched by instrumentation
    plain = pb_ops.push_back_fused_multi(
        groups, sizes, b0, (elems, elems), mask, memory_space=memory_space,
    )
    for g_i, g_p in zip(outs[0], plain[0]):
        for a, b in zip(g_i, g_p):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(outs[2]), np.asarray(plain[2]))


def test_push_back_degenerate_empty_wave_counts_zero():
    arr = gg.init(3, 2, nbuckets=1)
    sizes = jnp.zeros((3,), jnp.int32)
    elems = jnp.zeros((3, 0), jnp.float32)
    mask = jnp.zeros((3, 0), bool)
    outs = pb_ops.push_back_fused_multi(
        (arr.buckets,), sizes, 2, (elems,), mask, instrument=True,
    )
    assert sum(device.as_dict(outs[3]).values()) == 0.0


@pytest.mark.parametrize("memory_space", SPACES)
def test_paged_gather_counters_match_oracle(memory_space):
    rng = np.random.default_rng(4)
    S, T, N, P = 11, 4, 5, 3
    pool = jnp.asarray(rng.standard_normal((S, T, 3)), jnp.float32)
    pages = _fleet(rng, S, N, P, [3, 0, 2, 1, 3])
    out, vec = pg_ops.paged_gather(
        pool, pages, memory_space=memory_space, instrument=True
    )
    want_out, want_vec = pg_ops.paged_gather(
        pool, pages, use_ref=True, memory_space=memory_space, instrument=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(vec), np.asarray(want_vec))
    d = device.as_dict(vec)
    live = int(np.sum(np.asarray(pages) >= 0))
    assert d["paged_gather.tiles"] == float(live)
    # masked tiles cover the −1 entries plus the walk's row-tile padding
    assert d["paged_gather.masked_tiles"] >= float(N * P - live)
    assert d["paged_gather.launches"] >= 1.0


@pytest.mark.parametrize("memory_space", SPACES)
def test_paged_attend_counters_match_oracle(memory_space, monkeypatch):
    monkeypatch.setenv("REPRO_MEMORY_SPACE", memory_space)  # one tiling: no effect
    rng = np.random.default_rng(5)
    S, T, N, P, KH, G, D = 13, 4, 5, 3, 2, 3, 8
    pages = _fleet(rng, S, N, P, [3, 1, 2, 1, 3])
    kp = jnp.asarray(rng.standard_normal((S, T, KH, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((S, T, KH, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((N, KH, G, D)), jnp.float32)
    lengths = jnp.asarray([9, 2, 8, 1, 12], jnp.int32)
    out, vec = pg_ops.paged_attend(q, kp, vp, pages, lengths, instrument=True)
    want_out, want_vec = pg_ops.paged_attend(
        q, kp, vp, pages, lengths, use_ref=True, instrument=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(vec), np.asarray(want_vec))
    d = device.as_dict(vec)
    # a visited tile is one (row, block) step over every KV head, T token
    # lanes here (one block per slab); waste = lanes past kv_len
    assert d["paged_attend.lanes"] == d["paged_attend.tiles"] * T
    assert 0 < d["paged_attend.masked_lanes"] < d["paged_attend.lanes"]
    assert d["paged_attend.tiles_skipped"] > 0  # −1 pages were gated off


@pytest.mark.parametrize("memory_space", SPACES)
def test_flatten_counters_match_oracle(memory_space):
    rng = np.random.default_rng(6)
    nblocks, b0 = 5, 2
    arr = gg.init(nblocks, b0, nbuckets=1)
    per = rng.integers(0, 7, nblocks)
    m = max(int(per.max()), 1)
    elems = jnp.asarray(rng.standard_normal((nblocks, m)), jnp.float32)
    mask = jnp.asarray(np.arange(m)[None, :] < per[:, None])
    arr, _ = gg.push_back(arr, elems, mask)
    out, vec = fl_ops.flatten_segmented(
        arr.buckets, arr.sizes, arr.b0,
        memory_space=memory_space, instrument=True,
    )
    want_out, want_vec = fl_ops.flatten_segmented(
        arr.buckets, arr.sizes, arr.b0, use_ref=True, instrument=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(vec), np.asarray(want_vec))
    d = device.as_dict(vec)
    # span_rows counts elements (Σ sizes); rows_touched counts compact-block
    # rows the gather visited — nonzero whenever there is anything to move
    assert d["flatten.span_rows"] == float(jnp.sum(arr.sizes))
    assert d["flatten.launches"] == 1.0
    assert d["flatten.rows_touched"] > 0


def test_slab_append_counters_report_wave_occupancy():
    rng = np.random.default_rng(7)
    S, T, N, P, m = 14, 4, 4, 4, 3
    pages = np.asarray(_fleet(rng, S, N, P, [4, 2, 3, 4]))
    owners = np.full((S,), -1, np.int32)
    bases = np.zeros((S,), np.int32)
    for i in range(N):
        for p in range(P):
            if pages[i, p] >= 0:
                owners[pages[i, p]] = i
                bases[pages[i, p]] = p * T
    sizes = np.asarray([7, 1, 5, 10], np.int32)
    pool = jnp.asarray(rng.standard_normal((S, T)), jnp.float32)
    elems = jnp.asarray(rng.standard_normal((N, m)), jnp.float32)
    mask = jnp.asarray(rng.random((N, m)) > 0.4)
    outs = pg_ops.slab_append(
        pool, jnp.asarray(owners), jnp.asarray(bases), jnp.asarray(sizes),
        elems, mask, instrument=True,
    )
    assert len(outs) == 4
    d = device.as_dict(outs[3])
    assert d["slab_append.waves"] == 1.0
    assert d["slab_append.active_lanes"] == float(jnp.sum(mask))
    assert d["slab_append.lanes"] >= N * m
    plain = pg_ops.slab_append(
        pool, jnp.asarray(owners), jnp.asarray(bases), jnp.asarray(sizes),
        elems, mask,
    )
    assert len(plain) == 3  # instrumentation off → bare outputs


# --------------------------------------------------------------------------
# provably free when off
# --------------------------------------------------------------------------

def test_instrument_off_trace_is_unchanged_by_instrumented_traces():
    """Tracing an instrumented program must not contaminate later
    uninstrumented traces (a leaked tape would)."""
    rng = np.random.default_rng(8)
    arr = gg.init(4, 2, nbuckets=1)
    elems = jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)
    mask = jnp.asarray(rng.random((4, 5)) < 0.6)
    sizes = jnp.zeros((4,), jnp.int32)

    def run_off(b, s, e, mk):
        return pb_ops.push_back_fused(b, s, 2, e, mk)

    before = str(jax.make_jaxpr(run_off)(arr.buckets, sizes, elems, mask))
    with device.tape():
        pb_ops.push_back_fused(
            arr.buckets, sizes, 2, elems, mask, instrument=True
        )
    after = str(jax.make_jaxpr(run_off)(arr.buckets, sizes, elems, mask))
    assert before == after


def test_instrument_flag_keys_the_shared_jit_cache():
    """``instrument`` rides the frozen ModelConfig: replace() with the same
    value is the SAME cached step callable (zero extra compiles when off);
    flipping it is a different program."""
    from repro.configs import reduced
    from repro.serving import engine as eng

    cfg = reduced("qwen2.5-3b", cache_b0=4)
    assert cfg.instrument is False
    same = dataclasses.replace(cfg, instrument=False)
    flipped = dataclasses.replace(cfg, instrument=True)
    assert eng._decode_step_fn(cfg) is eng._decode_step_fn(same)
    assert eng._decode_step_fn(cfg) is not eng._decode_step_fn(flipped)
    assert eng._prefill_chunk_fn(cfg) is eng._prefill_chunk_fn(same)
