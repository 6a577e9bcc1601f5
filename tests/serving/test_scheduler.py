"""Scheduler invariants — property-tested without a model.

The admission scheduler is pure host state over a ``PageBook``, so the §7
serving invariants are checkable by simulation: this file acts as the engine
(claiming slabs per chunk task, releasing on completion) and asserts after
every event that

* slabs are conserved: pages owned by busy slots == allocator live count,
  and the allocator's own free-list/owner cross-checks pass;
* no slab is double-claimed (every claimed id was free, every id released
  exactly once);
* reservations never exceed the free list, and an admitted request can
  always cover its remaining chunks from its reservation — even while a
  decode-growth adversary claims unreserved slabs between chunks;
* admission is FIFO within equal slab need, with bounded skip-ahead
  (no request starves: the aged head blocks the queue until it fits);
* chunk plans tile ``[0, L)`` exactly, widths drawn from the bucket set
  (the final chunk too: hybrid layouts mask its padding out of the
  recurrence instead of compiling its exact width).
"""
import collections

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # property tests skip, example tests still run
    from _hypothesis_fallback import given, settings, st

from repro.pool import PageBook, QuotaExceeded
from repro.serving.scheduler import ChunkTask, Scheduler, bucket_for, bucket_widths


# ---------------------------------------------------------------- examples
def test_bucket_widths_geometric():
    assert bucket_widths(4, 32) == (4, 8, 16, 32)
    assert bucket_widths(8, 8) == (8,)
    assert bucket_widths(3, 20) == (3, 6, 12, 20)  # capped at chunk
    assert bucket_widths(64, 32) == (32,)  # b0 above chunk collapses
    with pytest.raises(ValueError):
        bucket_widths(0, 32)


def test_bucket_for_smallest_cover():
    bk = (4, 8, 16, 32)
    assert bucket_for(1, bk) == 4
    assert bucket_for(4, bk) == 4
    assert bucket_for(5, bk) == 8
    assert bucket_for(32, bk) == 32
    with pytest.raises(ValueError):
        bucket_for(33, bk)


def _mk(nslots=3, slab_tokens=4, chunk=8, **kw):
    book = PageBook(nslots, quota_slabs=kw.pop("quota_slabs", None))
    sched = Scheduler(book, slab_tokens=slab_tokens, chunk=chunk, **kw)
    return book, sched


def _grow(book):
    def ensure(short):
        book.grow(short)
        return True

    return ensure


def _run_prefill(book, sched):
    """Drive every planned chunk to completion; return the executed tasks."""
    done = []
    while sched.prefilling:
        for task in sched.next_chunks():
            if task.new_slabs:
                book.claim(task.slot, task.new_slabs, from_reservation=True)
            sched.chunk_done(task)
            done.append(task)
    return done


def test_chunks_tile_prompt_exactly():
    book, sched = _mk(chunk=8, slab_tokens=4)
    sched.submit(7, length=21)  # 8 + 8 + 5 → widths 8, 8, 8 (bucketed)
    assert [r for r, _, _ in sched.admit(_grow(book))] == [7]
    tasks = _run_prefill(book, sched)
    assert [(t.t0, t.live, t.width, t.final) for t in tasks] == [
        (0, 8, 8, False),
        (8, 8, 8, False),
        (16, 5, 8, True),
    ]
    assert sum(t.new_slabs for t in tasks) == sched.slabs_for(21)
    assert sched.decoding == [0] and not sched.prefilling


@pytest.mark.parametrize("length", [1, 5, 8, 13, 21, 23])
def test_final_chunk_pads_to_its_bucket(length):
    """Every tail takes the smallest bucket that holds it, so the widths a
    fleet compiles are the bucket set, whatever its prompt lengths."""
    book, sched = _mk(chunk=8, slab_tokens=4)
    sched.submit(0, length=length)
    sched.admit(_grow(book))
    tasks = _run_prefill(book, sched)
    tail = tasks[-1]
    assert tail.final and tail.width == bucket_for(tail.live, sched.buckets)
    assert all(t.width in sched.buckets for t in tasks)


def test_match_hook_shrinks_reservation_to_uncached_suffix():
    """Prefix-cache hook (DESIGN.md §10): a cached prefix shrinks the
    reservation to the uncached suffix and prefill starts at its t0; a
    fully cached prompt admits with zero prefill chunks."""
    book, sched = _mk(chunk=8, slab_tokens=4)
    cached = {7: 8, 8: 12, 9: 0}  # rid → cached prefix tokens
    sched.submit(7, length=14)  # 2 of 4 slabs cached → reserve 2
    sched.submit(8, length=12)  # fully cached → reserve 0, no prefill
    sched.submit(9, length=5)  # cold → whole need reserved
    admits = sched.admit(_grow(book), match=lambda r, L: cached[r])
    assert [(r, need) for r, _, need in admits] == [(7, 2), (8, 0), (9, 2)]
    slot = {r: s for r, s, _ in admits}
    assert sched.phase[slot[8]] == "decode" and slot[8] not in sched.prefilling
    assert int(sched.t0[slot[7]]) == 8 and int(sched.t0[slot[9]]) == 0
    # the caller aliases cached slabs before chunks run; model the trie as
    # an off-slot holder and alias into the admitted slot
    book.grow(2)
    cached_ids = book.alloc.claim(99, 2)  # stand-in for trie-held slabs
    book.alias(slot[7], cached_ids)
    tasks = _run_prefill(book, sched)
    assert [(t.rid, t.t0, t.live, t.final) for t in tasks] == [
        (7, 8, 6, True),  # suffix-only chunk, resumed at the cached t0
        (9, 0, 5, True),
    ]
    assert sum(t.new_slabs for t in tasks if t.rid == 7) == 2


def test_fifo_within_equal_need():
    book, sched = _mk(nslots=4)
    for rid, L in enumerate([9, 9, 9]):  # identical slab need
        sched.submit(rid, L)
    admitted = [r for r, _, _ in sched.admit(_grow(book))]
    assert admitted == [0, 1, 2]


def test_skip_ahead_admits_smaller_later_request():
    book, sched = _mk(nslots=2)
    book.grow(2)  # fixed 2-slab pool, no growth allowed
    sched.submit(0, length=40)  # needs 10 slabs — can never fit
    sched.submit(1, length=4)  # needs 1 slab — fits now
    admitted = [r for r, _, _ in sched.admit(lambda s: False)]
    assert admitted == [1]
    assert [w.rid for w in sched.pending] == [0]


def test_starved_head_blocks_queue():
    book, sched = _mk(nslots=2, starvation_limit=2)
    book.grow(2)
    sched.submit(0, length=12)  # needs 3 — never fits the 2-slab pool
    sched.submit(1, length=4)  # needs 1 — skips ahead (skip #1 for the head)
    assert [r for r, _, _ in sched.admit(lambda s: False)] == [1]
    slot1 = sched.rid_of_slot.index(1)
    book.release(slot1), sched.complete(slot1)
    sched.submit(2, length=4)  # would fit, but the head has now aged out…
    assert sched.admit(lambda s: False) == []  # skip #2 → head-of-line block
    # Growth lets the aged head in; FIFO resumes behind it.
    assert [r for r, _, _ in sched.admit(_grow(book))] == [0, 2]


def test_quota_breach_raises_and_preserves_queue():
    book, sched = _mk(nslots=2, quota_slabs=2)
    sched.submit(0, length=4)
    sched.submit(1, length=40)  # needs 10 > quota 2: can never admit
    sched.submit(2, length=4)
    with pytest.raises(QuotaExceeded):
        sched.admit(_grow(book))
    # rid=0 admitted before the raise; 1 and 2 still queued, in order.
    assert sched.rid_of_slot[0] == 0
    assert [w.rid for w in sched.pending] == [1, 2]


def test_reservation_shields_prefill_from_decode_growth():
    book, sched = _mk(nslots=2, chunk=8, slab_tokens=4)
    book.grow(6)
    sched.submit(0, length=24)  # needs 6 slabs — reserve all of them
    sched.admit(lambda s: False)
    assert book.alloc.reserved_total == 6
    # A decode tenant sees free − reserved: claiming 1 unreserved slab is a
    # shortfall even though 6 slabs are physically free.
    assert book.shortfall(1, tenant=1) == 1
    # The prefill itself draws from its reservation unimpeded.
    tasks = _run_prefill(book, sched)
    assert sum(t.new_slabs for t in tasks) == 6
    assert book.alloc.reserved_total == 0
    book.alloc.check()


# ------------------------------------------------------------- telemetry
def test_queue_metrics_against_hand_scheduled_trace():
    """Starvation-limit skip-ahead and per-request queue wait, asserted
    event-for-event against a hand-scheduled trace (ISSUE 8 satellite):

    tick 0: rid0 (needs 3 > 2-slab pool) skipped (#1); rid1 admitted, wait 0.
    tick 1: rid1 done; rid2 submitted; rid0 skipped (#2) → aged head blocks
            the queue, so rid2 (which would fit) is NOT admitted.
    tick 2: growth lets rid0 in (wait 2 ticks); rid2 follows (wait 1).
    """
    book, sched = _mk(nslots=2, starvation_limit=2)
    book.grow(2)
    reg = sched.obs.registry
    skips = reg.counter("sched.starvation_skips")
    blocks = reg.counter("sched.head_blocks")
    waits = reg.histogram("sched.queue_wait_ticks")

    sched.submit(0, length=12)  # needs 3 — never fits the 2-slab pool
    sched.submit(1, length=4)
    assert [r for r, _, _ in sched.admit(lambda s: False)] == [1]
    assert skips.total() == 1 and blocks.total() == 0
    assert waits.values(rid=1) == [0.0]

    slot1 = sched.rid_of_slot.index(1)
    book.release(slot1), sched.complete(slot1)
    sched.submit(2, length=4)
    assert sched.admit(lambda s: False) == []  # skip #2 → head-of-line block
    assert skips.total() == 2 and blocks.total() == 1
    assert waits.count() == 1, "nothing admitted while the head blocks"

    assert [r for r, _, _ in sched.admit(_grow(book))] == [0, 2]
    assert waits.values(rid=0) == [2.0]  # waited ticks 0 and 1
    assert waits.values(rid=2) == [1.0]  # submitted at tick 1, admitted at 2
    assert skips.total() == 2 and blocks.total() == 1  # growth ended the block
    # the timeline saw the same story, in order
    names = [e["name"] for e in sched.obs.tracer.events]
    assert names == ["starve_skip", "starve_skip", "head_block"]
    assert sched.obs.tracer.events[-1]["attrs"] == {"rid": 0}


def test_queue_wait_zero_for_immediate_admission():
    book, sched = _mk(nslots=3)
    for rid in range(3):
        sched.submit(rid, length=4)
    assert len(sched.admit(_grow(book))) == 3
    waits = sched.obs.registry.histogram("sched.queue_wait_ticks")
    assert waits.values() == [0.0, 0.0, 0.0]
    assert sched.tick == 1  # exactly one completed admit round


# ---------------------------------------------------------------- property
@given(
    st.lists(st.integers(min_value=1, max_value=40), min_size=1, max_size=12),
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_scheduler_simulation_invariants(lengths, seed):
    rng = np.random.default_rng(seed)
    nslots, slab_tokens, chunk = 3, 4, 8
    pool_cap = 16  # fixed pool: growth allowed up to the cap, then refused
    book = PageBook(nslots)
    sched = Scheduler(book, slab_tokens=slab_tokens, chunk=chunk)

    def ensure(short):
        if book.alloc.n_slabs + short > pool_cap:
            return False
        book.grow(short)
        return True

    submit_order = list(range(len(lengths)))
    need_of = {r: sched.slabs_for(L) for r, L in zip(submit_order, lengths)}
    for rid, L in zip(submit_order, lengths):
        sched.submit(rid, L)

    admitted_order: list[int] = []
    chunks_of: dict[int, list[ChunkTask]] = collections.defaultdict(list)
    completed: set[int] = set()
    live_ids: set[int] = set()  # slabs currently claimed by any slot

    def check_conservation():
        book.alloc.check()  # free list ∪ owned partition, reservation ledger
        owned = sum(int(book.npages[s]) for s in range(nslots))
        assert owned == book.alloc.live_count
        assert book.alloc.reserved_total <= book.alloc.free_count

    for _ in range(500):
        if not sched.busy:
            break
        for rid, slot, need in sched.admit(ensure):
            admitted_order.append(rid)
            assert need == need_of[rid]
        check_conservation()
        for task in sched.next_chunks():
            if task.new_slabs:
                ids, _ = book.claim(task.slot, task.new_slabs, from_reservation=True)
                got = set(ids.tolist())
                assert not got & live_ids  # no slab double-claimed
                live_ids |= got
                for i in got:
                    assert book.alloc.owner[i] == task.slot
            sched.chunk_done(task)
            chunks_of[task.rid].append(task)
            check_conservation()
        # Decode phase: adversarial growth claims + probabilistic completion.
        for slot in list(sched.decoding):
            if rng.random() < 0.3 and book.shortfall(1, tenant=slot) == 0:
                ids, _ = book.claim(slot, 1)  # growth — never touches reserved
                assert not set(ids.tolist()) & live_ids
                live_ids |= set(ids.tolist())
                check_conservation()
            if rng.random() < 0.5:
                freed = set(book.release(slot).tolist())
                assert freed <= live_ids  # released exactly what was claimed
                live_ids -= freed
                completed.add(sched.rid_of_slot[slot])
                sched.complete(slot)
                check_conservation()
    else:
        pytest.fail("scheduler did not drain in 500 steps (starvation?)")

    # Everyone ran: admitted exactly once, completed, chunks tile [0, L).
    assert sorted(admitted_order) == submit_order
    assert completed == set(submit_order)
    for rid, L in zip(submit_order, lengths):
        tasks = chunks_of[rid]
        t0 = 0
        for t in tasks:
            assert t.t0 == t0 and t.live >= 1
            assert t.width in sched.buckets and t.width >= t.live
            t0 += t.live
        assert t0 == L and tasks[-1].final
        assert sum(t.new_slabs for t in tasks) == need_of[rid]
    # FIFO within equal slab need (deterministic ensure → a skipped need
    # blocks every equal need behind it in the same scan).
    pos = {r: i for i, r in enumerate(admitted_order)}
    for a in submit_order:
        for b in submit_order:
            if a < b and need_of[a] == need_of[b]:
                assert pos[a] < pos[b], (a, b, admitted_order)
