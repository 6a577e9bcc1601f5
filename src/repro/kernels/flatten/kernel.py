"""Bucket-compaction + segmented-gather kernels — GGArray flatten (§VI.D).

The two-phase pattern flattens the bucket chain into a contiguous array once
per growth phase.  Per-block compaction is *fully static*: bucket level ``b``
always lands at column ``B0·(2^b − 1)`` of the per-block row (the LFVector
address map), so that kernel is a pure copy with static offsets.

The dynamic part — block-major global ordering by the runtime prefix table —
has two implementations:

``segmented_gather_pallas`` (the default, O(n))
    One grid step per output tile.  Each output index ``i`` belongs to the
    block whose ``block_starts`` interval contains it; locating the owner is
    a broadcasted compare-and-count against the (tiny) prefix table (a
    vectorized ``searchsorted``), and the element itself is a single gather
    from the compacted rows.  Work is O(capacity · log-ish nblocks) — linear
    in the array, unlike the one-hot dispatch matmul which multiplies a
    (T × S) one-hot against the data and is quadratic in the element count.
    This is what lets the freeze step of the two-phase runtime run at copy
    speed (DESIGN.md §2).

``dispatch_mxu`` (legacy, O(n²))
    Reuses the one-hot scatter matmul kernel, kept as a comparison point for
    ``benchmarks/bench_two_phase.py`` and as the MXU-friendly fallback.

Memory spaces (``common.GridPlan``, DESIGN.md §4.7): the ``vmem`` tilings
keep the whole compacted ``(nblocks, cap)`` plane (gather) / every level's
block-tile rows (compaction) resident per grid step.  On the ``hbm`` path
the planes stay in HBM: compaction is one HBM→HBM DMA per level (level →
its static columns), and the gather lays the compacted plane out as
``(nblocks, rows, 128)`` and, per output tile of ``DEFAULT_SEG_TILE``
indices, walks the block span ``[lo_t, hi_t)`` precomputed from the prefix
table: for each block it DMAs the aligned window of the block's row that
holds the tile's slice and shifts it into place with lane and sublane rolls
(Mosaic has no 1-D gather) — Σ spans ≈ nblocks + ntiles window DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import indexing
from repro.kernels import common
from repro.obs import device

__all__ = ["compact_blocks_pallas", "segmented_gather_pallas"]


def _seg_ctr(ctr_ref, t, lo, hi):
    """One gather tile's device counters: ``rows_touched`` is this tile's
    block span ``hi − lo`` — exactly the rows the hbm tiling DMAs (the vmem
    tiling computes the same span from the prefix table, so the counter is
    space-invariant)."""
    first = t == 0
    device.ctr_accum(ctr_ref, first, [
        ("flatten.launches", jnp.where(first, 1, 0)),
        ("flatten.rows_touched", hi - lo),
    ])

DEFAULT_BLOCK_TILE = 8
DEFAULT_SEG_TILE = 4096  # 32 rows of 128 lanes per output tile


# --------------------------------------------------------------------------
# compaction — bucket levels → (nblocks, capacity) rows, static columns.
# --------------------------------------------------------------------------

def _compact_vmem(*refs, starts):
    """refs = (*level_refs, out_ref); copy each level to its static columns."""
    *levels, out = refs
    for b, ref in enumerate(levels):
        size = ref.shape[1]
        out[:, starts[b] : starts[b] + size] = ref[...]


def _compact_hbm(*refs, starts, sizes):
    """Pure DMA program: each level → its static output columns (HBM→HBM),
    all rows at once; every copy is started before any is awaited."""
    *levels, out, sem = refs
    copies = [
        pltpu.make_async_copy(
            ref, out.at[:, pl.ds(starts[b], sizes[b])], sem.at[b]
        )
        for b, ref in enumerate(levels)
    ]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()


def compact_blocks_pallas(
    buckets: tuple[jax.Array, ...],  # level b: (nblocks, B0·2^b)
    b0: int,
    *,
    block_tile: int = DEFAULT_BLOCK_TILE,
    memory_space: str = "vmem",
    interpret: bool = False,
) -> jax.Array:
    """→ (nblocks, capacity) row-compacted array (in-block positions)."""
    nblocks = buckets[0].shape[0]
    nbuckets = len(buckets)
    if nblocks % block_tile:
        raise ValueError(f"nblocks {nblocks} must divide by tile {block_tile}")
    cap = indexing.capacity(b0, nbuckets)
    starts = indexing.bucket_starts(b0, nbuckets)
    sizes = indexing.bucket_sizes(b0, nbuckets)
    out_shape = jax.ShapeDtypeStruct((nblocks, cap), buckets[0].dtype)
    if memory_space == "hbm":
        any_spec = pl.BlockSpec(memory_space=pltpu.ANY)
        plan = common.GridPlan(
            memory_space="hbm",
            grid=(1,),
            num_tables=0,
            table_specs=(),
            in_specs=[any_spec] * nbuckets,
            out_specs=any_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA((nbuckets,))],
        )
        kernel = functools.partial(_compact_hbm, starts=starts, sizes=sizes)
        return plan.pallas_call(kernel, out_shape, interpret=interpret)(*buckets)
    plan = common.GridPlan(
        memory_space="vmem",
        grid=(nblocks // block_tile,),
        num_tables=0,
        table_specs=(),
        in_specs=[
            pl.BlockSpec((block_tile, sz), lambda i, s=None: (i, 0)) for sz in sizes
        ],
        out_specs=pl.BlockSpec((block_tile, cap), lambda i: (i, 0)),
    )
    kernel = functools.partial(_compact_vmem, starts=starts)
    return plan.pallas_call(kernel, out_shape, interpret=interpret)(*buckets)


# --------------------------------------------------------------------------
# segmented gather — block-major global ordering off the prefix table.
# --------------------------------------------------------------------------

def _seg_gather_vmem(
    starts_ref, ends_ref, compact_ref, *refs, seg_tile, instrument=False,
):
    o_ref = refs[0]
    """One output tile of the block-major global order.

    ``starts``/``ends`` are the runtime prefix-sum table (exclusive /
    inclusive-end per block); ``compact`` is the row-compacted plane.  The
    owning block of output index ``i`` is ``#{b : starts[b] <= i} - 1`` —
    valid because starts is non-decreasing with starts[0] == 0.
    """
    t = pl.program_id(0)
    nblocks, cap = compact_ref.shape
    idx = t * seg_tile + jax.lax.broadcasted_iota(jnp.int32, (seg_tile, 1), 0)[:, 0]
    starts = starts_ref[0, :]  # (nblocks,)
    ends = ends_ref[0, :]
    # Vectorized searchsorted over the on-chip prefix table: (seg_tile, nblocks)
    # compares, then a lane reduction — O(nblocks) per element, no matmul.
    owned = idx[:, None] >= starts[None, :]
    blk = jnp.sum(owned.astype(jnp.int32), axis=1) - 1
    blk = jnp.maximum(blk, 0)
    pos = idx - jnp.take(starts, blk)
    live = idx < jnp.take(ends, blk)
    # Single gather from the compacted plane (linearized to one axis).
    lin = blk * cap + jnp.minimum(pos, cap - 1)
    vals = jnp.take(compact_ref[...].reshape(-1), lin)
    o_ref[0, :] = jnp.where(live, vals, jnp.zeros_like(vals))
    if instrument:
        tbase = t * seg_tile
        lo = jnp.maximum(jnp.sum((starts <= tbase).astype(jnp.int32)) - 1, 0)
        hi = jnp.sum((starts <= tbase + seg_tile - 1).astype(jnp.int32))
        _seg_ctr(refs[1], t, lo, hi)


def _seg_gather_hbm(
    starts_ref, ends_ref, lo_ref, hi_ref, last_ref, compact_ref, *refs,
    cap, instrument=False,
):
    """One ``(R, 128)`` output tile (``R·128`` consecutive global indices).

    ``compact`` is the row-compacted plane laid out ``(nblocks, rows, 128)``
    in HBM.  For each block of the tile's precomputed span ``[lo_t, hi_t)``
    the output is the block's row shifted by ``d = tile_base − start``:
    ``out[q] = row[q + d]``.  The kernel DMAs the aligned ``2R``-row window
    of the row that holds ``[d, d + R·128)``, then shifts it on the VPU —
    a lane roll by ``d mod 128`` and a sublane roll by ``d // 128``, the
    lanes past the carry taken from the next row — and claims the lanes
    whose global index falls in the block's ``[start, end)``.  Intervals
    are disjoint, so each live lane is claimed once; dead lanes stay 0.
    Positions past ``cap`` (a size that overflowed its capacity) repeat the
    row's last slot, as the oracle's clamped index does — ``last`` holds
    those slots' words.
    """
    o_ref, buf, sem = refs[0], refs[-2], refs[-1]
    t = pl.program_id(0)
    R = o_ref.shape[0]
    W = buf.shape[0]  # 2R window rows
    nrows = compact_ref.shape[1]
    align = common.tile_rows(compact_ref.dtype)
    base = t * (R * 128)
    q = (
        jax.lax.broadcasted_iota(jnp.int32, (R, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)

    def claim(b, acc):
        s, e = starts_ref[b], ends_ref[b]
        d = base - s
        r0 = jnp.minimum(
            (jnp.maximum(d, 0) // (align * 128)) * align, nrows - W
        )
        r0 = pl.multiple_of(r0, align)
        cp = pltpu.make_async_copy(compact_ref.at[b, pl.ds(r0, W)], buf, sem)
        cp.start()
        cp.wait()
        dd = d - r0 * 128 + W * 128  # shifted positive: dd ∈ (W·64, 2W·128)
        k = dd // 128 - W  # sublane shift, may be negative
        l = dd % 128  # lane shift
        y = pltpu.roll(common.to_words(buf[...]), (128 - l) % 128, 1)
        z0 = pltpu.roll(y, (W - k) % W, 0)[:R]  # z0[s] = y[s + k]
        z1 = pltpu.roll(y, (2 * W - k - 1) % W, 0)[:R]  # z1[s] = y[s + k + 1]
        vals = jnp.where(lane < 128 - l, z0, z1)
        g = base + q
        vals = jnp.where(g - s >= cap, last_ref[b], vals)
        return jnp.where((g >= s) & (g < e), vals, acc)

    zero = jnp.zeros((R, 128), jnp.int32)
    acc = jax.lax.fori_loop(lo_ref[t], hi_ref[t], claim, zero)
    o_ref[...] = common.from_words(acc, o_ref.dtype)
    if instrument:
        _seg_ctr(refs[1], t, lo_ref[t], hi_ref[t])


def segmented_gather_pallas(
    compact: jax.Array,  # (nblocks, cap) row-compacted in-block positions
    starts: jax.Array,  # (nblocks,) int32 exclusive prefix sums of sizes
    ends: jax.Array,  # (nblocks,) int32 starts + sizes
    *,
    seg_tile: int = DEFAULT_SEG_TILE,
    memory_space: str = "vmem",
    instrument: bool = False,
    interpret: bool = False,
):
    """→ (nblocks·cap,) live elements in block-major global order, rest 0.

    The grid covers ``ceil(total / seg_tile)`` tiles; overhang indices in the
    last tile clamp to the final slot and fail the liveness test, so no input
    padding is needed for non-tile-aligned capacities.  With
    ``instrument=True`` → (out, counter block).
    """
    nblocks, cap = compact.shape
    total = nblocks * cap
    ntiles = -(-total // seg_tile)
    total_pad = ntiles * seg_tile
    starts = starts.reshape(nblocks).astype(jnp.int32)
    ends = ends.reshape(nblocks).astype(jnp.int32)
    out_shape = jax.ShapeDtypeStruct((1, total_pad), compact.dtype)
    if memory_space == "hbm":
        align = common.tile_rows(compact.dtype)
        if seg_tile % (128 * align):
            raise ValueError(f"hbm seg_tile {seg_tile} must hold whole tiles")
        R = seg_tile // 128
        # per-tile block spans off the prefix table (ops-level jnp, tiny)
        tbase = jnp.arange(ntiles, dtype=jnp.int32) * seg_tile
        lo = jnp.maximum(
            jnp.sum(starts[None, :] <= tbase[:, None], axis=1) - 1, 0
        )
        hi = jnp.sum(starts[None, :] <= (tbase + seg_tile - 1)[:, None], axis=1)
        # rows of 128 lanes per block, at least one 2R-row window, aligned
        nrows = max(-(-cap // 128), 2 * R)
        nrows += (-nrows) % align
        plane = jnp.pad(compact, ((0, 0), (0, nrows * 128 - cap)))
        plane = plane.reshape(nblocks, nrows, 128)
        plan = common.GridPlan(
            memory_space="hbm",
            grid=(ntiles,),
            num_tables=5,
            table_specs=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((R, 128), lambda t, *tables: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2 * R, 128), compact.dtype),
                pltpu.SemaphoreType.DMA,
            ],
            instrument=instrument,
        )
        kernel = functools.partial(
            _seg_gather_hbm, cap=cap, instrument=instrument
        )
        last = common.to_words(compact[:, cap - 1])
        outs = plan.pallas_call(
            kernel,
            jax.ShapeDtypeStruct((ntiles * R, 128), compact.dtype),
            interpret=interpret,
        )(starts, ends, lo, hi, last, plane)
        if instrument:
            return outs[0].reshape(-1)[:total], outs[1]
        return outs.reshape(-1)[:total]
    plan = common.GridPlan(
        memory_space="vmem",
        grid=(ntiles,),
        num_tables=2,
        table_specs=[
            pl.BlockSpec((1, nblocks), lambda t: (0, 0)),
            pl.BlockSpec((1, nblocks), lambda t: (0, 0)),
        ],
        in_specs=[pl.BlockSpec((nblocks, cap), lambda t: (0, 0))],
        out_specs=pl.BlockSpec((1, seg_tile), lambda t: (0, t)),
        instrument=instrument,
    )
    kernel = functools.partial(
        _seg_gather_vmem, seg_tile=seg_tile, instrument=instrument
    )
    outs = plan.pallas_call(kernel, out_shape, interpret=interpret)(
        starts.reshape(1, nblocks), ends.reshape(1, nblocks), compact
    )
    if instrument:
        return outs[0][0, :total], outs[1]
    return outs[0, :total]
