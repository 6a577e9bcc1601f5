"""Fused push-back kernel — offsets + multi-level scatter in one tiled pass.

The jnp append path is two dispatches: an exclusive prefix sum of the mask
(``core.insertion``) and then one scatter per bucket level.  This kernel fuses
the whole write phase: one grid step per block tile computes the per-block
offsets on the VPU (``cumsum``), resolves the dense insert permutation
(:func:`apply_insert_permutation` — exact int32 one-hot reduction, or the
``kernels/dispatch_mxu`` matmul for waves at least ``common.MXU_DISPATCH_WAVE``
lanes wide), and writes every bucket level in the same pass.

The scatter is expressed as a *gather* per level — output slot ``start_b + j``
takes wave element ``sel[start_b + j − size_row]`` when that offset is live —
because TPU Pallas has no dynamic scatter primitive; a shifted-window gather
over the (tiny) wave is the vectorizable formulation.  Bucket levels are
passed through ``input_output_aliases`` so untouched slots are never copied:
together with ``donate_argnums`` at the jit boundary this is what makes the
donated append O(wave) writes instead of O(capacity) copies.

Items are carried as one trailing feature axis ``D`` (non-scalar payloads are
flattened by ``ops``): every ref is ``(rows, width, D)`` with the permutation
computed on the 2-D ``(rows, m)`` mask and broadcast over ``D`` — this is the
3-D variant the KV-cache decode path needs ((heads, dim) items; was a jnp
fallback before).

The kernel takes ``ngroups`` independent payload *groups* sharing one mask
and size vector (each group has its own bucket tuple, feature width, and
dtype): the offsets and the one-hot permutation — the expensive part of a
tiny wave — are computed **once** and reused for every group's scatter.
This is what lets the quantized KV-cache decode write k/v/ks/vs in a single
launch instead of four.

Memory spaces (``common.GridPlan``, DESIGN.md §4.7): the ``vmem`` tiling
(:func:`push_back_pallas`, interpret-mode only) keeps every level's
block-tile rows resident per grid step.  The ``hbm`` tiling
(:func:`push_back_hbm`, the TPU path) leaves the levels in HBM
(``pltpu.ANY``, aliased in place) and grids over block rows: a row's wave
lands in the contiguous slots ``[size, size+count)``, so each level it meets
is touched in at most a few ``HBM_CHUNK``-slot column windows, and only
those are read, filled and written back.  The offsets come precomputed
(mask arithmetic, outside the kernel) and the wave is placed by
``common.wave_select`` — a one-hot bf16 matmul over byte planes, exact for
every payload bit pattern — because Mosaic lowers neither ``cumsum`` nor a
3-D gather.  Scalar payloads keep their levels 2-D: a trailing unit feature
axis would pad every HBM tile 128-fold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import indexing
from repro.kernels import common
from repro.kernels.dispatch_mxu import kernel as dispatch_kernel
from repro.obs import device

__all__ = ["push_back_pallas", "push_back_hbm", "apply_insert_permutation"]

DEFAULT_BLOCK_TILE = 8
HBM_CHUNK = 512  # column window of the hbm tiling (slots, 128-lane aligned)


def _ctr_pairs(mask, sizes, count, starts, bsizes):
    """Device-counter contributions of one grid step (DESIGN.md §9.x).

    ``level_writes`` is the true scatter volume: per row, the write interval
    ``[size, size+count)`` clipped to each level's ``[start, start+width)``
    — levels the interval misses contribute zero, so the sum equals the
    bucket slots actually written (both memory spaces, touched or not).
    """
    rows, m = mask.shape
    writes = jnp.zeros((), jnp.int32)
    for b in range(len(bsizes)):
        lo = jnp.maximum(sizes[:, 0], starts[b])
        hi = jnp.minimum(sizes[:, 0] + count[:, 0], starts[b] + bsizes[b])
        writes = writes + jnp.sum(jnp.maximum(hi - lo, 0))
    first = pl.program_id(0) == 0
    return first, [
        ("push_back.waves", jnp.where(first, 1, 0)),  # 1 per launch
        ("push_back.lanes", rows * m),
        ("push_back.active_lanes", jnp.sum(mask)),
        ("push_back.level_writes", writes),
    ]


def apply_insert_permutation(
    off: jax.Array,  # (rows, m) exclusive prefix sums of the mask
    mask: jax.Array,  # (rows, m) int32 0/1
    elems: jax.Array,  # (rows, m, D)
    dispatch: str,
) -> jax.Array:
    """Dense insert permutation: out[r, o] = elems[r, k] for the unique masked
    lane ``k`` with ``off[r, k] == o``.

    ``dispatch="onehot"``: exact int32 one-hot reduction + gather — value
    bits never touch arithmetic, bit-identical to the jnp scatter for every
    dtype.  ``dispatch="mxu"``: the one-hot becomes a dispatch matmul
    (``kernels/dispatch_mxu.permute_rows``) — the MXU path for wide waves,
    bit-exact for f32-representable payloads.  Slots past the row's lane
    count differ between the two (lane 0's value vs 0) but are dead under
    every caller's ``o < count`` write guard.
    """
    rows, m = mask.shape
    iota_o = jax.lax.broadcasted_iota(jnp.int32, (rows, m, m), 1)
    onehot = (off[:, None, :] == iota_o) & (mask[:, None, :] > 0)
    if dispatch == "mxu":
        return dispatch_kernel.permute_rows(onehot, elems)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (rows, m, m), 2)
    sel = jnp.sum(jnp.where(onehot, iota_k, 0), axis=2)  # (rows, m)
    return jnp.take_along_axis(elems, sel[:, :, None], axis=1)


def _level_window(gathered, sizes, count, level_tile, start, width, m):
    """One level's shifted-window gather — shared by both memory spaces."""
    rows = sizes.shape[0]
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    o = start + j - sizes  # wave offset landing at this slot
    valid = (o >= 0) & (o < count)
    oc = jnp.clip(o, 0, m - 1)
    vals = jnp.take_along_axis(gathered, oc[:, :, None], axis=1)
    return jnp.where(valid[:, :, None], vals, level_tile)


def _push_back_vmem(
    mask_ref, sizes_ref, *refs, starts, bsizes, ngroups, dispatches,
    instrument=False,
):
    nlev = len(bsizes)
    elems_refs = refs[:ngroups]
    level_in = refs[ngroups : ngroups + ngroups * nlev]  # group-major
    level_out = refs[ngroups + ngroups * nlev : ngroups + 2 * ngroups * nlev]
    nout = ngroups + 2 * ngroups * nlev
    pos_ref = refs[nout]
    nsz_ref = refs[nout + 1]

    mask = mask_ref[...]  # (rows, m) int32 0/1
    sizes = sizes_ref[...]  # (rows, 1) int32
    rows, m = mask.shape

    inc = jnp.cumsum(mask, axis=1)
    off = inc - mask  # exclusive prefix sum (the insertion offsets)
    count = inc[:, -1:]  # (rows, 1)
    pos = sizes + off  # absolute in-block positions

    for g in range(ngroups):
        # permutation resolved ONCE per group, reused by every level's scatter
        gathered = apply_insert_permutation(
            off, mask, elems_refs[g][...], dispatches[g]
        )
        for b in range(nlev):
            level_out[g * nlev + b][...] = _level_window(
                gathered, sizes, count, level_in[g * nlev + b][...],
                starts[b], bsizes[b], m,
            )

    pos_ref[...] = jnp.where(mask > 0, pos, -1)
    nsz_ref[...] = sizes + count
    if instrument:
        first, pairs = _ctr_pairs(mask, sizes, count, starts, bsizes)
        device.ctr_accum(refs[nout + 2], first, pairs)


def _chunk(width: int) -> int:
    """Column window of one level for the hbm tiling: the whole level row,
    or ``HBM_CHUNK`` slots when that tiles it (128-lane aligned)."""
    return HBM_CHUNK if width > HBM_CHUNK and width % HBM_CHUNK == 0 else width


def _push_back_hbm(
    sizes_ref, counts_ref, off_ref, *refs, starts, bsizes, ngroups, spans,
    instrument=False,
):
    """One block row per grid step: read-modify-write its write windows.

    The row's wave lands in ``[size, size+count)``; in level ``b`` that is
    at most ``spans[b]`` column chunks, each read, filled and written back
    through its aligned :func:`common.row_window` by
    :func:`common.fill_window`.
    """
    nlev = len(bsizes)
    planes = refs[:ngroups]
    levels = refs[ngroups + ngroups * nlev : ngroups + 2 * ngroups * nlev]
    bufs = refs[-ngroups * nlev - 1 : -1]
    sem = refs[-1]
    n = pl.program_id(0)
    size, count = sizes_ref[n], counts_ref[n]
    off = off_ref[0]  # (1, m) — exclusive prefix sums, −1 on masked lanes
    for g in range(ngroups):
        for b in range(nlev):
            width = _chunk(bsizes[b])
            lo = jnp.maximum(size - starts[b], 0)
            hi = jnp.minimum(size + count - starts[b], bsizes[b])
            for j in range(spans[b]):
                c = lo // width + j

                @pl.when((lo < hi) & (c * width < hi))
                def _(g=g, b=b, c=c, width=width):
                    view, rr = common.row_window(
                        levels[g * nlev + b], n, c * width, width
                    )
                    common.fill_window(
                        view, rr, bufs[g * nlev + b], sem, planes[g][0], off,
                        starts[b] + c * width - size, count,
                    )

    if instrument:
        m = off.shape[-1]
        writes = jnp.zeros((), jnp.int32)
        for b in range(nlev):
            lo = jnp.maximum(size, starts[b])
            hi = jnp.minimum(size + count, starts[b] + bsizes[b])
            writes = writes + jnp.maximum(hi - lo, 0)
        first = n == 0
        device.ctr_accum(refs[ngroups + 2 * ngroups * nlev], first, [
            ("push_back.waves", jnp.where(first, 1, 0)),
            ("push_back.lanes", m),
            ("push_back.active_lanes", count),
            ("push_back.level_writes", writes),
        ])


def push_back_hbm(
    bucket_groups: tuple[tuple[jax.Array, ...], ...],  # level b: (rows, B0·2^b[, D_g])
    sizes: jax.Array,  # (rows,) int32
    counts: jax.Array,  # (rows,) int32 — masked lanes per row
    off: jax.Array,  # (rows, m) int32 — exclusive prefix sums, −1 masked
    plane_groups: tuple[jax.Array, ...],  # byte planes: (rows, 4, m[, D_g])
    b0: int,
    *,
    instrument: bool = False,
    interpret: bool = False,
) -> tuple:
    """→ new level groups (aliased in place); + counter block if instrumented.

    The hbm tiling of the fused push-back: levels stay in HBM and only the
    column windows a row's wave writes are moved — O(wave) traffic per
    append, whatever the capacity.  ``counts`` and ``off`` are mask
    arithmetic computed by the caller, so the kernel runs no prefix scan.
    Scalar-item levels are 2-D and their rows must be padded to
    :func:`common.tile_rows`.
    """
    ngroups = len(plane_groups)
    rows, m = off.shape
    nlev = len(bucket_groups[0])
    starts = indexing.bucket_starts(b0, nlev)
    bsizes = indexing.bucket_sizes(b0, nlev)
    max_count = m if m else 1
    spans = tuple(
        min(w // _chunk(w), (max_count - 1) // _chunk(w) + 2) for w in bsizes
    )
    levels = [lvl for grp in bucket_groups for lvl in grp]
    nl = len(levels)
    any_spec = pl.BlockSpec(memory_space=pltpu.ANY)

    def buf(lvl):
        width = _chunk(lvl.shape[1])
        if lvl.ndim == 3:
            return pltpu.VMEM((1, width, lvl.shape[2]), lvl.dtype)
        return pltpu.VMEM(
            (min(lvl.shape[0], common.tile_rows(lvl.dtype)), width), lvl.dtype
        )

    plan = common.GridPlan(
        memory_space="hbm",
        grid=(rows,),
        num_tables=2,
        table_specs=(),
        in_specs=[pl.BlockSpec((1, 1, m), lambda n, s, c: (n, 0, 0))]
        + [
            pl.BlockSpec(
                (1, *p.shape[1:]), lambda n, s, c, k=p.ndim: (n,) + (0,) * (k - 1)
            )
            for p in plane_groups
        ]
        + [any_spec] * nl,
        out_specs=[any_spec] * nl,
        scratch_shapes=[buf(lvl) for lvl in levels] + [pltpu.SemaphoreType.DMA],
        aliases={1 + ngroups + i: i for i in range(nl)},
        instrument=instrument,
    )
    kernel = functools.partial(
        _push_back_hbm, starts=starts, bsizes=bsizes, ngroups=ngroups,
        spans=spans, instrument=instrument,
    )
    outs = plan.pallas_call(
        kernel,
        [jax.ShapeDtypeStruct(lvl.shape, lvl.dtype) for lvl in levels],
        interpret=interpret,
    )(sizes, counts, off.reshape(rows, 1, m), *plane_groups, *levels)
    groups = tuple(
        tuple(outs[g * nlev : (g + 1) * nlev]) for g in range(ngroups)
    )
    if instrument:
        return groups, outs[nl]
    return groups


def push_back_pallas(
    bucket_groups: tuple[tuple[jax.Array, ...], ...],  # per group, level b: (nblocks, B0·2^b, D_g)
    sizes: jax.Array,  # (nblocks, 1) int32
    b0: int,
    elem_groups: tuple[jax.Array, ...],  # per group: (nblocks, m, D_g)
    mask: jax.Array,  # (nblocks, m) int32 0/1
    *,
    block_tile: int = DEFAULT_BLOCK_TILE,
    dispatches: tuple[str, ...] | None = None,
    instrument: bool = False,
    interpret: bool = False,
) -> tuple:
    """The vmem tiling → (new level groups, positions (−1 where masked),
    new sizes (nblocks, 1)).

    With ``instrument=True`` the tuple gains a trailing (8, 128) int32
    counter block (``obs/device`` layout) accumulated in-kernel.
    """
    ngroups = len(elem_groups)
    nblocks, m, _ = elem_groups[0].shape
    if nblocks % block_tile:
        raise ValueError(f"nblocks {nblocks} must divide by tile {block_tile}")
    nlev = len(bucket_groups[0])
    starts = indexing.bucket_starts(b0, nlev)
    bsizes = indexing.bucket_sizes(b0, nlev)
    if dispatches is None:
        dispatches = ("onehot",) * ngroups
    dims = [e.shape[2] for e in elem_groups]
    row_spec = lambda width: pl.BlockSpec((block_tile, width), lambda i: (i, 0))
    item_spec = lambda width, d: pl.BlockSpec(
        (block_tile, width, d), lambda i: (i, 0, 0)
    )
    level_shapes = [
        jax.ShapeDtypeStruct((nblocks, sz, d), grp[0].dtype)
        for grp, d in zip(bucket_groups, dims)
        for sz in bsizes
    ]
    out_shape = level_shapes + [
        jax.ShapeDtypeStruct((nblocks, m), jnp.int32),
        jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
    ]
    nl = ngroups * nlev
    # level inputs alias their outputs: untouched slots are never copied.
    aliases = {2 + ngroups + i: i for i in range(nl)}
    level_specs = [item_spec(sz, d) for d in dims for sz in bsizes]
    plan = common.GridPlan(
        memory_space="vmem",
        grid=(nblocks // block_tile,),
        num_tables=0,
        table_specs=(),
        in_specs=[row_spec(m), row_spec(1)]
        + [item_spec(m, d) for d in dims]
        + level_specs,
        out_specs=level_specs + [row_spec(m), row_spec(1)],
        aliases=aliases,
        instrument=instrument,
    )
    kernel = functools.partial(
        _push_back_vmem,
        starts=starts, bsizes=bsizes, ngroups=ngroups, dispatches=dispatches,
        instrument=instrument,
    )
    outs = plan.pallas_call(kernel, out_shape, interpret=interpret)(
        mask, sizes, *elem_groups,
        *(lvl for grp in bucket_groups for lvl in grp),
    )
    groups = tuple(
        tuple(outs[g * nlev : (g + 1) * nlev]) for g in range(ngroups)
    )
    if instrument:
        return groups, outs[nl], outs[nl + 1], outs[nl + 2]
    return groups, outs[nl], outs[nl + 1]
