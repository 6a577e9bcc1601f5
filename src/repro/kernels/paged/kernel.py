"""Paged Pallas kernels — read/write a slab pool through page tables.

Three kernels back the arena subsystem (``repro.pool``, DESIGN.md §4), each
built on the shared :class:`repro.kernels.common.GridPlan` memory-space layer
(DESIGN.md §4.7; gather and append in two tilings, one index math):

``paged_gather_pallas``
    Materialize each logical array's contiguous view by walking its page
    table — the indirection-table read the arena's flatten path uses.  vmem:
    one grid step per row tile against the resident pool.  hbm: grid
    ``(narrays, pages)`` with the page table scalar-prefetched; the pool
    ``index_map`` reads ``pages[n, p]`` so each grid step DMAs exactly the
    one slab tile it emits.  Scalar-item pools stay 2-D ``(S, T)`` and take
    :func:`paged_gather_rows`, which DMAs each slab through its HBM row band.

``paged_attend_pallas``
    Flash-decode attention read straight from the token-major ``(S, T, KH,
    D)`` pool, every extent in one launch: grid ``(batch, blocks)``, where a
    block is ``tile`` tokens of one slab with all KV heads side by side
    (lane width ``KH·D``), and the online-softmax state of every query head
    lives in VMEM scratch.  The queries enter block-diagonally, so one MXU
    pass scores all heads and one more forms P·V.  One tiling, in both the
    interpreter and Mosaic: the K/V ``index_map`` reads a scalar-prefetched
    schedule (:func:`attend_schedule`) in which dead steps — pages past the
    live length (GGArray tail slabs), unclaimed pages, idle rows — repeat
    the previous block, so the pipeline skips their DMA.

``slab_append_pallas`` / ``slab_append_hbm``
    The push_back insert (exclusive mask scan + insert permutation, see
    ``kernels/push_back``) retargeted at the pool; the pool aliases its
    output so untouched slabs are never copied.  vmem: each grid step
    resolves its slab tile's wave elements through the slab's *owner* row;
    waves at least ``common.MXU_DISPATCH_WAVE`` lanes wide apply the insert
    permutation as an MXU dispatch matmul (``kernels/dispatch_mxu``).  hbm:
    one grid step per *array*, which reads, fills and writes back only the
    few slabs its wave lands in (a table built by ``ops`` from the owner and
    base tables) — O(wave) traffic whatever the pool size — placing the
    elements with the exact byte-plane matmul ``common.wave_select``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.paged.ref import (
    MASK_VALUE,
    expand_queries,
    head_diagonal,
    split_halves,
)
from repro.kernels.push_back.kernel import apply_insert_permutation
from repro.obs import device

__all__ = [
    "paged_gather_pallas",
    "paged_gather_pallas_extents",
    "paged_gather_rows",
    "paged_attend_pallas",
    "attend_tile",
    "attend_schedule",
    "slab_append_pallas",
    "slab_append_hbm",
    "DEFAULT_ROW_TILE",
]

DEFAULT_ROW_TILE = 8


# --------------------------------------------------------------------------
# gather — logical contiguous view through the page table.
# --------------------------------------------------------------------------

def _gather_vmem(pages_ref, pool_ref, *refs, instrument=False):
    out_ref = refs[0]
    pages = pages_ref[...]  # (rows, P) int32
    pool = pool_ref[...]  # (S, T, D)
    rows, P = pages.shape
    S, T, D = pool.shape
    idx = jnp.clip(pages, 0, S - 1).reshape(rows * P)
    g = jnp.take(pool, idx, axis=0).reshape(rows, P, T, D)
    valid = (pages >= 0)[:, :, None, None]
    out_ref[...] = jnp.where(valid, g, 0).reshape(rows, P * T, D)
    if instrument:
        first = pl.program_id(0) == 0
        live = jnp.sum((pages >= 0).astype(jnp.int32))
        device.ctr_accum(refs[1], first, [
            ("paged_gather.launches", jnp.where(first, 1, 0)),
            ("paged_gather.tiles", live),
            ("paged_gather.masked_tiles", rows * P - live),
        ])


def _gather_hbm(pages_ref, pool_ref, *refs, instrument=False):
    out_ref = refs[0]
    n, p = pl.program_id(0), pl.program_id(1)
    slab = pages_ref[n, p]  # this step's one DMA'd tile is pool[slab]
    out_ref[...] = jnp.where(slab >= 0, pool_ref[...], 0)
    if instrument:
        first = (n == 0) & (p == 0)
        live = jnp.where(slab >= 0, 1, 0)
        device.ctr_accum(refs[1], first, [
            ("paged_gather.launches", jnp.where(first, 1, 0)),
            ("paged_gather.tiles", live),
            ("paged_gather.masked_tiles", 1 - live),
        ])


def _gather_hbm_rows(pages_ref, pool_ref, out_ref, *refs, n_valid, instrument):
    """Scalar-item pool ``(S, T)``: one ``(tile_rows, T)`` output tile —
    page ``p`` of ``tile_rows`` arrays — per grid step.  Each array's slab
    row is read through its aligned :func:`common.row_window` and placed in
    its output row; page −1 rows stay zero.  ``pages_ref`` is this tile's
    rows of the page table, blocked into SMEM (the whole table can outgrow
    SMEM's 1 MiB, so it is not scalar-prefetched)."""
    if instrument:
        ctr_ref, buf, acc, sem = refs
    else:
        buf, acc, sem = refs
    i, p = pl.program_id(0), pl.program_id(1)
    tr, T = out_ref.shape
    acc[...] = jnp.zeros(acc.shape, jnp.int32)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (tr, T), 0)
    live = jnp.zeros((), jnp.int32)
    for r in range(tr):
        slab = pages_ref[r, p]

        @pl.when(slab >= 0)
        def _(r=r, slab=slab):
            view, rr = common.row_window(pool_ref, slab, 0, T)
            cp = pltpu.make_async_copy(view, buf, sem)
            cp.start()
            cp.wait()
            words = common.to_words(buf[...])
            band = jax.lax.broadcasted_iota(jnp.int32, words.shape, 0)
            row = jnp.sum(jnp.where(band == rr, words, 0), axis=0, keepdims=True)
            acc[...] = jnp.where(out_row == r, row, acc[...])

        live = live + jnp.where(slab >= 0, 1, 0)
    out_ref[...] = common.from_words(acc[...], out_ref.dtype)
    if instrument:
        first = (i == 0) & (p == 0)
        real = jnp.clip(n_valid - i * tr, 0, tr)
        device.ctr_accum(ctr_ref, first, [
            ("paged_gather.launches", jnp.where(first, 1, 0)),
            ("paged_gather.tiles", live),
            ("paged_gather.masked_tiles", real - live),
        ])


def paged_gather_rows(
    pool: jax.Array,  # (S, T) scalar items
    pages: jax.Array,  # (N, P) int32
    *,
    instrument: bool = False,
    interpret: bool = False,
):
    """hbm gather of a scalar-item pool → (N, P·T) logical views.

    A 2-D pool keeps its slabs in tiled rows (a unit feature axis would pad
    every HBM tile 128-fold), so this grids over ``(tile_rows, T)`` output
    tiles and DMAs each slab through its row band.  Rows are padded with
    page −1 to the tile.  With ``instrument=True`` → (out, counter block).
    """
    N, P = pages.shape
    S, T = pool.shape
    tr = common.tile_rows(pool.dtype)
    pages_p = common.pad_to(pages, tr, axis=0, value=-1)
    Np = pages_p.shape[0]
    plan = common.GridPlan(
        memory_space="hbm",
        grid=(Np // tr, P),
        num_tables=0,
        table_specs=(),
        in_specs=[
            pl.BlockSpec((tr, P), lambda i, p: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((tr, T), lambda i, p: (i, p)),
        scratch_shapes=[
            pltpu.VMEM((min(S, tr), T), pool.dtype),
            pltpu.VMEM((tr, T), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        instrument=instrument,
    )
    kernel = functools.partial(_gather_hbm_rows, n_valid=N, instrument=instrument)
    outs = plan.pallas_call(
        kernel, jax.ShapeDtypeStruct((Np, P * T), pool.dtype), interpret=interpret
    )(pages_p, pool)
    if instrument:
        return outs[0][:N], outs[1]
    return outs[:N]


def paged_gather_pallas(
    pool: jax.Array,  # (S, T, D)
    pages: jax.Array,  # (N, P) int32
    *,
    row_tile: int = DEFAULT_ROW_TILE,
    memory_space: str = "vmem",
    instrument: bool = False,
    interpret: bool = False,
):
    """→ (N, P·T, D) contiguous logical views (zeros under page −1).

    Any row count works: the vmem tiling pads ``N`` up to ``row_tile`` with
    page-table rows of −1 (provably inert — every lane reads as zero) and
    slices the result; the hbm tiling grids over rows directly.  With
    ``instrument=True`` → (out, counter block).
    """
    N, P = pages.shape
    S, T, D = pool.shape
    if memory_space == "hbm":
        plan = common.GridPlan(
            memory_space="hbm",
            grid=(N, P),
            num_tables=1,
            table_specs=(),
            in_specs=[
                pl.BlockSpec(
                    (1, T, D),
                    lambda n, p, pages: (jnp.clip(pages[n, p], 0, S - 1), 0, 0),
                )
            ],
            out_specs=pl.BlockSpec((1, T, D), lambda n, p, pages: (n, p, 0)),
            instrument=instrument,
        )
        outs = plan.pallas_call(
            functools.partial(_gather_hbm, instrument=instrument),
            jax.ShapeDtypeStruct((N, P * T, D), pool.dtype),
            interpret=interpret,
        )(pages, pool)
        if instrument:
            return outs[0], outs[1]
        return outs
    pages_p = common.pad_to(pages, row_tile, axis=0, value=-1)
    Np = pages_p.shape[0]
    plan = common.GridPlan(
        memory_space="vmem",
        grid=(Np // row_tile,),
        num_tables=1,
        table_specs=[pl.BlockSpec((row_tile, P), lambda i: (i, 0))],
        in_specs=[pl.BlockSpec((S, T, D), lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((row_tile, P * T, D), lambda i: (i, 0, 0)),
        instrument=instrument,
    )
    outs = plan.pallas_call(
        functools.partial(_gather_vmem, instrument=instrument),
        jax.ShapeDtypeStruct((Np, P * T, D), pool.dtype),
        interpret=interpret,
    )(pages_p, pool)
    if instrument:
        return outs[0][:N], outs[1]
    return outs[:N]


# --------------------------------------------------------------------------
# gather, segmented pool — the same walk through the two-level table.
# --------------------------------------------------------------------------

def _gather_vmem_extents(ext_ref, off_ref, *refs):
    *pools, out_ref = refs
    ext = ext_ref[...]  # (rows, P) int32 extent ids, −1 unclaimed
    off = off_ref[...]  # (rows, P) int32 offsets-in-extent
    rows, P = ext.shape
    T, D = pools[0].shape[1:]
    acc = jnp.zeros((rows, P, T, D), out_ref.dtype)
    for e, pool_ref in enumerate(pools):
        pool = pool_ref[...]  # (S_e, T, D)
        idx = jnp.clip(off, 0, pool.shape[0] - 1).reshape(rows * P)
        g = jnp.take(pool, idx, axis=0).reshape(rows, P, T, D)
        acc = jnp.where((ext == e)[:, :, None, None], g, acc)
    out_ref[...] = acc.reshape(rows, P * T, D)


def _gather_hbm_extents(ext_ref, off_ref, *refs):
    *pools, out_ref = refs
    n, p = pl.program_id(0), pl.program_id(1)
    e = ext_ref[n, p]  # the body consumes only the tile this id selects
    out = jnp.zeros(out_ref.shape, out_ref.dtype)
    for i, pool_ref in enumerate(pools):
        out = jnp.where(e == i, pool_ref[...], out)
    out_ref[...] = out


def _extent_tile_spec(e: int, size: int, block: tuple[int, ...]):
    """hbm BlockSpec for extent ``e``: the index_map resolves this grid
    step's (ext, off) pair via ``common.extent_row`` — one slab tile per
    extent per step, only the selected one consumed."""
    return pl.BlockSpec(
        block,
        lambda n, p, ext, off: (
            common.extent_row(ext[n, p], off[n, p], e, size),
            0,
            0,
        ),
    )


def paged_gather_pallas_extents(
    extents: tuple[jax.Array, ...],  # each (S_e, T, D)
    ext_tbl: jax.Array,  # (N, P) int32 — extent id per page, −1 unclaimed
    off_tbl: jax.Array,  # (N, P) int32 — offset-in-extent per page
    *,
    row_tile: int = DEFAULT_ROW_TILE,
    memory_space: str = "vmem",
    interpret: bool = False,
) -> jax.Array:
    """Multi-extent ``paged_gather_pallas``: same contiguous views, with the
    page table pre-resolved through the two-level (extent, offset) table so
    growth never had to copy the pool (``pool/extents``)."""
    N, P = ext_tbl.shape
    T, D = extents[0].shape[1:]
    E = len(extents)
    if memory_space == "hbm":
        plan = common.GridPlan(
            memory_space="hbm",
            grid=(N, P),
            num_tables=2,
            table_specs=(),
            in_specs=[
                _extent_tile_spec(e, ext.shape[0], (1, T, D))
                for e, ext in enumerate(extents)
            ],
            out_specs=pl.BlockSpec((1, T, D), lambda n, p, ext, off: (n, p, 0)),
        )
        return plan.pallas_call(
            _gather_hbm_extents,
            jax.ShapeDtypeStruct((N, P * T, D), extents[0].dtype),
            interpret=interpret,
        )(ext_tbl, off_tbl, *extents)
    ext_p = common.pad_to(ext_tbl, row_tile, axis=0, value=-1)
    off_p = common.pad_to(off_tbl, row_tile, axis=0, value=-1)
    Np = ext_p.shape[0]
    plan = common.GridPlan(
        memory_space="vmem",
        grid=(Np // row_tile,),
        num_tables=2,
        table_specs=[
            pl.BlockSpec((row_tile, P), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, P), lambda i: (i, 0)),
        ],
        in_specs=[
            pl.BlockSpec(ext.shape, lambda i: (0, 0, 0)) for ext in extents
        ],
        out_specs=pl.BlockSpec((row_tile, P * T, D), lambda i: (i, 0, 0)),
    )
    out = plan.pallas_call(
        _gather_vmem_extents,
        jax.ShapeDtypeStruct((Np, P * T, D), extents[0].dtype),
        interpret=interpret,
    )(ext_p, off_p, *extents)
    return out[:N]


# --------------------------------------------------------------------------
# attend — flash-decode through the page table, on the token-major pool.
# --------------------------------------------------------------------------

# Bytes of one K (or V) block.  The pipeline double-buffers K and V, so the
# blocks take 4× this of VMEM — well inside the default scoped limit.
ATTEND_BLOCK_BYTES = 1 << 20


def attend_tile(slab_tokens: int, width: int, dtype) -> int:
    """Tokens per attend block: the whole slab, halved while a
    ``(tile, width)`` block exceeds :data:`ATTEND_BLOCK_BYTES` and the half
    still fills whole sublane tiles (chat's 2 × 128 heads keep the 2048-token
    slab; 16 × 64 heads take 512-token blocks)."""
    tile = slab_tokens
    row = width * jnp.dtype(dtype).itemsize
    while tile * row > ATTEND_BLOCK_BYTES and tile % (2 * common.tile_rows(dtype)) == 0:
        tile //= 2
    return tile


def attend_schedule(ext_tbl, off_tbl, lengths, n_sub: int, tile: int, n_ext: int):
    """→ ``(step_ext (B·J,), fetch (E·B·J,))`` int32 for the ``(B, J)`` grid,
    ``J = pages · n_sub``.

    ``step_ext`` is the extent holding grid step ``(b, j)``'s block, or −1
    when the step is dead (page unclaimed, or its first token at or past
    the row's length).  ``fetch[e]`` is the block of extent ``e`` that step
    maps to: its own on a live step of ``e``, else that of the last live
    step of ``e`` before it (the first after it, for leading steps).  Between
    live steps every index map therefore repeats the previous step's block,
    and the pipeline moves no bytes for dead steps or idle rows.
    """
    B, P = ext_tbl.shape
    J = P * n_sub
    j = jnp.arange(J, dtype=jnp.int32)
    ext = ext_tbl[:, j // n_sub]
    live = (ext >= 0) & (j[None, :] * tile < lengths[:, None])
    step_ext = jnp.where(live, ext, -1).reshape(-1)
    blk = (off_tbl[:, j // n_sub] * n_sub + j % n_sub).reshape(-1)
    order = jnp.arange(B * J, dtype=jnp.int32)
    fetch = []
    for e in range(n_ext):
        mine = step_ext == e
        last = jax.lax.cummax(jnp.where(mine, order, -1))
        src = jnp.where(last >= 0, last, jnp.argmax(mine))
        fetch.append(jnp.where(jnp.any(mine), blk[src], 0))
    return step_ext, jnp.concatenate(fetch)


def _attend_update(q2, k, v, kv_len, t0, m_ref, l_ref, acc_ref):
    """One K/V block's online-softmax update, every query row at once.

    ``q2`` ``(2R, F)`` stacks the two halves of the block-diagonal queries
    (:func:`ref.expand_queries`); ``k``/``v`` ``(tile, F)`` are the pool's
    rows as stored, all heads side by side.  Scores and P·V are MXU passes
    in the pool's dtype with f32 accumulation; the halves sum to f32.
    """
    R = q2.shape[0] // 2
    s2 = jax.lax.dot_general(
        q2, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s2[:R] + s2[R:]  # (R, tile)
    kpos = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < kv_len, s, MASK_VALUE)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pw = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(pw, axis=-1, keepdims=True)
    pv2 = jnp.dot(split_halves(pw, v.dtype, 0), v, preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + (pv2[:R] + pv2[R:])
    m_ref[...] = m_new


def _attend_body(
    len_ref, ext_ref, fetch_ref, q_ref, *refs, n_ext, n_steps, tile, instrument,
):
    ks, vs = refs[:n_ext], refs[n_ext : 2 * n_ext]
    o_ref = refs[2 * n_ext]
    if instrument:
        ctr_ref, m_ref, l_ref, acc_ref = refs[2 * n_ext + 1 :]
    else:
        m_ref, l_ref, acc_ref = refs[2 * n_ext + 1 :]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    e = ext_ref[b * n_steps + j]  # −1: dead step, its blocks are the last ones
    t0 = j * tile
    for i in range(n_ext):

        @pl.when(e == i)
        def _(i=i):
            _attend_update(
                q_ref[0], ks[i][0], vs[i][0], kv_len, t0, m_ref, l_ref, acc_ref
            )

    @pl.when(j == n_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    if instrument:
        visit = jnp.where(e >= 0, 1, 0)
        first = (b == 0) & (j == 0)
        device.ctr_accum(ctr_ref, first, [
            ("paged_attend.launches", jnp.where(first, 1, 0)),
            ("paged_attend.tiles", visit),
            ("paged_attend.tiles_skipped", 1 - visit),
            ("paged_attend.lanes", visit * tile),
            ("paged_attend.masked_lanes",
             visit * (tile - jnp.clip(kv_len - t0, 0, tile))),
        ])


def paged_attend_pallas(
    q: jax.Array,  # (B, KH, G, D) f32, pre-scaled
    k_extents: tuple[jax.Array, ...],  # each (S_e, T, KH·D) token-major
    v_extents: tuple[jax.Array, ...],
    ext_tbl: jax.Array,  # (B, P) int32 — extent id per page, −1 unclaimed
    off_tbl: jax.Array,  # (B, P) int32 — offset-in-extent per page
    lengths: jax.Array,  # (B,) int32
    *,
    tile: int,
    instrument: bool = False,
    interpret: bool = False,
):
    """Flash-decode through the two-level page table → (B, KH, G, D) f32.

    Each extent is read where it lies, viewed as ``(S_e·T/tile, tile,
    KH·D)`` — a free reshape of the leading dims.  Grid ``(B, pages·T/tile)``: one step takes
    one block of ``tile`` tokens with every KV head; its index map reads
    the scalar-prefetched :func:`attend_schedule`.  With ``instrument=True``
    → (out, counter block); a counted tile is one such step.
    """
    B, KH, G, D = q.shape
    T = k_extents[0].shape[1]
    F = KH * D
    n_sub = T // tile
    P = ext_tbl.shape[1]
    J = P * n_sub
    E = len(k_extents)
    R = -(-(KH * G) // 16) * 16  # query rows, whole bf16 sublane tiles
    lengths = lengths.astype(jnp.int32)
    step_ext, fetch = attend_schedule(
        ext_tbl.astype(jnp.int32), off_tbl.astype(jnp.int32), lengths,
        n_sub, tile, E,
    )
    def view(x):
        # pinned to HBM when compiled: the kernel DMAs the blocks it needs,
        # so XLA must not prefetch a whole extent into VMEM for it
        x = x.reshape(x.shape[0] * n_sub, tile, F)
        return x if interpret else pltpu.with_memory_space_constraint(x, pltpu.HBM)

    def kv_spec(e: int):
        return pl.BlockSpec(
            (1, tile, F),
            lambda b, j, lens, ext, fetch: (fetch[(e * B + b) * J + j], 0, 0),
        )

    row = lambda b, j, *_: (b, 0, 0)
    plan = common.GridPlan(
        memory_space="hbm",
        grid=(B, J),
        num_tables=3,
        table_specs=(),
        in_specs=[
            pl.BlockSpec((1, 2 * R, F), row),
            *[kv_spec(e) for e in range(E)],
            *[kv_spec(e) for e in range(E)],
        ],
        out_specs=pl.BlockSpec((1, R, F), row),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, F), jnp.float32),
        ],
        instrument=instrument,
    )
    kernel = functools.partial(
        _attend_body, n_ext=E, n_steps=J, tile=tile, instrument=instrument
    )
    outs = plan.pallas_call(
        kernel, jax.ShapeDtypeStruct((B, R, F), jnp.float32), interpret=interpret
    )(
        lengths, step_ext, fetch,
        expand_queries(q, R, k_extents[0].dtype),
        *map(view, k_extents), *map(view, v_extents),
    )
    if instrument:
        return head_diagonal(outs[0], KH, G), outs[1]
    return head_diagonal(outs, KH, G)


# --------------------------------------------------------------------------
# slab append — multi-array wave insert, scattered through slab ownership.
# --------------------------------------------------------------------------

def _slab_scatter(gathered, owner, base, size, count, pool_in, m):
    """Write wave elements into one slab tile row set — shared index math.

    ``gathered (rows, m, D)``, ``owner/base/size/count`` broadcastable over
    the tile's slab rows; returns the updated ``(tile, T, D)`` tile.
    """
    tile, T = pool_in.shape[:2]
    j = jax.lax.broadcasted_iota(jnp.int32, (tile, T), 1)
    o = base + j - size
    valid = (owner[:, None] >= 0) & (o >= 0) & (o < count)
    vals = jnp.take_along_axis(gathered, jnp.clip(o, 0, m - 1)[:, :, None], axis=1)
    return jnp.where(valid[:, :, None], vals, pool_in)


def _slab_append_vmem(
    owners_ref, bases_ref, sizes_ref, mask_ref, elems_ref, pool_in_ref,
    pool_out_ref, *, dispatch,
):
    mask = mask_ref[...]  # (N, m) int32 0/1
    elems = elems_ref[...]  # (N, m, D)
    sizes = sizes_ref[...]  # (N, 1) int32
    N, m = mask.shape

    # push_back machinery: exclusive scan + insert permutation
    inc = jnp.cumsum(mask, axis=1)
    off = inc - mask
    count = inc[:, -1:]  # (N, 1)
    gathered = apply_insert_permutation(off, mask, elems, dispatch)  # (N, m, D)

    owners = owners_ref[...][:, 0]  # (tile,) — owner array per slab, −1 free
    bases = bases_ref[...]  # (tile, 1) logical position of slot 0
    own = jnp.clip(owners, 0, N - 1)
    pool_out_ref[...] = _slab_scatter(
        jnp.take(gathered, own, axis=0),
        owners,
        bases,
        jnp.take(sizes[:, 0], own)[:, None],
        jnp.take(count[:, 0], own)[:, None],
        pool_in_ref[...],
        m,
    )


def _slab_append_hbm(
    tbl_ref, first_ref, counts_ref, off_ref, planes_ref, pool_in_ref,
    pool_ref, buf, sem, *, spans,
):
    """One array per grid step: read-modify-write the slabs its wave writes.

    ``tbl[a·spans + j]`` is the ``j``-th slab array ``a``'s wave touches (−1
    when none) and ``first`` the wave offset landing on its slot 0; the
    slab is read, filled and written back in place through its aligned
    :func:`common.row_window` by :func:`common.fill_window`.
    """
    a = pl.program_id(0)
    T = buf.shape[1]
    count = counts_ref[a]
    for j in range(spans):
        slab = tbl_ref[a * spans + j]

        @pl.when(slab >= 0)
        def _(j=j, slab=slab):
            view, rr = common.row_window(pool_ref, slab, 0, T)
            common.fill_window(
                view, rr, buf, sem, planes_ref[0], off_ref[0],
                first_ref[a * spans + j], count,
            )


def slab_append_hbm(
    pool: jax.Array,  # (S, T) scalar items or (S, T, D)
    tbl: jax.Array,  # (N, spans) int32 — touched slab ids, −1 none
    first: jax.Array,  # (N, spans) int32 — wave offset at each slab's slot 0
    counts: jax.Array,  # (N,) int32 — masked lanes per array
    off: jax.Array,  # (N, m) int32 — exclusive prefix sums, −1 masked
    planes: jax.Array,  # (N, 4, m[, D]) bf16 byte planes of the wave
    *,
    interpret: bool = False,
) -> jax.Array:
    """The hbm tiling of slab-append → new pool, aliased in place.

    Grids over arrays, not slabs: only the ``spans`` slabs each wave
    touches move, so an append costs O(wave), whatever the pool size.
    """
    N, spans = tbl.shape
    m = off.shape[1]
    if pool.ndim == 3:
        buf = pltpu.VMEM((1, *pool.shape[1:]), pool.dtype)
    else:
        rows = min(pool.shape[0], common.tile_rows(pool.dtype))
        buf = pltpu.VMEM((rows, pool.shape[1]), pool.dtype)
    lead = lambda k: (lambda a, *_: (a,) + (0,) * (k - 1))
    plan = common.GridPlan(
        memory_space="hbm",
        grid=(N,),
        num_tables=3,
        table_specs=(),
        in_specs=[
            pl.BlockSpec((1, 1, m), lead(3)),
            pl.BlockSpec((1, *planes.shape[1:]), lead(planes.ndim)),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[buf, pltpu.SemaphoreType.DMA],
        aliases={2: 0},  # pool in-place: O(wave) writes
    )
    kernel = functools.partial(_slab_append_hbm, spans=spans)
    return plan.pallas_call(
        kernel, jax.ShapeDtypeStruct(pool.shape, pool.dtype), interpret=interpret
    )(
        tbl.reshape(-1), first.reshape(-1), counts,
        off.reshape(N, 1, m), planes, pool,
    )


def slab_append_pallas(
    pool: jax.Array,  # (S, T, D)
    owners: jax.Array,  # (S,) int32
    bases: jax.Array,  # (S,) int32
    sizes: jax.Array,  # (N,) int32
    elems: jax.Array,  # (N, m, D)
    mask: jax.Array,  # (N, m) int32 0/1
    *,
    slab_tile: int = DEFAULT_ROW_TILE,
    dispatch: str = "onehot",
    interpret: bool = False,
) -> jax.Array:
    """The vmem tiling → new pool (S, T, D); untouched slabs alias through
    unscathed."""
    S, T, D = pool.shape
    N, m = mask.shape
    owners = owners.reshape(S).astype(jnp.int32)
    bases = bases.reshape(S).astype(jnp.int32)
    sizes = sizes.reshape(N).astype(jnp.int32)
    out_shape = jax.ShapeDtypeStruct((S, T, D), pool.dtype)
    if S % slab_tile:
        raise ValueError(f"n_slabs {S} must divide by tile {slab_tile}")
    row = lambda width: pl.BlockSpec((slab_tile, width), lambda i: (i, 0))
    plan = common.GridPlan(
        memory_space="vmem",
        grid=(S // slab_tile,),
        num_tables=3,
        table_specs=[row(1), row(1), pl.BlockSpec((N, 1), lambda i: (0, 0))],
        in_specs=[
            pl.BlockSpec((N, m), lambda i: (0, 0)),
            pl.BlockSpec((N, m, D), lambda i: (0, 0, 0)),
            pl.BlockSpec((slab_tile, T, D), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((slab_tile, T, D), lambda i: (i, 0, 0)),
        aliases={2: 0},  # pool in-place: O(wave) writes
    )
    kernel = functools.partial(_slab_append_vmem, dispatch=dispatch)
    return plan.pallas_call(kernel, out_shape, interpret=interpret)(
        owners.reshape(S, 1), bases.reshape(S, 1), sizes.reshape(N, 1),
        mask, elems, pool
    )
