"""jit'd paged ops: padding/dispatch around the paged Pallas kernels.

All three ops flatten ``item_shape`` into one trailing feature axis around
the 3-D/4-D kernels (the ``kernels/push_back`` convention) and pad row/slab
counts to the kernel tile with provably inert rows (page −1 / owner −1).
``use_ref=True`` runs the jnp oracle — bit-identical in interpret mode.

``memory_space`` selects the kernel tiling (``common.resolve_memory_space``:
explicit > ``REPRO_MEMORY_SPACE`` > hbm on TPU / vmem in interpret mode);
``slab_append``'s ``dispatch`` selects the insert-permutation backend
(``common.resolve_dispatch`` — MXU matmul for wide waves).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.paged import kernel as _kernel
from repro.kernels.paged import ref as _ref
from repro.obs import device
from repro.pool import extents as _extents

__all__ = ["paged_gather", "paged_attend", "slab_append", "slab_append_donated"]


def _gather_ctr(table: jax.Array, space: str, row_tile: int) -> jax.Array:
    """jnp gather counters matching the in-kernel accounting: the vmem
    tiling pads rows with −1 pages, and those walked-but-dead entries are
    genuine masked-tile waste, so they count."""
    N, P = table.shape
    rows = N if space == "hbm" else N + (-N) % row_tile
    live = jnp.sum((table >= 0).astype(jnp.int32))
    return device.pack(**{
        "paged_gather.launches": 1,
        "paged_gather.tiles": live,
        "paged_gather.masked_tiles": rows * P - live,
    })


def _attend_ctr(table: jax.Array, lengths: jax.Array, T: int, KH: int) -> jax.Array:
    """jnp attend counters over a (B, P) liveness table — the per-(b, p)
    walk the kernel grids over, times the KH head steps."""
    B, P = table.shape
    p_idx = jnp.arange(P, dtype=jnp.int32)[None, :]
    kv = lengths.astype(jnp.int32)[:, None]
    visit = ((table >= 0) & (p_idx * T < kv)).astype(jnp.int32)  # (B, P)
    masked = visit * (T - jnp.clip(kv - p_idx * T, 0, T))
    tiles = jnp.sum(visit)
    return device.pack(**{
        "paged_attend.launches": 1,
        "paged_attend.tiles": KH * tiles,
        "paged_attend.tiles_skipped": KH * (B * P - tiles),
        "paged_attend.lanes": KH * tiles * T,
        "paged_attend.masked_lanes": KH * jnp.sum(masked),
    })


def _flat_item(x: jax.Array, lead: int) -> tuple[jax.Array, tuple[int, ...]]:
    """Collapse everything past ``lead`` leading dims into one feature axis."""
    item = x.shape[lead:]
    d = 1
    for dim in item:
        d *= dim
    return x.reshape(*x.shape[:lead], d), item


def _as_extents(pool) -> tuple[jax.Array, ...]:
    """Normalize a pool argument: flat array → 1-extent tuple; drop empty
    extents (they hold no slab ids, so the global numbering is unchanged)."""
    exts = tuple(pool) if isinstance(pool, (tuple, list)) else (pool,)
    live = tuple(e for e in exts if e.shape[0] > 0)
    return live or exts[:1]


@partial(
    jax.jit,
    static_argnames=("interpret", "use_ref", "memory_space", "instrument"),
)
def paged_gather(
    pool,  # (S, T, *item) or tuple of extents (S_e, T, *item)
    pages: jax.Array,  # (N, P) int32 — global slab ids
    *,
    interpret: bool | None = None,
    use_ref: bool = False,
    memory_space: str | None = None,
    instrument: bool = False,
) -> Any:
    """→ (N, P·T, *item) contiguous logical views (zeros under page −1).

    A tuple/list pool is a segmented :class:`~repro.pool.extents.ExtentPool`
    layout: the global page table is resolved through the two-level
    (extent, offset) table host-side and the kernel walks per-extent operands
    (the oracle is the same flat gather over the concatenated extents).
    ``instrument=True`` → (out, device counter vector): in-kernel on the
    single-extent fused path, the matching jnp oracle elsewhere.
    """
    exts = _as_extents(pool)
    T = exts[0].shape[1]
    N, P = pages.shape
    space = common.resolve_memory_space(memory_space, interpret)
    if use_ref:
        pool3, item = _flat_item(_extents.flat_data(exts), 2)
        out = _ref.gather_pages(pool3, pages).reshape(N, P * T, *item)
        if instrument:
            return out, _gather_ctr(pages, space, _kernel.DEFAULT_ROW_TILE)
        return out
    run = common.should_interpret(interpret)
    if len(exts) == 1 and exts[0].ndim == 2 and space == "hbm":
        outs = _kernel.paged_gather_rows(
            exts[0], pages, instrument=instrument, interpret=run
        )
        if instrument:
            return outs[0], device.from_block(outs[1])
        return outs
    if len(exts) == 1:
        pool3, item = _flat_item(exts[0], 2)
        outs = _kernel.paged_gather_pallas(
            pool3, pages, memory_space=space,
            instrument=instrument, interpret=run,
        )
        if instrument:
            return outs[0].reshape(N, P * T, *item), device.from_block(outs[1])
        return outs.reshape(N, P * T, *item)
    flat = [_flat_item(e, 2) for e in exts]
    item = flat[0][1]
    ext_tbl, off_tbl = _extents.resolve_pages(
        pages, tuple(e.shape[0] for e in exts)
    )
    out = _kernel.paged_gather_pallas_extents(
        tuple(p for p, _ in flat),
        ext_tbl,
        off_tbl,
        memory_space=space,
        interpret=run,
    ).reshape(N, P * T, *item)
    if instrument:
        return out, _gather_ctr(ext_tbl, space, _kernel.DEFAULT_ROW_TILE)
    return out


@partial(
    jax.jit,
    static_argnames=("interpret", "use_ref", "memory_space", "instrument"),
)
def paged_attend(
    q: jax.Array,  # (B, KH, G, D) f32, pre-scaled
    k_pool,  # (S, T, KH, D) token-major pool, or tuple of extents
    v_pool,  # (S, T, KH, D) or tuple of extents
    pages: jax.Array,  # (B, P) int32 — global slab ids
    lengths: jax.Array,  # (B,) int32
    *,
    interpret: bool | None = None,
    use_ref: bool = False,
    memory_space: str | None = None,
    instrument: bool = False,
) -> Any:
    """→ (B, KH, G, D) f32 attention output through the page table.

    Pools arrive in the cache's token-major ``(slab, slot, head, dim)``
    layout and are transposed head-major for the kernel's per-head blocking
    (a production pool would be laid out head-major to begin with).  Tuple
    pools are segmented extents; the walk resolves global slab ids through
    the two-level (extent, offset) table.  ``instrument=True`` → (out,
    device counter vector): in-kernel on the single-extent path, the
    matching jnp oracle elsewhere.
    """
    k_exts = _as_extents(k_pool)
    v_exts = _as_extents(v_pool)
    kh = tuple(k.transpose(2, 0, 1, 3) for k in k_exts)  # each (KH, S_e, T, D)
    vh = tuple(v.transpose(2, 0, 1, 3) for v in v_exts)
    KH, T = kh[0].shape[0], kh[0].shape[2]
    if use_ref:
        k1 = kh[0] if len(kh) == 1 else jnp.concatenate(kh, axis=1)
        v1 = vh[0] if len(vh) == 1 else jnp.concatenate(vh, axis=1)
        out = _ref.attend_paged(q, k1, v1, pages, lengths)
        if instrument:
            return out, _attend_ctr(pages, lengths, T, KH)
        return out
    space = common.resolve_memory_space(memory_space, interpret)
    run = common.should_interpret(interpret)
    if len(kh) == 1:
        outs = _kernel.paged_attend_pallas(
            q, kh[0], vh[0], pages, lengths,
            memory_space=space, instrument=instrument, interpret=run,
        )
        if instrument:
            return outs[0], device.from_block(outs[1])
        return outs
    ext_tbl, off_tbl = _extents.resolve_pages(
        pages, tuple(k.shape[1] for k in kh)
    )
    out = _kernel.paged_attend_pallas_extents(
        q, kh, vh, ext_tbl, off_tbl, lengths,
        memory_space=space, interpret=run,
    )
    if instrument:
        return out, _attend_ctr(ext_tbl, lengths, T, KH)
    return out


def _touched_slabs(owners, bases, sizes, counts, T: int, m: int):
    """→ (tbl, first), each (N, spans): the slabs a wave writes, per array.

    Array ``a`` writes positions ``[size, size+count)``: its logical pages
    ``size // T + j`` for ``j < spans``.  ``tbl`` holds each page's global
    slab id (−1 when the page is not written or not claimed — the write
    drops, as in the oracle) and ``first`` the wave offset landing on the
    page's slot 0.  Built from the per-slab owner/base tables by one
    scatter.
    """
    N = sizes.shape[0]
    spans = (max(m, 1) - 1) // T + 2
    page0 = sizes // T
    q = page0[:, None] + jnp.arange(spans, dtype=jnp.int32)[None, :]
    first = q * T - sizes[:, None]
    owners = owners.reshape(-1).astype(jnp.int32)
    rel = bases.reshape(-1).astype(jnp.int32) // T - page0[
        jnp.clip(owners, 0, N - 1)
    ]
    ok = (owners >= 0) & (rel >= 0) & (rel < spans)
    key = jnp.where(ok, owners * spans + rel, N * spans)
    lut = jnp.full((N * spans + 1,), -1, jnp.int32)
    lut = lut.at[key].set(jnp.arange(owners.shape[0], dtype=jnp.int32))
    lut = lut[: N * spans].reshape(N, spans)
    live = (q * T < (sizes + counts)[:, None]) & (counts > 0)[:, None]
    return jnp.where(live, lut, -1), first


def _slab_append(
    pool,  # (S, T, *item) or tuple of extents (S_e, T, *item)
    owners: jax.Array,  # (S,) int32 — owning array per slab, −1 free
    bases: jax.Array,  # (S,) int32 — logical position of each slab's slot 0
    sizes: jax.Array,  # (N,) int32
    elems: jax.Array,  # (N, m, *item)
    mask: jax.Array,  # (N, m) bool or 0/1 int
    *,
    interpret: bool | None = None,
    use_ref: bool = False,
    memory_space: str | None = None,
    dispatch: str = "auto",
    instrument: bool = False,
) -> tuple:
    """→ (new pool, new sizes (N,), positions (N, m) (−1 where masked)).

    A tuple pool comes back as a tuple with the *same structure*: the kernel
    launches once per extent against that extent's slice of the owner/base
    tables (slab ids are contiguous per extent), each launch aliasing its
    extent in place — growth never copied the pool, and neither does the
    append.  ``instrument=True`` appends a device counter vector (jnp wave
    accounting — same numbers on every path/space).
    """
    if mask.dtype != jnp.bool_:
        mask = mask != 0
    is_multi = isinstance(pool, (tuple, list))
    exts = tuple(pool) if is_multi else (pool,)
    T = exts[0].shape[1]
    N, m = mask.shape

    def ctr():
        # the kernel pads wave lanes to MXU_LANE in both memory spaces
        m_pad = m + (-m) % common.MXU_LANE
        return device.pack(**{
            "slab_append.waves": 1,
            "slab_append.lanes": N * m_pad,
            "slab_append.active_lanes": jnp.sum(mask.astype(jnp.int32)),
        })

    if m == 0:
        pos0 = jnp.zeros((N, 0), jnp.int32)
        if instrument:
            return pool, sizes, pos0, device.zeros()
        return pool, sizes, pos0
    ext_item = [_flat_item(e, 2) for e in exts]
    item = ext_item[0][1]
    elems3, _ = _flat_item(elems, 2)
    if use_ref:
        pool3 = _extents.flat_data([p for p, _ in ext_item])
        new_pool, new_sizes, pos = _ref.slab_append(
            pool3, owners, bases, sizes.astype(jnp.int32), elems3, mask
        )
        if not is_multi:
            new_pool = new_pool.reshape(pool.shape)
        else:
            out, lo = [], 0
            for e in exts:
                hi = lo + e.shape[0]
                out.append(new_pool[lo:hi].reshape(e.shape))
                lo = hi
            new_pool = tuple(out)
        if instrument:
            return new_pool, new_sizes, pos, ctr()
        return new_pool, new_sizes, pos
    # positions/counts are pure mask arithmetic — the hbm kernel takes them
    # precomputed, the vmem kernel recomputes them in-kernel for the scatter
    mask_i = mask.astype(jnp.int32)
    inc = jnp.cumsum(mask_i, axis=1)
    counts = inc[:, -1]
    sizes32 = sizes.astype(jnp.int32)
    pos = sizes32[:, None] + inc - mask_i
    space = common.resolve_memory_space(memory_space, interpret)
    run = common.should_interpret(interpret)
    tile = _kernel.DEFAULT_ROW_TILE
    if space == "hbm":
        tbl, first = _touched_slabs(owners, bases, sizes32, counts, T, m)
        planes = common.pad_to(
            common.byte_planes(elems if not item else elems3, 1),
            common.MXU_LANE, axis=2,
        )
        off = common.pad_to(
            jnp.where(mask, inc - mask_i, -1), common.MXU_LANE, axis=1, value=-1
        )

        def one_extent(e, lo: int) -> jax.Array:
            S_e = e.shape[0]
            view = e if not item else _flat_item(e, 2)[0]
            mine = (tbl >= lo) & (tbl < lo + S_e)
            new = _kernel.slab_append_hbm(
                view, jnp.where(mine, tbl - lo, -1), first, counts, off,
                planes, interpret=run,
            )
            return new.reshape(e.shape)

    else:
        disp = common.resolve_dispatch(dispatch, m, elems.dtype)
        elems_p = common.pad_to(elems3, common.MXU_LANE, axis=1)
        mask_p = common.pad_to(mask_i, common.MXU_LANE, axis=1)

        def one_extent(e, lo: int) -> jax.Array:
            ext3 = _flat_item(e, 2)[0]
            S_e = ext3.shape[0]
            own_e = jax.lax.dynamic_slice_in_dim(owners.reshape(-1), lo, S_e)
            base_e = jax.lax.dynamic_slice_in_dim(bases.reshape(-1), lo, S_e)
            # padded slabs: owner −1 — provably inert
            new = _kernel.slab_append_pallas(
                common.pad_to(ext3, tile, axis=0),
                common.pad_to(own_e, tile, axis=0, value=-1),
                common.pad_to(base_e, tile, axis=0),
                sizes32,
                elems_p,
                mask_p,
                dispatch=disp,
                interpret=run,
            )
            return new[:S_e].reshape(e.shape)

    new_exts, lo = [], 0
    for e in exts:
        new_exts.append(e if e.shape[0] == 0 else one_extent(e, lo))
        lo += e.shape[0]
    new_sizes = sizes + counts
    pos = jnp.where(mask, pos, -1)
    new_pool = tuple(new_exts) if is_multi else new_exts[0]
    if instrument:
        return new_pool, new_sizes, pos, ctr()
    return new_pool, new_sizes, pos


_SLAB_STATICS = ("interpret", "use_ref", "memory_space", "dispatch", "instrument")
slab_append = partial(jax.jit, static_argnames=_SLAB_STATICS)(_slab_append)
# The arena's hot path: the pool is donated, so together with the kernel's
# input_output_aliases an append is O(wave) writes, not O(pool) copies.
slab_append_donated = jax.jit(
    _slab_append, static_argnames=_SLAB_STATICS, donate_argnums=(0,)
)
