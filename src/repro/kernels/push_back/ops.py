"""jit'd fused push-back: padding/dispatch around the Pallas kernel.

``push_back_fused`` is the ``method="fused"`` backend of
``core.ggarray.push_back``/``append``: per-block prefix-sum offsets and the
scatter into every bucket level fused into one tiled pass.  The jnp
scan-then-scatter path (also reachable as ``use_ref=True``) is the
correctness oracle — results are bit-identical across the round-trip test
matrix (``tests/kernels/test_push_back.py``) in **both** memory spaces.

Non-scalar items are supported by flattening ``item_shape`` into one trailing
feature axis around the 3-D kernel.  ``push_back_fused_multi`` scatters
several payload *groups* (own buckets / feature width / dtype each) that
share one mask and size vector in a single launch, computing the offsets and
the insert permutation once — the KV-cache decode path writes k/v (and the
int8 quant scales) this way (``serving/kvcache.py::append``).

``memory_space`` selects the kernel tiling (``common.resolve_memory_space``:
explicit > ``REPRO_MEMORY_SPACE`` > hbm on TPU / vmem in interpret mode).
The hbm tiling takes the mask's exclusive prefix sums and lane counts and
the payload's byte planes, all computed here once per wave, and keeps
scalar-item levels 2-D.  ``dispatch`` applies to the vmem tiling, whose
insert-permutation backend it selects per payload group
(``common.resolve_dispatch``: ``"auto"`` routes waves at least
``MXU_DISPATCH_WAVE`` lanes wide through the MXU dispatch matmul).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import indexing
from repro.kernels import common
from repro.kernels.push_back import kernel as _kernel
from repro.kernels.push_back import ref as _ref
from repro.obs import device

__all__ = ["push_back_fused", "push_back_fused_multi"]


def _row_tile(bucket_groups, item_shapes) -> int:
    """Row padding of a wave: the kernel tile, raised to the HBM row tile of
    any 2-D (scalar-item) level so its row windows stay aligned."""
    tile = _kernel.DEFAULT_BLOCK_TILE
    for grp, item in zip(bucket_groups, item_shapes):
        if not item:
            tile = max(tile, common.tile_rows(grp[0].dtype))
    return tile


def _oracle_counters(mask, sizes, b0, nlev, nblocks, m, tile):
    """jnp device counters matching the in-kernel block's accounting: the
    same padded-wave geometry the fused kernel runs, so the use_ref path
    reports identical numbers (cross-checked in tests)."""
    rows_pad = nblocks + (-nblocks) % tile
    m_pad = m + (-m) % common.MXU_LANE
    starts = jnp.asarray(indexing.bucket_starts(b0, nlev), jnp.int32)
    widths = jnp.asarray(indexing.bucket_sizes(b0, nlev), jnp.int32)
    mask_i = mask.astype(jnp.int32)
    count = jnp.sum(mask_i, axis=1)
    lo = jnp.maximum(sizes.astype(jnp.int32)[:, None], starts[None, :])
    hi = jnp.minimum(
        (sizes.astype(jnp.int32) + count)[:, None], (starts + widths)[None, :]
    )
    writes = jnp.sum(jnp.maximum(hi - lo, 0))
    return device.pack(**{
        "push_back.waves": 1,
        "push_back.lanes": rows_pad * m_pad,
        "push_back.active_lanes": jnp.sum(mask_i),
        "push_back.padded_lanes": rows_pad * m_pad - nblocks * m,
        "push_back.level_writes": writes,
    })


@partial(
    jax.jit,
    static_argnames=(
        "b0", "interpret", "use_ref", "memory_space", "dispatch", "instrument",
    ),
)
def push_back_fused_multi(
    bucket_groups: tuple[tuple[jax.Array, ...], ...],
    sizes: jax.Array,  # (nblocks,) int32
    b0: int,
    elem_groups: tuple[jax.Array, ...],  # per group: (nblocks, m, *item_g)
    mask: jax.Array,  # (nblocks, m) bool or 0/1 integers
    *,
    interpret: bool | None = None,
    use_ref: bool = False,
    memory_space: str | None = None,
    dispatch: str = "auto",
    instrument: bool = False,
) -> tuple:
    """→ (new bucket groups, new sizes (nblocks,), positions (−1 masked)).

    ``instrument=True`` appends a device counter vector (``obs/device``
    layout): in-kernel counts on the fused path (plus the statically known
    padding waste), a matching jnp oracle on ``use_ref``/degenerate paths.
    """
    if mask.dtype != jnp.bool_:
        mask = mask != 0
    nblocks, m = elem_groups[0].shape[:2]
    nlev = len(bucket_groups[0])
    if m == 0:
        pos0 = jnp.zeros((nblocks, 0), jnp.int32)
        if instrument:
            return bucket_groups, sizes, pos0, device.zeros()
        return bucket_groups, sizes, pos0
    if use_ref:  # per-group oracle: positions/sizes are mask-only, identical
        groups, new_sizes, pos = [], None, None
        for buckets, elems in zip(bucket_groups, elem_groups):
            levels, new_sizes, pos = _ref.push_back(buckets, sizes, b0, elems, mask)
            groups.append(levels)
        if instrument:
            tile = _row_tile(bucket_groups, [e.shape[2:] for e in elem_groups])
            vec = _oracle_counters(mask, sizes, b0, nlev, nblocks, m, tile)
            return tuple(groups), new_sizes, pos, vec
        return tuple(groups), new_sizes, pos

    space = common.resolve_memory_space(memory_space, interpret)
    run = common.should_interpret(interpret)
    item_shapes = [e.shape[2:] for e in elem_groups]
    tile = _row_tile(bucket_groups, item_shapes)
    row_pad = (-nblocks) % tile

    def flat(x, item):
        d = 1
        for dim in item:
            d *= dim
        return x.reshape(*x.shape[: x.ndim - len(item)], d)

    def rows(x, value=0):  # padded rows: mask all-False, sizes 0 — inert
        return common.pad_to(x, tile, axis=0, value=value) if row_pad else x

    if space == "hbm":
        mask_i = mask.astype(jnp.int32)
        inc = jnp.cumsum(mask_i, axis=1)
        counts = inc[:, -1]
        off = jnp.where(mask, inc - mask_i, -1)
        # scalar levels stay 2-D: a unit feature axis pads HBM tiles 128-fold
        levels = [
            tuple(rows(lvl if not item else flat(lvl, item)) for lvl in grp)
            for grp, item in zip(bucket_groups, item_shapes)
        ]
        planes = [
            common.pad_to(
                rows(common.byte_planes(e if not item else flat(e, item), 1)),
                common.MXU_LANE, axis=2,
            )
            for e, item in zip(elem_groups, item_shapes)
        ]
        outs = _kernel.push_back_hbm(
            tuple(levels),
            rows(sizes.astype(jnp.int32)),
            rows(counts),
            common.pad_to(rows(off, -1), common.MXU_LANE, axis=1, value=-1),
            tuple(planes),
            b0,
            instrument=instrument,
            interpret=run,
        )
        groups = outs[0] if instrument else outs
        out_groups = tuple(
            tuple(lvl[:nblocks].reshape(orig.shape) for lvl, orig in zip(grp, og))
            for grp, og in zip(groups, bucket_groups)
        )
        new_sizes = sizes + counts
        pos = jnp.where(mask, sizes[:, None].astype(jnp.int32) + inc - mask_i, -1)
        if instrument:
            m_pad = m + (-m) % common.MXU_LANE
            pad_waste = (nblocks + row_pad) * m_pad - nblocks * m
            vec = device.from_block(outs[1]) + device.pack(
                **{"push_back.padded_lanes": pad_waste}
            )
            return out_groups, new_sizes, pos, vec
        return out_groups, new_sizes, pos

    dispatches = tuple(
        common.resolve_dispatch(dispatch, m, e.dtype) for e in elem_groups
    )
    buckets3 = [
        tuple(rows(flat(b, item)) for b in grp)
        for grp, item in zip(bucket_groups, item_shapes)
    ]
    elems3 = [
        common.pad_to(rows(flat(e, item)), common.MXU_LANE, axis=1)
        for e, item in zip(elem_groups, item_shapes)
    ]
    mask = common.pad_to(rows(mask), common.MXU_LANE, axis=1)
    outs = _kernel.push_back_pallas(
        tuple(buckets3),
        rows(sizes).reshape(-1, 1).astype(jnp.int32),
        b0,
        tuple(elems3),
        mask.astype(jnp.int32),
        dispatches=dispatches,
        instrument=instrument,
        interpret=run,
    )
    groups, pos, new_sizes = outs[:3]
    out_groups = tuple(
        tuple(
            lvl[:nblocks].reshape(nblocks, lvl.shape[1], *item)
            for lvl in grp
        )
        for grp, item in zip(groups, item_shapes)
    )
    if instrument:
        # tile/MXU padding waste is statically known here, not in-kernel
        pad_waste = mask.shape[0] * mask.shape[1] - nblocks * m
        vec = device.from_block(outs[3]) + device.pack(
            **{"push_back.padded_lanes": pad_waste}
        )
        return out_groups, new_sizes[:nblocks, 0], pos[:nblocks, :m], vec
    return out_groups, new_sizes[:nblocks, 0], pos[:nblocks, :m]


@partial(
    jax.jit,
    static_argnames=(
        "b0", "interpret", "use_ref", "memory_space", "dispatch", "instrument",
    ),
)
def push_back_fused(
    buckets: tuple[jax.Array, ...],
    sizes: jax.Array,  # (nblocks,) int32
    b0: int,
    elems: jax.Array,  # (nblocks, m, *item_shape)
    mask: jax.Array,  # (nblocks, m) bool or 0/1 integers
    *,
    interpret: bool | None = None,
    use_ref: bool = False,
    memory_space: str | None = None,
    dispatch: str = "auto",
    instrument: bool = False,
) -> tuple:
    """→ (new bucket levels, new sizes (nblocks,), positions (−1 masked));
    with ``instrument=True`` a trailing device counter vector rides along."""
    outs = push_back_fused_multi(
        (buckets,), sizes, b0, (elems,), mask,
        interpret=interpret, use_ref=use_ref,
        memory_space=memory_space, dispatch=dispatch, instrument=instrument,
    )
    if instrument:
        groups, new_sizes, pos, vec = outs
        return groups[0], new_sizes, pos, vec
    groups, new_sizes, pos = outs
    return groups[0], new_sizes, pos
