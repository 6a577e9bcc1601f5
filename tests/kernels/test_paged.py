"""Paged kernels (gather / attend / slab-append) vs their jnp oracles.

All comparisons are exact (``assert_array_equal``): interpret-mode kernels
mirror the references op-for-op, so any drift is a real indexing bug.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged import kernel as kernel_mod
from repro.kernels.paged import ops
from repro.obs import device
from repro.pool import extents as extents_mod


def _fleet(rng, S, T, N, P, npages):
    """Disjoint random slab assignment for N arrays."""
    pages = np.full((N, P), -1, np.int32)
    perm = rng.permutation(S)
    k = 0
    for i, c in enumerate(npages):
        for p in range(c):
            pages[i, p] = perm[k]
            k += 1
    return jnp.asarray(pages)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("item", [(), (3,), (2, 2)])
def test_paged_gather_matches_ref(dtype, item):
    rng = np.random.default_rng(0)
    S, T, N, P = 11, 4, 5, 3
    pool = jnp.asarray(
        rng.integers(-50, 50, (S, T, *item)).astype(np.dtype(dtype))
    )
    pages = _fleet(rng, S, T, N, P, [3, 0, 2, 1, 3])
    got = ops.paged_gather(pool, pages)
    want = ops.paged_gather(pool, pages, use_ref=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # page −1 reads as zeros
    assert not np.asarray(got)[1].any()


@pytest.mark.parametrize("lengths", [[9, 2, 8, 1, 12], [1, 1, 1, 1, 1]])
def test_paged_attend_matches_ref_bitwise(lengths):
    rng = np.random.default_rng(1)
    S, T, N, P = 13, 4, 5, 3
    KH, G, D = 2, 3, 8
    pages = _fleet(rng, S, T, N, P, [3, 1, 2, 1, 3])
    kp = jnp.asarray(rng.standard_normal((S, T, KH, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((S, T, KH, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((N, KH, G, D)), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = ops.paged_attend(q, kp, vp, pages, lengths)
    want = ops.paged_attend(q, kp, vp, pages, lengths, use_ref=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# The cells' two KV geometries: GQA (2 KV heads of 128, 8 queries each)
# and MHA (16 heads of 64) — lane widths 256 and 1024 of the flat pool.
GEOMETRIES = {"gqa": (2, 128, 8), "mha": (16, 64, 1)}
# Slabs of 32 tokens read in blocks of 16, so every slab spans two steps.
T_SMALL, TILE_SMALL = 32, 16
LENGTHS = {
    "ragged": [45, 0, 17, 64, 96],  # 0, partial blocks, slab ends (64 = 2 slabs)
    "boundaries": [16, 32, 48, 64, 80],  # every length ends on a block edge
    "full": [96, 96, 96, 96, 96],  # every page of every table live
}
PAGES = {"ragged": [2, 0, 1, 2, 3], "boundaries": [1, 1, 2, 2, 3], "full": [3] * 5}


def _attend_case(geometry, lengths, rows_idle=(), extents=1, seed=11):
    KH, D, G = GEOMETRIES[geometry]
    N, P = 5, 3
    lens = list(LENGTHS[lengths])
    npages = list(PAGES[lengths])
    for b in rows_idle:  # an idle slot: no pages, no length
        lens[b], npages[b] = 0, 0
    S = sum(npages) + 3
    rng = np.random.default_rng(seed)
    pages = _fleet(rng, S, T_SMALL, N, P, npages)
    kp = jnp.asarray(rng.standard_normal((S, T_SMALL, KH * D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((S, T_SMALL, KH * D)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((N, KH, G, D)) * D**-0.5, jnp.float32)
    if extents == 2:
        cut = S // 2
        kp, vp = (kp[:cut], kp[cut:]), (vp[:cut], vp[cut:])
    return q, kp, vp, pages, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("extents", [1, 2])
@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_token_major_attend_matches_ref_bitwise(geometry, lengths, extents):
    """The kernel reads the flat (S, T, KH·D) pool in sub-slab blocks, one
    or two extents, and matches ``ref.attend_paged`` bit for bit."""
    q, kp, vp, pages, lens = _attend_case(geometry, lengths, extents=extents)
    got = ops.paged_attend(q, kp, vp, pages, lens, tile=TILE_SMALL)
    want = ops.paged_attend(q, kp, vp, pages, lens, use_ref=True, tile=TILE_SMALL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and it is the attention: close to a float64 softmax over live tokens
    KH, D, G = GEOMETRIES[geometry]
    k = np.asarray(jnp.concatenate(kp) if extents == 2 else kp, np.float64)
    v = np.asarray(jnp.concatenate(vp) if extents == 2 else vp, np.float64)
    for b, L in enumerate(np.asarray(lens)):
        if L == 0:
            assert not np.asarray(got)[b].any()  # nothing live, nothing read
            continue
        ids = [s for s in np.asarray(pages)[b] if s >= 0]
        kk = np.concatenate([k[s] for s in ids])[:L].reshape(L, KH, D)
        vv = np.concatenate([v[s] for s in ids])[:L].reshape(L, KH, D)
        s = np.einsum("kgd,tkd->kgt", np.asarray(q[b], np.float64), kk)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(got)[b], np.einsum("kgt,tkd->kgd", w, vv), atol=1e-4
        )


@pytest.mark.parametrize("extents", [1, 2])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_token_major_attend_idle_rows(geometry, extents):
    """Rows whose pages are all −1 attend nothing and leave the other rows
    exactly as the oracle has them."""
    q, kp, vp, pages, lens = _attend_case(
        geometry, "ragged", rows_idle=(0, 3), extents=extents
    )
    assert (np.asarray(pages)[[0, 3]] == -1).all()
    got = ops.paged_attend(q, kp, vp, pages, lens, tile=TILE_SMALL)
    want = ops.paged_attend(q, kp, vp, pages, lens, use_ref=True, tile=TILE_SMALL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[[0, 3]].any()


@pytest.mark.parametrize("extents", [1, 2])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_token_major_attend_counters_match_oracle(geometry, extents):
    """In-kernel counters equal ``ops._attend_ctr``: a tile is one (row,
    block) step over every KV head."""
    q, kp, vp, pages, lens = _attend_case(
        geometry, "ragged", rows_idle=(3,), extents=extents
    )
    out, vec = ops.paged_attend(q, kp, vp, pages, lens, tile=TILE_SMALL, instrument=True)
    want = ops.paged_attend(q, kp, vp, pages, lens, tile=TILE_SMALL)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    sizes = tuple(e.shape[0] for e in (kp if extents == 2 else (kp,)))
    ext_tbl, _ = extents_mod.resolve_pages(pages, sizes)
    ctr = ops._attend_ctr(ext_tbl, lens, T_SMALL, TILE_SMALL)
    np.testing.assert_array_equal(np.asarray(vec), np.asarray(ctr))
    d = device.as_dict(vec)
    # live blocks: ceil(L / 16) per row; the rest of the 5 × 6 grid skipped
    live = sum(-(-int(L) // TILE_SMALL) for L in np.asarray(lens))
    assert d["paged_attend.tiles"] == live
    assert d["paged_attend.tiles_skipped"] == 5 * 6 - live
    assert d["paged_attend.lanes"] == live * TILE_SMALL
    assert d["paged_attend.masked_lanes"] == live * TILE_SMALL - int(np.sum(lens))


def test_attend_schedule_repeats_blocks_on_dead_steps():
    """Dead steps (unclaimed pages, blocks past the length, idle rows) map
    every extent to the block of the step before, so the pipeline moves no
    bytes for them; live steps map their extent to their own block."""
    ext = jnp.asarray([[0, 1, -1], [-1, -1, -1], [1, 0, 0], [0, -1, -1]], jnp.int32)
    off = jnp.asarray([[2, 0, -1], [-1, -1, -1], [1, 3, 0], [1, -1, -1]], jnp.int32)
    lens = jnp.asarray([40, 0, 70, 10], jnp.int32)
    n_sub, tile = 2, 16
    step_ext, fetch = kernel_mod.attend_schedule(ext, off, lens, n_sub, tile, 2)
    step_ext = np.asarray(step_ext).reshape(4, 6)
    fetch = np.asarray(fetch).reshape(2, 4, 6)
    want_live = np.asarray([
        [0, 0, 1, -1, -1, -1],  # 40 tokens: 3 blocks over pages 0 (ext 0) and 1 (ext 1)
        [-1] * 6,  # idle row
        [1, 1, 0, 0, 0, -1],  # 70 tokens: 5 blocks
        [0, -1, -1, -1, -1, -1],  # 10 tokens: 1 block
    ])
    np.testing.assert_array_equal(step_ext, want_live)
    flat = step_ext.reshape(-1)
    for e in range(2):
        f = fetch[e].reshape(-1)
        blocks = (np.asarray(off)[:, np.arange(6) // n_sub] * n_sub + np.arange(6) % n_sub).reshape(-1)
        live = np.flatnonzero(flat == e)
        np.testing.assert_array_equal(f[live], blocks[live])  # own block
        for i in range(1, f.size):
            if flat[i] != e:
                assert f[i] == f[i - 1], (e, i)  # a repeat: no DMA
        assert (f[: live[0]] == blocks[live[0]]).all()  # leading steps: first block


@pytest.mark.parametrize("item", [(), (2, 3)])
@pytest.mark.parametrize("masked", [False, True])
def test_slab_append_matches_ref_bitwise(item, masked):
    rng = np.random.default_rng(2)
    S, T, N, P, m = 14, 4, 4, 4, 3
    npages = [4, 2, 3, 4]
    pages = np.asarray(_fleet(rng, S, T, N, P, npages))
    owners = np.full((S,), -1, np.int32)
    bases = np.zeros((S,), np.int32)
    for i in range(N):
        for p in range(P):
            if pages[i, p] >= 0:
                owners[pages[i, p]] = i
                bases[pages[i, p]] = p * T
    sizes = np.asarray([7, 1, 5, 10], np.int32)
    pool = jnp.asarray(rng.standard_normal((S, T, *item)), jnp.float32)
    elems = jnp.asarray(rng.standard_normal((N, m, *item)), jnp.float32)
    mask = jnp.asarray(rng.random((N, m)) > 0.4 if masked else np.ones((N, m), bool))
    args = (pool, jnp.asarray(owners), jnp.asarray(bases), jnp.asarray(sizes), elems, mask)
    got = ops.slab_append(*args)
    want = ops.slab_append(*args, use_ref=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # round-trip: gathering back reads the wave at the assigned positions
    new_pool, new_sizes, pos = got
    view = np.asarray(ops.paged_gather(new_pool, jnp.asarray(pages)))
    pos_np, mask_np = np.asarray(pos), np.asarray(mask)
    for i in range(N):
        for lane in range(m):
            if mask_np[i, lane]:
                np.testing.assert_array_equal(
                    view[i, pos_np[i, lane]], np.asarray(elems[i, lane])
                )


def test_slab_append_leaves_unowned_slabs_untouched():
    rng = np.random.default_rng(3)
    S, T, N, m = 10, 4, 2, 5
    pool = jnp.asarray(rng.standard_normal((S, T)), jnp.float32)
    owners = np.full((S,), -1, np.int32)
    owners[4] = 0  # only slab 4 owned
    bases = np.zeros((S,), np.int32)
    sizes = jnp.zeros((N,), jnp.int32)
    elems = jnp.ones((N, m), jnp.float32) * 9.0
    mask = jnp.asarray(np.asarray([[True] * 4 + [False], [True] * 5]))
    new_pool, new_sizes, _ = ops.slab_append(
        pool, jnp.asarray(owners), jnp.asarray(bases), sizes, elems, mask
    )
    before, after = np.asarray(pool), np.asarray(new_pool)
    untouched = [s for s in range(S) if s != 4]
    np.testing.assert_array_equal(after[untouched], before[untouched])
    np.testing.assert_array_equal(after[4], [9.0] * 4)
    # array 1 owns nothing: its writes drop, but its count still advances
    np.testing.assert_array_equal(np.asarray(new_sizes), [4, 5])
