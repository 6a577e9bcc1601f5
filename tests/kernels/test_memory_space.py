"""Memory-space parity: vmem and hbm tilings vs the jnp oracles, bit-exact.

The three indirection kernel families (paged, push_back, flatten) each run
under two ``GridPlan`` tilings (kernels/common): all-VMEM-resident and
HBM-resident with scalar-prefetch tables.  Both must be **bit-identical** to
the jnp references across dtypes and ragged shapes — the deterministic
matrix below pins a curated grid; the hypothesis properties fuzz it.

The dispatch sweep additionally pins the MXU dispatch-matmul permutation
(``dispatch="mxu"``) to the exact one-hot path across the
``MXU_DISPATCH_WAVE`` threshold.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # property tests skip, example tests still run
    from _hypothesis_fallback import given, settings, st

from repro.core import ggarray as gg
from repro.core import indexing
from repro.kernels import common
from repro.kernels.flatten import ops as flatten_ops
from repro.kernels.paged import ops as paged_ops
from repro.kernels.push_back import ops as pb_ops

SPACES = ["vmem", "hbm"]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]


def _values(rng, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-1000, 1000, shape), dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _assert_trees_equal(got, want, msg):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=msg)


# --------------------------------------------------------------------------
# resolve helpers
# --------------------------------------------------------------------------

def test_resolve_memory_space_contract(monkeypatch):
    monkeypatch.delenv("REPRO_MEMORY_SPACE", raising=False)
    assert common.resolve_memory_space("hbm") == "hbm"
    assert common.resolve_memory_space("vmem") == "vmem"
    # interpret mode (this container) defaults to vmem…
    assert common.resolve_memory_space(None, None) == "vmem"
    # …explicit non-interpret defaults to hbm (the TPU serving default)
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    assert common.resolve_memory_space(None, False) == "hbm"
    # env overrides the default but not an explicit argument
    monkeypatch.setenv("REPRO_MEMORY_SPACE", "hbm")
    assert common.resolve_memory_space(None, True) == "hbm"
    assert common.resolve_memory_space("vmem", True) == "vmem"
    with pytest.raises(ValueError):
        common.resolve_memory_space("smem")


def test_vmem_tiling_refuses_compiled_mode():
    """The vmem tilings are interpret-mode oracles: asking to compile one
    (as a TPU run would) raises instead of rerouting to hbm in silence."""
    pool = jnp.zeros((4, 4, 2), jnp.float32)
    pages = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(NotImplementedError, match="vmem tiling"):
        paged_ops.paged_gather(pool, pages, memory_space="vmem", interpret=False)


def test_interpret_mode_is_refused_on_a_tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    assert common.should_interpret(None) is False
    assert common.should_interpret(False) is False
    with pytest.raises(RuntimeError, match="interpret=True"):
        common.should_interpret(True)
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="REPRO_FORCE_INTERPRET"):
        common.should_interpret(None)


def test_resolve_dispatch_threshold():
    thr = common.MXU_DISPATCH_WAVE
    assert common.resolve_dispatch("auto", thr - 1, jnp.float32) == "onehot"
    assert common.resolve_dispatch("auto", thr, jnp.float32) == "mxu"
    assert common.resolve_dispatch("auto", thr, jnp.bfloat16) == "mxu"
    assert common.resolve_dispatch("auto", thr, jnp.int16) == "mxu"
    # wide ints / f64 can exceed the f32 mantissa the MXU accumulates in
    assert common.resolve_dispatch("auto", thr, jnp.int32) == "onehot"
    assert common.resolve_dispatch("auto", thr, jnp.float64) == "onehot"
    assert common.resolve_dispatch("mxu", 1, jnp.float32) == "mxu"
    assert common.resolve_dispatch("onehot", 10 * thr, jnp.float32) == "onehot"


# --------------------------------------------------------------------------
# deterministic parity matrix (runs without hypothesis)
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


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("shape", [(9, 3, 5, 3), (8, 4, 4, 1), (5, 2, 3, 4)])
def test_paged_gather_parity(space, dtype, shape):
    S, T, N, P = shape
    rng = np.random.default_rng(zlib.crc32(repr((space, str(dtype), shape)).encode()))
    pool = _values(rng, (S, T, 2), dtype)
    npages = rng.integers(0, P + 1, N)
    npages[0] = min(P, S // max(N, 1))
    pages = _fleet(rng, S, N, P, np.minimum(npages, S // max(N, 1)))
    got = paged_ops.paged_gather(pool, pages, memory_space=space)
    want = paged_ops.paged_gather(pool, pages, use_ref=True)
    _assert_trees_equal(got, want, f"gather {space} {dtype} {shape}")


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("lengths", [[9, 2, 8, 1, 12], [1, 1, 1, 1, 1], [0, 5, 0, 3, 7]])
def test_paged_attend_parity(space, lengths):
    rng = np.random.default_rng(zlib.crc32(repr((space, lengths)).encode()))
    S, T, N, P = 13, 4, 5, 3
    KH, G, D = 2, 3, 8
    pages = _fleet(rng, S, N, P, [3, 1, 2, 1, 3])
    kp = jnp.asarray(rng.standard_normal((S, T, KH, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((S, T, KH, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((N, KH, G, D)), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_ops.paged_attend(q, kp, vp, pages, lengths, memory_space=space)
    want = paged_ops.paged_attend(q, kp, vp, pages, lengths, use_ref=True)
    _assert_trees_equal(got, want, f"attend {space}")


def _ownership(pages, S, T):
    owners = np.full((S,), -1, np.int32)
    bases = np.zeros((S,), np.int32)
    pg = np.asarray(pages)
    for i in range(pg.shape[0]):
        for p in range(pg.shape[1]):
            if pg[i, p] >= 0:
                owners[pg[i, p]] = i
                bases[pg[i, p]] = p * T
    return jnp.asarray(owners), jnp.asarray(bases)


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("dispatch", ["onehot", "mxu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_slab_append_parity(space, dispatch, dtype):
    rng = np.random.default_rng(zlib.crc32(repr((space, dispatch, str(dtype))).encode()))
    S, T, N, P, m = 14, 4, 4, 4, 5
    pages = _fleet(rng, S, N, P, [4, 2, 3, 4])
    owners, bases = _ownership(pages, S, T)
    sizes = jnp.asarray([7, 1, 5, 10], jnp.int32)
    pool = _values(rng, (S, T, 3), dtype)
    elems = _values(rng, (N, m, 3), dtype)
    mask = jnp.asarray(rng.random((N, m)) > 0.4)
    args = (pool, owners, bases, sizes, elems, mask)
    got = paged_ops.slab_append(*args, memory_space=space, dispatch=dispatch)
    want = paged_ops.slab_append(*args, use_ref=True)
    _assert_trees_equal(got, want, f"slab_append {space} {dispatch}")


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("S,T,N,P,m", [(14, 4, 4, 4, 5), (40, 8, 6, 5, 13), (5, 3, 2, 2, 4)])
def test_scalar_pool_parity(space, dtype, S, T, N, P, m):
    """Scalar items keep the pool 2-D (slabs in tiled rows): gather and
    slab-append through the row-band path match the oracles."""
    rng = np.random.default_rng(zlib.crc32(repr((space, str(dtype), S, T)).encode()))
    pages = _fleet(rng, S, N, P, np.minimum(rng.integers(0, P + 1, N), S // N))
    owners, bases = _ownership(pages, S, T)
    counts = (np.asarray(pages) >= 0).sum(axis=1)
    sizes = jnp.asarray(rng.integers(0, counts * T + 1), jnp.int32)
    pool = _values(rng, (S, T), dtype)
    got = paged_ops.paged_gather(pool, pages, memory_space=space)
    want = paged_ops.paged_gather(pool, pages, use_ref=True)
    _assert_trees_equal(got, want, f"scalar gather {space} {dtype}")
    elems = _values(rng, (N, m), dtype)
    mask = jnp.asarray(rng.random((N, m)) > 0.3)
    args = (pool, owners, bases, sizes, elems, mask)
    got = paged_ops.slab_append(*args, memory_space=space)
    want = paged_ops.slab_append(*args, use_ref=True)
    _assert_trees_equal(got, want, f"scalar slab_append {space} {dtype}")


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("nblocks,b0,nlev,m", [(5, 3, 3, 7), (8, 1, 4, 2), (3, 4, 2, 11)])
def test_push_back_parity(space, dtype, nblocks, b0, nlev, m):
    rng = np.random.default_rng(
        zlib.crc32(repr((space, str(dtype), nblocks, b0, nlev, m)).encode())
    )
    arr = gg.init(nblocks, b0, dtype=dtype, nbuckets=nlev)
    elems = _values(rng, (nblocks, m), dtype)
    mask = jnp.asarray(rng.random((nblocks, m)) > 0.3)
    sizes = jnp.asarray(
        rng.integers(0, indexing.capacity(b0, nlev) + 1, nblocks), jnp.int32
    )
    got = pb_ops.push_back_fused(
        arr.buckets, sizes, b0, elems, mask, memory_space=space
    )
    want = pb_ops.push_back_fused(arr.buckets, sizes, b0, elems, mask, use_ref=True)
    _assert_trees_equal(got, want, f"push_back {space} {dtype}")


@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("nblocks,b0,nlev", [(4, 2, 3), (5, 3, 3), (13, 1, 5), (3, 2, 4)])
def test_flatten_parity(space, dtype, nblocks, b0, nlev):
    rng = np.random.default_rng(
        zlib.crc32(repr((space, str(dtype), nblocks, b0, nlev)).encode())
    )
    arr = gg.init(nblocks, b0, dtype=dtype, nbuckets=nlev)
    per = rng.integers(0, indexing.capacity(b0, nlev) + 1, nblocks)
    m = max(int(per.max()), 1)
    elems = _values(rng, (nblocks, m), dtype)
    mask = jnp.asarray(np.arange(m)[None, :] < per[:, None])
    arr, _ = gg.push_back(arr, elems, mask)
    got = flatten_ops.flatten_segmented(
        arr.buckets, arr.sizes, arr.b0, memory_space=space
    )
    want = flatten_ops.flatten_segmented(
        arr.buckets, arr.sizes, arr.b0, use_ref=True
    )
    _assert_trees_equal(got, want, f"flatten {space} {dtype}")


@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_hbm_windows_past_one_tile(dtype):
    """Level rows wider than one hbm column chunk, and flattens spanning
    several output tiles: the window, chunk and shift arithmetic of the
    hbm tilings runs past its first tile and still matches the oracles."""
    rng = np.random.default_rng(zlib.crc32(repr(("tiles", str(dtype))).encode()))
    nblocks, b0, nlev, m = 11, 256, 6, 48  # levels 256 … 8192 slots
    cap = indexing.capacity(b0, nlev)
    arr = gg.init(nblocks, b0, dtype=dtype, nbuckets=nlev)
    sizes = jnp.asarray(rng.integers(0, cap - m, nblocks), jnp.int32)
    sizes = sizes.at[0].set(512 - 7)  # a wave across the 512-slot chunk edge
    elems = _values(rng, (nblocks, m), dtype)
    mask = jnp.asarray(rng.random((nblocks, m)) > 0.2)
    args = (arr.buckets, sizes, b0, elems, mask)
    got = pb_ops.push_back_fused(*args, memory_space="hbm")
    want = pb_ops.push_back_fused(*args, use_ref=True)
    _assert_trees_equal(got, want, f"push_back hbm tiles {dtype}")
    levels, new_sizes, _ = want
    got = flatten_ops.flatten_segmented(levels, new_sizes, b0, memory_space="hbm")
    want = flatten_ops.flatten_segmented(levels, new_sizes, b0, use_ref=True)
    _assert_trees_equal(got, want, f"flatten hbm tiles {dtype}")


# --------------------------------------------------------------------------
# MXU dispatch-matmul vs one-hot permutation across the wave threshold
# --------------------------------------------------------------------------

@pytest.mark.parametrize("space", SPACES)
@pytest.mark.parametrize(
    "m", [4, common.MXU_DISPATCH_WAVE - 1, common.MXU_DISPATCH_WAVE, 200]
)
def test_mxu_dispatch_matches_onehot_across_threshold(space, m):
    rng = np.random.default_rng(zlib.crc32(repr((space, m)).encode()))
    nblocks, b0, nlev = 4, 8, 4
    arr = gg.init(nblocks, b0, dtype=jnp.float32, nbuckets=nlev)
    elems = jnp.asarray(rng.standard_normal((nblocks, m)), jnp.float32)
    mask = jnp.asarray(rng.random((nblocks, m)) > 0.25)
    sizes = jnp.asarray(rng.integers(0, 2 * b0, nblocks), jnp.int32)
    outs = {
        d: pb_ops.push_back_fused(
            arr.buckets, sizes, b0, elems, mask, memory_space=space, dispatch=d
        )
        for d in ("onehot", "mxu", "auto")
    }
    _assert_trees_equal(outs["mxu"], outs["onehot"], f"mxu vs onehot m={m} {space}")
    _assert_trees_equal(outs["auto"], outs["onehot"], f"auto m={m} {space}")


# --------------------------------------------------------------------------
# hypothesis fuzzing (skips gracefully without hypothesis; CI runs in full)
# --------------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_property_push_back_spaces_bitwise(seed):
    """Any (space, dtype, ragged sizes, wave) → fused == oracle, both spaces."""
    rng = np.random.default_rng(seed)
    nblocks = int(rng.integers(1, 10))
    b0 = int(rng.integers(1, 6))
    nlev = int(rng.integers(1, 5))
    m = int(rng.integers(1, 24))
    dtype = DTYPES[int(rng.integers(0, len(DTYPES)))]
    arr = gg.init(nblocks, b0, dtype=dtype, nbuckets=nlev)
    elems = _values(rng, (nblocks, m), dtype)
    mask = jnp.asarray(rng.random((nblocks, m)) > rng.random())
    sizes = jnp.asarray(
        rng.integers(0, indexing.capacity(b0, nlev) + 2, nblocks), jnp.int32
    )
    want = pb_ops.push_back_fused(arr.buckets, sizes, b0, elems, mask, use_ref=True)
    for space in SPACES:
        got = pb_ops.push_back_fused(
            arr.buckets, sizes, b0, elems, mask, memory_space=space
        )
        _assert_trees_equal(got, want, f"push_back seed={seed} {space}")


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_property_paged_spaces_bitwise(seed):
    """Any (space, dtype, fleet layout, wave) → paged kernels == oracles."""
    rng = np.random.default_rng(seed)
    N = int(rng.integers(1, 7))
    P = int(rng.integers(1, 5))
    T = int(rng.integers(1, 6))
    S = N * P + int(rng.integers(0, 5))
    m = int(rng.integers(1, 12))
    dtype = DTYPES[int(rng.integers(0, len(DTYPES)))]
    pages = _fleet(rng, S, N, P, rng.integers(0, P + 1, N))
    pool = _values(rng, (S, T, 2), dtype)
    owners, bases = _ownership(pages, S, T)
    sizes = jnp.asarray(rng.integers(0, P * T + 1, N), jnp.int32)
    elems = _values(rng, (N, m, 2), dtype)
    mask = jnp.asarray(rng.random((N, m)) > rng.random())
    gather_want = paged_ops.paged_gather(pool, pages, use_ref=True)
    ap_args = (pool, owners, bases, sizes, elems, mask)
    append_want = paged_ops.slab_append(*ap_args, use_ref=True)
    for space in SPACES:
        got = paged_ops.paged_gather(pool, pages, memory_space=space)
        _assert_trees_equal(got, gather_want, f"gather seed={seed} {space}")
        got = paged_ops.slab_append(*ap_args, memory_space=space)
        _assert_trees_equal(got, append_want, f"append seed={seed} {space}")


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_property_flatten_spaces_bitwise(seed):
    """Any (space, dtype, ragged fill) → segmented flatten == oracle."""
    rng = np.random.default_rng(seed)
    nblocks = int(rng.integers(1, 14))
    b0 = int(rng.integers(1, 5))
    nlev = int(rng.integers(1, 5))
    dtype = DTYPES[int(rng.integers(0, len(DTYPES)))]
    arr = gg.init(nblocks, b0, dtype=dtype, nbuckets=nlev)
    per = rng.integers(0, indexing.capacity(b0, nlev) + 1, nblocks)
    m = max(int(per.max()), 1)
    elems = _values(rng, (nblocks, m), dtype)
    mask = jnp.asarray(np.arange(m)[None, :] < per[:, None])
    arr, _ = gg.push_back(arr, elems, mask)
    want = flatten_ops.flatten_segmented(arr.buckets, arr.sizes, arr.b0, use_ref=True)
    for space in SPACES:
        got = flatten_ops.flatten_segmented(
            arr.buckets, arr.sizes, arr.b0, memory_space=space
        )
        _assert_trees_equal(got, want, f"flatten seed={seed} {space}")
