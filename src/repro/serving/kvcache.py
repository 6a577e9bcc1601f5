"""KV-cache policies — the paper's §III structures as serving substrate.

Decode is a per-step ``push_back`` into per-layer K/V arrays whose final
length is unknown at allocation time — exactly the paper's motivating
scenario.  Three policies mirror its comparison (DESIGN.md §3), and a fourth
(``two_phase``, realized in ``serving/engine.py`` via ``freeze_cache`` /
``thaw_cache`` below) applies the paper's §VI.D pattern to the prefill →
decode handoff:

``static``      pre-allocate ``max_seq_len`` (paper's static array).  Fails
                (truncates) past capacity; pays worst-case VRAM up front.
``semistatic``  doubling buffer; **copies the whole cache** on growth (the
                host-resize baseline; the paper's memMap variant remaps pages
                instead — no XLA analog, so the copy is real here).
``ggarray``     geometric seq-dim buckets (bucket b holds ``B0·2^b`` steps):
                growth appends a bucket, never copies; capacity stays < 2×
                the live context + B0.  Attention walks the bucket chain with
                online-softmax merging — the rw_b access pattern.
``paged``       the slab arena (DESIGN.md §4): K/V live in one shared pool
                of ``slab_tokens``-sized slabs; each sequence holds a page
                table of slab indices.  Growth is "claim a slab" (no copy,
                no per-sequence worst case) and the fleet's capacity is
                bounded by live tokens + one slab per sequence.  Attention
                walks the pages in *geometric groups* (level b = pages
                ``[2^b−1, 2^(b+1)−1)``), which reproduces the ggarray bucket
                walk segment-for-segment — bit-exact when ``slab_tokens ==
                cache_b0``.  Served by ``serving/engine.py::BatchEngine``
                (continuous batching, slab reclamation).

A cache *slot* (one attention layer kind) is a dict of arrays; the serving
stack stacks slots over scan periods.  Bucket count is static per compiled
step; growth events change the pytree structure at the program boundary
(O(log n) recompiles total, warm-cached — DESIGN.md §2).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import indexing
from repro.models.attention import MASK_VALUE
from repro.obs import device

__all__ = [
    "init_cache",
    "cache_capacity",
    "capacity_of",
    "append",
    "attend",
    "copy_slab",
    "chunk_attend",
    "scatter_chunk",
    "grow_ggarray",
    "freeze_cache",
    "thaw_cache",
    "fill_from_prefill",
    "needed_levels",
    "cache_bytes",
]

Cache = dict[str, Any]


def needed_levels(b0: int, length: int) -> int:
    return max(indexing.min_buckets_for(b0, length), 1)


def cache_capacity(cfg: ModelConfig, policy: str, length_hint: int) -> int:
    if policy == "static":
        return length_hint
    if policy == "semistatic":
        cap = max(cfg.cache_b0, 1)
        while cap < length_hint:
            cap *= 2
        return cap
    if policy == "paged":
        T = cfg.slab_tokens
        return max(-(-length_hint // T), 1) * T
    return indexing.capacity(cfg.cache_b0, needed_levels(cfg.cache_b0, length_hint))


def _level_shapes(cfg: ModelConfig, nlevels: int) -> list[int]:
    return list(indexing.bucket_sizes(cfg.cache_b0, nlevels))


def init_cache(
    cfg: ModelConfig,
    batch: int,
    length_hint: int,
    policy: str | None = None,
    *,
    stack: int | None = None,
    dtype=None,
) -> Cache:
    """Empty cache slot sized for ``length_hint`` under ``policy``.

    ``stack``: leading periods dim (scan-over-layers stacking).
    ``cfg.cache_quant``: int8 K/V with per-(token, kv-head) scales — halves
    the decode memory-roofline term (the cache stream dominates it).
    """
    policy = cfg.cache_policy if policy is None else policy
    quant = cfg.cache_quant
    dtype = (jnp.int8 if quant else jnp.dtype(cfg.dtype)) if dtype is None else dtype
    lead = (stack,) if stack else ()
    kh, dh = cfg.n_kv_heads, cfg.head_dim

    def z(length):
        return jnp.zeros((*lead, batch, length, kh, dh), dtype)

    def zs(length):  # per-(token, head) dequant scales
        return jnp.zeros((*lead, batch, length, kh), jnp.bfloat16)

    if policy in ("static", "semistatic"):
        cap = cache_capacity(cfg, policy, length_hint)
        out = {"k": z(cap), "v": z(cap)}
        if quant:
            out["ks"] = zs(cap)
            out["vs"] = zs(cap)
        return out
    if policy == "paged":
        # Standalone slot: slabs pre-assigned batch-major (sequence b owns
        # slabs [b·maxp, (b+1)·maxp)).  BatchEngine instead manages pages
        # through a shared SlabAllocator (claim on growth, release on
        # completion) — see init_paged_caches/serving/engine.py.
        T = cfg.slab_tokens
        maxp = max(-(-length_hint // T), 1)
        n_slabs = batch * maxp
        base = jnp.arange(n_slabs, dtype=jnp.int32).reshape(batch, maxp)
        out = paged_pools(cfg, (*lead, n_slabs), dtype)
        out["pages"] = (
            jnp.broadcast_to(base, (*lead, batch, maxp)).copy() if lead else base
        )
        return out
    nlevels = needed_levels(cfg.cache_b0, length_hint)
    cache: Cache = {}
    for lvl, size in enumerate(_level_shapes(cfg, nlevels)):
        cache[f"k{lvl}"] = z(size)
        cache[f"v{lvl}"] = z(size)
        if quant:
            cache[f"ks{lvl}"] = zs(size)
            cache[f"vs{lvl}"] = zs(size)
    return cache


def paged_pools(cfg: ModelConfig, slabs: tuple[int, ...], dtype) -> Cache:
    """Zeroed K/V pools of ``slabs`` (leading dims + slab count) slabs of
    ``cfg.slab_tokens`` tokens: K and V hold each token's heads side by
    side, ``(…, T, KH·Dh)`` — the layout the decode kernel reads in place —
    and int8 caches (``cfg.cache_quant``) add ``(…, T, KH)`` scale pools."""
    T, kh = cfg.slab_tokens, cfg.n_kv_heads
    out = {
        "k_pool": jnp.zeros((*slabs, T, kh * cfg.head_dim), dtype),
        "v_pool": jnp.zeros((*slabs, T, kh * cfg.head_dim), dtype),
    }
    if cfg.cache_quant:
        out["ks_pool"] = jnp.zeros((*slabs, T, kh), jnp.bfloat16)
        out["vs_pool"] = jnp.zeros((*slabs, T, kh), jnp.bfloat16)
    return out


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(…, L, KH, Dh) → int8 values + (…, L, KH) scales."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


import re as _re

_LEVEL_KEY = _re.compile(r"^k(\d+)$")


def _levels(cache: Cache) -> int:
    return sum(1 for key in cache if _LEVEL_KEY.match(key))


def _is_ggarray(cache: Cache) -> bool:
    return "k0" in cache


def _is_paged(cache: Cache) -> bool:
    return "k_pool" in cache


def _is_quant(cache: Cache) -> bool:
    return "ks0" in cache or "ks" in cache or "ks_pool" in cache


# ---- segmented pools (pool/extents) ---------------------------------------
# K/V pools hold each token's heads side by side (``paged_pools``): a TPU
# tiles an array's two minor dims, so a trailing ``(KH, Dh)`` pair could
# only be viewed as one row by copying the pool.  Writes take any item
# shape of the right size; gathers hand back the pool's own.
# A paged pool value (``k_pool``/``v_pool``/``ks_pool``/``vs_pool``) is
# either a flat array (single-extent layout — the original trace) or a
# tuple of extents: growth appended an extent instead of copying the pool,
# and global slab ids resolve through the two-level (extent, offset) table.

def _pool_exts(pool) -> tuple[jax.Array, ...]:
    return tuple(pool) if isinstance(pool, (tuple, list)) else (pool,)


def _pool_first(pool) -> jax.Array:
    return _pool_exts(pool)[0]


def _pool_slabs(pool) -> int:
    return sum(e.shape[0] for e in _pool_exts(pool))


def _scatter_pool(pool, slab: jax.Array, slot: jax.Array, vals: jax.Array):
    """``pool.at[slab, slot].set(vals, mode="drop")`` through the extent
    table; ``slab`` entries < 0 or ≥ n_slabs drop.  Returns the pool in its
    own structure (flat array or tuple of extents)."""
    exts = _pool_exts(pool)
    vals = vals.reshape(vals.shape[0], *exts[0].shape[2:])
    if not isinstance(pool, (tuple, list)):
        S = exts[0].shape[0]
        tgt = jnp.where((slab >= 0) & (slab < S), slab, S)
        return exts[0].at[tgt, slot].set(vals, mode="drop")
    from repro.pool import extents as _extents

    ext_t, off_t = _extents.resolve_pages(
        slab, tuple(e.shape[0] for e in exts)
    )
    out = list(exts)
    for e, ext in enumerate(exts):
        tgt = jnp.where(ext_t == e, off_t, ext.shape[0])
        out[e] = ext.at[tgt, slot].set(vals, mode="drop")
    return tuple(out)


def copy_slab(pool, src: int, dst: int, *, axis: int = 0):
    """Device copy of one slab ``src → dst`` across the flat or extent
    layout — the **copy-on-write** private copy (DESIGN.md §10): a decode or
    chunk append that would write into a *shared* slab (refcount > 1) first
    duplicates that one slab into a fresh claim, then appends there, so the
    cached original is never mutated in place.

    ``src``/``dst`` are host ints, so extent routing is pure host
    arithmetic; the copy itself is one sliced gather + scatter on device
    (one slab's bytes — never the pool).  ``axis`` is the slab axis
    (0 for arena pools, 1 for the engine's period-stacked pools).
    """
    exts = list(_pool_exts(pool))
    flat = not isinstance(pool, (tuple, list))

    def locate(s: int) -> tuple[int, int]:
        base = 0
        for e, ext in enumerate(exts):
            if s < base + ext.shape[axis]:
                return e, s - base
            base += ext.shape[axis]
        raise IndexError(f"slab {s} outside pool of {base}")

    se, so = locate(src)
    de, do = locate(dst)
    lead = (slice(None),) * axis
    exts[de] = exts[de].at[lead + (do,)].set(exts[se][lead + (so,)])
    return exts[0] if flat else tuple(exts)


def _scatter_slab(pool, slab: jax.Array, vals: jax.Array):
    """Whole-slab scatter (``pool.at[slab].set``) through the extent table;
    ``slab`` entries < 0 or ≥ n_slabs drop."""
    exts = _pool_exts(pool)
    vals = vals.reshape(vals.shape[:2] + exts[0].shape[2:])
    if not isinstance(pool, (tuple, list)):
        S = exts[0].shape[0]
        tgt = jnp.where((slab >= 0) & (slab < S), slab, S)
        return exts[0].at[tgt].set(vals, mode="drop")
    from repro.pool import extents as _extents

    ext_t, off_t = _extents.resolve_pages(
        slab, tuple(e.shape[0] for e in exts)
    )
    out = list(exts)
    for e, ext in enumerate(exts):
        tgt = jnp.where(ext_t == e, off_t, ext.shape[0])
        out[e] = ext.at[tgt].set(vals, mode="drop")
    return tuple(out)


def capacity_of(cache: Cache) -> int:
    """Sequence-slot capacity of one cache slot — static host-side metadata.

    Capacity is pytree *structure* (shapes), never device data, so the
    engine's per-step growth check costs zero transfers.  For paged caches
    this is the page-table reach (claimed or not); the live guarantee is the
    allocator's, not the shape's.
    """
    if _is_paged(cache):
        return cache["pages"].shape[-1] * _pool_first(cache["k_pool"]).shape[-2]
    if "k" in cache:
        return cache["k"].shape[-3]
    return indexing.capacity(cache["k0"].shape[-3], _levels(cache))


def grow_ggarray(cache: Cache, cfg: ModelConfig, levels: int = 1) -> Cache:
    """Copy-free growth: append the next geometric bucket level(s)."""
    n = _levels(cache)
    proto = cache["k0"]
    out = dict(cache)
    for lvl in range(n, n + levels):
        size = cfg.cache_b0 * (1 << lvl)
        shape = (*proto.shape[:-3], size, *proto.shape[-2:])
        out[f"k{lvl}"] = jnp.zeros(shape, proto.dtype)
        out[f"v{lvl}"] = jnp.zeros(shape, proto.dtype)
        if _is_quant(cache):
            sshape = (*proto.shape[:-3], size, proto.shape[-2])
            out[f"ks{lvl}"] = jnp.zeros(sshape, jnp.bfloat16)
            out[f"vs{lvl}"] = jnp.zeros(sshape, jnp.bfloat16)
    return out


def cache_bytes(cache: Cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


# --------------------------------------------------------------------------
# freeze / thaw — the two-phase handoff at the prefill → decode boundary.
#
# A ggarray cache's per-sequence layout is the LFVector address map: level
# ``lvl`` covers contiguous in-sequence positions [start_lvl, start_lvl +
# size_lvl).  Flattening is therefore a *static* concatenation along the seq
# axis (the kernels' segmented gather degenerates to a copy — there is no
# ragged per-block table here), and thaw is the inverse static slicing.
# Frozen caches use the static-policy layout, so ``attend`` takes the
# single-segment path: one softmax pass instead of one per bucket level —
# the "regular access" speed the paper's two-phase pattern is about.
# --------------------------------------------------------------------------

_KEY_AXIS = {"k": -3, "v": -3, "ks": -2, "vs": -2}


def freeze_cache(cache: Cache) -> Cache:
    """ggarray cache → contiguous static-layout cache (runtime freeze()).

    Pass-through keys (``cross_k``/``cross_v``, already-static caches) are
    preserved.  This is the once-per-phase O(n) copy the pattern amortizes.
    """
    if not _is_ggarray(cache):
        return dict(cache)
    n = _levels(cache)
    bases = ["k", "v"] + (["ks", "vs"] if _is_quant(cache) else [])
    out = {
        key: val
        for key, val in cache.items()
        if not any(key.startswith(b) and key[len(b) :].isdigit() for b in bases)
    }
    for base in bases:
        out[base] = jnp.concatenate(
            [cache[f"{base}{lvl}"] for lvl in range(n)], axis=_KEY_AXIS[base]
        )
    return out


def _slice_level(arr: jax.Array, lo: int, size: int, axis: int) -> jax.Array:
    """arr[..., lo:lo+size, ...] along ``axis``, zero-padded to ``size``."""
    axis = axis % arr.ndim
    cap = arr.shape[axis]
    take = max(min(cap - lo, size), 0)
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(lo, lo + take)
    seg = arr[tuple(idx)]
    if take < size:
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, size - take)
        seg = jnp.pad(seg, widths)
    return seg


def thaw_cache(cache: Cache, b0: int) -> Cache:
    """Contiguous static-layout cache → ggarray cache (runtime thaw()).

    Produces the smallest bucket chain whose capacity covers the frozen
    buffer; the last level zero-pads past it.  Inverse of ``freeze_cache``
    up to that tail padding.
    """
    if _is_ggarray(cache):
        return dict(cache)
    cap = cache["k"].shape[-3]
    nlev = max(indexing.min_buckets_for(b0, cap), 1)
    starts = indexing.bucket_starts(b0, nlev)
    sizes = indexing.bucket_sizes(b0, nlev)
    bases = ["k", "v"] + (["ks", "vs"] if _is_quant(cache) else [])
    out = {key: val for key, val in cache.items() if key not in bases}
    for base in bases:
        for lvl in range(nlev):
            out[f"{base}{lvl}"] = _slice_level(
                cache[base], starts[lvl], sizes[lvl], _KEY_AXIS[base]
            )
    return out


# --------------------------------------------------------------------------
# append — push_back of one decode step. k/v: (B, 1, KH, Dh); pos: (B,) or ().
# --------------------------------------------------------------------------

def append(
    cache: Cache,
    k: jax.Array,
    v: jax.Array,
    pos: jax.Array,
    cfg: ModelConfig | None = None,
) -> Cache:
    """``cfg`` (optional) threads ``kernel_memory_space`` to the fused
    push-back kernel; without it the kernel-layer default applies (hbm on
    TPU, vmem in interpret mode — ``kernels/common``)."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), k.shape[:1])  # (B,)
    rows = jnp.arange(k.shape[0])
    quant = _is_quant(cache)
    if quant:
        k, k_s = _quantize_kv(k)
        v, v_s = _quantize_kv(v)
    if _is_paged(cache):
        # scatter through the page table: slab = pages[b, pos // T].  An
        # unclaimed page (−1) or out-of-table position drops the write —
        # the idle-slot / truncation semantics of the batch engine.
        T = _pool_first(cache["k_pool"]).shape[-2]
        maxp = cache["pages"].shape[-1]
        pidx = jnp.clip(pos // T, 0, maxp - 1)
        slab = cache["pages"][rows, pidx]
        slab = jnp.where((slab >= 0) & (pos < maxp * T), slab, -1)  # ⇒ drop
        slot = pos % T
        if cfg is not None and cfg.instrument:
            # one decode token per lane; a −1 slab is a dropped (wasted) lane
            device.record(device.pack(**{
                "slab_append.waves": 1,
                "slab_append.lanes": int(k.shape[0]),
                "slab_append.active_lanes": jnp.sum((slab >= 0).astype(jnp.int32)),
            }))
        out = dict(cache)
        out["k_pool"] = _scatter_pool(cache["k_pool"], slab, slot, k[:, 0])
        out["v_pool"] = _scatter_pool(cache["v_pool"], slab, slot, v[:, 0])
        if quant:
            out["ks_pool"] = _scatter_pool(cache["ks_pool"], slab, slot, k_s[:, 0])
            out["vs_pool"] = _scatter_pool(cache["vs_pool"], slab, slot, v_s[:, 0])
        return out
    if not _is_ggarray(cache):
        cap = cache["k"].shape[-3]
        tgt = jnp.where(pos < cap, pos, cap)  # static policy truncates past cap
        out = {
            "k": cache["k"].at[rows, tgt].set(k[:, 0], mode="drop"),
            "v": cache["v"].at[rows, tgt].set(v[:, 0], mode="drop"),
        }
        if quant:
            out["ks"] = cache["ks"].at[rows, tgt].set(k_s[:, 0], mode="drop")
            out["vs"] = cache["vs"].at[rows, tgt].set(v_s[:, 0], mode="drop")
        return out
    # ggarray: the decode hot path is a one-lane-per-sequence wave (m=1),
    # which sits far below the measured fused-kernel crossover
    # (kernels/tuning.FUSED_PUSH_BACK_MIN_WAVE) — the empirical "auto"
    # resolution pins it to the jnp scan+scatter path (``use_ref``), which is
    # bit-identical and ~7× faster at this wave width.  Wider waves (batched
    # cache refill) go through the fused Pallas kernel: offsets + every-level
    # scatter in one aliased pass, all payloads (k/v + quant scales) sharing
    # one mask/permutation via the multi-group variant.
    from repro.kernels.push_back import ops as push_back_ops
    from repro.kernels.tuning import resolve_push_back_method

    n = _levels(cache)
    b0 = cache["k0"].shape[-3]
    lane = jnp.ones((k.shape[0], 1), bool)
    bases = ["k", "v"] + (["ks", "vs"] if quant else [])
    payloads = [k, v] + ([k_s, v_s] if quant else [])
    bucket_groups = tuple(
        tuple(cache[f"{base}{lvl}"] for lvl in range(n)) for base in bases
    )
    inst = cfg is not None and cfg.instrument
    outs = push_back_ops.push_back_fused_multi(
        bucket_groups, pos, b0, tuple(payloads), lane,
        use_ref=resolve_push_back_method("auto", k.shape[1]) != "fused",
        memory_space=cfg.kernel_memory_space if cfg is not None else None,
        instrument=inst,
    )
    groups = outs[0]
    if inst:
        device.record(outs[3])
    out = dict(cache)
    for base, levels in zip(bases, groups):
        for lvl in range(n):
            out[f"{base}{lvl}"] = levels[lvl]
    return out


# --------------------------------------------------------------------------
# attend — one-token attention against the cache (rw_b bucket walk).
# --------------------------------------------------------------------------

def _partial_scores(q, k, v, kpos, live_len, state):
    """Online-softmax update of ``state`` with one K/V segment.

    q: (B, KH, G, Dh) f32 · k/v: (B, L, KH, Dh) · kpos: (L,) global positions.
    """
    m, l, acc = state
    s = jnp.einsum("bkgd,blkd->bkgl", q, k.astype(jnp.float32))
    live = kpos[None, :] < live_len[:, None]  # (B, L)
    s = jnp.where(live[:, None, None, :], s, MASK_VALUE)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum("bkgl,blkd->bkgd", p, v.astype(jnp.float32))
    return m_new, l, acc


def attend(
    cache: Cache, q: jax.Array, length: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """q: (B, 1, H, Dh); ``length``: live entries per sequence ((B,) or ()).

    Returns (B, 1, H, Dh).  For ggarray caches this is the paper's bucket
    walk: one partial-softmax pass per level, merged online — the O(log n)
    'multiple pointers' cost the paper measures in Fig. 5 is the extra
    per-level masking/merge here.  Paged caches take the flash-decode kernel
    on a TPU (:func:`resolve_paged_attend`); elsewhere they walk the page
    table in the same geometric segmentation (level b = pages [2^b−1,
    2^(b+1)−1), padded to the full level width), so with ``slab_tokens ==
    cache_b0`` the result is **bit-exact** vs the ggarray walk whenever
    ``length ≥ 1`` — stale slab contents only ever sit behind exact-zero
    softmax weights.
    """
    B, _, H, Dh = q.shape
    kh = cfg.n_kv_heads
    g = H // kh
    scale = Dh ** -0.5
    qf = q[:, 0].reshape(B, kh, g, Dh).astype(jnp.float32) * scale
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    state = (
        jnp.full((B, kh, g), MASK_VALUE, jnp.float32),
        jnp.zeros((B, kh, g), jnp.float32),
        jnp.zeros((B, kh, g, Dh), jnp.float32),
    )
    quant = _is_quant(cache)

    def _kv(ck, cv, sk, sv):
        if not quant:
            return ck, cv
        return _dequant(ck, sk), _dequant(cv, sv)

    if _is_paged(cache):
        out = _attend_paged(cache, qf, length, cfg, state)
        return out.reshape(B, 1, H, Dh).astype(q.dtype)
    if _is_ggarray(cache):
        n = _levels(cache)
        b0 = cache["k0"].shape[-3]
        starts = indexing.bucket_starts(b0, n)
        for lvl in range(n):
            kpos = starts[lvl] + jnp.arange(cache[f"k{lvl}"].shape[-3])
            kk, vv = _kv(
                cache[f"k{lvl}"], cache[f"v{lvl}"],
                cache.get(f"ks{lvl}"), cache.get(f"vs{lvl}"),
            )
            state = _partial_scores(qf, kk, vv, kpos, length, state)
    else:
        kpos = jnp.arange(cache["k"].shape[-3])
        kk, vv = _kv(cache["k"], cache["v"], cache.get("ks"), cache.get("vs"))
        state = _partial_scores(qf, kk, vv, kpos, length, state)
    m, l, acc = state
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def _gather_pool(pool, grp: jax.Array) -> jax.Array:
    """pool (S, T, …) or tuple of extents, page group (B, w) → (B, w·T, …);
    −1 pages gather slab 0 (the values are dead: every lane they cover is
    softmax-masked).  Multi-extent pools resolve global ids through the
    two-level table and select per extent."""
    exts = _pool_exts(pool)
    T = exts[0].shape[1]
    B, w = grp.shape
    if len(exts) == 1:
        S = exts[0].shape[0]
        out = exts[0][jnp.clip(grp, 0, max(S - 1, 0))]  # (B, w, T, …)
        return out.reshape(B, w * T, *exts[0].shape[2:])
    from repro.pool import extents as _extents

    ext_t, off_t = _extents.resolve_pages(grp, tuple(e.shape[0] for e in exts))
    out = jnp.zeros((B, w, *exts[0].shape[1:]), exts[0].dtype)
    for e, ext in enumerate(exts):
        g = ext[jnp.clip(off_t, 0, ext.shape[0] - 1)]
        sel = (ext_t == e).reshape(B, w, *([1] * (g.ndim - 2)))
        out = jnp.where(sel, g, out)
    return out.reshape(B, w * T, *exts[0].shape[2:])


def _gather_kv(cache, grp: jax.Array, kh: int) -> tuple[jax.Array, jax.Array]:
    """Gather a page group's K and V → each (B, w·T, KH, Dh), dequantised
    to f32 for int8 pools."""
    k = _gather_pool(cache["k_pool"], grp)
    v = _gather_pool(cache["v_pool"], grp)
    k = k.reshape(*k.shape[:-1], kh, -1)
    v = v.reshape(*v.shape[:-1], kh, -1)
    if not _is_quant(cache):
        return k, v
    return (
        _dequant(k, _gather_pool(cache["ks_pool"], grp)),
        _dequant(v, _gather_pool(cache["vs_pool"], grp)),
    )


def _levels_walk_ctr(pages, length, T: int, npools: int) -> jax.Array:
    """Device counters for the jnp geometric-levels walk: every level is
    gathered at its padded-to-power-of-two width (−1 pages included — the
    walk masks them in softmax, it does not skip them), so ``masked_lanes``
    is the real over-read this path pays vs the gated Pallas kernel."""
    from repro.pool.arena import geometric_page_groups

    B = pages.shape[0]
    tiles = 0
    lanes = 0
    live_pages = jnp.zeros((), jnp.int32)
    masked = jnp.zeros((), jnp.int32)
    kv = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    for lo, hi in geometric_page_groups(pages.shape[-1]):
        full = 1
        while full < hi - lo:
            full *= 2
        tiles += B * full
        lanes += B * full * T
        live_pages = live_pages + jnp.sum((pages[:, lo:hi] >= 0).astype(jnp.int32))
        live_lanes = jnp.clip(kv - lo * T, 0, full * T)
        masked = masked + jnp.sum(full * T - live_lanes)
    return device.pack(**{
        "paged_gather.launches": npools,
        "paged_gather.tiles": npools * live_pages,
        "paged_gather.masked_tiles": npools * (tiles - live_pages),
        "paged_attend.launches": 1,
        "paged_attend.tiles": tiles,
        "paged_attend.lanes": lanes,
        "paged_attend.masked_lanes": masked,
    })


PAGED_ATTEND_IMPLS = ("auto", "levels", "pallas")


def resolve_paged_attend(impl: str, quant: bool) -> str:
    """Resolve ``cfg.paged_attend_impl`` for one cache → ``"levels"`` or
    ``"pallas"``.

    ``"auto"`` takes the flash-decode kernel where Pallas compiles natively
    (not ``common.should_interpret(None)``: a TPU) and the pool holds
    unquantised K/V, and the ``levels`` walk otherwise: on the CPU, where
    the walk is the bit-exact oracle against ``Engine``, and for int8
    pools, which the kernel cannot dequantise.  Explicit ``"levels"`` and
    ``"pallas"`` are honoured, except that an int8 pool always walks.
    """
    if impl not in PAGED_ATTEND_IMPLS:
        raise ValueError(f"paged_attend_impl {impl!r} not in {PAGED_ATTEND_IMPLS}")
    if quant:
        return "levels"
    if impl == "auto":
        from repro.kernels import common

        return "levels" if common.should_interpret(None) else "pallas"
    return impl


def _attend_paged(cache, qf, length, cfg, state):
    """The paged walk: the flash-decode kernel, or geometric page groups."""
    from repro.pool.arena import geometric_page_groups

    pages = cache["pages"]
    T = _pool_first(cache["k_pool"]).shape[-2]
    if resolve_paged_attend(cfg.paged_attend_impl, _is_quant(cache)) == "pallas":
        from repro.kernels.paged import ops as paged_ops

        if cfg.instrument:
            out, vec = paged_ops.paged_attend(
                qf, cache["k_pool"], cache["v_pool"], pages, length,
                instrument=True,
            )
            device.record(vec)
            return out
        return paged_ops.paged_attend(
            qf, cache["k_pool"], cache["v_pool"], pages, length
        )
    if cfg.instrument:
        device.record(
            _levels_walk_ctr(pages, length, T, 4 if _is_quant(cache) else 2)
        )
    for lo, hi in geometric_page_groups(pages.shape[-1]):
        width = hi - lo
        full = 1
        while full < width:
            full *= 2
        grp = pages[:, lo:hi]
        if width < full:  # pad to the ggarray level width (exact no-op lanes)
            grp = jnp.pad(grp, ((0, 0), (0, full - width)), constant_values=-1)
        kk, vv = _gather_kv(cache, grp, cfg.n_kv_heads)
        kpos = lo * T + jnp.arange(full * T)
        state = _partial_scores(qf, kk, vv, kpos, length, state)
    m, l, acc = state
    return acc / jnp.maximum(l[..., None], 1e-30)


# --------------------------------------------------------------------------
# chunked prefill over a paged slot — prefix walk + in-chunk causal pass.
#
# Bit-exactness contract (DESIGN.md §7): with ``attention_chunk`` c in the
# cache's geometric chain and the prefill chunk size a multiple of c, the
# chunk/monolithic partitions put the same *live* score lanes into the same
# online-softmax updates, and dead lanes (pad tokens, unwritten slab slots,
# whole future chunks) contribute exactly 0.0 — ``exp(MASK_VALUE − m)``
# underflows to 0.0 and ``x + 0.0 == x`` — so chunked prefill reproduces the
# monolithic blockwise attention bit for bit.  The update body below is a
# verbatim transcription of ``attention._blockwise_attention``'s scan body
# for that reason: same einsums, same mask/max/exp/accumulate order.
# --------------------------------------------------------------------------


def _chunk_state_update(state, qr, kk, vv, live):
    """One online-softmax update — attention._blockwise_attention's body."""
    from repro.models.attention import SoftmaxState

    s = jnp.einsum("bqkgd,bckd->bqkgc", qr, kk.astype(jnp.float32))
    s = jnp.where(live, s, MASK_VALUE)
    m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(state.m - m_new)
    l = state.l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bqkgc,bckd->bqkgd", p, vv.astype(jnp.float32))
    acc = state.acc * alpha[..., None] + pv
    return SoftmaxState(m_new, l, acc)


def chunk_attend(
    cache: Cache,
    pages_row: jax.Array,  # (maxp,) claimed slab ids for this slot (−1 pad)
    q: jax.Array,  # (1, Cb, H, Dh) chunk queries
    k_chunk: jax.Array,  # (1, Cb, KH, Dh) chunk keys (pre-scatter)
    v_chunk: jax.Array,
    t0: jax.Array,  # () tokens already prefilled (chunk's global offset)
    live: jax.Array,  # () live tokens in this chunk (≤ Cb; rest is padding)
    cfg: ModelConfig,
    first: bool = False,  # STATIC t0 == 0: skip the (all-dead) prefix walk
) -> jax.Array:
    """Chunk-of-prefill attention for one paged slot → (1, Cb, H, Dh).

    The prefix ([0, t0), gathered through ``pages_row``) is walked in
    ``attention_chunk`` steps carrying the online-softmax state, then the
    chunk attends itself causally — one linear pass, exactly the monolithic
    chunk sequence restricted to this chunk's queries.  ``first=True`` skips
    the prefix walk: at t0 = 0 every prefix lane is dead, and dead-lane
    updates are exact no-ops (the §7 contract), so dropping them is
    bit-identical and saves the gather.
    """
    from repro.models.attention import SoftmaxState

    B, Sq, H, Dh = q.shape
    kh = cfg.n_kv_heads
    g = H // kh
    c = cfg.attention_chunk
    qr = q.reshape(B, Sq, kh, g, Dh).astype(jnp.float32) * (Dh ** -0.5)
    state = SoftmaxState(
        m=jnp.full((B, Sq, kh, g), MASK_VALUE, jnp.float32),
        l=jnp.zeros((B, Sq, kh, g), jnp.float32),
        acc=jnp.zeros((B, Sq, kh, g, Dh), jnp.float32),
    )
    quant = _is_quant(cache)

    # ---- prefix: pool gather, fixed maxp·T width (one trace ∀ t0 > 0) ----
    T = _pool_first(cache["k_pool"]).shape[-2]
    Skv = pages_row.shape[0] * T
    if Skv and not first:
        if cfg.instrument:
            # fixed-width prefix gather: every page slot walked, −1 = waste
            np_ = 4 if quant else 2
            live_p = jnp.sum((pages_row >= 0).astype(jnp.int32))
            device.record(device.pack(**{
                "paged_gather.launches": np_,
                "paged_gather.tiles": np_ * live_p,
                "paged_gather.masked_tiles": np_ * (pages_row.shape[0] - live_p),
            }))
        grp = pages_row[None]  # (1, maxp)
        pk, pv_ = _gather_kv(cache, grp, kh)
        cc = min(c, Skv)
        pad = (-Skv) % cc
        if pad:
            pk = jnp.pad(pk, ((0, 0), (0, pad), (0, 0), (0, 0)))
            pv_ = jnp.pad(pv_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nch = pk.shape[1] // cc
        kc = jnp.moveaxis(pk.reshape(B, nch, cc, kh, Dh), 1, 0)
        vc = jnp.moveaxis(pv_.reshape(B, nch, cc, kh, Dh), 1, 0)

        def body(st, xs):
            ci, kk, vv = xs
            kpos = ci * cc + jnp.arange(cc)
            live_m = (kpos < t0)[None, None, None, None, :]
            return _chunk_state_update(st, qr, kk, vv, live_m), None

        state, _ = jax.lax.scan(body, state, (jnp.arange(nch), kc, vc))

    # ---- the chunk itself: causal, pad lanes (≥ live) dead ---------------
    co = min(c, Sq)
    pad = (-Sq) % co
    kc_own = jnp.pad(k_chunk, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k_chunk
    vc_own = jnp.pad(v_chunk, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v_chunk
    qpos = jnp.arange(Sq)
    for ci in range(kc_own.shape[1] // co):
        j = ci * co + jnp.arange(co)
        live_m = (j[None, :] < live) & (qpos[:, None] >= j[None, :])
        state = _chunk_state_update(
            state,
            qr,
            kc_own[:, ci * co : (ci + 1) * co],
            vc_own[:, ci * co : (ci + 1) * co],
            live_m[None, :, None, None, :],
        )
    out = state.acc / jnp.maximum(state.l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def scatter_chunk(
    cache: Cache,
    pages_row: jax.Array,  # (maxp,) claimed slab ids (−1 pad)
    k_chunk: jax.Array,  # (1, Cb, KH, Dh)
    v_chunk: jax.Array,
    t0: jax.Array,
    live: jax.Array,
    cfg: ModelConfig,
) -> Cache:
    """Write a chunk's live K/V into the slot's claimed slabs → new pools.

    Per-token int8 quantization is chunk-invariant, so the stored codes are
    identical to a monolithic fill.  Dead lanes (pad, unclaimed page) route
    to the out-of-bounds slab and drop.
    """
    T = _pool_first(cache["k_pool"]).shape[-2]
    maxp = pages_row.shape[0]
    Cb = k_chunk.shape[1]
    quant = _is_quant(cache)
    k, v = k_chunk[0], v_chunk[0]  # (Cb, KH, Dh)
    if quant:
        k, k_s = _quantize_kv(k)
        v, v_s = _quantize_kv(v)
    pos = t0 + jnp.arange(Cb)
    pidx = jnp.clip(pos // T, 0, maxp - 1)
    slab = pages_row[pidx]
    ok = (jnp.arange(Cb) < live) & (slab >= 0) & (pos < maxp * T)
    slab = jnp.where(ok, slab, -1)  # dead lanes ⇒ mode="drop"
    slot = pos % T
    if cfg.instrument:
        device.record(device.pack(**{
            "slab_append.waves": 1,
            "slab_append.lanes": Cb,
            "slab_append.active_lanes": jnp.sum(ok.astype(jnp.int32)),
        }))
    out = dict(cache)
    out["k_pool"] = _scatter_pool(cache["k_pool"], slab, slot, k)
    out["v_pool"] = _scatter_pool(cache["v_pool"], slab, slot, v)
    if quant:
        out["ks_pool"] = _scatter_pool(cache["ks_pool"], slab, slot, k_s)
        out["vs_pool"] = _scatter_pool(cache["vs_pool"], slab, slot, v_s)
    return out


# --------------------------------------------------------------------------
# prefill → cache (the phase transition: contiguous K/V sliced into buckets).
# --------------------------------------------------------------------------

def fill_from_prefill(
    cache: Cache, k_full: jax.Array, v_full: jax.Array
) -> Cache:
    """Load (B, S, KH, Dh) prefill K/V into an (empty) cache slot.

    ggarray: bucket b receives the contiguous slice [start_b, start_b+len_b)
    — static slicing, no search (the inverse of ``flatten``).
    """
    S = k_full.shape[1]
    quant = _is_quant(cache)
    k_s = v_s = None
    if quant:
        k_full, k_s = _quantize_kv(k_full)
        v_full, v_s = _quantize_kv(v_full)
    if _is_paged(cache):
        # page-sliced scatter: page p takes positions [p·T, (p+1)·T); rows
        # whose page is unclaimed drop (shorter sequences in the batch)
        T = _pool_first(cache["k_pool"]).shape[-2]
        maxp = cache["pages"].shape[-1]
        npages = min(-(-S // T), maxp)
        rows = jnp.arange(k_full.shape[0])

        def _seg(x, p):  # (B, ≤T, …) zero-padded to T
            seg = x[:, p * T : (p + 1) * T]
            if seg.shape[1] < T:
                widths = [(0, 0)] * x.ndim
                widths[1] = (0, T - seg.shape[1])
                seg = jnp.pad(seg, widths)
            return seg

        out = dict(cache)
        for p in range(npages):
            slab = cache["pages"][rows, p]  # −1 unclaimed ⇒ drop
            out["k_pool"] = _scatter_slab(out["k_pool"], slab, _seg(k_full, p))
            out["v_pool"] = _scatter_slab(out["v_pool"], slab, _seg(v_full, p))
            if quant:
                out["ks_pool"] = _scatter_slab(out["ks_pool"], slab, _seg(k_s, p))
                out["vs_pool"] = _scatter_slab(out["vs_pool"], slab, _seg(v_s, p))
        return out
    if not _is_ggarray(cache):
        cap = cache["k"].shape[-3]
        n = min(S, cap)
        out = {
            "k": cache["k"].at[:, :n].set(k_full[:, :n]),
            "v": cache["v"].at[:, :n].set(v_full[:, :n]),
        }
        if quant:
            out["ks"] = cache["ks"].at[:, :n].set(k_s[:, :n])
            out["vs"] = cache["vs"].at[:, :n].set(v_s[:, :n])
        return out
    nlev = _levels(cache)
    b0 = cache["k0"].shape[-3]
    starts = indexing.bucket_starts(b0, nlev)
    sizes = indexing.bucket_sizes(b0, nlev)
    out = dict(cache)
    for lvl in range(nlev):
        lo = starts[lvl]
        if lo >= S:
            break
        n = min(sizes[lvl], S - lo)
        out[f"k{lvl}"] = cache[f"k{lvl}"].at[:, :n].set(k_full[:, lo : lo + n])
        out[f"v{lvl}"] = cache[f"v{lvl}"].at[:, :n].set(v_full[:, lo : lo + n])
        if quant:
            out[f"ks{lvl}"] = cache[f"ks{lvl}"].at[:, :n].set(k_s[:, lo : lo + n])
            out[f"vs{lvl}"] = cache[f"vs{lvl}"].at[:, :n].set(v_s[:, lo : lo + n])
    return out
