"""Pure-jnp oracles for the paged kernels.

Each mirrors its Pallas kernel's accumulation structure op-for-op (same
segment widths, same reduction axes, same masked-update formulation), so
interpret-mode kernel runs are **bit-identical** to these references — the
contract the test matrix asserts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "gather_pages",
    "attend_paged",
    "expand_queries",
    "head_diagonal",
    "split_halves",
    "slab_append",
    "MASK_VALUE",
]

MASK_VALUE = -1e30  # matches models.attention.MASK_VALUE (serving softmax mask)


def gather_pages(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """pool (S, T, D), pages (N, P) int32 → (N, P·T, D); page < 0 → zeros."""
    S, T, D = pool.shape
    N, P = pages.shape
    out = pool[jnp.clip(pages, 0, max(S - 1, 0))]  # (N, P, T, D)
    valid = (pages >= 0)[:, :, None, None]
    return jnp.where(valid, out, 0).reshape(N, P * T, D)


def split_halves(x: jax.Array, dtype, axis: int) -> jax.Array:
    """f32 ``x`` → ``[hi; lo]`` stacked on ``axis``, each in ``dtype``.

    ``hi`` is ``x`` rounded to ``dtype`` and ``lo`` the rounded remainder,
    so a matmul against values held in ``dtype`` (the pool's K/V) that sums
    the two halves' products keeps ~16 bits of ``x``'s mantissa in bf16 —
    every product exact, accumulated in f32 — with no upcast of the pool.
    """
    hi = x.astype(dtype)
    lo = (x - hi.astype(jnp.float32)).astype(dtype)
    return jnp.concatenate([hi, lo], axis=axis)


def expand_queries(q: jax.Array, rows: int, dtype) -> jax.Array:
    """q (B, KH, G, D) → (B, 2·rows, KH·D) block-diagonal query halves.

    Row ``h·G + g`` holds ``q[h, g]`` in head ``h``'s ``D`` lanes and zeros
    elsewhere, so one matmul against a ``(tokens, KH·D)`` block of the
    token-major pool scores every head at once; rows past ``KH·G`` are zero
    padding.  The rows are then :func:`split_halves` in ``dtype``.
    """
    B, KH, G, D = q.shape
    eye = jnp.eye(KH, dtype=q.dtype)
    blk = (q[:, :, :, None, :] * eye[None, :, None, :, None]).reshape(
        B, KH * G, KH * D
    )
    blk = jnp.pad(blk, ((0, 0), (0, rows - KH * G), (0, 0)))
    return split_halves(blk, dtype, 1)


def head_diagonal(out: jax.Array, KH: int, G: int) -> jax.Array:
    """(B, rows, KH·D) products of every query row with every head's V →
    (B, KH, G, D): each row's own head."""
    B, _, F = out.shape
    o = out[:, : KH * G].reshape(B, KH, G, KH, F // KH)
    return jnp.diagonal(o, axis1=1, axis2=3).transpose(0, 3, 1, 2)


def attend_paged(
    q: jax.Array,  # (B, KH, G, D) f32, pre-scaled
    k_pool: jax.Array,  # (S, T, KH·D) — token-major pool
    v_pool: jax.Array,  # (S, T, KH·D)
    pages: jax.Array,  # (B, P) int32
    lengths: jax.Array,  # (B,) int32 live tokens per sequence
    tile: int,  # tokens per block (divides T)
) -> jax.Array:
    """One-token attention through the page table, block by block.

    Online-softmax merge in block order — the flash-decode structure the
    Pallas kernel runs per grid step, on the same block-diagonal query
    halves and ``tile``-token blocks.  A block past the live length (or of
    an unclaimed ``-1`` page) leaves the state untouched, exactly like the
    kernel's skipped steps.
    """
    B, KH, G, D = q.shape
    S, T = k_pool.shape[:2]
    F = KH * D
    R = -(-(KH * G) // 16) * 16
    q2 = expand_queries(q, R, k_pool.dtype)
    kf = k_pool.reshape(S, T, F)
    vf = v_pool.reshape(S, T, F)
    m = jnp.full((B, R, 1), MASK_VALUE, jnp.float32)
    l = jnp.zeros((B, R, 1), jnp.float32)
    acc = jnp.zeros((B, R, F), jnp.float32)
    lengths = lengths.astype(jnp.int32)
    for j in range(pages.shape[1] * (T // tile)):
        slab = pages[:, j * tile // T]  # (B,)
        lo = j * tile % T
        k = jnp.take(kf, jnp.maximum(slab, 0), axis=0)[:, lo : lo + tile]
        v = jnp.take(vf, jnp.maximum(slab, 0), axis=0)[:, lo : lo + tile]
        s2 = jnp.einsum("brf,btf->brt", q2, k, preferred_element_type=jnp.float32)
        s = s2[:, :R] + s2[:, R:]
        kpos = j * tile + jnp.arange(tile, dtype=jnp.int32)
        live = kpos[None, :] < lengths[:, None]  # (B, tile)
        s = jnp.where(live[:, None, :], s, MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pw = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(pw, axis=-1, keepdims=True)
        pv2 = jnp.einsum(
            "brt,btf->brf", split_halves(pw, v.dtype, 1), v,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha + (pv2[:, :R] + pv2[:, R:])
        # block skipped entirely (kernel's dead step) when dead for a row
        use = ((slab >= 0) & (j * tile < lengths))[:, None, None]
        m = jnp.where(use, m_new, m)
        l = jnp.where(use, l_new, l)
        acc = jnp.where(use, acc_new, acc)
    return head_diagonal(acc / jnp.maximum(l, 1e-30), KH, G)


def slab_append(
    pool: jax.Array,  # (S, T, D)
    owners: jax.Array,  # (S,) int32 — owning array per slab, −1 = free
    bases: jax.Array,  # (S,) int32 — logical position of the slab's slot 0
    sizes: jax.Array,  # (N,) int32 — live elements per array
    elems: jax.Array,  # (N, m, D)
    mask: jax.Array,  # (N, m) bool
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """→ (new pool, new sizes, positions (N, m) (−1 where masked)).

    The push_back prefix-sum machinery on an ownership-indirected pool:
    per-array exclusive-scan offsets order the wave, and each slab slot
    ``bases[s] + j`` takes wave element ``offset = bases[s] + j − sizes[o]``
    of its owner ``o`` — the same scatter-as-gather formulation as
    ``kernels/push_back``, with one extra owner indirection per slab row.
    """
    mask_i = mask.astype(jnp.int32)
    inc = jnp.cumsum(mask_i, axis=1)
    off = inc - mask_i
    counts = inc[:, -1]  # (N,)
    pos = sizes[:, None] + off

    N, m = mask.shape
    iota_o = jax.lax.broadcasted_iota(jnp.int32, (N, m, m), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (N, m, m), 2)
    onehot = (off[:, None, :] == iota_o) & (mask_i[:, None, :] > 0)
    sel = jnp.sum(jnp.where(onehot, iota_k, 0), axis=2)
    gathered = jnp.take_along_axis(elems, sel[:, :, None], axis=1)  # (N, m, D)

    own = jnp.clip(owners, 0, N - 1)
    S, T = pool.shape[:2]
    j = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    o = bases[:, None] + j - sizes[own][:, None]  # wave offset at this slot
    valid = (owners[:, None] >= 0) & (o >= 0) & (o < counts[own][:, None])
    vals = jnp.take_along_axis(
        gathered[own], jnp.clip(o, 0, m - 1)[:, :, None], axis=1
    )  # (S, T, D)
    new_pool = jnp.where(valid[:, :, None], vals, pool)
    return new_pool, sizes + counts, jnp.where(mask, pos, -1)
