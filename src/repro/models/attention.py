"""Attention: GQA projections + three interchangeable inner implementations.

``blockwise``  — pure-JAX flash (online softmax over KV chunks via ``lax.scan``,
                 optional query chunking): the dry-run/compile path.  Never
                 materializes a (Sq, Skv) score tensor, so 32k prefill and 500k
                 caches lower with bounded live memory.
``xla``        — naive einsum softmax (tiny shapes / oracle).
``pallas``     — the kernels/flash_attention TPU kernel (interpret off-TPU).

The decode path (one query token against a cache) lives in serving/kvcache.py
and reuses ``_chunk_update`` below for its per-bucket partial attention — the
GGArray rw_b access pattern (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.context import constrain
from repro.models.modules import Param, apply_rope, dense_init, rms_norm, rope

__all__ = [
    "init_attention",
    "attention_block",
    "project_qkv",
    "project_out",
    "inner_attention",
    "SoftmaxState",
    "softmax_state_init",
    "chunk_update",
    "softmax_state_finish",
    "MASK_VALUE",
]

MASK_VALUE = -1e30


# --------------------------------------------------------------------------
# Online-softmax machinery (shared by prefill blockwise + decode buckets).
# --------------------------------------------------------------------------

class SoftmaxState(NamedTuple):
    m: jax.Array  # (..., 1) running max
    l: jax.Array  # (..., 1) running denominator
    acc: jax.Array  # (..., d) running numerator


def softmax_state_init(shape: tuple[int, ...], d: int) -> SoftmaxState:
    return SoftmaxState(
        m=jnp.full((*shape, 1), MASK_VALUE, jnp.float32),
        l=jnp.zeros((*shape, 1), jnp.float32),
        acc=jnp.zeros((*shape, d), jnp.float32),
    )


def chunk_update(
    state: SoftmaxState,
    s: jax.Array,  # (..., kv_chunk) masked scores, f32
    v: jax.Array,  # broadcastable to (..., kv_chunk, d), f32
) -> SoftmaxState:
    m_new = jnp.maximum(state.m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(state.m - m_new)
    l = state.l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = state.acc * alpha + p @ v
    return SoftmaxState(m_new, l, acc)


def softmax_state_finish(state: SoftmaxState) -> jax.Array:
    return state.acc / jnp.maximum(state.l, 1e-30)


# --------------------------------------------------------------------------
# Inner attention implementations. q: (B, Sq, H, Dh); k,v: (B, Skv, KH, Dh).
# --------------------------------------------------------------------------

def _xla_attention(q, k, v, *, group, causal, q_offset=0):
    B, Sq, H, Dh = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    qr = q.reshape(B, Sq, KH, group, Dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr, k.astype(jnp.float32)) * (Dh ** -0.5)
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        mask = qpos[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def _blockwise_attention(q, k, v, *, group, causal, chunk, q_offset=0):
    """Flash attention in pure JAX: scan over KV chunks, carry softmax state."""
    B, Sq, H, Dh = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    chunk = min(chunk, Skv)
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    qr = q.reshape(B, Sq, KH, group, Dh).astype(jnp.float32) * (Dh ** -0.5)
    # q stays seq-sharded; each KV chunk is small and streamed per scan step.
    # Without these constraints the chunk-major reshape can lose the seq
    # sharding (n_chunks not mesh-divisible, e.g. VLM's 33024 tokens) and
    # GSPMD replicates the f32 q (10 GB global on 32k prefill).
    qr = constrain(qr, ("batch", "seq", None, None, None))
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, KH, Dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, KH, Dh), 1, 0)
    kc = constrain(kc, (None, "batch", None, None, None))
    vc = constrain(vc, (None, "batch", None, None, None))
    qpos = q_offset + jnp.arange(Sq)

    def body(state: SoftmaxState, xs):
        # state.m/l: (B, Sq, KH, G); state.acc: (B, Sq, KH, G, Dh)
        ci, kk, vv = xs
        s = jnp.einsum("bqkgd,bckd->bqkgc", qr, kk.astype(jnp.float32))
        kpos = ci * chunk + jnp.arange(chunk)
        live = kpos < Skv
        if causal:
            live = live[None, :] & (qpos[:, None] >= kpos[None, :])
            s = jnp.where(live[None, :, None, None, :], s, MASK_VALUE)
        else:
            s = jnp.where(live[None, None, None, None, :], s, MASK_VALUE)
        m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(state.m - m_new)
        l = state.l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgc,bckd->bqkgd", p, vv.astype(jnp.float32))
        acc = state.acc * alpha[..., None] + pv
        return SoftmaxState(m_new, l, acc), None

    state0 = SoftmaxState(
        m=jnp.full((B, Sq, KH, group), MASK_VALUE, jnp.float32),
        l=jnp.zeros((B, Sq, KH, group), jnp.float32),
        acc=jnp.zeros((B, Sq, KH, group, Dh), jnp.float32),
    )
    # Nested remat: without it the backward pass saves the (B,Sq,KH,G,chunk)
    # score/probability tensors of EVERY chunk — the flash-backward property
    # (recompute s/p per chunk) comes from checkpointing the chunk body.
    state, _ = jax.lax.scan(jax.checkpoint(body), state0, (jnp.arange(n_chunks), kc, vc))
    out = state.acc / jnp.maximum(state.l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def _merge_states(a: SoftmaxState, b: SoftmaxState) -> SoftmaxState:
    """Combine two online-softmax partials over disjoint KV sets."""
    m = jnp.maximum(a.m, b.m)
    ea, eb = jnp.exp(a.m - m), jnp.exp(b.m - m)
    return SoftmaxState(
        m=m,
        l=a.l * ea + b.l * eb,
        acc=a.acc * ea[..., None] + b.acc * eb[..., None],
    )


def _rect_state(qr, k, v, chunk, kv_offset=0):
    """Unmasked blockwise attention returning the softmax state.

    qr: (B, Sq, KH, G, Dh) pre-scaled f32; k/v: (B, Skv, KH, Dh).
    """
    B, Sq, KH, G, Dh = qr.shape
    Skv = k.shape[1]
    chunk = min(chunk, Skv)
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, KH, Dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, KH, Dh), 1, 0)

    def body(state, xs):
        ci, kk, vv = xs
        s = jnp.einsum("bqkgd,bckd->bqkgc", qr, kk.astype(jnp.float32))
        live = ci * chunk + jnp.arange(chunk) < Skv
        s = jnp.where(live[None, None, None, None, :], s, MASK_VALUE)
        m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(state.m - m_new)
        l = state.l * alpha + jnp.sum(p, axis=-1)
        acc = state.acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vv.astype(jnp.float32)
        )
        return SoftmaxState(m_new, l, acc), None

    state0 = SoftmaxState(
        m=jnp.full((B, Sq, KH, G), MASK_VALUE, jnp.float32),
        l=jnp.zeros((B, Sq, KH, G), jnp.float32),
        acc=jnp.zeros((B, Sq, KH, G, Dh), jnp.float32),
    )
    if n_chunks == 1:
        state, _ = body(state0, (jnp.int32(0), kc[0], vc[0]))
        return state
    state, _ = jax.lax.scan(jax.checkpoint(body), state0, (jnp.arange(n_chunks), kc, vc))
    return state


def _diag_state(qr, k, v, q_offset, kv_offset):
    """One causal leaf block: masked single-chunk attention state."""
    B, Sq, KH, G, Dh = qr.shape
    s = jnp.einsum("bqkgd,bckd->bqkgc", qr, k.astype(jnp.float32))
    qpos = q_offset + jnp.arange(Sq)
    kpos = kv_offset + jnp.arange(k.shape[1])
    mask = qpos[:, None] >= kpos[None, :]
    s = jnp.where(mask[None, :, None, None, :], s, MASK_VALUE)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    return SoftmaxState(m, l, acc)


def _causal_tri_state(qr, k, v, chunk, q_offset=0):
    """Recursive triangular causal attention (flop-exact ~n(n+1)/2 chunks).

    causal([A;B]) = [causal(A); merge(causal(B), rect(B→A))] — the strictly-
    lower rectangle is *unmasked*, so no masked-out chunk work is computed.
    Halves 32k-prefill attention FLOPs vs the rectangular+mask formulation
    (§Perf cell C); recursion depth is log2(S/chunk), unrolled statically.
    """
    S = qr.shape[1]
    if S <= chunk:
        return _diag_state(qr, k, v, q_offset, q_offset)
    half = S // 2
    qa, qb = qr[:, :half], qr[:, half:]
    ka, kb = k[:, :half], k[:, half:]
    va, vb = v[:, :half], v[:, half:]
    state_a = _causal_tri_state(qa, ka, va, chunk, q_offset)
    state_b = _causal_tri_state(qb, kb, vb, chunk, q_offset + half)
    state_b = _merge_states(state_b, _rect_state(qb, ka, va, chunk))
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1), state_a, state_b)


def _blockwise_tri_attention(q, k, v, *, group, causal, chunk, q_offset=0):
    B, Sq, H, Dh = q.shape
    KH = k.shape[2]
    qr = q.reshape(B, Sq, KH, group, Dh).astype(jnp.float32) * (Dh ** -0.5)
    # no seq-gather here: with chunk == seq/shards the recursion's halving
    # splits are all shard-aligned, so diagonal leaves stay shard-local
    if not causal or Sq != k.shape[1]:
        state = _rect_state(qr, k, v, chunk)
    else:
        state = _causal_tri_state(qr, k, v, chunk, q_offset)
    out = state.acc / jnp.maximum(state.l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def _pallas_attention(q, k, v, *, group, causal):
    from repro.kernels.flash_attention import ops as fa_ops

    B, Sq, H, Dh = q.shape
    KH = k.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KH, k.shape[1], Dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KH, v.shape[1], Dh)
    out = fa_ops.flash_attention(qh, kh, vh, group=group, causal=causal)
    return out.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)


def inner_attention(q, k, v, cfg: ModelConfig, *, causal=None, q_offset=0):
    causal = cfg.causal if causal is None else causal
    group = q.shape[2] // k.shape[2]
    if cfg.attention_impl == "xla":
        return _xla_attention(q, k, v, group=group, causal=causal, q_offset=q_offset)
    if cfg.attention_impl == "pallas":
        return _pallas_attention(q, k, v, group=group, causal=causal)
    if cfg.attention_impl == "blockwise_tri":
        return _blockwise_tri_attention(
            q, k, v, group=group, causal=causal, chunk=cfg.attention_chunk, q_offset=q_offset
        )
    return _blockwise_attention(
        q, k, v, group=group, causal=causal, chunk=cfg.attention_chunk, q_offset=q_offset
    )


# --------------------------------------------------------------------------
# Full attention block: projections (+bias), qk-norm, rope.
# --------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig, dtype) -> Param:
    d, dh = cfg.d_model, cfg.head_dim
    keys = jax.random.split(key, 4)
    p: Param = {
        "wq": dense_init(keys[0], (d, cfg.n_heads, dh), dtype),
        "wk": dense_init(keys[1], (d, cfg.n_kv_heads, dh), dtype),
        "wv": dense_init(keys[2], (d, cfg.n_kv_heads, dh), dtype),
        "wo": dense_init(keys[3], (cfg.n_heads, dh, d), dtype, fan_in=cfg.n_heads * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, dh), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, dh), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def project_qkv(p: Param, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """x: (B, S, D) → q (B,S,H,Dh), k,v (B,S,KH,Dh) with bias/qk-norm/rope.

    Activations are head-sharded (Megatron TP): dWq/dWk/dWv then come out
    head-sharded with no model-axis gradient reduction (§Perf).
    """
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), ("batch", None, "heads", None))
    k = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), ("batch", None, "kv_heads", None))
    v = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), ("batch", None, "kv_heads", None))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.attention_multiplier is not None:
        # every attend scales scores by 1/sqrt(head_dim); folding the rest
        # of the multiplier into q gives scores × attention_multiplier
        mult = cfg.attention_multiplier * cfg.head_dim ** 0.5
        q = (q.astype(jnp.float32) * mult).astype(q.dtype)
    if cfg.position_embedding == "nope":
        return q, k, v
    cos, sin = rope(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def project_out(p: Param, attn_out: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"])


def attention_block(
    p: Param,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    causal: bool | None = None,
    kv: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Self-attention (or cross-attention when ``kv`` is provided)."""
    if kv is None:
        q, k, v = project_qkv(p, x, cfg, positions)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        k, v = kv
        causal = False
    out = inner_attention(q, k, v, cfg, causal=causal)
    return project_out(p, out)
