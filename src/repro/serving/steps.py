"""Serving steps: prefill (context encode → cache) and decode (one token).

Both walk the same period-scanned layer stack as training; the cache pytree
rides the scan as xs/ys so its leaves carry the (n_periods, ...) stacking.
``decode_step`` is the ``serve_step`` the decode_32k / long_500k dry-run
cells lower: one new token against a cache filled to seq_len.

Each stage of the ``prefill_chunk`` and ``decode_step`` bodies runs under a
``jax.named_scope`` (``SCOPES``), which only adds metadata: the scope is an
element of every operation's ``op_name``, so a profiler trace can sum the
device time of each stage.  A trace reduction groups by these names, so a
rename is a change of yardstick.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.context import constrain
from repro.models import ssm as ssm_mod
from repro.models.attention import inner_attention, project_out, project_qkv
from repro.models.modules import rms_norm
from repro.models.transformer import add_branch, embed_tokens, ffn, head_logits

from repro.obs import device
from repro.serving import kvcache

__all__ = [
    "SCOPES",
    "prefill",
    "prefill_chunk",
    "decode_step",
    "init_decode_caches",
    "logits_from_hidden",
]


# ``ssm``: a Mamba layer's mixer (projections, conv, SSD scan or recurrence,
# gated norm) with its conv/SSD state rows read and written; ``moe``: the
# router, the held experts and their combine (a shared expert is ``mlp``).
SCOPES = (
    "embed", "norm", "qkv", "pool_read", "kv_append", "attend", "out_proj",
    "mlp", "pool_write", "lm_head", "ssm", "moe",
)


def logits_from_hidden(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return head_logits(params, x, cfg, ("batch", "vocab"))


# Cache slots ride the period scan with a leading (n_periods, …) stacking;
# _cache_get/_cache_put index one period in/out.  Values are tree-mapped, not
# indexed directly: a paged pool entry may be a *tuple of extents*
# (pool/extents segmented layout) rather than one array.

def _cache_get(full: dict, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), full
    )


def _cache_put(full: dict, part: dict, i):
    out = dict(full)
    for k, p in part.items():  # only updated keys (cross K/V stay as-is)
        out[k] = jax.tree.map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(
                a, b.astype(a.dtype), i, 0
            ),
            full[k],
            p,
        )
    return out


# --------------------------------------------------------------------------
# Prefill: full context forward, emitting filled caches per layer.
# --------------------------------------------------------------------------

def prefill(
    params: dict,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    capacity_hint: int | None = None,
    policy: str | None = None,
    prefix_embeds: jax.Array | None = None,
    memory: jax.Array | None = None,
    lengths: jax.Array | None = None,  # (B,) per-seq prompt lengths (right-pad)
) -> tuple[jax.Array, list]:
    """→ (last-position logits (B, V), caches list[slot])."""
    policy = cfg.cache_policy if policy is None else policy
    x = embed_tokens(params, tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    cap = capacity_hint if capacity_hint is not None else S
    positions = jnp.arange(S)[None, :]

    def period_body(carry, period_params):
        (x,) = carry
        x = constrain(x, ("batch", "seq", None))
        caches_out = []
        for slot, kind in enumerate(cfg.layout):
            sp = period_params[slot]
            h = rms_norm(x, sp["norm1"], cfg.norm_eps)
            if kind == "mamba":
                # right-padded rows of shorter prompts stay out of the state
                y, state = ssm_mod.mamba_block(
                    sp["mamba"], h, cfg, return_state=True, lengths=lengths
                )
                x = add_branch(x, y, cfg)
                cache = {"conv": state.conv, "ssd": state.ssd}
            else:
                q, k, v = project_qkv(sp["attn"], h, cfg, positions)
                att = inner_attention(q, k, v, cfg, causal=True)
                x = add_branch(x, project_out(sp["attn"], att), cfg)
                cache = kvcache.init_cache(cfg, B, cap, policy)
                cache = kvcache.fill_from_prefill(cache, k, v)
                if memory is not None:
                    ck = jnp.einsum("bsd,dhk->bshk", memory, sp["cross"]["wk"])
                    cv = jnp.einsum("bsd,dhk->bshk", memory, sp["cross"]["wv"])
                    hc = rms_norm(x, sp["cross_norm"], cfg.norm_eps)
                    qc = jnp.einsum("bsd,dhk->bshk", hc, sp["cross"]["wq"])
                    attc = inner_attention(qc, ck, cv, cfg, causal=False)
                    x = x + project_out(sp["cross"], attc)
                    cache = dict(cache, cross_k=ck, cross_v=cv)
            if cfg.has_ffn(kind):
                x, _ = ffn(sp, x, slot, cfg)
            caches_out.append(cache)
        return (x,), caches_out

    body = jax.checkpoint(period_body) if cfg.remat else period_body
    (x,), caches = jax.lax.scan(body, (x,), params["layers"])
    if lengths is None:
        last = x[:, -1]
    else:
        last = x[jnp.arange(B), jnp.asarray(lengths, jnp.int32) - 1]
    logits = logits_from_hidden(params, last, cfg)
    return logits, caches


# --------------------------------------------------------------------------
# Chunked prefill: one chunk of one slot's prompt into the shared paged
# caches (BatchEngine admission, DESIGN.md §7).  Compiles per chunk *bucket*
# width, never per prompt length — the O(log C) trace bound.
# --------------------------------------------------------------------------

def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # (1, Cb) bucket-padded chunk of one prompt
    caches: list,  # the BatchEngine's shared (donated) caches
    slot: jax.Array,  # () decode-slot index owning this prompt
    t0: jax.Array,  # () tokens of this prompt already prefilled
    live: jax.Array,  # () live tokens in this chunk (Cb − live are padding)
    pages_row: jax.Array,  # (maxp,) the slot's claimed slab ids, −1-padded
    cfg: ModelConfig,
    first: bool = True,  # STATIC: t0 == 0 (fresh state, no prefix to attend)
) -> tuple[jax.Array, list]:
    """→ (last-live-position logits (1, V), updated caches) — plus the
    summed device counter vector when ``cfg.instrument`` is set.

    The engine's device page table stays −1 for the slot until the final
    chunk (prefilling slots are inert under concurrent decode steps), so the
    claimed pages arrive as the separate ``pages_row`` operand.  K/V scatter
    targets the claimed slabs; Mamba layers run the resumable SSD block
    against the slot's state row, masked by ``live`` so the padding of a
    bucket-padded tail never enters the recurrence.  Logits only matter on
    the final chunk.

    ``first`` must be static (it is known at chunk-planning time): the first
    chunk runs from a ZERO recurrence — a reused slot's state rows still
    hold the previous occupant's final state — on the monolithic SSD chunk
    grid (``state=None`` → ``Q = min(chunk_size, L)``), and skips the prefix
    walk outright (every prefix lane is dead at t0 = 0).
    """
    with jax.named_scope("embed"):
        x = embed_tokens(params, tokens, cfg)
    Cb = tokens.shape[1]
    positions = (t0 + jnp.arange(Cb))[None, :]  # (1, Cb) global positions
    valid = (jnp.arange(Cb) < live)[None, :]  # (1, Cb) live lanes of the chunk

    def period_body(carry, xs):
        if cfg.instrument:
            x, caches, ctr = carry
        else:
            x, caches = carry
        x = constrain(x, ("batch", None, None))
        period_params, idx = xs
        # the tape is opened per scan-body iteration so recorded vectors
        # never escape their trace level (device.tape docstring)
        scope = device.tape() if cfg.instrument else contextlib.nullcontext()
        with scope as t:
            for lslot, kind in enumerate(cfg.layout):
                sp = period_params[lslot]
                if kind == "mamba":
                    with jax.named_scope("norm"):
                        h = rms_norm(x, sp["norm1"], cfg.norm_eps)
                    with jax.named_scope("ssm"):
                        c = _cache_get(caches[lslot], idx)
                        st = (
                            None
                            if first
                            else ssm_mod.MambaState(
                                conv=c["conv"][slot][None], ssd=c["ssd"][slot][None]
                            )
                        )
                        # the padded tail is masked out of the recurrence
                        y, st = ssm_mod.mamba_block(
                            sp["mamba"], h, cfg, state=st, return_state=True,
                            lengths=live[None],
                        )
                        caches[lslot] = _cache_put(
                            caches[lslot],
                            {
                                "conv": c["conv"].at[slot].set(
                                    st.conv[0].astype(c["conv"].dtype)
                                ),
                                "ssd": c["ssd"].at[slot].set(st.ssd[0]),
                            },
                            idx,
                        )
                        x = add_branch(x, y, cfg)
                    if cfg.has_ffn(kind):
                        x, _ = ffn(sp, x, lslot, cfg, valid)
                    continue
                with jax.named_scope("pool_read"):
                    c = _cache_get(caches[lslot], idx)
                with jax.named_scope("norm"):
                    h = rms_norm(x, sp["norm1"], cfg.norm_eps)
                with jax.named_scope("qkv"):
                    q, k, v = project_qkv(sp["attn"], h, cfg, positions)
                with jax.named_scope("attend"):
                    att = kvcache.chunk_attend(
                        c, pages_row, q, k, v, t0, live, cfg, first=first
                    )
                with jax.named_scope("out_proj"):
                    x = add_branch(x, project_out(sp["attn"], att), cfg)
                with jax.named_scope("kv_append"):
                    c2 = kvcache.scatter_chunk(c, pages_row, k, v, t0, live, cfg)
                x, _ = ffn(sp, x, lslot, cfg, valid)
                with jax.named_scope("pool_write"):
                    caches[lslot] = _cache_put(caches[lslot], c2, idx)
        if cfg.instrument:
            return (x, caches, ctr + t.total()), None
        return (x, caches), None

    if cfg.instrument:
        (x, new_caches, ctr), _ = jax.lax.scan(
            period_body,
            (x, list(caches), device.zeros()),
            (params["layers"], jnp.arange(cfg.n_periods)),
        )
    else:
        (x, new_caches), _ = jax.lax.scan(
            period_body,
            (x, list(caches)),
            (params["layers"], jnp.arange(cfg.n_periods)),
        )
    with jax.named_scope("lm_head"):
        last = jax.lax.dynamic_index_in_dim(x[0], live - 1, 0, keepdims=False)
        logits = logits_from_hidden(params, last[None], cfg)
    if cfg.instrument:
        return logits, new_caches, ctr
    return logits, new_caches


# --------------------------------------------------------------------------
# Decode: one token, cache push_back + bucket-walk attention.
# --------------------------------------------------------------------------

def init_decode_caches(
    cfg: ModelConfig,
    batch: int,
    length_hint: int,
    *,
    policy: str | None = None,
    enc_len: int | None = None,
) -> list:
    """Empty caches sized for a context of ``length_hint`` (dry-run entry)."""
    policy = cfg.cache_policy if policy is None else policy
    caches = []
    P = cfg.n_periods
    dt = jnp.dtype(cfg.dtype)
    for slot, kind in enumerate(cfg.layout):
        if kind == "mamba":
            st = ssm_mod.init_mamba_state(cfg, batch, dt)
            caches.append(
                {
                    "conv": jnp.zeros((P, *st.conv.shape), dt),
                    "ssd": jnp.zeros((P, *st.ssd.shape), jnp.float32),
                }
            )
            continue
        c = kvcache.init_cache(cfg, batch, length_hint, policy, stack=P)
        if cfg.n_enc_layers and enc_len:
            c = dict(
                c,
                cross_k=jnp.zeros((P, batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dt),
                cross_v=jnp.zeros((P, batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dt),
            )
        caches.append(c)
    return caches


def decode_step(
    params: dict,
    token: jax.Array,  # (B,) or (B, 1)
    caches: list,
    length: jax.Array,  # () or (B,) live context length
    cfg: ModelConfig,
    active: jax.Array | None = None,  # (B,) bool — rows whose state may move
) -> tuple[jax.Array, list]:
    """One serve step → (logits (B, V), updated caches).

    ``active`` masks *state writes* for rows mid-chunked-prefill: their KV
    appends already drop (page table −1) but Mamba conv/SSD rows would be
    clobbered by the batch-wide recurrence without the gate.

    With ``cfg.instrument`` the return gains a third element: the summed
    device counter vector (obs/device) recorded by the cache ops across all
    periods — device data, no transfer.
    """
    token = token.reshape(token.shape[0], 1)
    with jax.named_scope("embed"):
        x = embed_tokens(params, token, cfg)
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    positions = pos[:, None]  # (B, 1)
    valid = (pos > 0)[:, None]  # rows with a sequence (idle/prefilling: 0)

    def period_body(carry, xs):
        # caches ride the CARRY and are updated in place (dynamic-update-
        # slice) — the xs→ys formulation double-buffers the whole KV cache
        # (2× HBM on a 32k×128 cache; caught by the dry-run memory analysis).
        if cfg.instrument:
            x, caches, ctr = carry
        else:
            x, caches = carry
        x = constrain(x, ("batch", None, None))
        period_params, idx = xs
        # per-iteration tape: kvcache records land here and fold into the
        # scan carry, so the counter vector rides the step as device data
        scope = device.tape() if cfg.instrument else contextlib.nullcontext()
        with scope as t:
            for slot, kind in enumerate(cfg.layout):
                sp = period_params[slot]
                if kind == "mamba":
                    with jax.named_scope("norm"):
                        h = rms_norm(x, sp["norm1"], cfg.norm_eps)
                    with jax.named_scope("ssm"):
                        c = _cache_get(caches[slot], idx)
                        y, st = ssm_mod.mamba_decode_step(
                            sp["mamba"], h, ssm_mod.MambaState(c["conv"], c["ssd"]), cfg
                        )
                        new_conv, new_ssd = st.conv, st.ssd
                        if active is not None:
                            keep = active[:, None, None]
                            new_conv = jnp.where(keep, new_conv, c["conv"])
                            new_ssd = jnp.where(keep[..., None], new_ssd, c["ssd"])
                        caches[slot] = _cache_put(
                            caches[slot], {"conv": new_conv, "ssd": new_ssd}, idx
                        )
                        x = add_branch(x, y, cfg)
                    if cfg.has_ffn(kind):
                        x, _ = ffn(sp, x, slot, cfg, valid)
                    continue
                with jax.named_scope("pool_read"):
                    c = _cache_get(caches[slot], idx)
                with jax.named_scope("norm"):
                    h = rms_norm(x, sp["norm1"], cfg.norm_eps)
                with jax.named_scope("qkv"):
                    q, k, v = project_qkv(sp["attn"], h, cfg, positions)
                kv_only = {key: val for key, val in c.items() if not key.startswith("cross")}
                with jax.named_scope("kv_append"):
                    c2 = kvcache.append(kv_only, k, v, pos, cfg)
                with jax.named_scope("attend"):
                    att = kvcache.attend(c2, q, pos + 1, cfg)
                with jax.named_scope("out_proj"):
                    x = add_branch(x, project_out(sp["attn"], att), cfg)
                if "cross_k" in c:
                    hc = rms_norm(x, sp["cross_norm"], cfg.norm_eps)
                    qc = jnp.einsum("bsd,dhk->bshk", hc, sp["cross"]["wq"])
                    enc_len = c["cross_k"].shape[-3]
                    attc = kvcache.attend(
                        {"k": c["cross_k"], "v": c["cross_v"]}, qc,
                        jnp.full((B,), enc_len, jnp.int32), cfg,
                    )
                    x = x + project_out(sp["cross"], attc)
                x, _ = ffn(sp, x, slot, cfg, valid)
                with jax.named_scope("pool_write"):
                    caches[slot] = _cache_put(caches[slot], c2, idx)
        if cfg.instrument:
            return (x, caches, ctr + t.total()), None
        return (x, caches), None

    if cfg.instrument:
        (x, new_caches, ctr), _ = jax.lax.scan(
            period_body,
            (x, list(caches), device.zeros()),
            (params["layers"], jnp.arange(cfg.n_periods)),
        )
    else:
        (x, new_caches), _ = jax.lax.scan(
            period_body,
            (x, list(caches)),
            (params["layers"], jnp.arange(cfg.n_periods)),
        )
    with jax.named_scope("lm_head"):
        logits = logits_from_hidden(params, x[:, 0], cfg)
    if cfg.instrument:
        return logits, new_caches, ctr
    return logits, new_caches
