"""Decoder stack: period-scanned heterogeneous layers (DESIGN.md §5).

``cfg.layout`` lists the layer kinds of one period (dense: ``("attn",)``;
Jamba: 7×mamba + 1×attn); parameters are stacked over ``n_periods`` and the
stack runs as one ``lax.scan`` — HLO stays O(one period) deep for a 64-layer
model, which keeps 80 dry-run compiles tractable and gives a uniform remat
boundary (one checkpoint per period when ``cfg.remat``).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.context import constrain
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.modules import Param, dense_init, embed, rms_norm, unembed

__all__ = [
    "init_params", "forward", "init_period_layers", "embed_tokens", "add_branch",
    "ffn", "head_logits",
]


# --------------------------------------------------------------------------
# The layer's pieces, shared by ``forward`` and the serving steps
# (serving/steps.py): one definition of the embedding, the residual branch,
# the FFN sub-block after a mixer, and the output head.
# --------------------------------------------------------------------------

def embed_tokens(params: Param, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Token embeddings in the compute dtype, × ``embedding_multiplier``."""
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def add_branch(x: jax.Array, y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The residual stream plus one sub-block's output (× the multiplier)."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def ffn(
    sp: Param, x: jax.Array, slot: int, cfg: ModelConfig, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """The norm → FFN sub-block that follows a mixer → (x, aux loss).

    MoE slots run ``moe_block`` (router and routed experts) under the named
    scope ``moe`` and a shared expert, where the layer has one, under
    ``mlp``; other slots a dense MLP under ``mlp``.  The residual add joins
    the last of them.  ``valid`` marks real tokens for the MoE's device
    counters.
    """
    with jax.named_scope("norm"):
        h = constrain(rms_norm(x, sp["norm2"], cfg.norm_eps), ("batch", "seq", None))
    aux = jnp.zeros((), jnp.float32)
    if not cfg.is_moe_layer(slot):
        with jax.named_scope("mlp"):
            return add_branch(x, mlp_mod.mlp_block(sp["mlp"], h, cfg.activation), cfg), aux
    with jax.named_scope("moe"):
        out, aux = moe_mod.moe_block(sp["moe"], h, cfg, valid)
        if not cfg.moe.d_ff_shared:
            return add_branch(x, out, cfg), aux
    with jax.named_scope("mlp"):  # the shared expert, once for every token
        out = out + mlp_mod.mlp_block(sp["moe"]["shared"], h, "swiglu")
        return add_branch(x, out, cfg), aux


def head_logits(params: Param, x: jax.Array, cfg: ModelConfig, axes: tuple) -> jax.Array:
    """Final norm → output head → logits (÷ ``logits_scaling``), with the
    vocabulary's padding columns masked.  ``axes`` names x's leading dims
    and the vocabulary's for the sharding constraint."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = constrain(unembed(x, table), axes)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.padded_vocab != cfg.vocab_size:  # mask vocab-padding columns
        live = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(live, logits, -1e30)
    return logits


def _init_slot(key: jax.Array, slot: int, kind: str, cfg: ModelConfig, dtype) -> Param:
    d = cfg.d_model
    p: Param = {"norm1": jnp.ones((d,), dtype)}
    k1, k2, k3 = jax.random.split(key, 3)
    if kind == "mamba":
        # the slot key itself: mixer-only Mamba stacks draw the same weights
        p["mamba"] = ssm_mod.init_mamba(key, cfg, dtype)
    else:
        p["attn"] = attn_mod.init_attention(k1, cfg, dtype)
    if not cfg.has_ffn(kind):
        return p
    p["norm2"] = jnp.ones((d,), dtype)
    if cfg.is_moe_layer(slot):
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_mod.init_mlp(k2, d, cfg.d_ff, cfg.activation, dtype)
    if cfg.n_enc_layers and kind == "attn":  # enc-dec decoder: cross-attention
        p["cross_norm"] = jnp.ones((d,), dtype)
        p["cross"] = attn_mod.init_attention(k3, cfg, dtype)
    return p


def init_period_layers(key: jax.Array, cfg: ModelConfig, dtype) -> list[Param]:
    """One param pytree per layout slot, leaves stacked over periods."""
    slots = []
    for slot, kind in enumerate(cfg.layout):
        kslot = jax.random.fold_in(key, slot)
        keys = jax.random.split(kslot, cfg.n_periods)
        slots.append(
            jax.vmap(lambda k, s=slot, kd=kind: _init_slot(k, s, kd, cfg, dtype))(keys)
        )
    return slots


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_params(key: jax.Array, cfg: ModelConfig) -> Param:
    """Random parameters from ``key``, built in one compiled program.

    Under ``jit`` each draw fuses with its scale and dtype cast, so a
    full-width model never holds a stacked f32 weight: peak memory is the
    ``param_dtype`` parameters themselves.
    """
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 4)
    params: Param = {
        "embed": (jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model)) * 0.02).astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "layers": init_period_layers(keys[1], cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (
            jax.random.normal(keys[2], (cfg.padded_vocab, cfg.d_model)) * 0.02
        ).astype(dtype)
    if cfg.n_enc_layers:
        from repro.models import encdec

        params["encoder"] = encdec.init_encoder(keys[3], cfg, dtype)
    return params


def _apply_slot(
    sp: Param,
    x: jax.Array,
    kind: str,
    slot: int,
    cfg: ModelConfig,
    positions: jax.Array,
    memory_kv: tuple[jax.Array, jax.Array] | None,
) -> tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    # constrain each norm output: forces the SP seq-gather (and its transpose
    # reduce-scatter) to move the bf16 tensor, not the norm's f32 internal
    # upcast — halves every activation collective's payload (§Perf).
    h = constrain(rms_norm(x, sp["norm1"], cfg.norm_eps), ("batch", "seq", None))
    if kind == "mamba":
        x = add_branch(x, ssm_mod.mamba_block(sp["mamba"], h, cfg), cfg)
    else:
        x = add_branch(x, attn_mod.attention_block(sp["attn"], h, cfg, positions), cfg)
    if memory_kv is not None:
        h = constrain(rms_norm(x, sp["cross_norm"], cfg.norm_eps), ("batch", "seq", None))
        x = x + attn_mod.attention_block(sp["cross"], h, cfg, positions, kv=memory_kv)
    if cfg.has_ffn(kind):
        x, aux = ffn(sp, x, slot, cfg)
    return x, aux


def forward(
    params: Param,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    prefix_embeds: jax.Array | None = None,
    memory: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward → (logits, aux_loss).

    ``prefix_embeds``: (B, P, D) multimodal stub embeddings prepended to the
    token embeddings (VLM patches / audio frames).  ``memory``: (B, Senc, D)
    encoder output for enc-dec cross-attention.
    """
    x = embed_tokens(params, tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x, ("batch", "seq", None))
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    memory_kv = None
    if memory is not None:
        # cross-attention K/V are shared by all decoder layers per-slot; they
        # are computed inside each slot from its own projections, so pass the
        # raw memory and let the slot project (stacked weights under scan).
        memory_kv = memory

    def period_body(carry, period_params):
        from repro.distributed.sharding import constrain_param_tree

        x, aux = carry
        # DP batch + sequence-parallel residual stream at every period
        # boundary — this is what the scan carry (and remat save) inherits.
        x = constrain(x, ("batch", "seq", None))
        # pin sliced layer params (and, via transpose, their cotangents)
        period_params = constrain_param_tree(period_params, cfg)
        for slot, kind in enumerate(cfg.layout):
            sp = period_params[slot]
            mkv = None
            if memory_kv is not None and kind == "attn":
                k = jnp.einsum("bsd,dhk->bshk", memory_kv, sp["cross"]["wk"])
                v = jnp.einsum("bsd,dhk->bshk", memory_kv, sp["cross"]["wv"])
                mkv = (k, v)
            x, a = _apply_slot(sp, x, kind, slot, cfg, positions, mkv)
            aux = aux + a
        return (x, aux), None

    body = jax.checkpoint(period_body) if cfg.remat else period_body
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    return head_logits(params, x, cfg, ("batch", None, "vocab")), aux
