"""Mixture-of-Experts with parallel-insertion dispatch (DESIGN.md §3).

Assigning each routed token a unique slot in its expert's buffer **is** the
paper's insertion problem: experts ↔ LFVector blocks, token assignments ↔ the
insertion mask, and the per-expert rank is an exclusive prefix sum over the
assignment matrix — computed here by the same ``insertion_offsets`` machinery
(``cfg.insertion_method`` selects atomic/scan/mxu, the paper's three
algorithms; the MXU scan is the Pallas kernel).

Two execution paths:

``_moe_local``   — single-device / small-token path: one global buffer.
``_moe_sharded`` — the production path under a mesh (shard_map): each shard
    routes its own tokens and runs the insertion scan **shard-locally** (the
    paper's block-local independence, one LFVector set per shard), builds a
    local (E, C_local, D) buffer, and exchanges expert rows with one
    ``all_to_all`` over the EP ('model') axis — the Megatron/Tutel pattern.
    A global scatter-dispatch under auto-SPMD forces GSPMD to materialize
    replicated (E·C, D) intermediates (dbrx: >600 GB/device, caught by the
    dry-run); the shard-local formulation keeps every buffer
    O(local_tokens).

``_moe_held``     — the dropless serving layer (``MoEConfig.dropless``): the
    router scores every expert in float32, this chip holds the share
    ``cfg.held_experts`` of them and computes, for every token, the part of
    the result its own experts give — each held expert runs on every token
    of the batch, weighted by the token's gate (0 where not routed), so no
    assignment is ever dropped and a token's output does not depend on the
    rest of the batch.

Expert capacity follows the GGArray geometry when ``ggarray_capacity`` is on:
instead of a fixed capacity factor (drop on overflow — the static-array
failure mode of §V), the buffer capacity snaps to the next geometric bucket
level, trading ≤2× memory for no drops; growth across steps is a copy-free
program-boundary event exactly like GGArray growth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.core import indexing
from repro.core.insertion import insertion_offsets
from repro.distributed.context import active_mesh, constrain
from repro.models.mlp import init_mlp
from repro.models.modules import Param, dense_init
from repro.obs import device

__all__ = ["init_moe", "moe_block", "expert_capacity"]


def expert_capacity(moe: MoEConfig, n_tokens: int) -> int:
    """Per-expert buffer slots for a batch of ``n_tokens`` routed tokens."""
    mean = n_tokens * moe.top_k / moe.n_experts
    if moe.ggarray_capacity:
        # GGArray geometry: capacity = next bucket-chain level ≥ the mean load
        # (≤2× the needed memory, no token drops at ≤2× skew).
        need = int(mean) + 1
        nb = indexing.min_buckets_for(moe.capacity_b0, need)
        return indexing.capacity(moe.capacity_b0, max(nb, 1))
    return max(int(mean * moe.capacity_factor), 1)


def init_moe(key: jax.Array, cfg: ModelConfig, dtype) -> Param:
    """Router over all ``n_experts``; expert weights for the experts held
    here (all of them, unless the layer is dropless and sharded)."""
    moe = cfg.moe
    d, dff = cfg.d_model, moe.d_ff_expert
    n = cfg.held_experts[1] if moe.dropless else moe.n_experts
    keys = jax.random.split(key, 4)
    p = {
        "router": dense_init(keys[0], (d, moe.n_experts), jnp.float32),
        "w_gate": dense_init(keys[1], (n, d, dff), dtype, fan_in=d),
        "w_up": dense_init(keys[2], (n, d, dff), dtype, fan_in=d),
        "w_down": dense_init(keys[3], (n, dff, d), dtype, fan_in=dff),
    }
    if moe.d_ff_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d, moe.d_ff_shared, "swiglu", dtype)
    return p


def _route_and_pack(p, xt, cfg, C):
    """Route tokens, run the parallel-insertion scan, pack expert buffers.

    xt: (T, D) → (buf (E, C, D), slot (Tk,), gate (T, k), stats).  Pure local
    jnp — usable standalone or inside shard_map (where T is per-shard and the
    insertion scan is the paper's block-local LFVector push_back).
    """
    moe = cfg.moe
    T, D = xt.shape
    logits = xt.astype(jnp.float32) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, moe.top_k)  # (T, k)
    gate = gate / jnp.clip(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    # parallel insertion: experts are the LFVector blocks (paper §III.B)
    flat_expert = expert.reshape(-1)  # (Tk,)
    assign = jax.nn.one_hot(flat_expert, moe.n_experts, dtype=jnp.int32).T  # (E, Tk)
    offsets, _ = insertion_offsets(assign.astype(bool), method=cfg.insertion_method)
    rank = jnp.take_along_axis(offsets.T, flat_expert[:, None], axis=1)[:, 0]

    keep = rank < C
    slot = jnp.where(keep, flat_expert * C + rank, -1)
    xrep = jnp.repeat(xt, moe.top_k, axis=0)  # (Tk, D)
    tgt = jnp.where(slot >= 0, slot, moe.n_experts * C)
    buf = jnp.zeros((moe.n_experts * C, D), xt.dtype).at[tgt].set(xrep, mode="drop")
    density = jnp.mean(assign.astype(jnp.float32), axis=1)
    router_prob = jnp.mean(probs, axis=0)
    return buf.reshape(moe.n_experts, C, D), slot, gate, (density, router_prob)


def _expert_ffn(p, buf):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _combine(out_buf, slot, gate, T, D, dtype):
    flat = out_buf.reshape(-1, D)
    gathered = flat[jnp.where(slot >= 0, slot, 0)]
    gathered = jnp.where((slot >= 0)[:, None], gathered, 0.0)
    k = slot.shape[0] // T
    return jnp.sum(gathered.reshape(T, k, D) * gate[..., None].astype(dtype), axis=1)


def _moe_local(p: Param, x: jax.Array, cfg: ModelConfig):
    """One global buffer — single-device tests and tiny decode batches."""
    moe = cfg.moe
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    C = expert_capacity(moe, T)
    buf, slot, gate, (density, router_prob) = _route_and_pack(p, xt, cfg, C)
    out_buf = _expert_ffn(p, buf)
    out = _combine(out_buf, slot, gate, T, D, x.dtype)
    aux = moe.n_experts * jnp.sum(density * router_prob) * moe.top_k
    return out.reshape(B, S, D), aux


def _moe_sharded(p: Param, x: jax.Array, cfg: ModelConfig, mesh):
    """shard_map path: local routing + insertion, all_to_all over EP axis."""
    moe = cfg.moe
    B, S, D = x.shape
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tp = mesh.shape["model"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    T_local = (B // dp_size) * (S // tp)
    C_local = expert_capacity(moe, T_local)

    def local_block(xl, router, w_gate, w_up, w_down):
        # xl: (B/dp, S/tp, D) — this shard's tokens (one LFVector set/shard)
        b, s, _ = xl.shape
        pl = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        xt = xl.reshape(b * s, D)
        buf, slot, gate, (density, router_prob) = _route_and_pack(pl, xt, cfg, C_local)
        # EP exchange: scatter expert rows to their owners, gather this
        # shard's experts from every peer → (E/tp, tp·C_local, D)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)
        out = _expert_ffn(pl, buf)
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0, tiled=True)
        y = _combine(out, slot, gate, b * s, D, xl.dtype)
        aux_n = jnp.sum(density * router_prob)
        aux = moe.n_experts * moe.top_k * jax.lax.pmean(
            aux_n, tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        )
        return y.reshape(b, s, D), aux

    xspec = P(dp if dp else None, "model", None)
    out, aux = jax.shard_map(
        local_block,
        mesh=mesh,
        in_specs=(xspec, P(), P("model", None, None), P("model", None, None), P("model", None, None)),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def held_weights(p: Param, xt: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Each token's gate on each held expert, 0 where it was not routed there.

    xt (T, D) → (T, n_held) float32.  The router scores all ``n_experts`` in
    float32; a token's ``top_k`` logits are softmaxed among themselves (the
    same weights as a full softmax renormalised over the chosen experts).
    """
    moe = cfg.moe
    first, n = cfg.held_experts
    logits = jnp.matmul(
        xt.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )  # (T, E)
    top, expert = jax.lax.top_k(logits, moe.top_k)  # (T, k)
    gate = jax.nn.softmax(top, axis=-1)
    hit = (expert - first)[..., None] == jnp.arange(n)  # (T, k, n)
    return jnp.sum(jnp.where(hit, gate[..., None], 0.0), axis=1)


def _moe_held(p: Param, x: jax.Array, cfg: ModelConfig, valid=None):
    """Dropless held experts. x: (B, S, D) → (out, aux).

    Every held expert runs on every token (capacity = tokens, which cannot
    overflow); the gate weights the down-projection's input, so a token
    that was not routed to an expert gets exactly nothing from it.
    ``valid`` (B, S) marks real tokens for the device counters only.
    """
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    w = held_weights(p, xt, cfg)  # (T, n)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, p["w_gate"]))
    h = h * jnp.einsum("td,edf->etf", xt, p["w_up"])
    h = (h.astype(jnp.float32) * w.T[:, :, None]).astype(x.dtype)
    out = jnp.einsum(
        "etf,efd->td", h, p["w_down"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    if device.recording():
        load = (w > 0).astype(jnp.float32)
        if valid is not None:
            load = load * valid.reshape(-1, 1).astype(jnp.float32)
        load = jnp.sum(load, axis=0)  # (n,) assignments per held expert
        device.record(device.pack(**{
            "moe.held_assignments": jnp.sum(load),
            "moe.max_held_load": jnp.max(load),
        }))
    return out.reshape(B, S, D), jnp.zeros((), jnp.float32)


def moe_block(
    p: Param, x: jax.Array, cfg: ModelConfig, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Top-k routed expert MLP. x: (B, S, D) → (out, aux_loss)."""
    mesh = active_mesh()
    moe = cfg.moe
    B, S, D = x.shape
    if moe.dropless:
        return _moe_held(p, x, cfg, valid)
    if mesh is not None and "model" in mesh.shape:
        tp = mesh.shape["model"]
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.shape:
                dp *= mesh.shape[a]
        if S % tp == 0 and moe.n_experts % tp == 0 and B % dp == 0:
            return _moe_sharded(p, x, cfg, mesh)
    return _moe_local(p, x, cfg)
