"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) layer.

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
work *within* a chunk, a linear recurrence *across* chunk states — memory
stays O(L·d + chunks·state), which is what makes ``long_500k`` runnable for
SSM/hybrid archs (DESIGN.md §6).  Decode carries an O(1) recurrent state
(conv window + SSD state) per layer — no KV cache at all, hence GGArray's
cache role is inapplicable for pure-SSM archs (noted §Arch-applicability).

Jamba's Mamba blocks reuse this layer with the SSD formulation (d_state=16);
the original Jamba uses Mamba-1 — recorded as an adaptation in DESIGN.md.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.modules import Param, dense_init, rms_norm

__all__ = ["init_mamba", "mamba_block", "mamba_decode_step", "init_mamba_state", "MambaState"]


class MambaState(NamedTuple):
    conv: jax.Array  # (B, d_conv-1, d_inner + 2*g*n) — rolling conv window
    ssd: jax.Array  # (B, nh, hd, n) — recurrent SSD state


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_ssm_heads(cfg.d_model)
    return s, di, nh, s.head_dim, s.n_groups, s.d_state


def init_mamba(key: jax.Array, cfg: ModelConfig, dtype) -> Param:
    # Projections are kept as separate weights (not the fused zxbcdt matrix of
    # the reference impl) so each can carry its own TP sharding: wz/wx shard
    # the inner (head) dim, wBC is shared across heads and stays replicated,
    # wdt is per-head.  Math is identical; XLA fuses the matmuls back.
    s, di, nh, hd, g, n = _dims(cfg)
    d = cfg.d_model
    conv_ch = di + 2 * g * n
    keys = jax.random.split(key, 6)
    return {
        "wz": dense_init(keys[0], (d, di), dtype),
        "wx": dense_init(keys[1], (d, di), dtype),
        "wBC": dense_init(keys[2], (d, 2 * g * n), dtype),
        "wdt": dense_init(keys[3], (d, nh), dtype),
        "conv_w": dense_init(keys[4], (s.d_conv, conv_ch), dtype, fan_in=s.d_conv),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_w": jnp.ones((di,), dtype),
        "out_proj": dense_init(keys[5], (di, d), dtype),
    }


def _split_proj(p: Param, x: jax.Array, cfg: ModelConfig):
    z = x @ p["wz"]
    xBC = jnp.concatenate([x @ p["wx"], x @ p["wBC"]], axis=-1)
    dt = x @ p["wdt"]
    return z, xBC, dt


def _causal_conv(p: Param, xBC: jax.Array, d_conv: int) -> jax.Array:
    """Depthwise causal conv along L via shifted adds (window is tiny)."""
    out = xBC * p["conv_w"][-1]
    for i in range(1, d_conv):
        shifted = jnp.pad(xBC, ((0, 0), (i, 0), (0, 0)))[:, : xBC.shape[1]]
        out = out + shifted * p["conv_w"][-1 - i]
    return jax.nn.silu(out + p["conv_b"])


def _segsum(dA: jax.Array) -> jax.Array:
    """L[i, j] = sum_{j<k<=i} dA[k] for i>=j else -inf. dA: (..., Q)."""
    Q = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def mamba_block(
    p: Param,
    x: jax.Array,
    cfg: ModelConfig,
    state: MambaState | None = None,
    *,
    return_state: bool = False,
    lengths: jax.Array | None = None,
):
    """Full-sequence SSD pass. x: (B, L, D) → (B, L, D) [, final MambaState].

    ``lengths`` (B,) masks the recurrence by length: rows at and past a
    sequence's length are padding (a bucket-padded prefill tail, or the
    right-padding of a ragged batch).  Their ``dt`` is zeroed, which makes
    them state-neutral (decay exp(0) = 1, input dt·B·x = 0), and the conv
    window handed on is the one ending at the live end, so the final state
    is exactly that of the unpadded sequence.

    ``state`` makes this a *resumable* chunk step (serving's chunked
    prefill): ``state.ssd`` seeds the inter-chunk recurrence and
    ``state.conv`` supplies the raw pre-conv history the causal conv window
    reaches back into.  With a zero state the history rows are zeros — the
    exact values the implicit left zero-pad used to contribute — so the
    ``state=None`` path is bit-identical to before.
    """
    s, di, nh, hd, g, n = _dims(cfg)
    B, L, _ = x.shape
    # Resumable calls keep the full chunk grid: a short tail (L < chunk_size)
    # must pad up to the same Q the monolithic pass used, or the repartition
    # changes fp association (pad steps are dt-zeroed, hence state-neutral).
    Q = s.chunk_size if state is not None else min(s.chunk_size, L)
    pad = (-L) % Q
    Lp = L + pad
    nc = Lp // Q

    z, xBC, dt = _split_proj(p, x, cfg)
    hist = (
        state.conv.astype(xBC.dtype)
        if state is not None
        else jnp.zeros((B, s.d_conv - 1, xBC.shape[-1]), xBC.dtype)
    )
    xBC = jnp.concatenate([hist, xBC], axis=1)  # (B, d_conv-1 + L, ch)
    # raw (pre-conv) tail → the next step's conv window; the history concat
    # keeps it full-width even for L < d_conv-1 prompts
    if lengths is None:
        conv_tail = xBC[:, xBC.shape[1] - (s.d_conv - 1) :, :]
    else:  # the d_conv-1 rows that end at each sequence's live end
        rows = jnp.asarray(lengths, jnp.int32)[:, None] + jnp.arange(s.d_conv - 1)
        conv_tail = jnp.take_along_axis(xBC, rows[:, :, None], axis=1)
    if pad:  # pad to a chunk multiple; dt is zeroed on pad steps below, which
        # makes them state-neutral (decay=exp(0)=1, contribution dt·B·x=0)
        z = jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
        xBC = jnp.pad(xBC, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    xBC = _causal_conv(p, xBC, s.d_conv)[:, s.d_conv - 1 :]
    xs, Bm, Cm = jnp.split(xBC, [di, di + g * n], axis=-1)
    xs = xs.reshape(B, Lp, nh, hd)
    Bm = Bm.reshape(B, Lp, g, n)
    Cm = Cm.reshape(B, Lp, g, n)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, Lp, nh)
    if lengths is not None:
        live = jnp.arange(Lp)[None, :] < jnp.asarray(lengths, jnp.int32)[:, None]
        dt = dt * live.astype(dt.dtype)[:, :, None]
    elif pad:
        dt = dt * (jnp.arange(Lp) < L).astype(dt.dtype)[None, :, None]
    A = -jnp.exp(p["A_log"])  # (nh,)
    dA = dt * A  # (B, Lp, nh) log-decay

    # chunk reshape: (B, nc, Q, ...)
    xc = xs.reshape(B, nc, Q, nh, hd).astype(jnp.float32)
    Bc = Bm.reshape(B, nc, Q, g, n).astype(jnp.float32)
    Cc = Cm.reshape(B, nc, Q, g, n).astype(jnp.float32)
    dtc = dt.reshape(B, nc, Q, nh)
    dAc = dA.reshape(B, nc, Q, nh)

    # heads → groups mapping (heads per group)
    hpg = nh // g
    Bh = jnp.repeat(Bc, hpg, axis=3)  # (B, nc, Q, nh, n)
    Ch = jnp.repeat(Cc, hpg, axis=3)

    # ---- within-chunk (quadratic, attention-like) ----
    Lmat = jnp.exp(_segsum(jnp.moveaxis(dAc, -1, -2)))  # (B, nc, nh, Q, Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Ch, Bh)  # (B, nc, nh, Q, Q)
    xdt = xc * dtc[..., None]  # (B, nc, Q, nh, hd)
    y_diag = jnp.einsum("bchqk,bchqk,bckhp->bcqhp", scores, Lmat, xdt)

    # ---- chunk states ----
    cs = jnp.cumsum(dAc, axis=2)  # (B, nc, Q, nh)
    tot = cs[:, :, -1:, :]  # (B, nc, 1, nh)
    decay_to_end = jnp.exp(tot - cs)  # (B, nc, Q, nh)
    chunk_states = jnp.einsum(
        "bcqhn,bcqh,bcqhp->bchpn", Bh, decay_to_end * dtc, xc
    )  # (B, nc, nh, hd, n)

    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(tot[:, :, 0, :])  # (B, nc, nh)
    s0 = (
        state.ssd.astype(jnp.float32)
        if state is not None
        else jnp.zeros((B, nh, hd, n), jnp.float32)
    )

    def scan_body(carry, xs_):
        st, dec = xs_  # st: (B, nh, hd, n), dec: (B, nh)
        new = carry * dec[:, :, None, None] + st
        return new, carry  # emit the state *entering* each chunk

    final_ssd, prev_states = jax.lax.scan(
        scan_body,
        s0,
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B, nc, nh, hd, n)

    # ---- state → output ----
    y_off = jnp.einsum(
        "bcqhn,bchpn,bcqh->bcqhp", Ch, prev_states, jnp.exp(cs)
    )
    y = (y_diag + y_off).reshape(B, Lp, nh, hd)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, Lp, di)[:, :L].astype(x.dtype)
    z = z[:, :L]

    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if return_state:
        return out, MambaState(conv=conv_tail, ssd=final_ssd)
    return out


def init_mamba_state(cfg: ModelConfig, batch: int, dtype) -> MambaState:
    s, di, nh, hd, g, n = _dims(cfg)
    return MambaState(
        conv=jnp.zeros((batch, s.d_conv - 1, di + 2 * g * n), dtype),
        ssd=jnp.zeros((batch, nh, hd, n), jnp.float32),
    )


def mamba_decode_step(
    p: Param, x: jax.Array, state: MambaState, cfg: ModelConfig
) -> tuple[jax.Array, MambaState]:
    """One-token recurrent step. x: (B, 1, D) → (B, 1, D), new state."""
    s, di, nh, hd, g, n = _dims(cfg)
    B = x.shape[0]
    z, xBC, dt = _split_proj(p, x, cfg)  # (B, 1, ...)
    xBC = xBC[:, 0]

    # rolling conv window
    window = jnp.concatenate([state.conv, xBC[:, None]], axis=1)  # (B, d_conv, ch)
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xBC = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    xs, Bm, Cm = jnp.split(xBC, [di, di + g * n], axis=-1)
    xs = xs.reshape(B, nh, hd).astype(jnp.float32)
    Bm = Bm.reshape(B, g, n).astype(jnp.float32)
    Cm = Cm.reshape(B, g, n).astype(jnp.float32)
    hpg = nh // g
    Bh = jnp.repeat(Bm, hpg, axis=1)  # (B, nh, n)
    Ch = jnp.repeat(Cm, hpg, axis=1)

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B, nh)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A)  # (B, nh)

    new_ssd = state.ssd * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt, xs, Bh
    )
    y = jnp.einsum("bhn,bhpn->bhp", Ch, new_ssd) + xs * p["D"][None, :, None]
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"], MambaState(conv=new_conv, ssd=new_ssd)
