"""Plain float32 reference of the Granite 4.0-H decoder, and its random weights.

Follows the published architecture (``granitemoehybrid`` ``config.json``):
the token embedding × ``embedding_multiplier``; per layer RMSNorm → a mixer
→ the residual, the mixer's output × ``residual_multiplier``; RMSNorm → the
mixture of experts plus the shared expert → the residual, × the same
multiplier; final RMSNorm; the tied output head, logits ÷ ``logits_scaling``.
The mixer is the one ``layer_types`` names:

- ``mamba``: Mamba-2.  One input projection split into the gate z, x, B, C
  and dt; a depthwise causal conv (width ``mamba_d_conv``, with bias) and
  SiLU over (x, B, C); dt = softplus(dt + dt_bias), A = −exp(A_log); the
  sequential recurrence, token by token,
  h_t = exp(dt_t·A)·h_{t−1} + dt_t·x_t⊗B_t,  y_t = C_t·h_t + D·x_t;
  the gated RMSNorm of y·SiLU(z); the output projection.  This is the
  recurrence itself, deliberately not the chunked (SSD) algorithm.
- ``attention``: grouped-query attention with no position embedding (NoPE),
  no bias, scores × ``attention_multiplier``.

The mixture of experts: router logits over all ``router_outputs`` experts
(``assumed`` in the configuration), softmax over each token's
``num_experts_per_tok`` largest logits, and the SwiGLU experts of width
``intermediate_size`` weighted by those gates.  Everything is computed in
float32 with every matrix product at ``Precision.HIGHEST``, layer by layer,
with attention in blocks of queries.

Departures from the published model, both the configuration's cut:

- only the first ``num_hidden_layers`` of ``layer_types`` are run (one
  pipeline stage);
- only the ``num_local_experts`` experts this chip holds (experts
  0 .. num_local_experts − 1 of the router's outputs) are computed: the
  router still scores every expert and picks a token's top experts among
  all of them, and what the experts held elsewhere would add is left out,
  as it is in the program.

Nothing here comes from the system under test.  The weights are drawn here
from the seed, in bfloat16 (the served type; the Mamba scalars A_log, D and
dt_bias and the router in float32, as the program holds them), as one
jitted call on the device, in the nesting the serving program takes: one
entry of ``layers`` per layer of the period, stacked over periods.

``lowp=True`` is the control, as in ``qwen2``: every matrix product's
operands rounded to the precision below the configuration's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.qwen2 import HIGHEST, LOWER, _key, _matmul, _pow2, _rms

Q_BLOCK = 512  # attention queries per block
S_BLOCK = 4096  # sequences are padded to a multiple of this (few shapes)


def sizes(hf: dict) -> dict:
    """The shapes the reference needs, from the HF-style config keys."""
    d = int(hf["hidden_size"])
    h = int(hf["num_attention_heads"])
    vocab = int(hf["vocab_size"])
    layers = int(hf["num_hidden_layers"])
    kinds = tuple(hf["layer_types"][:layers])
    period = next(p for p in range(1, layers + 1)
                  if layers % p == 0 and all(kinds[i] == kinds[i % p] for i in range(layers)))
    return dict(
        d=d,
        layers=layers,
        kinds=kinds[:period],
        heads=h,
        kv_heads=int(hf["num_key_value_heads"]),
        head_dim=d // h,
        vocab=vocab,
        padded_vocab=-(-vocab // 256) * 256,
        eps=float(hf["rms_norm_eps"]),
        tied=bool(hf["tie_word_embeddings"]),
        m_heads=int(hf["mamba_n_heads"]),
        m_head_dim=int(hf["mamba_d_head"]),
        m_state=int(hf["mamba_d_state"]),
        m_groups=int(hf["mamba_n_groups"]),
        m_conv=int(hf["mamba_d_conv"]),
        experts=int(hf["assumed"]["router_outputs"]),
        held=int(hf["num_local_experts"]),
        top_k=int(hf["num_experts_per_tok"]),
        ff=int(hf["intermediate_size"]),
        shared_ff=int(hf["shared_intermediate_size"]),
        emb_mult=float(hf["embedding_multiplier"]),
        res_mult=float(hf["residual_multiplier"]),
        attn_mult=float(hf["attention_multiplier"]),
        logits_div=float(hf["logits_scaling"]),
    )


@functools.partial(jax.jit, static_argnames=("shape_key",))
def _draw(key, shape_key):
    s = dict(shape_key)
    d, P = s["d"], s["layers"] // len(s["kinds"])
    H, KH, Dh = s["heads"], s["kv_heads"], s["head_dim"]
    nh, hd, n, g, K = s["m_heads"], s["m_head_dim"], s["m_state"], s["m_groups"], s["m_conv"]
    di = nh * hd
    E, F, Fs = s["held"], s["ff"], s["shared_ff"]
    bf16 = jnp.bfloat16
    f32 = jnp.float32
    keys = iter(jax.random.split(key, 32 * len(s["kinds"]) + 8))

    def normal(shape, scale, dtype=bf16):
        return (jax.random.normal(next(keys), (P, *shape), f32) * scale).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), (P, *shape), f32, lo, hi)

    def around(shape, mean):
        return (mean * (1.0 + 0.05 * jax.random.normal(next(keys), (P, *shape), f32))).astype(bf16)

    def moe():
        return {
            "router": normal((d, s["experts"]), 1 / math.sqrt(d), f32),
            "w_gate": normal((E, d, F), 1 / math.sqrt(d)),
            "w_up": normal((E, d, F), 1 / math.sqrt(d)),
            "w_down": normal((E, F, d), 1 / math.sqrt(F)),
            "shared": {
                "w_gate": normal((d, Fs), 1 / math.sqrt(d)),
                "w_up": normal((d, Fs), 1 / math.sqrt(d)),
                "w_down": normal((Fs, d), 1 / math.sqrt(Fs)),
            },
        }

    def mamba():
        # Mamba-2's own initialisation of the scalars: A in [1, 16], dt
        # log-uniform in [0.001, 0.1] behind the softplus, D = 1; the conv as
        # a depthwise kernel of fan-in d_conv
        dt = jnp.exp(uniform((nh,), math.log(1e-3), math.log(1e-1)))
        ch = di + 2 * g * n
        bound = 1 / math.sqrt(K)
        return {
            "wz": normal((d, di), 1 / math.sqrt(d)),
            "wx": normal((d, di), 1 / math.sqrt(d)),
            "wBC": normal((d, 2 * g * n), 1 / math.sqrt(d)),
            "wdt": normal((d, nh), 1 / math.sqrt(d)),
            "conv_w": uniform((K, ch), -bound, bound).astype(bf16),
            "conv_b": uniform((ch,), -bound, bound).astype(bf16),
            "A_log": jnp.log(uniform((nh,), 1.0, 16.0)),
            "D": 1.0 + 0.05 * jax.random.normal(next(keys), (P, nh), f32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus⁻¹(dt)
            "norm_w": around((di,), 1.0),
            "out_proj": normal((di, d), 1 / math.sqrt(di)),
        }

    def attention():
        # NoPE attention is peaked only through q·k: q and k are drawn at
        # 5/sqrt(fan_in), so scores × 1/128 spread with a standard
        # deviation of about 2, as RoPE stacks' do at 1.3/sqrt(fan_in)
        qk = 5.0 / math.sqrt(d)
        return {
            "wq": normal((d, H, Dh), qk),
            "wk": normal((d, KH, Dh), qk),
            "wv": normal((d, KH, Dh), 1 / math.sqrt(d)),
            "wo": normal((H, Dh, d), 1 / math.sqrt(H * Dh)),
        }

    layers = []
    for kind in s["kinds"]:
        mixer = {"mamba": mamba()} if kind == "mamba" else {"attn": attention()}
        layers.append({"norm1": around((d,), 1.0), **mixer, "norm2": around((d,), 1.0),
                       "moe": moe()})
    # The table is tied: drawn at 1/(8·sqrt(d)) so that the scaled
    # embedding (× 12) leaves the current token's own logit only a little
    # above the rest, and the final norm's weight (about 256) spreads the
    # logits (÷ 16) with a standard deviation of about 2.
    t = 1.0 / (8.0 * math.sqrt(d))
    table = (jax.random.normal(next(keys), (s["padded_vocab"], d), f32) * t).astype(bf16)
    gain = 2.0 * s["logits_div"] / (t * math.sqrt(d))  # 256
    final = (gain * (1.0 + 0.05 * jax.random.normal(next(keys), (d,), f32))).astype(bf16)
    params = {"embed": table, "final_norm": final, "layers": layers}
    if not s["tied"]:
        params["unembed"] = (jax.random.normal(next(keys), (s["padded_vocab"], d), f32)
                             * t).astype(bf16)
    return params


def make_params(hf: dict, seed: int) -> dict:
    """bf16 weights drawn from ``seed`` on the default device, in one call."""
    return _draw(_key(seed), tuple(sorted(sizes(hf).items())))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(h, p, s, lowp):
    """Mamba-2 over the whole sequence by its recurrence. h (S, d) → (S, d)."""
    S = h.shape[0]
    nh, hd, n, g, K = s["m_heads"], s["m_head_dim"], s["m_state"], s["m_groups"], s["m_conv"]
    di = nh * hd
    f32 = jnp.float32
    z = _matmul(h, p["wz"], lowp)
    xbc = jnp.concatenate([_matmul(h, p["wx"], lowp), _matmul(h, p["wBC"], lowp)], axis=-1)
    dt = _matmul(h, p["wdt"], lowp)
    # depthwise causal conv: out_t = Σ_k w_k · in_{t−(K−1)+k} + b
    w = p["conv_w"].astype(f32)
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(w[k] * padded[k:k + S] for k in range(K)) + p["conv_b"].astype(f32)
    xbc = _silu(conv)
    xs = xbc[:, :di].reshape(S, nh, hd)
    Bm = jnp.repeat(xbc[:, di:di + g * n].reshape(S, g, n), nh // g, axis=1)  # (S, nh, n)
    Cm = jnp.repeat(xbc[:, di + g * n:].reshape(S, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (S, nh)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state + (
            dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n), f32), (xs, Bm, Cm, dt))
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(S, di) * _silu(z)
    y = _rms(y, p["norm_w"], s["eps"])
    return _matmul(y, p["out_proj"], lowp)


def _attention(h, p, s, lowp):
    """Causal NoPE GQA, in blocks of queries. h (S, d) → (S, d)."""
    S, d = h.shape
    H, KH, Dh = s["heads"], s["kv_heads"], s["head_dim"]
    q = _matmul(h, p["wq"].reshape(d, H * Dh), lowp).reshape(S, H, Dh)
    k = _matmul(h, p["wk"].reshape(d, KH * Dh), lowp).reshape(S, KH, Dh)
    v = _matmul(h, p["wv"].reshape(d, KH * Dh), lowp).reshape(S, KH, Dh)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * s["attn_mult"]
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision=HIGHEST)

    nb = S // Q_BLOCK
    out = jax.lax.map(block, (jnp.arange(nb), q.reshape(nb, Q_BLOCK, H, Dh)))
    return _matmul(out.reshape(S, H * Dh), p["wo"].reshape(H * Dh, d), lowp)


def _swiglu(h, wg, wu, wd, lowp):
    return _matmul(_silu(_matmul(h, wg, lowp)) * _matmul(h, wu, lowp), wd, lowp)


def _moe(h, p, s, lowp):
    """The held experts' part of the routed result, plus the shared expert."""
    logits = _matmul(h, p["router"], lowp)  # (S, experts)
    top, idx = jax.lax.top_k(logits, s["top_k"])
    gate = jax.nn.softmax(top, axis=-1)
    out = _swiglu(h, p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"], lowp)
    for e in range(s["held"]):  # the experts held here are 0 .. held − 1
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)  # (S,)
        out = out + weight[:, None] * _swiglu(
            h, p["w_gate"][e], p["w_up"][e], p["w_down"][e], lowp)
    return out


def _layer(x, lp, kind, s, lowp):
    h = _rms(x, lp["norm1"], s["eps"])
    mixed = _mamba(h, lp["mamba"], s, lowp) if kind == "mamba" else _attention(h, lp["attn"], s, lowp)
    x = x + s["res_mult"] * mixed
    h = _rms(x, lp["norm2"], s["eps"])
    return x + s["res_mult"] * _moe(h, lp["moe"], s, lowp)


@functools.partial(jax.jit, static_argnames=("shape_key", "lowp"))
def _gaps(params, tokens, rows, served, shape_key, lowp):
    """Logit gaps at the positions ``rows`` of one sequence, as ``qwen2``'s."""
    s = dict(shape_key)
    emb = params["embed"]
    x = emb[tokens].astype(jnp.float32) * s["emb_mult"]
    period = len(s["kinds"])
    for layer in range(s["layers"]):
        slot, stage = layer % period, layer // period
        lp = jax.tree.map(lambda a: a[stage], params["layers"][slot])
        x = _layer(x, lp, s["kinds"][slot], s, lowp)
    hid = _rms(x[rows], params["final_norm"], s["eps"])
    table = emb if s["tied"] else params["unembed"]
    logits = _matmul(hid, table[: s["vocab"]].T, lowp) / s["logits_div"]
    top = jnp.max(logits, axis=-1)
    pick = jnp.argmax(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return top - got, logits, pick


def logits_at(params, hf: dict, tokens, rows, served, *, lowp: bool = False):
    """→ (gap of each served token, logits (n, vocab), first choices) at
    ``rows`` of ``tokens``, all in float32."""
    import numpy as np

    S = len(tokens)
    n = len(rows)
    Sp = -(-S // S_BLOCK) * S_BLOCK if S > S_BLOCK else _pow2(S, Q_BLOCK)
    npad = _pow2(n, 256)
    tok = np.zeros((Sp,), np.int32)
    tok[:S] = tokens
    r = np.zeros((npad,), np.int32)
    r[:n] = rows
    sv = np.zeros((npad,), np.int32)
    sv[:n] = served
    gap, logits, pick = _gaps(
        params, jnp.asarray(tok), jnp.asarray(r), jnp.asarray(sv),
        tuple(sorted(sizes(hf).items())), LOWER[hf["torch_dtype"]] if lowp else "",
    )
    return gap[:n], logits[:n], pick[:n]
