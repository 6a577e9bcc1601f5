"""The work a hybrid Mamba-2 / attention stack with held experts needs,
counted from its published shapes (``reference.granite_hybrid.sizes``).

As in ``work.py``, operations and bytes are what the algorithm requires,
never what an implementation happens to do:

- a Mamba-2 mixer per token: its projections (2 × weights), the depthwise
  conv (2 × width × channels) and the recurrence h = exp(dt·A)·h + dt·x⊗B,
  y = C·h (6 × heads × head_dim × state); its state, the SSD rows in
  float32 and the conv window in bfloat16, read once and written once per
  decode step of each sequence;
- attention as in ``work.py`` (causal, each query against its own context);
- the experts: the router over every expert, and each token's expected
  share of routed experts held here (top_k × held / experts of them, by the
  uniform-routing expectation), plus the shared expert; a dense-capacity
  implementation that runs every held expert on every token does
  held / (top_k · held / experts) = experts / top_k times this work.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _counts(s: dict) -> dict:
    d, nh, hd, n, g = s["d"], s["m_heads"], s["m_head_dim"], s["m_state"], s["m_groups"]
    di = nh * hd
    ch = di + 2 * g * n
    H, KH, Dh = s["heads"], s["kv_heads"], s["head_dim"]
    period = s["kinds"]
    reps = s["layers"] // len(period)
    return dict(
        mamba_layers=reps * sum(k == "mamba" for k in period),
        attn_layers=reps * sum(k != "mamba" for k in period),
        mamba_matmul=d * (2 * di + 2 * g * n + nh) + di * d,
        mamba_other=ch * s["m_conv"] + ch + 3 * nh + di,  # conv w + b, A, D, dt_bias, norm
        mamba_token_flops=2 * ch * s["m_conv"] + 6 * nh * hd * n,
        state_bytes=nh * hd * n * F32 + (s["m_conv"] - 1) * ch * BF16,
        attn_matmul=d * (H + 2 * KH) * Dh + H * Dh * d,
        expert=3 * d * s["ff"],
        shared=3 * d * s["shared_ff"],
        router=d * s["experts"],
        routed_per_token=s["top_k"] * s["held"] / s["experts"],
    )


def ffn_flops_per_token(s: dict) -> float:
    """Router, the routed experts held here (expected share) and the shared
    expert, for one token of one layer."""
    c = _counts(s)
    return 2.0 * (c["router"] + c["routed_per_token"] * c["expert"] + c["shared"])


def mamba_flops_per_token(s: dict) -> float:
    c = _counts(s)
    return 2.0 * c["mamba_matmul"] + c["mamba_token_flops"]


def attention_flops(s: dict, ctx: int) -> float:
    """Scores and weighted values of one query against ``ctx`` keys, all
    attention layers."""
    return 4.0 * _counts(s)["attn_layers"] * s["heads"] * s["head_dim"] * ctx


def token_flops(s: dict) -> float:
    """Every layer's projections, mixers and experts for one token, without
    attention's context term and the output head."""
    c = _counts(s)
    per_layer_ffn = ffn_flops_per_token(s)
    return (c["mamba_layers"] * mamba_flops_per_token(s)
            + c["attn_layers"] * 2.0 * c["attn_matmul"]
            + (c["mamba_layers"] + c["attn_layers"]) * per_layer_ffn)


def head_flops(s: dict) -> float:
    return 2.0 * s["vocab"] * s["d"]


def param_bytes(s: dict) -> float:
    """Every weight held on this chip once (bf16; the router and the Mamba
    scalars A, D, dt_bias in float32), the tied table once."""
    c = _counts(s)
    d = s["d"]
    ffn = BF16 * (s["held"] * c["expert"] + c["shared"]) + F32 * c["router"] + 2 * BF16 * d
    mamba = BF16 * (c["mamba_matmul"] + c["mamba_other"]) + (F32 - BF16) * 3 * s["m_heads"]
    layers = c["mamba_layers"] * (mamba + ffn) + c["attn_layers"] * (BF16 * c["attn_matmul"] + ffn)
    table = s["vocab"] * d * (1 if s["tied"] else 2)
    return float(layers + BF16 * (table + d))


def kv_bytes_per_token(s: dict) -> int:
    return 2 * _counts(s)["attn_layers"] * s["kv_heads"] * s["head_dim"] * BF16


def decode_step(s: dict, ctxs: list[int]) -> tuple[float, float]:
    """One batched decode step → (flops, bytes).  ``ctxs``: each active
    sequence's context including the token being decoded.  Bytes: every
    weight once, each sequence's Mamba state read and written once, its own
    live K/V and the new K/V."""
    n = len(ctxs)
    flops = n * (token_flops(s) + head_flops(s)) + sum(attention_flops(s, c) for c in ctxs)
    state = 2 * _counts(s)["mamba_layers"] * _counts(s)["state_bytes"]
    nbytes = param_bytes(s) + n * state + sum(ctxs) * kv_bytes_per_token(s)
    return float(flops), float(nbytes)


def prefill_chunk(s: dict, t0: int, live: int, final: bool) -> float:
    """FLOPs one prefill chunk needs: ``live`` prompt tokens at ``t0 ..``,
    causal attention over their prefix, the head only on the final chunk."""
    keys = live * t0 + live * (live + 1) // 2
    flops = live * token_flops(s) + attention_flops(s, keys)
    if final:
        flops += head_flops(s)
    return float(flops)

