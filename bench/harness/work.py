"""The work a model needs, counted from its published shapes.

Operations and bytes here are what the algorithm requires, never what an
implementation happens to do: no padded lanes, no upcast copies, no
gathered pages past a sequence's own length.  A change to the serving
program cannot move these counts, only the time they are divided by.

``s`` is ``reference.<arch>.sizes(config)``.
"""
from __future__ import annotations

BF16 = 2  # bytes per served weight and per K/V element


def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, h, kh, dh, ff = s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    attn = d * (h + 2 * kh) * dh + h * dh * d
    mlp = 3 * d * ff
    return attn + mlp


def body_matmul_params(s: dict) -> int:
    return s["layers"] * layer_matmul_params(s)


def head_params(s: dict) -> int:
    return s["vocab"] * s["d"]


def param_bytes(s: dict) -> int:
    """Every weight of the served model once (the tied table counted once)."""
    d, h, kh, dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    per_layer = layer_matmul_params(s) + (h + 2 * kh) * dh + 2 * d  # + biases, norms
    table = s["vocab"] * d * (1 if s["tied"] else 2)
    return BF16 * (s["layers"] * per_layer + table + d)


def kv_bytes_per_token(s: dict) -> int:
    """K and V of one token across all layers."""
    return 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * BF16


def attention_flops(s: dict, ctx: int) -> int:
    """Scores and weighted values of one query against ``ctx`` keys, all layers."""
    return 4 * s["layers"] * s["heads"] * s["head_dim"] * ctx


def decode_step(s: dict, ctxs: list[int]) -> tuple[float, float]:
    """One batched decode step → (flops, bytes).  ``ctxs``: each active
    sequence's context length including the token being decoded.  Bytes:
    every weight once, each sequence's own live K/V, and the new K/V."""
    n = len(ctxs)
    total_ctx = sum(ctxs)
    flops = 2 * n * (body_matmul_params(s) + head_params(s))
    flops += sum(attention_flops(s, c) for c in ctxs)
    nbytes = param_bytes(s) + total_ctx * kv_bytes_per_token(s)
    return float(flops), float(nbytes)


def prefill_chunk(s: dict, t0: int, live: int, final: bool) -> float:
    """FLOPs one prefill chunk needs: ``live`` prompt tokens at positions
    ``t0 ..``, causal attention over their prefix, and the output head only
    where the first token is drawn (the final chunk)."""
    flops = 2 * live * body_matmul_params(s)
    # Σ_{p=t0}^{t0+live-1} (p + 1) keys, causal
    keys = live * t0 + live * (live + 1) // 2
    flops += attention_flops(s, keys)
    if final:
        flops += 2 * head_params(s)
    return float(flops)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
