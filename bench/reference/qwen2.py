"""Plain float32 reference of the Qwen2 decoder, and its random weights.

Follows the published architecture (Qwen2 ``config.json`` and
``modeling_qwen2``): token embedding; per layer RMSNorm → grouped-query
attention with biased q/k/v projections and rotary position embedding
(``rotate_half`` form, ``rope_theta``) → residual → RMSNorm → SwiGLU MLP →
residual; final RMSNorm; the output head (tied to the embedding when
``tie_word_embeddings``).  Everything is computed in float32 with every
matrix product at ``Precision.HIGHEST``, layer by layer, with attention in
blocks of queries so that a 10k-token sequence fits beside the weights.

Nothing here comes from the system under test.  The weights are drawn here
from the seed, in bfloat16 (the served type), as one jitted call on the
device, and laid out in the nesting the serving program takes: stacked over
layers under ``layers[0]``.

``lowp=True`` is the control: the same forward with every matrix product's
operands rounded to the precision below the configuration's — float8 (e4m3,
one scale per row of activations and per output column of weights) below
bfloat16, bfloat16 below float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest float8_e4m3fn
Q_BLOCK = 512  # attention queries per block


def sizes(hf: dict) -> dict:
    """The shapes the reference needs, from the HF-style config keys."""
    d = int(hf["hidden_size"])
    h = int(hf["num_attention_heads"])
    vocab = int(hf["vocab_size"])
    return dict(
        d=d,
        layers=int(hf["num_hidden_layers"]),
        heads=h,
        kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim", d // h)),
        ff=int(hf["intermediate_size"]),
        vocab=vocab,
        padded_vocab=-(-vocab // 256) * 256,
        eps=float(hf["rms_norm_eps"]),
        theta=float(hf["rope_theta"]),
        tied=bool(hf["tie_word_embeddings"]),
    )


def _key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("shape_key",))
def _draw(key, shape_key):
    s = dict(shape_key)
    d, L, H, KH, Dh, F = s["d"], s["layers"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"]
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(bf16)

    def around_one(shape):
        return (1.0 + 0.05 * jax.random.normal(next(keys), shape, jnp.float32)).astype(bf16)

    # q and k are drawn 1.3x wider than 1/sqrt(fan_in), so that attention
    # is peaked enough for a lost or misplaced K/V row to move the logits
    # well past rounding.  Much wider (2x) makes the random stack chaotic:
    # over 24 layers bf16 rounding alone then moves logits by several units
    # (CPU, qwen1.5-0.5b widths, 1024 tokens: widest bf16 gap 2.8 at 2x,
    # 0.056 at 1.3x, against 1.6 for float8).
    qk = 1.3 / math.sqrt(d)
    layer = {
        "norm1": around_one((L, d)),
        "attn": {
            "wq": normal((L, d, H, Dh), qk),
            "wk": normal((L, d, KH, Dh), qk),
            "wv": normal((L, d, KH, Dh), 1 / math.sqrt(d)),
            "wo": normal((L, H, Dh, d), 1 / math.sqrt(H * Dh)),
            "bq": normal((L, H, Dh), 0.1),
            "bk": normal((L, KH, Dh), 0.1),
            "bv": normal((L, KH, Dh), 0.1),
        },
        "norm2": around_one((L, d)),
        "mlp": {
            "w_gate": normal((L, d, F), 1 / math.sqrt(d)),
            "w_up": normal((L, d, F), 1 / math.sqrt(d)),
            "w_down": normal((L, F, d), 1 / math.sqrt(F)),
        },
    }
    # the (tied) table at 2/sqrt(d): logits of a unit-RMS hidden state
    # spread with a standard deviation of about 2
    params = {
        "embed": normal((s["padded_vocab"], d), 2.0 / math.sqrt(d)),
        "final_norm": around_one((d,)),
        "layers": [layer],
    }
    if not s["tied"]:
        params["unembed"] = normal((s["padded_vocab"], d), 1 / math.sqrt(d))
    return params


def make_params(hf: dict, seed: int) -> dict:
    """bf16 weights drawn from ``seed`` on the default device, in one call."""
    return _draw(_key(seed), tuple(sorted(sizes(hf).items())))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# the control's precision: the one below the configuration's dtype
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def _matmul(x, w, lowp: str):
    """x (..., k) @ w (k, n) in float32; under ``lowp`` both operands are
    first rounded to that precision (float8 with per-row and per-column
    scales)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if lowp == "float8_e4m3fn":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    elif lowp:
        x = x.astype(lowp).astype(jnp.float32)
        w = w.astype(lowp).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotary embedding, ``rotate_half`` form: x (S, heads, Dh)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v, s):
    """Causal GQA over the whole sequence, in blocks of queries.
    q (S, H, Dh), k/v (S, KH, Dh) → (S, H, Dh)."""
    S = q.shape[0]
    g = s["heads"] // s["kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, *q.shape[1:])
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) / math.sqrt(s["head_dim"])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(S, *q.shape[1:])


def _layer(x, lp, pos, s, lowp):
    S, d = x.shape
    H, KH, Dh = s["heads"], s["kv_heads"], s["head_dim"]
    a = lp["attn"]
    h = _rms(x, lp["norm1"], s["eps"])
    q = _matmul(h, a["wq"].reshape(d, H * Dh), lowp).reshape(S, H, Dh) + a["bq"].astype(jnp.float32)
    k = _matmul(h, a["wk"].reshape(d, KH * Dh), lowp).reshape(S, KH, Dh) + a["bk"].astype(jnp.float32)
    v = _matmul(h, a["wv"].reshape(d, KH * Dh), lowp).reshape(S, KH, Dh) + a["bv"].astype(jnp.float32)
    q = _rope(q, pos, s["theta"])
    k = _rope(k, pos, s["theta"])
    att = _attention(q, k, v, s).reshape(S, H * Dh)
    x = x + _matmul(att, a["wo"].reshape(H * Dh, d), lowp)
    m = lp["mlp"]
    h = _rms(x, lp["norm2"], s["eps"])
    gate = _matmul(h, m["w_gate"], lowp)
    up = _matmul(h, m["w_up"], lowp)
    return x + _matmul(jax.nn.silu(gate) * up, m["w_down"], lowp)


@functools.partial(jax.jit, static_argnames=("shape_key", "lowp"))
def _gaps(params, tokens, rows, served, shape_key, lowp):
    """Logit gaps at the positions ``rows`` of one sequence.

    tokens (S,) — the prompt and the served tokens, padded to a multiple of
    ``Q_BLOCK``; rows (n,) — positions whose next token was served;
    served (n,) — those tokens.  → (top − logit of ``served``, top − logit
    of this forward's own first choice, that choice), each (n,); "top" is
    this forward's largest logit.
    """
    s = dict(shape_key)
    S = tokens.shape[0]
    emb = params["embed"]
    x = emb[tokens].astype(jnp.float32)
    pos = jnp.arange(S)
    layers = params["layers"][0]

    def body(x, lp):
        return _layer(x, lp, pos, s, lowp), None

    x, _ = jax.lax.scan(body, x, layers)
    hid = _rms(x[rows], params["final_norm"], s["eps"])
    table = emb if s["tied"] else params["unembed"]
    logits = _matmul(hid, table[: s["vocab"]].T, lowp)
    top = jnp.max(logits, axis=-1)
    pick = jnp.argmax(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return top - got, logits, pick


def _pow2(n: int, least: int) -> int:
    p = least
    while p < n:
        p *= 2
    return p


def logits_at(params, hf: dict, tokens, rows, served, *, lowp: bool = False):
    """→ (gap of each served token, logits (n, vocab), first choices) at
    ``rows`` of ``tokens``, all in float32."""
    import numpy as np

    S = len(tokens)
    n = len(rows)
    Sp, npad = _pow2(S, Q_BLOCK), _pow2(n, 256)  # few shapes, few compiles
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
