"""granite-4.0-h-small at a reduced size on the CPU, against the plain float32
reference ``bench/reference/granite_hybrid.py`` on seeded random weights.

The program (``registry.reduced`` with the period of 10, float32) and the
reference are given the same weights, drawn by the reference.  Tolerances:

- ``FORWARD_ATOL`` 2e-3 on logits that spread with a standard deviation of
  about 2: the program's chunked SSD algorithm and the reference's token-by-
  token recurrence sum the same float32 terms in different orders, which
  moves logits by ~1e-5 here; the bfloat16 control (every matrix product's
  operands rounded to bfloat16) moves them by more than 1e-2.
- ``GAP_LIMIT`` 1e-3 on the gap by which a served token's reference logit
  lies below the reference's best: a float32 server reads ~1e-5, a wrong
  K/V row, lost state or a misrouted expert reads the logits' spread.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchroot import REPO  # noqa: F401  (puts bench/ on sys.path)
from reference import granite_hybrid as ref
from repro.configs import reduced
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.serving.engine import BatchEngine

FORWARD_ATOL = 2e-3
GAP_LIMIT = 1e-3
EXPERTS, TOP_K, SHARDS = 8, 3, 2


def _hf(cfg) -> dict:
    """The HF-style configuration of a reduced program config."""
    s = cfg.ssm
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "num_hidden_layers": cfg.n_layers,
        "layer_types": ["attention" if k == "attn" else k for k in cfg.layout] * cfg.n_periods,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "mamba_n_heads": s.n_ssm_heads(cfg.d_model), "mamba_d_head": s.head_dim,
        "mamba_d_state": s.d_state, "mamba_n_groups": s.n_groups, "mamba_d_conv": s.d_conv,
        "num_local_experts": cfg.held_experts[1], "num_experts_per_tok": cfg.moe.top_k,
        "intermediate_size": cfg.moe.d_ff_expert,
        "shared_intermediate_size": cfg.moe.d_ff_shared,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier, "logits_scaling": cfg.logits_scaling,
        "torch_dtype": cfg.dtype, "assumed": {"router_outputs": cfg.moe.n_experts},
    }


def _cfg(**over):
    moe = MoEConfig(n_experts=EXPERTS, top_k=TOP_K, d_ff_expert=32, d_ff_shared=48,
                    dropless=True)
    kw = dict(n_layers=10, moe=moe, expert_shards=SHARDS, attention_multiplier=1 / 16)
    kw.update(over)
    return reduced("granite-4.0-h-small", **kw)


def _params(cfg, seed=3):
    """The reference's draw, in the program's dtype."""
    p = ref.make_params(_hf(cfg), seed)
    return jax.tree.map(lambda a: a.astype(jnp.dtype(cfg.param_dtype)), p), p


def _prompts(lengths, vocab, seed=5):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lengths]


def test_reduced_config_keeps_the_published_period():
    cfg = _cfg()
    assert cfg.layout == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert cfg.n_periods == 1 and cfg.mamba_ffn and cfg.moe.dropless
    assert cfg.held_experts == (0, EXPERTS // SHARDS)


def test_forward_matches_reference_logits():
    """``transformer.forward`` (the chunked SSD, NoPE, the multipliers, the
    mixer → FFN layer) against the reference's full forward; the bfloat16
    control misses by more than the tolerance."""
    cfg = _cfg()
    params, raw = _params(cfg)
    hf = _hf(cfg)
    (toks,) = _prompts([61], cfg.vocab_size)
    logits, _ = transformer.forward(params, jnp.asarray([toks]), cfg)
    rows = np.arange(len(toks))
    _, want, _ = ref.logits_at(raw, hf, toks, rows, np.zeros_like(rows))
    got = np.asarray(logits[0, :, : cfg.vocab_size])
    assert np.std(np.asarray(want)) > 1.0  # the logits spread
    np.testing.assert_allclose(got, np.asarray(want), atol=FORWARD_ATOL, rtol=0)
    _, low, _ = ref.logits_at(raw, hf, toks, rows, np.zeros_like(rows), lowp=True)
    assert np.max(np.abs(np.asarray(low) - np.asarray(want))) > 5 * FORWARD_ATOL


def _served_gaps(cfg, raw, prompts, outs):
    gaps = []
    for prompt, toks in zip(prompts, outs):
        served = toks[len(prompt):]
        seq = prompt + served[:-1]
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        g, _, _ = ref.logits_at(raw, _hf(cfg), seq, rows, served)
        gaps.append(float(np.max(np.asarray(g))))
    return gaps


def test_batch_engine_serves_the_reference():
    """Chunked prefill (chunks of 32, slabs of 8 tokens, SSD chunks of 8)
    and decode through the paged pool and the Mamba state rows: prompts that
    cross slabs, SSD chunks and prefill chunks, with tails padded to their
    bucket, in 3 slots so that slots are reused."""
    cfg = _cfg()
    params, raw = _params(cfg)
    lengths = [45, 70, 13, 33, 100]
    prompts = _prompts(lengths, cfg.vocab_size)
    be = BatchEngine(params, cfg, max_batch=3)
    outs = be.run_all(prompts, 6)
    assert be.stats.prefill_chunks == sum(-(-n // 32) for n in lengths)
    assert max(_served_gaps(cfg, raw, prompts, outs)) <= GAP_LIMIT
    be.check_free_list()


def test_hybrid_prefill_compiles_bucket_bounded_programs():
    """Many distinct tail lengths compile no more tail programs than there
    are buckets: padded tails are masked out of the recurrence, not cut to
    their exact width."""
    cfg = _cfg()
    params, _ = _params(cfg)
    lengths = list(range(33, 63, 2))  # 15 tails, 1 .. 29 tokens
    be = BatchEngine(params, cfg, max_batch=4, initial_slabs=40, max_pages_hint=8)
    widths = []
    real = be._run_chunk

    def spy(task):
        widths.append((task.width, task.t0 == 0))
        return real(task)

    be._run_chunk = spy
    be.run_all(_prompts(lengths, cfg.vocab_size), 2)
    tails = {w for w, first in widths if not first}
    assert len({n - 32 for n in lengths}) == 15
    assert tails <= set(be.sched.buckets) and len(tails) <= len(be.sched.buckets)
    assert be.stats.prefill_traces <= len(be.sched.buckets) + 1  # + the first chunk


def _moe_input(cfg, T=24, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((1, T, cfg.d_model)), jnp.float32)


def _ref_moe(cfg, p, x, held):
    s = dict(ref.sizes(_hf(cfg)), held=held)
    return ref._moe(x[0], p, s, "")


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each share computes its own experts' part; all shares' routed parts,
    plus the shared expert counted once, are the uncut reference layer."""
    cfg = _cfg(expert_shards=1)
    p = jax.tree.map(lambda a: a[0], _params(cfg)[0]["layers"][0]["moe"])
    x = _moe_input(cfg)
    want = _ref_moe(cfg, p, x, EXPERTS)
    n = EXPERTS // SHARDS
    parts = []
    for shard in range(SHARDS):
        sc = dataclasses.replace(cfg, expert_shards=SHARDS, expert_shard=shard)
        sp = dict(p, **{k: p[k][shard * n:(shard + 1) * n] for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe_mod.moe_block(sp, x, sc)[0][0])
    shared = _ref_moe(cfg, p, x, 0)  # the shared expert alone
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(want), atol=1e-5)


def test_dropless_layer_takes_every_token_on_one_expert():
    """A router that sends every token to one held expert: the capacity
    path would keep ~capacity_factor·T·k/E of them, the held layer keeps all."""
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(cfg)[0]["layers"][0]["moe"])
    first, n = cfg.held_experts
    hot = first + n - 1
    p = dict(p, router=p["router"].at[:, hot].set(10.0))
    x = jnp.abs(_moe_input(cfg, T=32)) + 0.5  # every token's logit for `hot` is largest
    w = moe_mod.held_weights(p, x[0], cfg)
    assert bool(jnp.all(w[:, hot - first] > 0))
    got = moe_mod.moe_block(p, x, cfg)[0][0]
    want = _ref_moe(cfg, p, x, n) - _ref_moe(cfg, p, x, 0)  # routed part only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert moe_mod.expert_capacity(cfg.moe, 32) < 32  # the old path would drop


def test_held_expert_counters_count_routed_assignments():
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(cfg)[0]["layers"][0]["moe"])
    x = _moe_input(cfg, T=16)
    from repro.obs import device

    with device.tape() as t:
        moe_mod.moe_block(p, x, cfg)
    ctr = device.as_dict(t.total())
    w = moe_mod.held_weights(p, x[0], cfg)
    assert ctr["moe.held_assignments"] == float(jnp.sum(w > 0))
    assert ctr["moe.max_held_load"] == float(jnp.max(jnp.sum(w > 0, axis=0)))


@pytest.mark.parametrize("lengths", [[9, 9, 9], [5, 17, 30]])
def test_engine_oracle_masks_ragged_prompts(lengths):
    """The batched ``Engine`` right-pads ragged prompts; the recurrence is
    masked by length, so each prompt generates as it does alone."""
    from repro.serving.engine import Engine

    cfg = _cfg()
    params, _ = _params(cfg)
    prompts = _prompts(lengths, cfg.vocab_size, seed=9)
    together = Engine(params, cfg, policy="ggarray", max_len=64).generate(prompts, 4)
    alone = [Engine(params, cfg, policy="ggarray", max_len=64).generate([p], 4)[0]
             for p in prompts]
    assert together == alone
