"""Chunked prefill is token-for-token identical to monolithic admission.

The DESIGN.md §7 bit-exactness contract: splitting a prompt into bucketed
chunks must not change a single sampled token versus (a) the same
``BatchEngine`` admitting monolithically and (b) the plain ggarray
``Engine``.  Exactness holds because chunk boundaries land on the
monolithic attention grid (``prefill_chunk % attention_chunk == 0``) so the
online-softmax partition of *live* score lanes is unchanged, pad lanes
contribute exactly ``0.0`` (``exp(MASK_VALUE − m)`` underflows), and the
static first-chunk flag keeps single-chunk prompts on the oracle's own
``Q = min(chunk, L)`` Mamba grid while resumed chunks keep the full grid.

Multi-chunk prompts (L > attention_chunk = 32) are the regression surface —
the admission default flipped to chunked, so these lengths exercise prefix
attends over already-scattered slabs and resumed Mamba state.
"""
import jax
import numpy as np

from repro.configs import reduced
from repro.models import transformer
from repro.serving.engine import BatchEngine, Engine


def _setup(arch="qwen2.5-3b"):
    cfg = reduced(arch, cache_b0=4)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(lengths, seed=11):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 50, L)] for L in lengths]


def test_chunked_matches_monolithic_and_engine_multichunk():
    """Attention-only stack, lengths spanning 1–3 chunks of C=32."""
    cfg, params = _setup()
    prompts = _prompts([33, 40, 64, 70, 5])
    t_new = 4
    want = Engine(params, cfg, policy="ggarray", max_len=80).generate(
        prompts, max_new_tokens=t_new, temperature=0.0
    )
    chunked = BatchEngine(params, cfg, max_batch=3, admission="chunked")
    mono = BatchEngine(params, cfg, max_batch=3, admission="monolithic")
    got_c = chunked.run_all(prompts, t_new)
    got_m = mono.run_all(prompts, t_new)
    for i in range(len(prompts)):
        assert got_c[i] == want[i], f"chunked diverged from Engine on {i}"
        assert got_m[i] == want[i], f"monolithic diverged from Engine on {i}"
    # it really chunked: 2+2+2+3+1 chunk executions across the fleet
    assert chunked.stats.prefill_chunks == sum(-(-L // 32) for L in (33, 40, 64, 70, 5))
    chunked.check_free_list()


def test_chunked_matches_engine_hybrid_equal_length():
    """Hybrid (Mamba+attn) stack vs the batched Engine oracle, equal-length
    prompts (raggedness is covered by the chunked-vs-monolithic test below,
    and the oracle's own ragged batches by tests/bench/test_bench_granite.py).
    """
    cfg, params = _setup("jamba-v0.1-52b")
    prompts = _prompts([40, 40, 40], seed=3)
    t_new = 4
    want = Engine(params, cfg, policy="ggarray", max_len=64).generate(
        prompts, max_new_tokens=t_new, temperature=0.0
    )
    be = BatchEngine(params, cfg, max_batch=3, admission="chunked")
    assert be.run_all(prompts, t_new) == want
    assert be.stats.prefill_chunks == 6  # 40 = 32 + an 8-token tail
    be.check_free_list()


def test_chunked_matches_monolithic_hybrid_ragged_with_reuse():
    """Ragged hybrid prompts through max_batch=2: exercises slot *reuse*
    (a resumed chunk must not seed Mamba state from the previous tenant)
    and prefill/decode interleaving, token-for-token vs monolithic."""
    cfg, params = _setup("jamba-v0.1-52b")
    prompts = _prompts([33, 40, 37], seed=7)
    t_new = 4
    got_c = BatchEngine(params, cfg, max_batch=2, admission="chunked").run_all(
        prompts, t_new
    )
    got_m = BatchEngine(params, cfg, max_batch=2, admission="monolithic").run_all(
        prompts, t_new
    )
    assert got_c == got_m
