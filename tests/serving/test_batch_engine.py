"""BatchEngine (policy="paged"): continuous batching over the slab arena.

Acceptance (ISSUE 3): ≥ 8 concurrent ragged-length sequences through one
shared pool, total pool capacity < 2× peak live tokens + one slab per
sequence, paged attend bit-exact vs the ggarray-policy oracle.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import reduced
from repro.models import transformer
from repro.serving import kvcache
from repro.serving.engine import BatchEngine, Engine


def _setup(arch="qwen2.5-3b", **over):
    cfg = reduced(arch, cache_b0=4, **over)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


RAGGED_PROMPTS = [
    [1, 2, 3],
    [4, 5],
    [6, 7, 8, 9, 10],
    [11],
    [12, 13],
    [3, 1, 4, 1, 5, 9],
    [2, 6],
    [5, 3, 5, 8, 9, 7, 9, 3],
    [2, 7, 1, 8],
    [6, 6, 6],
]


def test_paged_attend_bit_exact_vs_ggarray_oracle():
    """kvcache-level: identical K/V traces → bitwise-identical attention."""
    cfg, _ = _setup()
    B, KH, DH, H = 3, cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
    rng = np.random.default_rng(5)
    n = 21
    ks = jnp.asarray(rng.standard_normal((B, n, KH, DH)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((B, n, KH, DH)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    lengths = jnp.asarray([n, 6, 1], jnp.int32)
    outs = {}
    for policy in ("ggarray", "paged"):
        cache = kvcache.init_cache(cfg, B, n, policy, dtype=jnp.float32)
        cache = kvcache.fill_from_prefill(cache, ks[:, :10], vs[:, :10])
        for t in range(10, n):
            cache = kvcache.append(cache, ks[:, t : t + 1], vs[:, t : t + 1], jnp.int32(t))
        outs[policy] = np.asarray(kvcache.attend(cache, q, lengths, cfg))
    np.testing.assert_array_equal(outs["paged"], outs["ggarray"])


def test_batch_engine_serves_ragged_fleet_within_pool_bound():
    """≥ 8 concurrent ragged sequences; capacity < 2·peak_live + T·nseq;
    greedy tokens identical to the ggarray-policy Engine."""
    cfg, params = _setup()
    T_new = 9
    want = Engine(params, cfg, policy="ggarray", max_len=64).generate(
        RAGGED_PROMPTS, max_new_tokens=T_new, temperature=0.0
    )
    be = BatchEngine(params, cfg, max_batch=8)
    rids = [be.submit(p, T_new) for p in RAGGED_PROMPTS]
    out = be.run()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"request {i} diverged from ggarray oracle"
    # the fleet really was concurrent and the pool really was shared
    assert be.stats.admitted == len(RAGGED_PROMPTS)
    assert be.stats.completed == len(RAGGED_PROMPTS)
    assert be.stats.decode_steps < len(RAGGED_PROMPTS) * (T_new - 1), (
        "continuous batching must overlap sequences"
    )
    # acceptance bound: capacity < 2× peak live tokens + one slab/sequence
    slab = cfg.slab_tokens
    bound = 2 * be.stats.peak_live_tokens + slab * be.B
    assert be.stats.peak_pool_tokens < bound, (
        f"pool {be.stats.peak_pool_tokens} ≥ bound {bound}"
    )
    assert be.stats.reused_slabs > 0, "completed sequences' slabs must recycle"
    # scheduling itself is host-sync-free: no stop-token drain ever fired;
    # the only device→host reads are the two final run() drains (the token
    # stream + the per-request first tokens), all audited by site.
    syncs = be.obs.registry.counter("serve.host_syncs")
    assert syncs.value(site="stop_drain") == 0, "must be host-sync-free"
    assert syncs.value(site="stream_drain") == 1
    assert syncs.value(site="first_token_drain") == 1
    assert be.stats.host_syncs == 2 == syncs.total()
    be.check_free_list()


def test_batch_engine_admits_more_requests_than_slots():
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=3)
    rids = [be.submit(p, 5) for p in RAGGED_PROMPTS[:7]]
    out = be.run()
    for rid, prompt in zip(rids, RAGGED_PROMPTS[:7]):
        assert len(out[rid]) == len(prompt) + 5
    be.check_free_list()
    assert be.alloc.live_count == 0, "all slabs must be released at drain"


def test_batch_engine_stop_token_evicts_early():
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=2, stop_token=None)
    rid = be.submit([1, 2, 3], 6)
    out = be.run()
    tok = out[rid][4]  # first decoded token — use it as the stop token
    be2 = BatchEngine(params, cfg, max_batch=2, stop_token=int(tok))
    rid2 = be2.submit([1, 2, 3], 6)
    out2 = be2.run()
    assert len(out2[rid2]) <= len(out[rid])
    assert be2.stats.host_syncs > 0  # stop detection is the one read/step
    be2.check_free_list()


def test_batch_engine_quota_is_enforced():
    from repro.pool import QuotaExceeded

    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=2, quota_slabs=1)
    be.submit(list(range(1, 12)), 4)  # 11 tokens: needs 3 slabs of 4
    with pytest.raises(QuotaExceeded):
        be.run()


def test_batch_engine_pallas_attend_close_to_levels():
    cfg, params = _setup()
    cfgp = dataclasses.replace(cfg, paged_attend_impl="pallas")
    prompts = RAGGED_PROMPTS[:4]
    out_lv = BatchEngine(params, cfg, max_batch=4).run_all(prompts, 6)
    out_pl = BatchEngine(params, cfgp, max_batch=4).run_all(prompts, 6)
    # fp accumulation order differs (flash per-page vs level walk); greedy
    # argmax almost always agrees — require ≥ 3 of 4 identical streams
    same = sum(out_lv[i] == out_pl[i] for i in range(len(prompts)))
    assert same >= len(prompts) - 1


@pytest.mark.parametrize(
    "backend,impl,quant,want",
    [
        ("tpu", "auto", False, "pallas"),  # bf16 pool on the chip: the kernel
        ("tpu", "auto", True, "levels"),  # int8 pool: the kernel cannot dequantise
        ("cpu", "auto", False, "levels"),  # the CPU oracle walk
        ("cpu", "auto", True, "levels"),
        ("tpu", "levels", False, "levels"),  # explicit values are honoured
        ("cpu", "pallas", False, "pallas"),
        ("tpu", "pallas", True, "levels"),  # …but an int8 pool always walks
    ],
)
def test_paged_attend_auto_resolution(monkeypatch, backend, impl, quant, want):
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kvcache.resolve_paged_attend(impl, quant) == want


def test_paged_attend_default_is_auto_and_checked():
    cfg, _ = _setup()
    assert cfg.paged_attend_impl == "auto"
    with pytest.raises(ValueError):
        kvcache.resolve_paged_attend("flash", False)


def test_batch_engine_quant_cache_pools_are_int8():
    """cache_quant stores int8 codes + bf16 scales in the pools and still
    decodes the same tokens as the quantized ggarray Engine."""
    cfg, params = _setup(cache_quant=True)
    be = BatchEngine(params, cfg, max_batch=2)
    for i in be._attn_slots():
        assert be.caches[i]["k_pool"].dtype == jnp.int8
        assert be.caches[i]["ks_pool"].dtype == jnp.bfloat16
    prompts = RAGGED_PROMPTS[:3]
    want = Engine(params, cfg, policy="ggarray", max_len=64).generate(
        prompts, max_new_tokens=5, temperature=0.0
    )
    assert be.run_all(prompts, 5) == want
    be.check_free_list()


def test_batch_engine_peak_live_counts_admissions():
    """max_new_tokens=1 requests never decode; peak live must still count
    their prefill context (the capacity-bound denominator)."""
    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=2)
    be.run_all([[1, 2, 3, 4, 5]] * 3, 1)
    assert be.stats.decode_steps == 0
    assert be.stats.peak_live_tokens >= 5


def test_batch_engine_mamba_hybrid_arch():
    """Hybrid (attention + SSM) stacks serve through the paged pool too.

    Prompts are equal-length: the batched Engine oracle right-pads ragged
    prompts through the Mamba recurrence (pad tokens enter the state), so
    only the unpadded case is an exact reference.
    """
    cfg, params = _setup("jamba-v0.1-52b")
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [2, 7, 1, 8], [9, 9, 9, 9]]
    want = Engine(params, cfg, policy="ggarray", max_len=64).generate(
        prompts, max_new_tokens=5, temperature=0.0
    )
    be = BatchEngine(params, cfg, max_batch=4)
    rids = [be.submit(p, 5) for p in prompts]
    out = be.run()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i]
    # ragged prompts (incl. shorter than the conv window) still serve fine
    be2 = BatchEngine(params, cfg, max_batch=2)
    outs = be2.run_all([[1], [2, 3], [4, 5, 6, 7, 8]], 4)
    assert [len(o) for o in outs] == [5, 6, 9]
    be2.check_free_list()


@pytest.mark.parametrize("schedule", ("doubling", "tz"))
def test_batch_engine_extent_pool_matches_oracle_zero_copy(schedule):
    """Segmented extent pool (ISSUE 7): token-for-token parity with the
    ggarray oracle, and growth never memcpys a live pool byte."""
    cfg, params = _setup()
    T_new = 6
    want = Engine(params, cfg, policy="ggarray", max_len=64).generate(
        RAGGED_PROMPTS, max_new_tokens=T_new, temperature=0.0
    )
    be = BatchEngine(params, cfg, max_batch=8, grow_chunk=schedule)
    rids = [be.submit(p, T_new) for p in RAGGED_PROMPTS]
    out = be.run()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"request {i} diverged under {schedule}"
    assert be.stats.pool_grow_events > 0, "fleet must have outgrown the seed"
    assert be.stats.pool_copied_bytes == 0, "extent growth must never memcpy"
    assert sum(s > 0 for s in be._extent_sizes) > 1
    be.check_free_list()


def test_batch_engine_growth_counts_reserved_slabs():
    """In-flight chunked-prefill reservations are committed demand: doubling
    growth sizes off live + reserved, so converting those reservations to
    claims cannot trigger an immediate second grow.  With the accounting in
    place, grow events stay O(log final slabs)."""
    import math

    cfg, params = _setup()
    be = BatchEngine(params, cfg, max_batch=4, grow_chunk="doubling")
    prompts = [list(range(1, 17)), list(range(3, 15)), list(range(2, 12))]
    rids = [be.submit(p, 4) for p in prompts]
    out = be.run()
    assert all(len(out[r]) == len(p) + 4 for r, p in zip(rids, prompts))
    total = sum(be._extent_sizes)
    assert be.stats.pool_grow_events <= math.ceil(math.log2(max(total, 2))) + 1
    assert be.stats.pool_copied_bytes == 0
    be.check_free_list()
