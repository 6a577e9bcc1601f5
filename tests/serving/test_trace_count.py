"""Trace-count regression: bucketed padding bounds prefill compilation.

Ten requests with ten *distinct* prompt lengths must compile a number of
prefill traces bounded by the bucket table — at most one per
(bucket width, first-chunk flag) pair — never one per length.  This is the
whole point of bucketed admission: O(log chunk) traces for arbitrary
length fleets.  The engines here pre-carve the pool (``initial_slabs``)
and page table (``max_pages_hint``) so the pool-shape components of the
trace key stay constant and the bound is exact.

A second engine over the same config must hit the shared jit cache and
compile *nothing*: the step functions are module-level ``lru_cache``
factories keyed on the frozen ``ModelConfig``, not per-instance closures —
verified with a ``jax.monitoring`` compile-event spy.
"""
import jax
import jax.monitoring
import numpy as np

from repro.configs import reduced
from repro.models import transformer
from repro.serving.engine import BatchEngine

DISTINCT_LENGTHS = [1, 2, 3, 5, 7, 9, 13, 21, 33, 40]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _setup():
    cfg = reduced("qwen2.5-3b", cache_b0=4)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 50, L)] for L in lengths]


def test_ten_lengths_compile_bucket_bounded_traces():
    cfg, params = _setup()
    prompts = _prompts(DISTINCT_LENGTHS)
    assert len({len(p) for p in prompts}) == len(prompts)  # all distinct
    be = BatchEngine(
        params, cfg, max_batch=4, initial_slabs=64, max_pages_hint=16
    )
    be.run_all(prompts, 2)
    n_buckets = len(be.sched.buckets)
    assert be.stats.prefill_traces <= 2 * n_buckets, (
        f"{be.stats.prefill_traces} prefill traces for {n_buckets} buckets"
    )
    assert be.stats.prefill_traces < len(prompts), (
        "trace count scaled with distinct lengths — bucketing is broken"
    )
    # every prompt token ran: ceil(L / C) chunks per request
    C = be.sched.C
    assert be.stats.prefill_chunks == sum(-(-L // C) for L in DISTINCT_LENGTHS)
    # the pre-carve really did pin the pool: no demand growth → no key churn
    assert be.stats.pool_grow_events == 0


def test_second_engine_compiles_nothing():
    cfg, params = _setup()
    prompts = _prompts([5, 33, 40])
    kw = dict(max_batch=2, initial_slabs=32, max_pages_hint=16)
    first = BatchEngine(params, cfg, **kw).run_all(prompts, 3)

    compiles: list[str] = []

    def spy(event, duration, **attrs):
        if event == COMPILE_EVENT:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(spy)
    try:
        warm = BatchEngine(params, cfg, **kw).run_all(prompts, 3)
    finally:
        jax.monitoring.unregister_event_duration_listener(spy)
    assert warm == first
    assert not compiles, (
        f"warm engine recompiled {len(compiles)} traces — the jit cache "
        "is per-instance instead of shared"
    )
