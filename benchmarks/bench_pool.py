"""Slab-arena serving benchmark — sequences/s, TTFT, and pool utilization.

Compares the paged-policy ``BatchEngine`` (one shared slab pool, bucketed
chunked-prefill admission, continuous batching, slab reclamation) against
(a) the same engine under monolithic admission and (b) the per-array
``ggarray`` policy (``Engine.generate``: every sequence owns a geometric
bucket chain) on the same ragged request fleet:

* ``seqs_per_s`` — completed sequences per wall second, end to end
  (admission prefill + decode + reclamation).  CPU-relative like every
  wall-clock number here: the claim under test is the *ordering*, not ms.
  Timed engines are fresh instances after a warm-up engine over the same
  fleet: the step jits are shared per-``ModelConfig`` (module-level
  factories), so the timed run measures steady-state serving, not tracing.
* ``ttft_ms`` — mean time-to-first-token over the fleet (chunked admission
  interleaves prefill chunks with decode, so long prompts no longer block
  the queue for their whole prefill).
* ``prefill_traces`` — distinct prefill compilations; bounded by the
  bucket table (O(log chunk)), not by distinct prompt lengths.
* ``pool_utilization`` / ``capacity_ratio`` — peak live tokens vs peak pool
  capacity (the §V memory metric at fleet scale); the arena's bound is
  live + one slab per sequence, the per-array policy pays bucket rounding.
* ``prefix_hit_rate`` / ``prefix_ttft_{hit,cold}_ms`` — the shared-prefix
  fleet (one system prompt, many tenants, ``prefix_cache=True``): hit rate
  must be 1.0 and the full-hit TTFT skips the entire chunked prefill
  (``check_regression.py`` gates both via ``METRICS_pool.json``).

Usage: ``python benchmarks/bench_pool.py [--smoke] [--profile]`` → rows on
stdout + ``BENCH_pool.json`` (benchmarks/run.py schema).  ``--profile``
additionally writes a ``jax.profiler`` trace of the timed paged run under
``REPRO_BENCH_DIR`` (default ``.``)/``profile_pool`` for the CI artifact.
"""
from __future__ import annotations

import contextlib
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, emit, smoke_mode, write_json, write_metrics_json
from repro.configs import reduced
from repro.models import transformer
from repro.pool.extents import grow_extents, grow_flat, init_extent_pool, plan_extents
from repro.serving import kvcache
from repro.serving.engine import BatchEngine, Engine


def _fleet(rng, nseqs, max_prompt):
    return [
        rng.integers(1, 200, rng.integers(1, max_prompt + 1)).tolist()
        for _ in range(nseqs)
    ]


def _serve(params, cfg, prompts, new_tokens, max_batch, admission, grow_chunk=1):
    """One fresh engine over the fleet → (engine, wall seconds, ttfts)."""
    be = BatchEngine(
        params, cfg, max_batch=max_batch, admission=admission, grow_chunk=grow_chunk
    )
    rids = [be.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    be.run()
    dt = time.perf_counter() - t0
    ttfts = [be._requests[r].first_token_dispatch for r in rids]
    return be, dt, ttfts


def _grow_sweep(schedule: str, waves: int, slab_size: int):
    """Per-grow latency of doubling demand ``waves`` times from one slab.

    ``"flat"`` is the realloc pool (alloc + memcpy of the live prefix);
    the extent schedules allocate one fresh extent and copy nothing.
    Returns (p95 µs per grow step, total live bytes memcpy'd).
    """
    pool = init_extent_pool(1, slab_size, (), jnp.float32)
    times, copied = [], 0
    for _ in range(waves):
        short = pool.n_slabs  # double the fleet's demand each wave
        t0 = time.perf_counter()
        if schedule == "flat":
            copied += pool.extents[0].size * pool.dtype.itemsize
            pool = grow_flat(pool, short)
        else:
            pool = grow_extents(
                pool, plan_extents(pool.extent_sizes, short, schedule)
            )
        jax.block_until_ready(pool.extents[-1])
        times.append(time.perf_counter() - t0)
    return float(np.quantile(times, 0.95)) * 1e6, copied


def main() -> None:
    smoke = smoke_mode() or "--smoke" in sys.argv
    profile = "--profile" in sys.argv
    nseqs = 6 if smoke else 12
    # past attention_chunk=32 so the chunked path really chunks
    max_prompt = 40 if smoke else 70
    new_tokens = 5 if smoke else 16
    max_batch = 4 if smoke else 8

    cfg = reduced("qwen2.5-3b", cache_b0=4)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = _fleet(rng, nseqs, max_prompt)

    # --- paged: shared pool, chunked admission ----------------------------
    # The warm-up engine compiles every (bucket, first) prefill trace and
    # the decode trace into the shared per-config jit cache; the timed
    # engine reuses them all (tests/serving/test_trace_count.py pins this).
    _serve(params, cfg, prompts, new_tokens, max_batch, "chunked")
    prof_dir = os.path.join(os.environ.get("REPRO_BENCH_DIR", "."), "profile_pool")
    prof = jax.profiler.trace(prof_dir) if profile else contextlib.nullcontext()
    try:
        with prof:
            be, dt_paged, ttfts = _serve(
                params, cfg, prompts, new_tokens, max_batch, "chunked"
            )
    except BaseException:
        # a run that dies mid-trace must not leave a half-written trace dir
        # behind — CI would upload it as if it were a real profile artifact
        if profile:
            shutil.rmtree(prof_dir, ignore_errors=True)
        raise
    peak_live = be.stats.peak_live_tokens
    util = peak_live / max(be.stats.peak_pool_tokens, 1)
    emit(
        "pool_paged_seqs_per_s",
        dt_paged / nseqs * 1e6,
        f"{nseqs / dt_paged:.2f}/s chunks={be.stats.prefill_chunks}",
    )
    emit(
        "pool_paged_ttft_ms",
        float(np.mean(ttfts)) * 1e6,
        f"mean={np.mean(ttfts) * 1e3:.1f}ms p95={np.quantile(ttfts, 0.95) * 1e3:.1f}ms",
    )
    emit(
        "pool_paged_prefill_traces",
        float(be.stats.prefill_traces),
        f"buckets={be.sched.buckets} distinct_lengths={len({len(p) for p in prompts})}",
    )
    emit(
        "pool_paged_utilization",
        util * 100.0,
        f"peak_live={peak_live} pool={be.stats.peak_pool_tokens} "
        f"reused={be.stats.reused_slabs}",
    )
    emit(
        "pool_paged_capacity_ratio",
        be.stats.peak_pool_tokens / max(peak_live, 1),
        f"bound<2x+slab/seq grow_events={be.stats.pool_grow_events}",
    )

    # --- extent growth schedules: zero-copy pool growth (DESIGN.md §8) ----
    # Grow-step microbench: p95 latency of one growth under doubling demand,
    # realloc pool ("flat": alloc + full-pool memcpy) vs extent appends.
    grow_waves = 8 if smoke else 12
    grow_slab = 1024 if smoke else 4096
    grow_p95 = {}
    for sched in ("flat", "doubling", "tz"):
        p95_us, copied = _grow_sweep(sched, grow_waves, grow_slab)
        grow_p95[sched] = p95_us
        emit(
            f"pool_grow_p95_us_{sched}",
            p95_us,
            f"{grow_waves} doublings slab={grow_slab}f32 copied={copied}B",
        )
        emit(
            f"pool_grow_copied_bytes_{sched}",
            float(copied),
            "live bytes memcpy'd by growth (extent schedules must be 0)",
        )
    # Steady-state serving under each extent schedule: same fleet, growth
    # retraces bounded by the extent count instead of realloc copies.
    for sched in ("doubling", "tz"):
        _serve(params, cfg, prompts, new_tokens, max_batch, "chunked", sched)
        bs, dt_s, _ = _serve(
            params, cfg, prompts, new_tokens, max_batch, "chunked", sched
        )
        nx = sum(1 for s in bs._extent_sizes if s > 0)
        emit(
            f"pool_paged_seqs_per_s_{sched}",
            dt_s / nseqs * 1e6,
            f"{nseqs / dt_s:.2f}/s vs_flat={dt_paged / dt_s:.2f} extents={nx} "
            f"grow_events={bs.stats.pool_grow_events} "
            f"copied={bs.stats.pool_copied_bytes}B",
        )
        emit(
            f"pool_serve_copied_bytes_{sched}",
            float(bs.stats.pool_copied_bytes),
            f"engine pool bytes memcpy'd end-to-end (flat engine: "
            f"{be.stats.pool_copied_bytes}B)",
        )

    # --- paged, monolithic admission: the pre-chunking scheduler ----------
    _serve(params, cfg, prompts, new_tokens, max_batch, "monolithic")
    bm, dt_mono, ttfts_m = _serve(
        params, cfg, prompts, new_tokens, max_batch, "monolithic"
    )
    emit(
        "pool_monolithic_seqs_per_s",
        dt_mono / nseqs * 1e6,
        f"{nseqs / dt_mono:.2f}/s chunked_speedup={dt_mono / dt_paged:.2f}",
    )
    emit(
        "pool_monolithic_ttft_ms",
        float(np.mean(ttfts_m)) * 1e6,
        f"chunked_ttft_ratio={np.mean(ttfts) / max(np.mean(ttfts_m), 1e-12):.2f}",
    )

    # --- shared-prefix fleet: copy-on-write prefix caching (§10) ----------
    # One system prompt, many tenants: the first request pays the chunked
    # prefill and publishes its slabs; every later identical prompt admits
    # with zero prefill chunks (full hit) and aliases the cached slabs.
    fleet_n = 8 if smoke else 32
    sys_prompt = rng.integers(1, 200, 36).tolist()  # 36 % slab_tokens == 0
    bp = BatchEngine(params, cfg, max_batch=max_batch, prefix_cache=True)
    r_cold = bp.submit(list(sys_prompt), new_tokens)
    bp.run()
    ttft_cold = bp._requests[r_cold].first_token_dispatch
    chunks_cold = bp.stats.prefill_chunks
    hits0 = bp.stats.prefix_hits
    for _ in range(fleet_n):
        bp.submit(list(sys_prompt), new_tokens)
    bp.run()
    hit_rate = (bp.stats.prefix_hits - hits0) / fleet_n
    fleet_chunks = bp.stats.prefill_chunks - chunks_cold
    # apples-to-apples TTFT: one more hit request alone (no queue wait),
    # against the cold request that ran alone through the same jit cache
    r_hit = bp.submit(list(sys_prompt), new_tokens)
    bp.run()
    ttft_hit = bp._requests[r_hit].first_token_dispatch
    ttft_hit_ratio = ttft_hit / max(ttft_cold, 1e-12)
    emit(
        "pool_prefix_hit_rate",
        hit_rate * 100.0,
        f"{fleet_n} shared-prefix requests, {fleet_chunks} prefill chunks, "
        f"cow={bp.stats.cow_copies} live_slabs={bp.alloc.n_slabs}",
    )
    emit(
        "pool_prefix_ttft_cold_ms",
        ttft_cold * 1e6,
        "first request: full chunked prefill, publishes the prompt slabs",
    )
    emit(
        "pool_prefix_ttft_hit_ms",
        ttft_hit * 1e6,
        f"fully cached: first token from the first decode step, "
        f"hit/cold={ttft_hit_ratio:.2f}",
    )

    # --- ggarray oracle: one bucket chain per sequence --------------------
    eng = Engine(params, cfg, policy="ggarray", max_len=256)
    eng.generate(prompts, new_tokens)  # warm-up
    eng = Engine(params, cfg, policy="ggarray", max_len=256)
    t0 = time.perf_counter()
    eng.generate(prompts, new_tokens)
    dt_gg = time.perf_counter() - t0
    # per-sequence bucket-chain capacity at end of generation
    lens = [len(p) + new_tokens for p in prompts]
    caps = [kvcache.cache_capacity(cfg, "ggarray", n) for n in lens]
    live = sum(lens)
    emit(
        "pool_ggarray_seqs_per_s",
        dt_gg / nseqs * 1e6,
        f"{nseqs / dt_gg:.2f}/s paged_vs_ggarray={dt_gg / dt_paged:.2f}",
    )
    emit(
        "pool_ggarray_capacity_ratio",
        sum(caps) / live,
        f"live={live} allocated={sum(caps)} (per-array bucket rounding)",
    )
    emit(
        "pool_capacity_advantage",
        (sum(caps) / live) / max(be.stats.peak_pool_tokens / max(peak_live, 1), 1e-9),
        "arena slots per ggarray slot at equal live data",
    )

    # --- device counter plane: see inside the pool (DESIGN.md §9.x) -------
    # A separate instrumented engine over the same fleet (the timed engines
    # stay uninstrumented so the wall-clocks are untouched); its in-kernel
    # counters yield the geometry metrics check_regression.py ratchets.
    bi = BatchEngine(params, cfg, max_batch=max_batch, instrument=True)
    for p in prompts:
        bi.submit(p, new_tokens)
    bi.run()
    dev = bi.drain_device_counters()
    decode_tokens = nseqs * new_tokens
    attend_lanes = max(dev["paged_attend.lanes"], 1.0)
    append_lanes = max(dev["slab_append.lanes"], 1.0)
    masked_waste = dev["paged_attend.masked_lanes"] / attend_lanes
    tiles_per_token = dev["paged_attend.tiles"] / max(decode_tokens, 1)
    occupancy = dev["slab_append.active_lanes"] / append_lanes
    emit(
        "pool_device_masked_lane_waste_pct",
        masked_waste * 100.0,
        f"attend lanes past kv_len / lanes walked "
        f"({dev['paged_attend.masked_lanes']:.0f}/{attend_lanes:.0f})",
    )
    emit(
        "pool_device_tiles_per_token",
        tiles_per_token,
        f"attend KV tiles per decoded token over {decode_tokens} tokens",
    )
    emit(
        "pool_device_append_occupancy_pct",
        occupancy * 100.0,
        f"slab-append active/total lanes "
        f"({dev['slab_append.active_lanes']:.0f}/{append_lanes:.0f})",
    )

    # --- telemetry artifact: full registry snapshots of the timed engines -
    # check_regression.py --metrics gates TTFT p95 (chunked/monolithic),
    # pool utilization, and the device-counter waste ratchet from this file;
    # the rest is for diagnosis.
    write_metrics_json(
        "pool",
        {
            "chunked": be.obs.snapshot(),
            "monolithic": bm.obs.snapshot(),
            "device": {
                "counters": dev,
                "masked_lane_waste": masked_waste,
                "tiles_per_token": tiles_per_token,
                "append_occupancy": occupancy,
                "decode_tokens": decode_tokens,
            },
            "prefix": {
                "hit_rate": hit_rate,
                "ttft_cold_ms": ttft_cold * 1e3,
                "ttft_hit_ms": ttft_hit * 1e3,
                "ttft_hit_ratio": ttft_hit_ratio,
                "fleet": fleet_n,
                "suffix_chunks": fleet_chunks,
                "cow_copies": bp.stats.cow_copies,
                "live_slabs": bp.alloc.n_slabs,
                "metrics": bp.obs.snapshot(),
            },
        },
    )


if __name__ == "__main__":
    start = len(Row.rows)
    print("name,us_per_call,derived")
    main()
    write_json("pool", Row.rows[start:])
