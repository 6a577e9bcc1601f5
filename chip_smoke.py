"""Smoke run of both main paths on one TPU chip, at full size.

    python chip_smoke.py [--seed N]

Phases, in order; any failure exits non-zero and prints no result:

* pre-flight — the backend must be a TPU (there is no CPU fallback) and no
  interpret or memory-space override may be set in the environment;
* A, serving — qwen2.5-3b at its published widths, parameters drawn from
  ``--seed``, served through ``BatchEngine`` with its default options
  (on a TPU the decode attend is then the Pallas kernel): 8 requests,
  prompts of 256–4096 tokens, 32 new tokens each;
* B, Pallas attend and extents — the same requests with
  ``paged_attend_impl="pallas"`` and ``grow_chunk="doubling"``: first-token
  logits must match phase A, the decode step must hold the compiled kernel
  (``tpu_custom_call``), and the kernel must match its jnp oracle at the
  model's KV geometry on one and on several extents;
* C, grow → freeze — a ``TwoPhasePipeline`` of int32 grown by waves of 256
  lanes per block (the fused push-back) past 2^26 elements, frozen through
  the segmented flatten and compared element for element with a jnp
  reference, thawed, grown and frozen again; then the same through
  ``TwoPhasePipeline.from_arena`` (slab-append, paged gather,
  ``SlabArena.flatten``).

JAX's persistent compilation cache is on: in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``<repo>/.jax_cache``.  The last line of standard
output is ``{"ok": true, "device": {...}}``.

Each phase is a function of its sizes, so a CPU test can run them at a
reduced configuration; only ``main`` refuses a backend that is not a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
ARCH = "qwen2.5-3b"
N_REQUESTS = 8
MAX_BATCH = 8
PROMPT_LENS = (256, 4096)
NEW_TOKENS = 32
LOGIT_RTOL = 0.02  # bf16 model: |Δlogit| ≤ 2% of the largest |logit|
ATTEND_ATOL = 2e-3  # f32 kernel output vs its f32 oracle, bf16 K/V inputs
MIN_ELEMS = 1 << 26  # phase C: 256 MB of int32
WAVE = 256  # lanes per block per append wave (≥ FUSED_PUSH_BACK_MIN_WAVE)
NBLOCKS = 1024
B0 = 256
SLAB = 256


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Sums JAX's compile events: seconds spent compiling (or fetching a
    compiled program from the persistent cache) and cache hits/misses."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._duration)
        self._mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._duration)
        self._mon.unregister_event_listener(self._event)

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses

    def since(self, mark) -> str:
        s, h, m = mark
        return (
            f"compile_s={self.seconds - s:.3f} cache_hits={self.hits - h} "
            f"cache_misses={self.misses - m}"
        )


# --------------------------------------------------------------------------
# A / B — serving
# --------------------------------------------------------------------------

def make_prompts(seed: int, n: int, lens: tuple[int, int], vocab: int):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(lens[0], lens[1] + 1, n)
    return [rng.integers(0, vocab, int(L)).tolist() for L in lengths]


def serve(params, cfg, prompts, new_tokens: int, *, max_batch: int, **engine_kw):
    """Serve ``prompts`` through ``BatchEngine`` → (engine, outputs, logits).

    ``logits[i]`` are request ``i``'s logits at its first generated token —
    the final prefill chunk's, which the engine samples from.
    """
    from repro.serving.engine import BatchEngine

    class Tap(BatchEngine):
        def _finish_prefill(self, req, slot, logits):
            self.first_logits[req.rid] = logits
            super()._finish_prefill(req, slot, logits)

    eng = Tap(params, cfg, max_batch=max_batch, **engine_kw)
    eng.first_logits = {}
    rids = [eng.submit(p, new_tokens) for p in prompts]
    out = eng.run()
    outs = [out[r] for r in rids]
    for p, o in zip(prompts, outs):
        if len(o) != len(p) + new_tokens or o[: len(p)] != list(p):
            raise AssertionError(
                f"request of {len(p)} tokens came back with {len(o) - len(p)} "
                f"new tokens, want {new_tokens}"
            )
    eng.check_free_list()
    logits = np.stack(
        [np.asarray(eng.first_logits[r], np.float32)[0] for r in rids]
    )
    if not np.isfinite(logits).all():
        raise AssertionError("non-finite first-token logits")
    return eng, outs, logits


def engine_report(eng) -> str:
    s = eng.stats
    return (
        f"requests={s.completed} decode_steps={s.decode_steps} "
        f"prefill_chunks={s.prefill_chunks} "
        f"peak_slabs={s.peak_pool_tokens // eng.T} "
        f"peak_live_tokens={s.peak_live_tokens} "
        f"pool_grow_events={s.pool_grow_events} host_syncs={s.host_syncs}"
    )


def phase_serving(params, cfg, prompts, new_tokens: int, max_batch: int):
    """A: the default serving path → first-token logits for phase B."""
    eng, _, logits = serve(params, cfg, prompts, new_tokens, max_batch=max_batch)
    log(f"phase A serving: ok {engine_report(eng)}")
    return logits


def attend_parity(cfg, batch: int, seed: int) -> float:
    """Pallas paged attend vs its jnp oracle at ``cfg``'s KV geometry, on
    one extent and on two → the largest absolute difference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged import ops as paged_ops

    T, KH, D = cfg.slab_tokens, cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // KH
    P = 2
    S = batch * P
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, KH, G, D), jnp.float32) * D**-0.5
    kp = jax.random.normal(kk, (S, T, KH, D), jnp.float32).astype(cfg.dtype)
    vp = jax.random.normal(kv, (S, T, KH, D), jnp.float32).astype(cfg.dtype)
    pages = jnp.asarray(rng.permutation(S).reshape(batch, P), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, P * T + 1, batch), jnp.int32)
    worst = 0.0
    half = S // 2
    for k_pool, v_pool in [
        (kp, vp),
        ((kp[:half], kp[half:]), (vp[:half], vp[half:])),
    ]:
        got = paged_ops.paged_attend(q, k_pool, v_pool, pages, lengths)
        want = paged_ops.paged_attend(
            q, k_pool, v_pool, pages, lengths, use_ref=True
        )
        worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    if not worst <= ATTEND_ATOL:
        raise AssertionError(f"paged attend off its oracle by {worst}")
    return worst


def phase_pallas(params, cfg, prompts, new_tokens: int, max_batch: int,
                 want_logits, *, require_compiled: bool):
    """B: Pallas decode attend over segmented extent pools."""
    import jax

    cfg_b = dataclasses.replace(cfg, paged_attend_impl="pallas")
    eng, _, logits = serve(
        params, cfg_b, prompts, new_tokens, max_batch=max_batch,
        grow_chunk="doubling",
    )
    scale = max(1.0, float(np.max(np.abs(want_logits))))
    diff = float(np.max(np.abs(logits - want_logits)))
    if not diff <= LOGIT_RTOL * scale:
        raise AssertionError(
            f"first-token logits differ by {diff} (limit {LOGIT_RTOL * scale})"
        )
    text = (
        eng._decode.lower(eng.params, eng.cur_tok, eng.caches, eng.lengths)
        .compile()
        .as_text()
    )
    custom = "tpu_custom_call" in text
    if require_compiled and not custom:
        raise AssertionError("decode step holds no compiled Pallas kernel")
    extents = len(eng._extent_sizes)
    worst = attend_parity(cfg, max_batch, seed=0)
    log(
        f"phase B pallas: ok {engine_report(eng)} extents={extents} "
        f"max_logit_diff={diff} tpu_custom_call={custom} "
        f"attend_vs_oracle_max_abs={worst} backend={jax.default_backend()}"
    )


# --------------------------------------------------------------------------
# C — grow → freeze
# --------------------------------------------------------------------------

def _waves(nblocks: int, wave: int, min_elems: int, seed: int):
    """Seeded waves (device elements, host mask) until ≥ ``min_elems``."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    total = 0
    i = 0
    while total < min_elems:
        mask = rng.random((nblocks, wave)) < 0.9
        elems = jax.random.randint(
            jax.random.fold_in(key, i), (nblocks, wave),
            jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max, jnp.int32,
        )
        total += int(mask.sum())
        i += 1
        yield elems, mask


class _Reference:
    """A jnp-only GGArray fed the same waves (scan push-back, jnp flatten)."""

    def __init__(self, nblocks: int, b0: int):
        import jax.numpy as jnp

        from repro.core import ggarray as gg

        self.gg = gg
        self.arr = gg.init(nblocks, b0, dtype=jnp.int32)
        self.planner = gg.CapacityPlanner()

    def append(self, elems, mask):
        gg = self.gg
        self.arr = self.planner.reserve(self.arr, elems.shape[1], mask=mask)
        self.arr, _, headroom = gg.append(self.arr, elems, mask, method="scan")
        self.planner.note_append(self.arr, headroom)

    def flat(self):
        return self.gg.flatten(self.arr)


def _same(got, want, total) -> int:
    """Elements equal up to ``total``, zeros past it → live count; raises."""
    import jax.numpy as jnp

    n = min(got.shape[0], want.shape[0])
    idx = jnp.arange(n)
    ok = jnp.where(idx < total, got[:n] == want[:n], got[:n] == 0)
    ok = bool(jnp.all(ok)) and bool(jnp.all(got[n:] == 0))
    total = int(total)
    if not ok or total > n:
        raise AssertionError("frozen array differs from the jnp reference")
    return total


def _grow_freeze_cycles(pipe, ref, waves, extra) -> str:
    from repro.kernels import common

    space = common.resolve_memory_space(pipe.memory_space)
    nwaves = 0
    for elems, mask in waves:
        pipe.append(elems, mask, method="auto")
        ref.append(elems, mask)
        nwaves += 1
    frozen = pipe.freeze()
    want, total = ref.flat()
    n1 = _same(frozen.data, want, total)
    pipe.thaw()
    elems, mask = extra
    pipe.append(elems, mask, method="auto")
    ref.append(elems, mask)
    frozen = pipe.freeze()
    want, total = ref.flat()
    n2 = _same(frozen.data, want, total)
    return (
        f"memory_space={space} waves={nwaves + 1} elements={n1} "
        f"after_thaw={n2} freezes={pipe.stats.freezes} "
        f"grow_events={pipe.stats.grow_events} "
        f"host_syncs={pipe.stats.host_syncs}"
    )


def phase_grow_freeze(nblocks: int, b0: int, wave: int, min_elems: int, seed: int):
    """C1: ``TwoPhasePipeline`` — fused push-back + segmented flatten."""
    from repro.runtime import TwoPhasePipeline
    import jax.numpy as jnp

    waves = list(_waves(nblocks, wave, min_elems + nblocks * wave, seed))
    pipe = TwoPhasePipeline(nblocks, b0, dtype=jnp.int32)
    ref = _Reference(nblocks, b0)
    report = _grow_freeze_cycles(pipe, ref, waves[:-1], waves[-1])
    log(f"phase C grow/freeze: ok nblocks={nblocks} b0={b0} {report}")


def phase_arena_freeze(narrays: int, slab: int, wave: int, min_elems: int, seed: int):
    """C2: ``TwoPhasePipeline.from_arena`` — slab-append, paged gather,
    ``SlabArena.flatten``; the pool is pre-carved to the run's size."""
    from repro.pool import SlabArena
    from repro.runtime import TwoPhasePipeline
    import jax.numpy as jnp

    waves = list(_waves(narrays, wave, min_elems + narrays * wave, seed + 1))
    per = max(int(m.sum(axis=1).max()) for _, m in waves) * len(waves)
    pages = -(-per // slab) + 1
    arena = SlabArena(
        narrays, slab, dtype=jnp.int32,
        initial_slabs=narrays * pages, max_pages=pages,
    )
    pipe = TwoPhasePipeline.from_arena(arena)
    ref = _Reference(narrays, slab)
    report = _grow_freeze_cycles(pipe, ref, waves[:-1], waves[-1])
    arena.check_invariants()
    log(
        f"phase C arena grow/freeze: ok narrays={narrays} slab={slab} "
        f"slabs={arena.pool.n_slabs} {report}"
    )


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def preflight() -> dict:
    import jax

    for var in ("REPRO_FORCE_INTERPRET", "REPRO_MEMORY_SPACE"):
        if var in os.environ:
            raise SystemExit(f"pre-flight: {var} is set; unset it for a chip run")
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"pre-flight: backend is {backend!r}, not a TPU")
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


def enable_compile_cache() -> str:
    """Persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set (JAX
    reads it itself), else the fixed ``<repo>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    info = preflight()
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog()
    try:
        sys.path.insert(0, str(REPO / "src"))
        import jax

        from repro import configs
        from repro.models import transformer

        cfg = configs.get(ARCH)
        mark = compiles.mark()
        params = transformer.init_params(jax.random.PRNGKey(args.seed), cfg)
        jax.block_until_ready(params)
        log(f"setup params: {ARCH} {compiles.since(mark)}")
        prompts = make_prompts(args.seed, N_REQUESTS, PROMPT_LENS, cfg.vocab_size)
        log(f"prompts: lengths={[len(p) for p in prompts]} new_tokens={NEW_TOKENS}")

        mark = compiles.mark()
        logits = phase_serving(params, cfg, prompts, NEW_TOKENS, MAX_BATCH)
        log(f"setup A: {compiles.since(mark)}")
        gc.collect()  # engines hold reference cycles: free A's pool now
        mark = compiles.mark()
        phase_pallas(params, cfg, prompts, NEW_TOKENS, MAX_BATCH, logits,
                     require_compiled=True)
        log(f"setup B: {compiles.since(mark)}")
        del params
        gc.collect()  # and B's pool and the parameters, before phase C

        mark = compiles.mark()
        phase_grow_freeze(NBLOCKS, B0, WAVE, MIN_ELEMS, args.seed)
        phase_arena_freeze(NBLOCKS, SLAB, WAVE, MIN_ELEMS, args.seed)
        log(f"setup C: {compiles.since(mark)}")
        log(f"total: {compiles.since((0.0, 0, 0))} "
            f"wall_s={time.perf_counter() - t_start:.3f}")
    finally:
        compiles.close()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
