"""Batched serving engine with growth-on-demand KV caches.

The engine realizes the paper's runtime dynamics end to end: prompts are
prefetched into a cache sized for *the prompt only* (no worst-case
pre-allocation), then decode pushes tokens until capacity, at which point the
policy's growth event fires:

- ``ggarray``   → ``grow_ggarray``: allocate the next geometric bucket,
                  **no copy**; the step function recompiles once per level
                  (O(log n) total, warm-cached thereafter).
- ``semistatic``→ doubling realloc: allocate 2× and copy every live K/V byte.
- ``static``    → no growth; the engine must have pre-allocated ``max_len``
                  up front (the worst-case VRAM the paper's Fig. 3 prices).
- ``two_phase`` → the paper's §VI.D pattern as a serving policy: prefill
                  grows a ggarray cache (copy-free), then the cache is
                  **frozen** (``freeze_cache``) into the contiguous static
                  layout before decode, so every decode step attends in one
                  softmax pass instead of one per bucket level.  On capacity
                  exhaustion the engine thaws → grows a bucket → refreezes
                  (an O(n) copy, but only O(log n) times over a generation —
                  the amortized freeze the runtime's TwoPhasePipeline models).

The decode loop follows the host-sync-free protocol (DESIGN.md §2): the
jitted step **donates** the cache pytree (K/V scatters reuse the input
buffers instead of double-buffering the cache), the capacity/growth check is
pure host arithmetic against a length mirror (decode appends exactly one
slot per step), and sampled tokens are materialized once after the loop —
so a generation's device→host contacts are O(log n) growth events plus one
final token transfer, not O(steps).

``Engine.stats`` exposes alloc/copy/grow counters and byte volumes so the
benchmarks can reproduce the paper's Table II / Fig. 6 structure.

A fifth policy lives in its own engine: :class:`BatchEngine` serves the
``paged`` cache policy (the slab arena, DESIGN.md §4) with **continuous
batching** — per-request admit/evict into a fixed slot grid, one shared slab
pool for the whole fleet, slab reclamation when a sequence completes.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.obs import DeviceCounterPlane, ServingTimeline
from repro.serving import kvcache, prefix as prefix_mod, scheduler as sched_mod, steps
from repro.serving.sampler import sample

__all__ = ["Engine", "EngineStats", "BatchEngine", "BatchStats", "Request"]


# --------------------------------------------------------------------------
# Shared jit factories — keyed on the (hashable, frozen) ModelConfig, so
# every engine instance serving the same config reuses one traced executable
# instead of re-tracing per instance (``jax.jit`` caches per function object:
# a per-engine ``functools.partial`` made warm-up engines useless).  The
# jitted functions are named, so XLA calls the programs ``jit_decode_step``
# and ``jit_prefill_chunk`` (a partial would be ``jit__unknown``): profiler
# traces find them by these names.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _decode_step_fn(cfg: ModelConfig):
    def decode_step(params, token, caches, length, active=None):
        return steps.decode_step(params, token, caches, length, cfg, active)

    return jax.jit(decode_step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _prefill_chunk_fn(cfg: ModelConfig):
    def prefill_chunk(params, tokens, caches, slot, t0, live, pages_row, first=True):
        return steps.prefill_chunk(
            params, tokens, caches, slot, t0, live, pages_row, cfg, first
        )

    return jax.jit(prefill_chunk, donate_argnums=(2,), static_argnames=("first",))


class _StatsView:
    """Base for the legacy ``*Stats`` surfaces: read-only properties over an
    ``obs`` metrics registry.  The dataclass field names survive; the engine
    writes the registry, the view computes on read — one source of truth.
    """

    def __init__(self, registry):
        self._reg = registry

    def _ct(self, name: str) -> int:
        return int(self._reg.counter(name).total())

    def _hwm(self, name: str) -> int:
        return int(self._reg.gauge(name).hwm())

    def __repr__(self) -> str:
        fields = ", ".join(
            f"{n}={getattr(self, n)}"
            for n in dir(type(self))
            if isinstance(getattr(type(self), n), property)
        )
        return f"{type(self).__name__}({fields})"


class EngineStats(_StatsView):
    """Legacy Engine counters — a thin view over ``engine.obs.registry``."""

    grow_events = property(lambda s: s._ct("engine.grow_events"))
    freeze_events = property(lambda s: s._ct("engine.freeze_events"))
    copied_bytes = property(lambda s: s._ct("engine.copied_bytes"))
    allocated_bytes = property(lambda s: s._ct("engine.allocated_bytes"))
    decode_steps = property(lambda s: s._ct("engine.decode_steps"))
    compiles = property(lambda s: s._ct("engine.compiles"))


class Engine:
    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        *,
        policy: str | None = None,
        max_len: int = 4096,
        instrument: bool = False,
        seed: int = 0,
        obs: ServingTimeline | None = None,
    ):
        if instrument:
            cfg = dataclasses.replace(cfg, instrument=True)
        self.params = params
        self.cfg = cfg
        self.policy = cfg.cache_policy if policy is None else policy
        if self.policy == "paged":
            raise ValueError(
                "the paged (slab-arena) policy is served by BatchEngine, "
                "which owns the pool/page-table lifecycle"
            )
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.obs = obs if obs is not None else ServingTimeline()
        self.stats = EngineStats(self.obs.registry)
        self.devctr = DeviceCounterPlane(self.obs.registry)
        self._decode_compiled: dict[Any, Any] = {}

    def _host_read(self, x, site: str):
        """The audited device→host read: every transfer lands in one metric."""
        self.obs.registry.counter(
            "serve.host_syncs", "device→host reads, by site"
        ).inc(site=site)
        return jax.device_get(x)

    # -- capacity of the current cache (seq slots) -------------------------
    def _capacity(self, caches) -> int:
        for slot, kind in enumerate(self.cfg.layout):
            if kind == "attn":
                return kvcache.capacity_of(caches[slot])
        return 1 << 30  # attention-free: no cache capacity limit

    def _grow(self, caches) -> list:
        """Policy growth event; updates metrics with alloc/copy volumes."""
        reg = self.obs.registry
        reg.counter("engine.grow_events").inc()
        self.obs.event("grow", policy=self.policy)
        cfg = self.cfg
        out = []
        for slot, kind in enumerate(cfg.layout):
            c = caches[slot]
            if kind != "attn":
                out.append(c)
                continue
            if self.policy == "ggarray":
                grown = kvcache.grow_ggarray(c, cfg)
                reg.counter("engine.allocated_bytes").inc(
                    kvcache.cache_bytes(grown) - kvcache.cache_bytes(c)
                )
                out.append(grown)
            elif self.policy == "two_phase":
                # thaw → add a bucket (copy-free) → refreeze for flat decode.
                grown = kvcache.grow_ggarray(kvcache.thaw_cache(c, cfg.cache_b0), cfg)
                frozen = kvcache.freeze_cache(grown)
                reg.counter("engine.copied_bytes").inc(kvcache.cache_bytes(c))
                reg.counter("engine.allocated_bytes").inc(
                    kvcache.cache_bytes(frozen) - kvcache.cache_bytes(c)
                )
                reg.counter("engine.freeze_events").inc()
                out.append(frozen)
            elif self.policy == "semistatic":
                old_k, old_v = c["k"], c["v"]
                cap = old_k.shape[-3]
                new_k = jnp.zeros((*old_k.shape[:-3], cap * 2, *old_k.shape[-2:]), old_k.dtype)
                new_v = jnp.zeros_like(new_k)
                # THE copy (realloc semantics — what GGArray avoids)
                new_k = jax.lax.dynamic_update_slice_in_dim(new_k, old_k, 0, axis=old_k.ndim - 3)
                new_v = jax.lax.dynamic_update_slice_in_dim(new_v, old_v, 0, axis=old_v.ndim - 3)
                reg.counter("engine.allocated_bytes").inc(
                    kvcache.cache_bytes({"k": new_k, "v": new_v})
                )
                reg.counter("engine.copied_bytes").inc(kvcache.cache_bytes(c))
                out.append(dict(c, k=new_k, v=new_v))
            else:
                raise RuntimeError("static cache cannot grow: pre-allocate max_len")
        return out

    def _decode_fn(self, caches):
        """jit'd decode_step per cache pytree structure (growth ⇒ new entry).

        The cache argument is **donated**: the step scatters the new K/V into
        the input buffers instead of double-buffering the whole cache, and
        the engine rebinds the returned pytree each step.  One executable per
        bucket structure → O(log n) compiles over a generation.
        """
        key = jax.tree.structure((caches,))
        if key not in self._decode_compiled:
            self.obs.registry.counter("engine.compiles").inc()
            cfg = self.cfg

            @functools.partial(jax.jit, donate_argnums=(2,))
            def fn(params, token, caches, length):
                return steps.decode_step(params, token, caches, length, cfg)

            self._decode_compiled[key] = fn
        return self._decode_compiled[key]

    # -- public API --------------------------------------------------------
    def generate(
        self,
        prompts: list[list[int]],
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
    ) -> list[list[int]]:
        cfg = self.cfg
        B = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        Lp = int(lens.max())
        toks = np.zeros((B, Lp), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p

        hint = Lp if self.policy != "static" else self.max_len
        # two_phase: the grow phase is a ggarray prefill; frozen below.
        prefill_policy = "ggarray" if self.policy == "two_phase" else self.policy
        logits, caches = steps.prefill(
            self.params, jnp.asarray(toks), cfg,
            capacity_hint=hint, policy=prefill_policy, lengths=jnp.asarray(lens),
        )
        if self.policy == "two_phase":
            caches = [
                kvcache.freeze_cache(c) if kind == "attn" else c
                for c, kind in zip(caches, cfg.layout)
            ]
            self.obs.registry.counter("engine.freeze_events").inc()
        self.obs.registry.counter("engine.allocated_bytes").inc(sum(
            kvcache.cache_bytes(c) for c, k in zip(caches, cfg.layout) if k == "attn"
        ))
        lengths = jnp.asarray(lens)
        # Host mirror of the longest live context: decode appends exactly one
        # slot per step, so the growth check is pure host arithmetic — the
        # amortized protocol touches the device only at actual growth events
        # (O(log n) per generation), never per step.
        max_len_host = int(lens.max())
        out = [list(p) for p in prompts]
        self.key, k = jax.random.split(self.key)
        sampled = [sample(k, logits, temperature)]

        for _ in range(max_new_tokens - 1):
            if max_len_host + 1 >= self._capacity(caches) and self.policy != "static":
                caches = self._grow(caches)
            fn = self._decode_fn(caches)
            if cfg.instrument:
                logits, caches, ctr = fn(self.params, sampled[-1], caches, lengths)
                self.devctr.add(ctr)
            else:
                logits, caches = fn(self.params, sampled[-1], caches, lengths)
            lengths = lengths + 1
            max_len_host += 1
            self.obs.registry.counter("engine.decode_steps").inc()
            self.key, k = jax.random.split(self.key)
            sampled.append(sample(k, logits, temperature))
        # one transfer for the whole generation, after the loop dispatched
        tokens = np.asarray(self._host_read(jnp.stack(sampled), "token_drain"))  # (T, B)
        for i in range(B):
            out[i].extend(int(t) for t in tokens[:, i])
        return out


# --------------------------------------------------------------------------
# BatchEngine — continuous batching over the slab arena (policy="paged").
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One sequence in flight: prompt in, ``max_new_tokens`` greedy out."""

    rid: int
    prompt: list[int]
    max_new_tokens: int
    slot: int = -1
    admit_step: int = -1  # index into the decode stream at admission
    generated: int = 0  # tokens sampled so far (incl. the prefill sample)
    first_tok: Any = None  # device scalar — materialized once, at the end
    done: bool = False
    submit_t: float = 0.0  # host wall-clock at submit()
    queue_wait: float = 0.0  # submit → admission (seconds)
    # host dispatch times: JAX is asynchronous, so these time the enqueue of
    # the work, not the device producing the token
    first_token_dispatch: float = 0.0  # submit → first token's sample dispatched (s)
    decode_s: float = 0.0  # host time dispatching the decode steps this req was in
    decode_dispatch_ms_per_token: float = 0.0  # decode_s / (generated − 1), at completion


class BatchStats(_StatsView):
    """Legacy BatchEngine counters — a thin view over ``be.obs.registry``.

    The field names of the old dataclass survive unchanged; each is now a
    read of the metrics registry (DESIGN.md §9 catalog), so the legacy view
    and the telemetry snapshot agree by construction.  ``peak_*`` are gauge
    high-water marks; ``host_syncs`` is the total across *every* audited
    device→host read site (``serve.host_syncs{site=…}``), not just the
    stop-token drain.
    """

    admitted = property(lambda s: s._ct("serve.admitted"))
    completed = property(lambda s: s._ct("serve.completed"))
    prefills = property(lambda s: s._ct("serve.prefills"))
    prefill_chunks = property(lambda s: s._ct("serve.prefill_chunks"))
    prefill_traces = property(
        lambda s: int(s._reg.gauge("serve.prefill_traces").value())
    )
    decode_steps = property(lambda s: s._ct("serve.decode_steps"))
    pool_grow_events = property(lambda s: s._ct("pool.grow_events"))
    pool_copied_bytes = property(lambda s: s._ct("pool.copied_bytes"))
    grown_slabs = property(lambda s: s._ct("pool.grown_slabs"))
    reused_slabs = property(lambda s: s._ct("pool.reused_slabs"))
    released_slabs = property(lambda s: s._ct("pool.released_slabs"))
    prefix_hits = property(lambda s: s._ct("serve.prefix_hits"))
    prefix_tokens_reused = property(lambda s: s._ct("serve.prefix_tokens_reused"))
    cow_copies = property(lambda s: s._ct("serve.cow_copies"))
    peak_live_tokens = property(lambda s: s._hwm("pool.live_tokens"))
    peak_pool_tokens = property(lambda s: s._hwm("pool.capacity_tokens"))
    host_syncs = property(lambda s: s._ct("serve.host_syncs"))


class BatchEngine:
    """Continuous-batch serving over one shared slab pool (DESIGN.md §4).

    ``max_batch`` decode *slots* run in lockstep; requests stream through
    them: admit → prefill → batched donated decode steps (idle slots are
    inert: their page rows are −1 so appends drop, and zero lengths mask
    their attention) → completion (slabs released to the free list, slot
    re-admitted).  All per-layer caches share one page table per sequence;
    K/V pools are per scan period.

    Admission (``admission=``, see ``serving/scheduler``):

    - ``"chunked"`` (default) — the scheduler reserves the prompt's whole
      slab need up front, then streams the prompt through ``prefill_chunk``
      in bucket-padded windows *interleaved with decode steps* (vLLM-style
      chunked prefill).  Prefill compiles O(log chunk) traces total; decode
      keeps running for already-admitted sequences while new prompts fill.
      A prefilling slot is inert to the decode step: its device page row
      stays −1 (appends drop), its length is 0 (attention masked), and the
      ``active`` mask gates its Mamba state rows; its claimed pages land in
      the device table only on the final chunk.  Attention is bit-identical
      to monolithic admission (dead-lane contract, DESIGN.md §7); int8
      caches attend the *dequantized* prefix on chunks after the first, so
      multi-chunk quantized prompts are approximate (stored codes still
      match exactly).
    - ``"monolithic"`` — the original path: one eager whole-prompt prefill
      scattered into the claimed slabs at admission (compiles per prompt
      length, decode stalls for the whole prompt).

    Scheduling is **host-sync-free** by default: completion is budget
    arithmetic on host length mirrors, and every sampled token stays on
    device until ``run()`` materializes the whole stream in one transfer.
    Passing ``stop_token`` trades that for one (B,) read per step (counted
    in ``stats.host_syncs``).

    Pool sizing: the pool grows only when the free list is exhausted
    (released slabs are always reused first), by
    ``pool.planner.growth_amount(n_slabs, shortfall, grow_chunk)`` slabs.
    With the default ``grow_chunk=1`` capacity tracks demand exactly: at
    every instant ``pool_tokens ≤ live_tokens + slab_tokens ·
    active_sequences`` — the fleet-level analog of the paper's 2× bound,
    asserted in the acceptance test.  ``grow_chunk="geometric"`` doubles the
    pool instead (O(log slabs) realloc copies over a run), and a high-water
    pre-carve trades idle capacity for zero growth copies at steady state.

    ``grow_chunk="doubling"`` / ``"tz"`` select the **segmented extent
    layout** (``pool/extents``, DESIGN.md §8): the K/V pools become tuples
    of extents and growth *appends an extent* instead of realloc-copying —
    ``stats.pool_copied_bytes`` stays 0 for the whole run.  Global slab ids
    are unchanged (extent-order), so page tables, the free bitmap, and the
    allocator are identical across layouts; the attention/scatter paths
    resolve ids through the host-derived two-level (extent, offset) table.
    Growth sizing counts reserved-but-unclaimed slabs from in-flight chunked
    prefills as committed demand, so converting a reservation to claims
    cannot trigger an immediate second grow.  Each growth changes the cache
    pytree structure → one decode retrace per extent (O(log n) under
    doubling, O(√n) under tz — the same boundary-recompile pattern as
    ggarray bucket growth).

    ``prefix_cache=True`` (chunked, attention-only layouts) turns on
    **copy-on-write prefix caching** (DESIGN.md §10): completed prompts
    publish their full slabs into a host-side trie; a new request aliases
    the longest cached prefix into its page table (refcount++, zero bytes
    moved) and prefills only the uncached suffix — a fully cached prompt
    admits with zero prefill chunks and takes its first token from the
    first decode step.  Appends into a shared slab copy that one slab first
    (``serve.cow_copies``), so cached data is never mutated in place and
    outputs stay bit-exact vs cold-start.  Off by default: retained cached
    slabs intentionally outlive their sequences, which relaxes the tight
    pool-capacity bound above (LRU eviction under pool pressure bounds the
    retention instead).

    Kernel memory space follows ``cfg.kernel_memory_space``
    (``kernels/common``: hbm on TPU, vmem in interpret mode by default).
    """

    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        *,
        max_batch: int = 8,
        grow_chunk: int | str = 1,
        quota_slabs: int | None = None,
        stop_token: int | None = None,
        admission: str = "chunked",
        prefill_chunk: int | None = None,
        max_chunks_per_step: int | None = None,
        initial_slabs: int = 0,
        max_pages_hint: int = 0,
        prefix_cache: bool = False,
        instrument: bool = False,
        seed: int = 0,
        obs: ServingTimeline | None = None,
    ):
        from repro.pool import PageBook, is_extent_schedule

        if instrument:
            # baked into the (frozen, hashable) config so the shared jit
            # factories key on it: an uninstrumented engine reuses the
            # pre-PR executables byte for byte (compile-spy tested)
            cfg = dataclasses.replace(cfg, instrument=True)
        if cfg.n_enc_layers or cfg.n_prefix_embeds:
            raise NotImplementedError("BatchEngine serves decoder-only stacks")
        if admission not in ("chunked", "monolithic"):
            raise ValueError(f"unknown admission policy {admission!r}")
        if prefix_cache and admission != "chunked":
            raise ValueError("prefix_cache requires chunked admission")
        if prefix_cache and "mamba" in cfg.layout:
            # SSM state is a recurrence, not a page table: a cached prefix
            # carries no conv/SSD state to resume from, so hybrid layouts
            # must prefill every prompt token.
            raise ValueError("prefix_cache requires an attention-only layout")
        self.params = params
        self.cfg = cfg
        self.T = cfg.slab_tokens
        self.B = max_batch
        self.grow_chunk = grow_chunk
        # "doubling"/"tz" → segmented extent pools (zero-copy growth);
        # _extent_sizes mirrors the tuple structure of every pool entry.
        self._extent_mode = is_extent_schedule(grow_chunk)
        self._extent_sizes: list[int] = [0] if self._extent_mode else []
        self.stop_token = stop_token
        self.admission = admission
        self.key = jax.random.PRNGKey(seed)
        self.obs = obs if obs is not None else ServingTimeline()
        self.stats = BatchStats(self.obs.registry)
        # device counter plane (DESIGN.md §9.x): step functions hand their
        # counter vectors here; draining stays lazy (Counter.add_lazy)
        self.devctr = DeviceCounterPlane(self.obs.registry)
        # shared host bookkeeping (same object the arena uses): allocator +
        # per-slot page counts + slab→page mapping + table-width policy
        self.book = PageBook(max_batch, quota_slabs=quota_slabs)
        # device-side free-list bitmap (mirrors alloc.free; tests cross-check)
        self.free_dev = jnp.ones((0,), bool)
        self._len_host = np.zeros((max_batch,), np.int64)
        self.caches = self._init_caches()
        self.lengths = jnp.zeros((max_batch,), jnp.int32)
        self.cur_tok = jnp.zeros((max_batch,), jnp.int32)
        self._slots: list[Request | None] = [None] * max_batch
        self._pending: collections.deque[Request] = collections.deque()
        self._requests: dict[int, Request] = {}
        self._stream: list[jax.Array] = []  # sampled (B,) per decode step
        self._next_rid = 0
        self._decode = _decode_step_fn(cfg)
        self.sched: sched_mod.Scheduler | None = None
        self._trace_keys: set = set()
        if admission == "chunked":
            C = cfg.attention_chunk if prefill_chunk is None else prefill_chunk
            hybrid = "mamba" in cfg.layout
            # Bit-exactness alignment (DESIGN.md §7): chunk boundaries must
            # land on the monolithic attention grid, and on the SSD chunk
            # grid for hybrid layouts.
            if "attn" in cfg.layout and C % cfg.attention_chunk:
                raise ValueError(
                    f"prefill_chunk={C} must be a multiple of "
                    f"attention_chunk={cfg.attention_chunk}"
                )
            if hybrid and C % cfg.ssm.chunk_size:
                raise ValueError(
                    f"prefill_chunk={C} must be a multiple of "
                    f"ssm.chunk_size={cfg.ssm.chunk_size}"
                )
            self.sched = sched_mod.Scheduler(
                self.book, slab_tokens=self.T, chunk=C,
                exact_tail=hybrid, max_chunks_per_step=max_chunks_per_step,
                obs=self.obs,
            )
        # prefix cache (DESIGN.md §10): completed prompts publish their full
        # slabs into a host-side trie; admission aliases matched prefixes
        # into the new page table and prefills only the uncached suffix.
        self.prefix = (
            prefix_mod.PrefixCache(self.alloc, slab_tokens=self.T, obs=self.obs)
            if prefix_cache
            else None
        )
        self._matched: dict[int, np.ndarray] = {}  # rid → pinned slab ids
        # pre-carve: pool capacity / table width paid at init (not counted as
        # growth events — growth stats measure *demand*-driven reallocs)
        if max_pages_hint:
            self._ensure_table_width(max_pages_hint)
        if initial_slabs:
            self._grow_pool(initial_slabs, count=False)

    @property
    def alloc(self):
        return self.book.alloc

    # ---- telemetry helpers ----------------------------------------------
    def _host_read(self, x, site: str):
        """The audited device→host read: every transfer lands in one metric
        (``serve.host_syncs{site=…}``), so ``stats.host_syncs`` counts *all*
        sites — stop drains, final stream drains, debug checks — not just
        the stop-token path.
        """
        self.obs.registry.counter(
            "serve.host_syncs", "device→host reads, by site"
        ).inc(site=site)
        return jax.device_get(x)

    def _sample_live(self) -> None:
        """Refresh the pool occupancy gauges (host arithmetic only).

        Live tokens include the already-prefilled prefix of in-flight
        chunked admissions (``sched.t0``): those K/V rows occupy pool slabs
        even though the slot's published length is still 0, so the true
        high-water mark (``peak_live_tokens``) must see them.
        """
        live = self.live_tokens
        if self.sched is not None:
            live += sum(int(self.sched.t0[s]) for s in self.sched.prefilling)
        cap = self.pool_tokens
        self.obs.gauge_sample("pool.live_tokens", live)
        self.obs.gauge_sample("pool.capacity_tokens", cap)
        self.obs.gauge_sample("pool.utilization", live / cap if cap else 0.0)

    def drain_device_counters(self) -> dict[str, float]:
        """Flush + materialize the device counter plane → {slot: total}.

        This is a DRAIN POINT (one ``device_get`` per slot with pending
        adds) — call it at end of run / bench report time, never per step.
        """
        return self.devctr.counters()

    def _flightrec_state(self) -> dict:
        """Full host-side engine snapshot for postmortem bundles.

        Everything here is host bookkeeping (PageBook/allocator/scheduler
        mirrors) — building the state dict never touches the device.
        """
        alloc = self.alloc
        state: dict[str, Any] = {
            "n_slots": self.B,
            "slab_tokens": self.T,
            "admission": self.admission,
            "extent_sizes": list(self._extent_sizes),
            "len_host": self._len_host.tolist(),
            "slots": [
                None
                if r is None
                else {"rid": r.rid, "generated": r.generated,
                      "max_new_tokens": r.max_new_tokens, "done": r.done}
                for r in self._slots
            ],
            "allocator": {
                "n_slabs": alloc.n_slabs,
                "free_slabs": int(np.sum(alloc.free)),
                "free_ids": np.flatnonzero(alloc.free).tolist(),
                "refcounts": np.asarray(alloc.refcount).tolist(),
                "refcount_sum": int(np.sum(alloc.refcount)),
            },
            "page_tables": [
                [int(s) for s in self.book.pages_of[slot]]
                for slot in range(self.B)
            ],
            "reserved_total": int(self.book.reserved_total),
            "scheduler": self.sched.describe() if self.sched is not None else None,
            "prefix": (
                {"cached_slabs": [int(s) for s in self.prefix.cached_slabs()]}
                if self.prefix is not None
                else None
            ),
            "pinned": {rid: ids.tolist() for rid, ids in self._matched.items()},
        }
        return state

    def _flight_dump(self, reason: str, error: BaseException | None = None,
                     invariant: dict | None = None) -> None:
        """Dump a postmortem bundle; never raises, never dumps twice for
        the same exception (nested failure paths re-raise through step())."""
        if error is not None and getattr(error, "_flightrec_dumped", False):
            return
        try:
            state = self._flightrec_state()
            if invariant:
                state["invariant"] = dict(invariant)
            try:
                metrics = self.obs.snapshot()  # lazy-counter drain point
            except Exception:
                metrics = None
            try:
                device_counters = self.devctr.counters()
            except Exception:
                device_counters = None
            self.obs.flight.dump(
                reason=reason, error=error, state=state,
                metrics=metrics, device_counters=device_counters,
            )
        except Exception:
            return  # the recorder must not mask the original failure
        if error is not None:
            try:
                error._flightrec_dumped = True
            except Exception:
                pass

    def _note_admitted(self, req: Request, slot: int) -> None:
        req.queue_wait = time.time() - req.submit_t
        self.obs.registry.counter("serve.admitted").inc()
        self.obs.registry.histogram(
            "serve.queue_wait_ms", "submit → admission wall-clock"
        ).observe(req.queue_wait * 1e3, rid=req.rid)
        self.obs.event("admit", rid=req.rid, slot=slot)

    def _note_first_token(self, req: Request) -> None:
        """Record the first token's host dispatch time exactly once; the
        histogram sample and the timeline event carry the same float, so the
        acceptance test reconciles them by equality, not tolerance.  The
        token is not ready yet (JAX is asynchronous): this is no TTFT."""
        req.first_token_dispatch = time.time() - req.submit_t
        ms = req.first_token_dispatch * 1e3
        self.obs.registry.histogram(
            "serve.first_token_dispatch_ms",
            "submit → first token's sample dispatched (host dispatch)",
        ).observe(ms, rid=req.rid)
        self.obs.event("first_token", rid=req.rid, first_token_dispatch_ms=ms)

    # ---- cache construction ---------------------------------------------
    def _init_caches(self) -> list:
        cfg = self.cfg
        P = cfg.n_periods
        dt = jnp.dtype(cfg.dtype)
        kh, dh = cfg.n_kv_heads, cfg.head_dim
        caches = []
        for kind in cfg.layout:
            if kind == "mamba":
                from repro.models import ssm as ssm_mod

                st = ssm_mod.init_mamba_state(cfg, self.B, dt)
                caches.append(
                    {
                        "conv": jnp.zeros((P, *st.conv.shape), dt),
                        "ssd": jnp.zeros((P, *st.ssd.shape), jnp.float32),
                    }
                )
                continue
            kv_dt = jnp.int8 if cfg.cache_quant else dt  # int8 codes + scales
            c = {
                "k_pool": jnp.zeros((P, 0, self.T, kh, dh), kv_dt),
                "v_pool": jnp.zeros((P, 0, self.T, kh, dh), kv_dt),
                "pages": jnp.full((P, self.B, self.book.max_pages), -1, jnp.int32),
            }
            if cfg.cache_quant:
                c["ks_pool"] = jnp.zeros((P, 0, self.T, kh), jnp.bfloat16)
                c["vs_pool"] = jnp.zeros((P, 0, self.T, kh), jnp.bfloat16)
            if self._extent_mode:  # tuple-of-extents layout (one empty seed)
                for key in ("k_pool", "v_pool", "ks_pool", "vs_pool"):
                    if key in c:
                        c[key] = (c[key],)
            caches.append(c)
        return caches

    def _attn_slots(self):
        return [i for i, kind in enumerate(self.cfg.layout) if kind == "attn"]

    # ---- pool / page-table management -----------------------------------
    def _grow_pool(self, extra: int, *, count: bool = True) -> None:
        """Add ≥ ``extra`` slabs of pool capacity.

        Flat layout: realloc — widen every pool array by ``extra`` slabs and
        **copy** the live bytes (counted in ``stats.pool_copied_bytes``).
        Extent layout: append fresh extent(s) per the schedule's plan —
        existing extents keep their device buffers, zero bytes copied.
        ``count=False`` (init pre-carve) skips the growth-event counters:
        growth stats measure demand-driven reallocs, not paid-up-front
        capacity.
        """
        if self._extent_mode:
            from repro.pool import plan_extents

            self._append_extents(
                plan_extents(tuple(self._extent_sizes), extra, self.grow_chunk),
                count=count,
            )
            return

        def widen(pool):
            self.obs.registry.counter("pool.copied_bytes").inc(
                pool.size * pool.dtype.itemsize
            )
            pad = jnp.zeros((pool.shape[0], extra, *pool.shape[2:]), pool.dtype)
            return jnp.concatenate([pool, pad], axis=1)

        for i in self._attn_slots():
            c = self.caches[i]
            for key in ("k_pool", "v_pool", "ks_pool", "vs_pool"):
                if key in c:
                    c[key] = widen(c[key])
        self._finish_grow(extra, count=count)

    def _append_extents(self, sizes: list[int], *, count: bool = True) -> None:
        """Zero-copy growth: append fresh extents to every pool tuple."""
        sizes = [s for s in sizes if s > 0]
        if not sizes:
            return
        # a zero-size seed extent holds no slab ids — drop it once real
        # extents exist so kernels never carry dead operands
        keep = [j for j, s in enumerate(self._extent_sizes) if s > 0]
        for i in self._attn_slots():
            c = self.caches[i]
            for key in ("k_pool", "v_pool", "ks_pool", "vs_pool"):
                if key not in c:
                    continue
                exts = list(c[key])
                proto = exts[0]
                exts = [exts[j] for j in keep] if keep else []
                for s in sizes:
                    exts.append(
                        jnp.zeros(
                            (proto.shape[0], s, *proto.shape[2:]), proto.dtype
                        )
                    )
                c[key] = tuple(exts)
        self._extent_sizes = [self._extent_sizes[j] for j in keep] + sizes
        self._finish_grow(sum(sizes), count=count)

    def _finish_grow(self, extra: int, *, count: bool = True) -> None:
        self.book.grow(extra)
        self.free_dev = jnp.concatenate([self.free_dev, jnp.ones((extra,), bool)])
        if count:
            self.obs.registry.counter("pool.grow_events").inc()
            self.obs.registry.counter("pool.grown_slabs").inc(extra)
            self.obs.event("pool_grow", slabs=extra, n_slabs=self.alloc.n_slabs)
        self._sample_live()

    def _grow_for(self, short: int) -> None:
        """Cover a free-list shortfall, sized by the growth schedule.

        Reserved-but-unclaimed slabs from in-flight chunked prefills count
        as committed demand (``reserved=``): a grow sized off the free list
        alone could be exhausted again by the claims that convert those
        reservations within the same scheduler step.

        Pool pressure is the prefix cache's eviction signal: before paying
        for new capacity, LRU cached slabs nobody aliases are released back
        to the free list, and only the remaining shortfall is grown.
        """
        from repro.pool import growth_amount, plan_extents

        if self.prefix is not None:
            freed = self.prefix.evict(short)
            if len(freed):
                self.free_dev = self.free_dev.at[jnp.asarray(freed)].set(True)
                self.obs.registry.counter("pool.released_slabs").inc(len(freed))
                short -= len(freed)
                if short <= 0:
                    self._sample_live()
                    return
        reserved = self.book.reserved_total
        if self._extent_mode:
            self._append_extents(
                plan_extents(
                    tuple(self._extent_sizes), short, self.grow_chunk,
                    reserved=reserved,
                )
            )
            return
        self._grow_pool(
            growth_amount(
                self.alloc.n_slabs, short, self.grow_chunk, reserved=reserved
            )
        )

    def _ensure_table_width(self, need: int) -> None:
        widened = self.book.widen(need)
        if widened is None:
            return
        old, new = widened
        for i in self._attn_slots():
            c = self.caches[i]
            pad = jnp.full((c["pages"].shape[0], self.B, new - old), -1, jnp.int32)
            c["pages"] = jnp.concatenate([c["pages"], pad], axis=-1)

    def _claim(self, slot: int, k: int) -> np.ndarray:
        """Claim ``k`` slabs for decode slot ``slot`` (reuse-first)."""
        if k == 0:
            return np.zeros((0,), np.int32)
        self._ensure_table_width(int(self.book.npages[slot]) + k)
        short = self.book.shortfall(k)
        if short:
            self._grow_for(short)
        before_reuse = self.alloc.reuse_claims
        ids, page0 = self.book.claim(slot, k)
        self.obs.registry.counter("pool.reused_slabs").inc(
            self.alloc.reuse_claims - before_reuse
        )
        cols = jnp.arange(page0, page0 + k)
        dev_ids = jnp.asarray(ids)
        for i in self._attn_slots():
            c = self.caches[i]
            c["pages"] = c["pages"].at[:, slot, cols].set(dev_ids)
        self.free_dev = self.free_dev.at[dev_ids].set(False)
        return ids

    def _release(self, slot: int) -> None:
        ids = self.book.release(slot)
        if len(ids):
            self.free_dev = self.free_dev.at[jnp.asarray(ids)].set(True)
        for i in self._attn_slots():
            c = self.caches[i]
            c["pages"] = c["pages"].at[:, slot, :].set(-1)
        self._len_host[slot] = 0
        self.lengths = self.lengths.at[slot].set(0)
        self.obs.registry.counter("pool.released_slabs").inc(len(ids))
        self._sample_live()

    @property
    def pool_tokens(self) -> int:
        return self.alloc.n_slabs * self.T

    @property
    def live_tokens(self) -> int:
        return int(self._len_host.sum())

    def utilization(self) -> float:
        return self.live_tokens / self.pool_tokens if self.pool_tokens else 0.0

    # ---- request lifecycle ----------------------------------------------
    def submit(self, prompt: list[int], max_new_tokens: int) -> int:
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid=rid, prompt=list(prompt), max_new_tokens=max_new_tokens,
            submit_t=time.time(),
        )
        self._requests[rid] = req
        self.obs.registry.counter("serve.submitted").inc()
        self.obs.event("submit", rid=rid, prompt_len=len(req.prompt))
        if self.sched is not None:
            self.sched.submit(rid, len(req.prompt))
        else:
            self._pending.append(req)
        return rid

    def _admit(self, req: Request, slot: int) -> None:
        cfg = self.cfg
        Lp = len(req.prompt)
        self._note_admitted(req, slot)
        self._claim(slot, max(-(-Lp // self.T), 1))
        with self.obs.span("prefill", rid=req.rid, tokens=Lp):
            toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None, :])
            logits, pcaches = steps.prefill(
                self.params, toks, cfg, capacity_hint=Lp, policy="static"
            )
        self.obs.registry.counter("serve.prefills").inc()
        for i, kind in enumerate(cfg.layout):
            if kind == "mamba":
                for key in ("conv", "ssd"):
                    val = pcaches[i][key][:, 0]
                    want = self.caches[i][key].shape[2]
                    if key == "conv" and val.shape[1] < want:
                        # prompt shorter than the conv window: the missing
                        # history is zeros, oldest-first (left pad)
                        val = jnp.pad(
                            val, ((0, 0), (want - val.shape[1], 0), (0, 0))
                        )
                    self.caches[i][key] = (
                        self.caches[i][key].at[:, slot].set(val)
                    )
                continue
            self._fill_slot_pages(i, slot, pcaches[i], Lp)
        self.lengths = self.lengths.at[slot].set(Lp)
        self._len_host[slot] = Lp
        self._sample_live()
        self.key, k = jax.random.split(self.key)
        first = sample(k, logits, 0.0)[0]
        req.first_tok = first
        self._note_first_token(req)
        self.cur_tok = self.cur_tok.at[slot].set(first)
        req.slot = slot
        req.admit_step = len(self._stream)
        req.generated = 1
        self._slots[slot] = req
        if req.generated >= req.max_new_tokens:
            self._complete(req)

    def _set_slabs(self, pool, ids: np.ndarray, vals: jax.Array):
        """``pool.at[:, ids].set(vals)`` across the flat or extent layout.

        ``ids`` are *host* slab ids, so extent routing is pure host
        arithmetic — one sliced scatter per extent that owns any of them.
        """
        if not self._extent_mode:
            return pool.at[:, jnp.asarray(ids, jnp.int32)].set(vals)
        exts = list(pool)
        base = 0
        for e, size in enumerate(self._extent_sizes):
            sel = np.flatnonzero((ids >= base) & (ids < base + size))
            if len(sel):
                local = jnp.asarray(ids[sel] - base, jnp.int32)
                exts[e] = exts[e].at[:, local].set(vals[:, sel])
            base += size
        return tuple(exts)

    def _fill_slot_pages(self, i: int, slot: int, pcache: dict, Lp: int) -> None:
        """Scatter a (P, 1, Lp, …) static prefill cache into claimed slabs."""
        c = self.caches[i]
        npages = int(self.book.npages[slot])
        ids = self.book.pages_in_order(slot)

        def paged(x):  # (P, Lp, …) → (P, npages, T, …)
            pad = npages * self.T - x.shape[1]
            widths = [(0, 0)] * x.ndim
            widths[1] = (0, pad)
            x = jnp.pad(x, widths)
            return x.reshape(x.shape[0], npages, self.T, *x.shape[2:])

        c["k_pool"] = self._set_slabs(c["k_pool"], ids, paged(pcache["k"][:, 0]))
        c["v_pool"] = self._set_slabs(c["v_pool"], ids, paged(pcache["v"][:, 0]))
        if "ks_pool" in c:
            c["ks_pool"] = self._set_slabs(
                c["ks_pool"], ids, paged(pcache["ks"][:, 0])
            )
            c["vs_pool"] = self._set_slabs(
                c["vs_pool"], ids, paged(pcache["vs"][:, 0])
            )

    def _complete(self, req: Request) -> None:
        req.done = True
        if self.prefix is not None:
            # publish the full prompt slabs into the trie *before* release:
            # the trie's addref keeps them alive when the tenant's page
            # references drop, so a reclaim becomes a cache fill
            self.prefix.publish(req.prompt, self.book.pages_of[req.slot])
        self._release(req.slot)
        if self.sched is not None:
            self.sched.complete(req.slot)
        self._slots[req.slot] = None
        self.obs.registry.counter("serve.completed").inc()
        if req.generated > 1:
            req.decode_dispatch_ms_per_token = req.decode_s / (req.generated - 1) * 1e3
            self.obs.registry.histogram(
                "serve.decode_dispatch_ms_per_token",
                "mean host dispatch time of the decode steps per output token",
            ).observe(req.decode_dispatch_ms_per_token, rid=req.rid)
        self.obs.event("complete", rid=req.rid, generated=req.generated)

    # ---- chunked admission ----------------------------------------------
    def _ensure_free_slabs(self, short: int) -> bool:
        """Scheduler grow hook: the engine always covers a reservation."""
        self._grow_for(short)
        return True

    def _run_chunk(self, task) -> None:
        """Execute one scheduler ChunkTask: claim → prefill_chunk → advance."""
        req = self._requests[task.rid]
        slot = task.slot
        if task.new_slabs:
            before = self.alloc.reuse_claims
            ids, _ = self.book.claim(slot, task.new_slabs, from_reservation=True)
            self.obs.registry.counter("pool.reused_slabs").inc(
                self.alloc.reuse_claims - before
            )
            self.free_dev = self.free_dev.at[jnp.asarray(ids)].set(False)
        row = np.full((self.book.max_pages,), -1, np.int32)
        order = self.book.pages_in_order(slot)
        row[: len(order)] = order
        toks = np.zeros((1, task.width), np.int32)
        toks[0, : task.live] = req.prompt[task.t0 : task.t0 + task.live]
        first = task.t0 == 0
        key = (task.width, first, self.alloc.n_slabs, self.book.max_pages)
        if key not in self._trace_keys:
            self._trace_keys.add(key)
            self.obs.registry.gauge(
                "serve.prefill_traces", "distinct prefill-chunk trace keys"
            ).set(len(self._trace_keys))
        with self.obs.span(
            "prefill_chunk", rid=task.rid, t0=task.t0, width=task.width
        ):
            outs = _prefill_chunk_fn(self.cfg)(
                self.params, jnp.asarray(toks), self.caches,
                jnp.asarray(slot, jnp.int32), jnp.asarray(task.t0, jnp.int32),
                jnp.asarray(task.live, jnp.int32), jnp.asarray(row), first=first,
            )
            if self.cfg.instrument:
                logits, self.caches, ctr = outs
                self.devctr.add(ctr)
            else:
                logits, self.caches = outs
        self.obs.registry.counter("serve.prefill_chunks").inc()
        self.sched.chunk_done(task)
        self._sample_live()
        if task.final:
            self._finish_prefill(req, slot, logits)

    def _finish_prefill(self, req: Request, slot: int, logits) -> None:
        """Final chunk done: publish pages to the device table, arm decode."""
        npages = int(self.book.npages[slot])
        ids = jnp.asarray(self.book.pages_in_order(slot), jnp.int32)
        cols = jnp.arange(npages)
        for i in self._attn_slots():
            c = self.caches[i]
            c["pages"] = c["pages"].at[:, slot, cols].set(ids)
        Lp = len(req.prompt)
        self.lengths = self.lengths.at[slot].set(Lp)
        self._len_host[slot] = Lp
        self.obs.registry.counter("serve.prefills").inc()
        self._sample_live()
        self.key, k = jax.random.split(self.key)
        first = sample(k, logits, 0.0)[0]
        req.first_tok = first
        self._note_first_token(req)
        self.cur_tok = self.cur_tok.at[slot].set(first)
        req.admit_step = len(self._stream)
        req.generated = 1
        if req.generated >= req.max_new_tokens:
            self._complete(req)

    # ---- prefix caching (DESIGN.md §10) ----------------------------------
    def _match_prefix(self, rid: int, length: int) -> int:
        """Scheduler ``match`` hook: longest cached prefix → tokens cached.

        The matched slabs are **pinned** (one ``addref`` each) before the
        scheduler's ``ensure`` hook can run — growth may evict LRU cached
        slabs, and a pinned slab (refcount ≥ 2) is never evictable.  Pins
        transfer into the page table at admission (``book.adopt``) or are
        dropped when the request doesn't admit this round.
        """
        if self.prefix is None:
            return 0
        blocks, ids = self.prefix.match(self._requests[rid].prompt)
        if not blocks:
            return 0
        self.alloc.addref(ids)
        self._matched[rid] = ids
        return blocks * self.T

    def _drop_pins(self) -> None:
        for ids in self._matched.values():
            self.alloc.release(ids)
        self._matched.clear()

    def _adopt_prefix(self, req: Request, slot: int, need: int) -> None:
        """Transfer the pinned match into the slot's page table."""
        ids = self._matched.pop(req.rid)
        cached = len(ids) * self.T
        self._ensure_table_width(len(ids) + need)
        self.book.adopt(slot, ids)
        self.obs.registry.counter(
            "serve.prefix_hits", "admissions that reused cached prefix slabs"
        ).inc()
        self.obs.registry.counter(
            "serve.prefix_tokens_reused", "prompt tokens served from cache"
        ).inc(cached)
        self.obs.event(
            "prefix_hit", rid=req.rid, tokens=cached, blocks=len(ids),
            full=cached >= len(req.prompt),
        )

    def _arm_full_hit(self, req: Request, slot: int) -> None:
        """Fully cached prompt: zero prefill chunks.  Publish the aliased
        pages to the device table and arm decode on the *last* prompt token
        (its K/V rewrite COWs the tail slab) — the request's first token
        comes from the first decode step, where its dispatch time is recorded.
        """
        Lp = len(req.prompt)
        npages = int(self.book.npages[slot])
        ids = jnp.asarray(self.book.pages_in_order(slot), jnp.int32)
        cols = jnp.arange(npages)
        for i in self._attn_slots():
            c = self.caches[i]
            c["pages"] = c["pages"].at[:, slot, cols].set(ids)
        self.lengths = self.lengths.at[slot].set(Lp - 1)
        self._len_host[slot] = Lp - 1
        self.cur_tok = self.cur_tok.at[slot].set(req.prompt[-1])
        req.generated = 0  # first sample arrives from the first decode step
        self._sample_live()

    def _cow_if_shared(self, slot: int, page: int) -> None:
        """Copy-on-write guard: make ``slot``'s slab at ``page`` private.

        A shared slab (refcount > 1) about to be appended into is first
        copied — one slab's bytes — into a fresh claim; the page table
        repoints and one reference on the original drops.  The cached
        original is never mutated in place, so every other alias (and the
        trie) keeps bit-identical data.
        """
        old = int(self.book.pages_of[slot][page])
        if int(self.alloc.refcount[old]) <= 1:
            return
        short = self.book.shortfall(1)
        if short:
            self._grow_for(short)
        before = self.alloc.reuse_claims
        new = int(self.alloc.claim(slot, 1)[0])
        self.obs.registry.counter("pool.reused_slabs").inc(
            self.alloc.reuse_claims - before
        )
        self.book.replace(slot, page, new)
        self.alloc.release(np.asarray([old], np.int32), tenant=slot)
        publish = self.sched is None or self.sched.phase[slot] == "decode"
        for i in self._attn_slots():
            c = self.caches[i]
            for key in ("k_pool", "v_pool", "ks_pool", "vs_pool"):
                if key in c:
                    c[key] = kvcache.copy_slab(c[key], old, new, axis=1)
            if publish:  # prefill rows stay −1 until the final chunk
                c["pages"] = c["pages"].at[:, slot, page].set(new)
        self.free_dev = self.free_dev.at[new].set(False)
        self.obs.registry.counter(
            "serve.cow_copies", "shared slabs privately copied before append"
        ).inc()
        self.obs.event("cow_copy", slot=slot, page=page, src=old, dst=new)

    # ---- the decode loop -------------------------------------------------
    def _admit_pending(self) -> None:
        if self.sched is not None:
            try:
                admits = self.sched.admit(
                    self._ensure_free_slabs,
                    match=self._match_prefix if self.prefix is not None else None,
                )
            except BaseException as e:
                self._drop_pins()
                from repro.pool import QuotaExceeded

                if isinstance(e, QuotaExceeded):
                    self._flight_dump("quota_exceeded", e)
                raise
            for rid, slot, need in admits:
                req = self._requests[rid]
                req.slot = slot
                self._slots[slot] = req
                if rid in self._matched:
                    self._adopt_prefix(req, slot, need)
                else:
                    self._ensure_table_width(need)
                self._note_admitted(req, slot)
                if self.sched.phase[slot] == "decode":  # fully cached prompt
                    self._arm_full_hit(req, slot)
            self._drop_pins()  # matched but not admitted this round
            return
        for slot in range(self.B):
            if not self._pending:
                return
            if self._slots[slot] is None:
                self._admit(self._pending.popleft(), slot)

    def step(self) -> bool:
        """Admit, run prefill chunks, one batched decode step (interleaved).

        → False when nothing is active.  Chunked admission runs up to
        ``max_chunks_per_step`` prefill chunks *and then* decodes the slots
        already in the decode phase — admitted sequences keep generating
        while new prompts stream in.

        Any failure inside the step dumps a flight-recorder postmortem
        bundle (event ring + engine state + drained counters) before the
        exception propagates — DESIGN.md §9.y.

        The step is the root span ``engine.step`` (``step``: the index its
        decode step would take in the stream).  Its children cover the host
        work between dispatches: ``engine.admit`` (admission and chunk
        planning), ``prefill_chunk``, ``engine.grow`` (claims and pool
        growth for the slots about to overflow), ``engine.cow`` (the
        copy-on-write guard), ``decode_step`` and ``engine.finish``
        (everything after the decode dispatch).
        """
        try:
            with self.obs.span("engine.step", step=len(self._stream)):
                return self._step_inner()
        except BaseException as e:
            self._flight_dump("step_failure", e)
            raise

    def _step_inner(self) -> bool:
        with self.obs.span("engine.admit"):
            self._admit_pending()
            tasks = self.sched.next_chunks() if self.sched is not None else []
        for task in tasks:
            self._run_chunk(task)
        if self.sched is not None:
            active = [
                r for r in self._slots
                if r is not None and self.sched.phase[r.slot] == "decode"
            ]
        else:
            active = [r for r in self._slots if r is not None]
        if not active:
            return bool(tasks)
        # capacity: claim the next slab before overflow.  The shortfall is
        # sized over the whole batch first so one growth event covers every
        # needy slot this step (per-slot grows would fire once per sequence
        # under synchronized overflow — the double-grow the tests assert
        # against).
        with self.obs.span("engine.grow"):
            needy = [
                r.slot
                for r in active
                if self._len_host[r.slot] + 1 > self.book.npages[r.slot] * self.T
            ]
            if needy:
                short = self.book.shortfall(len(needy))
                if short:
                    self._grow_for(short)
                for slot in needy:
                    self._claim(slot, 1)
        # copy-on-write guard: the slab each slot is about to append into
        # must be private (a full-hit admission decodes its last prompt
        # token into the shared tail slab — copy it first, never mutate)
        with self.obs.span("engine.cow"):
            for req in active:
                pos = int(self._len_host[req.slot])
                if pos // self.T < int(self.book.npages[req.slot]):
                    self._cow_if_shared(req.slot, pos // self.T)
        if self.sched is not None and self.sched.prefilling:
            act = np.zeros((self.B,), bool)
            act[[r.slot for r in active]] = True
            active_mask = jnp.asarray(act)
        else:
            active_mask = None
        step_t0 = time.perf_counter()
        with self.obs.span(
            "decode_step", step=len(self._stream), active=len(active)
        ):
            if self.cfg.instrument:
                logits, self.caches, ctr = self._decode(
                    self.params, self.cur_tok, self.caches, self.lengths,
                    active=active_mask,
                )
                self.devctr.add(ctr)  # a list append — no transfer
            else:
                logits, self.caches = self._decode(
                    self.params, self.cur_tok, self.caches, self.lengths,
                    active=active_mask,
                )
            self.key, k = jax.random.split(self.key)
            sampled = sample(k, logits, 0.0)
        step_dt = time.perf_counter() - step_t0
        with self.obs.span("engine.finish"):
            self._stream.append(sampled)
            self.cur_tok = sampled
            mask = np.zeros((self.B,), np.int32)
            for req in active:
                mask[req.slot] = 1
            self.lengths = self.lengths + jnp.asarray(mask)
            self._len_host += mask
            self.obs.registry.counter("serve.decode_steps").inc()
            self._sample_live()
            stops = None
            if self.stop_token is not None:
                # one (B,) read per step — the price of stop-token scheduling
                stops = np.asarray(self._host_read(sampled, "stop_drain"))
            for req in active:
                first_decode = req.generated == 0  # full-hit: first token is here
                req.generated += 1
                req.decode_s += step_dt
                if first_decode:
                    req.first_tok = sampled[req.slot]
                    req.admit_step = len(self._stream)
                    self._note_first_token(req)
                hit_stop = stops is not None and stops[req.slot] == self.stop_token
                if req.generated >= req.max_new_tokens or hit_stop:
                    self._complete(req)
        return True

    def _has_work(self) -> bool:
        if any(r is not None for r in self._slots):
            return True
        if self.sched is not None:
            return self.sched.busy
        return bool(self._pending)

    def run(self) -> dict[int, list[int]]:
        """Drain every submitted request → {rid: prompt + generated tokens}.

        One device→host transfer materializes the whole token stream after
        the loop (plus one for the per-request prefill samples).
        """
        while self._has_work():
            self.step()
        rids = sorted(self._requests)
        firsts = {}
        if rids:
            stack = jnp.stack([self._requests[r].first_tok for r in rids])
            vals = np.asarray(self._host_read(stack, "first_token_drain"))
            firsts = {r: int(v) for r, v in zip(rids, vals)}
        stream = (
            np.asarray(self._host_read(jnp.stack(self._stream), "stream_drain"))
            if self._stream
            else np.zeros((0, self.B), np.int32)
        )
        out = {}
        for rid in rids:
            req = self._requests[rid]
            toks = [firsts[rid]]
            lo = req.admit_step
            toks.extend(
                int(t) for t in stream[lo : lo + req.generated - 1, req.slot]
            )
            out[rid] = list(req.prompt) + toks
        return out

    def run_all(self, prompts: list[list[int]], max_new_tokens: int) -> list[list[int]]:
        """Submit + drain in one call → outputs in prompt order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        out = self.run()
        return [out[r] for r in rids]

    # ---- verification (test/debug only: reads the device) ----------------
    def check_free_list(self) -> None:
        """Device bitmap ⇔ host allocator ⇔ page-table ⇔ refcount audit.

        Refcount conservation (DESIGN.md §10): every reference on a claimed
        slab is exactly one page-table entry, one prefix-cache node, or one
        in-flight admission pin — Σ references == ``alloc.refcount`` per
        slab, and a slab is live iff someone references it.

        A violation dumps a flight-recorder postmortem bundle naming the
        offending slab ids before the assertion propagates.
        """
        try:
            self._check_free_list_inner()
        except AssertionError as e:
            self._flight_dump("engine_invariant", e)  # no-op if already dumped
            raise

    def _check_free_list_inner(self) -> None:
        free = np.asarray(self._host_read(self.free_dev, "free_list_debug"))
        if not (free == self.alloc.free).all():
            bad = np.flatnonzero(free != self.alloc.free)
            err = AssertionError(f"device free bitmap drifted: slabs {bad}")
            self._flight_dump(
                "free_bitmap_drift", err,
                invariant={"check": "free_bitmap", "offending_slabs": bad.tolist()},
            )
            raise err
        self.alloc.check()
        refs = np.zeros((self.alloc.n_slabs,), np.int64)
        for slot in range(self.B):
            for s in self.book.pages_of[slot]:
                refs[s] += 1
        if self.prefix is not None:
            for s in self.prefix.cached_slabs():
                refs[s] += 1
        for ids in self._matched.values():
            for s in ids:
                refs[s] += 1
        bad = np.flatnonzero(refs != self.alloc.refcount)
        if len(bad):
            err = AssertionError(
                f"refcounts drift from page tables + prefix cache: {bad}"
            )
            self._flight_dump(
                "refcount_mismatch", err,
                invariant={
                    "check": "refcount_conservation",
                    "offending_slabs": bad.tolist(),
                    "expected_refcount": refs[bad].tolist(),
                    "actual_refcount": np.asarray(
                        self.alloc.refcount
                    )[bad].tolist(),
                },
            )
            raise err
        bad = np.flatnonzero((refs > 0) == self.alloc.free)
        if len(bad):
            err = AssertionError(
                "slab freed while referenced (or live without references): "
                f"{bad}"
            )
            self._flight_dump(
                "liveness_drift", err,
                invariant={"check": "liveness", "offending_slabs": bad.tolist()},
            )
            raise err
        for i in self._attn_slots():
            pages = np.asarray(
                self._host_read(self.caches[i]["pages"], "free_list_debug")
            )[0]
            claimed = pages[pages >= 0]
            assert not free[claimed].any() if len(claimed) else True
            for slot in range(self.B):
                npg = int(self.book.npages[slot])
                row = pages[slot]
                if self.sched is not None and self.sched.phase[slot] == "prefill":
                    # chunked prefills hold claimed slabs the device table
                    # doesn't list yet (published on the final chunk)
                    assert (row == -1).all(), f"slot {slot}: published early"
                else:
                    want = np.asarray(self.book.pages_of[slot], np.int64)
                    assert (row[:npg] == want).all(), f"slot {slot}: row drift"
                    assert (row[npg:] == -1).all(), f"slot {slot}: stray pages"
