"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix (``bench/traffic/<name>.json``).
The run:

1. refuses to start unless JAX's backend is a TPU with as many chips as the
   cell asks for (and no interpret or memory-space override is set);
2. draws the weights from ``--seed`` on the device, builds the
   ``BatchEngine`` the configuration states, and serves a warm set that
   compiles every shape the mix will use — set-up ends when the window
   opens, after ``ramp_s`` of the mix's own traffic;
3. serves the mix for ``--seconds``; a watcher thread stamps each token when
   it is ready on the device;
4. with ``--trace 1``, traces the window with the profiler and reports the
   cell's per-layer metrics; with ``--trace 0``, its end-to-end metrics;
5. once the window has closed and the engine is freed, compares a sample of
   served requests with the plain float32 reference (``harness/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``: each number compared, with its limit.  The
same numbers end standard error.  JAX's persistent compilation cache lives
in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import cells, check, drive, traffic, tracing  # noqa: E402
from harness.record import Record  # noqa: E402

TRACE_LEAD_S = 2.0  # the profiler starts this long before the window opens
WARM_INDEX = 1 << 40  # prompt ids of the warm set come from indices past any request


class Refused(SystemExit):
    """The run cannot be made here; nothing is printed to standard output."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", action="append", default=[],
                    help="extra directory searched for BENCHMARK.json, configs/, "
                         "traffic/ and metrics/ before bench/")
    return ap.parse_args(argv)


# --------------------------------------------------------------------------
# platform
# --------------------------------------------------------------------------

def preflight(chips: int, allow_cpu: bool) -> tuple[dict, dict | None]:
    """→ (device info, peaks of this device kind).  Refuses anything but a
    TPU with ``chips`` chips; ``allow_cpu`` (tests only) admits the CPU."""
    if not allow_cpu:
        for var in ("REPRO_FORCE_INTERPRET", "REPRO_MEMORY_SPACE"):
            if var in os.environ:
                raise Refused(f"refused: {var} is set; unset it for a chip run")
    import jax

    backend = jax.default_backend()
    devs = jax.devices()
    if backend != "tpu" and not allow_cpu:
        raise Refused(f"refused: JAX's backend is {backend!r}, not a TPU")
    if len(devs) < chips:
        raise Refused(f"refused: {len(devs)} device(s), the cell needs {chips}")
    kind = devs[0].device_kind
    info = {"platform": devs[0].platform, "kind": kind, "count": len(devs)}
    return info, peaks(kind, backend)


def peaks(kind: str, backend: str) -> dict | None:
    """bench/peaks.json's entry for ``kind``; a TPU kind not in the table is
    refused, never given a default.  Other backends have none."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind in table:
        return table[kind]
    if backend == "tpu":
        raise Refused(f"refused: no peaks for device kind {kind!r} in bench/peaks.json")
    return None


def enable_compile_cache() -> str:
    import jax

    path = str(cells.CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """When JAX compiled a program or loaded one from the persistent cache."""

    def __init__(self):
        import jax.monitoring

        self.times: list[float] = []
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._duration)
        self._mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.times.append(time.perf_counter())

    def count(self, start: float, end: float) -> int:
        return sum(1 for t in self.times if start <= t < end)

    def close(self):
        self._mon.unregister_event_duration_listener(self._duration)
        self._mon.unregister_event_listener(self._event)


# --------------------------------------------------------------------------
# model and engine
# --------------------------------------------------------------------------

HF_FIELDS = {  # config.json key → ModelConfig field
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(conf: dict):
    """The program's ``ModelConfig`` for the configuration file: the registry
    entry with every size the file states, in the file's dtype."""
    from repro import configs

    if conf.get("hidden_act") != "silu":
        raise SystemExit(f"configuration: hidden_act {conf.get('hidden_act')!r} is not served")
    fields = {f: conf[k] for k, f in HF_FIELDS.items()}
    fields.update(dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"], d_head=None)
    fields.update(conf.get("program_options", {}))
    return dataclasses.replace(configs.get(conf["registry"]), **fields)


def name_step_programs(cfg) -> None:
    """Give the engine's two step programs their names in the trace.

    They are jitted ``functools.partial`` objects, which XLA would name
    ``jit__unknown``; the name is all this sets (the computation and its
    compiled code are unchanged), and a program that names them itself is
    left as it is."""
    from repro.serving import engine as engine_mod

    for jitted, name in ((engine_mod._decode_step_fn(cfg), "decode_step"),
                         (engine_mod._prefill_chunk_fn(cfg), "prefill_chunk")):
        inner = getattr(jitted, "__wrapped__", None)
        if isinstance(inner, functools.partial) and not hasattr(inner, "__name__"):
            inner.__name__ = name


def build_engine(params, cfg, conf: dict):
    from repro.serving.engine import BatchEngine

    name_step_programs(cfg)
    e = conf["engine"]
    pages = -(-int(e["max_context"]) // cfg.slab_tokens)
    return BatchEngine(
        params, cfg,
        max_batch=int(e["max_batch"]),
        initial_slabs=int(e["pool_slabs"]),
        max_pages_hint=pages,
        quota_slabs=pages,
    )


def warm(eng, mix, prompt_max: int, output_max: int) -> None:
    """Serve a fixed warm set that compiles every shape the mix can use:
    prompts of every slab count up to the longest (both chunk kinds), and
    sequences that end on every slab count, some by crossing a slab
    boundary while decoding."""
    T = eng.T
    out = min(16, output_max)
    lens = {prompt_max}
    for k in range(1, -(-prompt_max // T) + 1):
        lens.add(min((k - 1) * T + 64, prompt_max))
        lens.add(min(k * T - out // 2, prompt_max))
    for i, n in enumerate(sorted(lens)):
        eng.submit(mix.prompt_ids(WARM_INDEX + i, n), out)
    while eng._has_work():
        eng.step()
    import jax

    jax.block_until_ready((eng.caches, eng._stream[-1] if eng._stream else None))


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def run(args, *, allow_cpu: bool = False, control: bool = False) -> dict:
    roots = [Path(r).resolve() for r in args.root]
    cell = cells.load_cell(args.workload, roots)
    info, peak = preflight(cell.chips, allow_cpu)
    if not allow_cpu:
        enable_compile_cache()
    compiles = CompileLog()
    try:
        return _run(args, cell, roots, info, peak, compiles, control)
    finally:
        compiles.close()


def _run(args, cell, roots, info, peak, compiles, control: bool) -> dict:
    import jax

    sys.path.insert(0, str(cells.CHECKOUT / "src"))
    conf, spec = cell.config, cell.traffic
    ref = importlib.import_module(f"reference.{conf['reference']}")
    cfg = model_config(conf)
    sizes = ref.sizes(conf)
    pmax, omax = traffic.longest(spec["prompt"]), traffic.longest(spec["output"])
    if pmax + omax > int(conf["engine"]["max_context"]):
        raise SystemExit(f"{cell.traffic_name}: sequences outgrow the configuration's max_context")

    params = ref.make_params(conf, args.seed)
    jax.block_until_ready(params)
    eng = build_engine(params, cfg, conf)
    mix = traffic.Mix(spec, args.seed, sizes["vocab"])
    warm(eng, mix, pmax, omax)

    tracing_on = bool(args.trace)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if tracing_on else None
    drv = drive.Load(eng, mix, annotations=tracing_on)
    counters = ("pool.copied_bytes",)
    at: dict[str, dict] = {}
    state = {"trace": "off", "mark": None}
    t0 = time.perf_counter()
    t_open = t0 + float(spec["ramp_s"])
    t_close = t_open + args.seconds

    def read_counters():
        reg = eng.obs.registry
        return {c: reg.counter(c).total() for c in counters}

    def hooks(now: float) -> None:
        if tracing_on and state["trace"] == "off" and now >= t_open - TRACE_LEAD_S:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(tracing.MARK):
                state["mark"] = time.perf_counter()
            state["trace"] = "on"
        if "open" not in at and now >= t_open:
            at["open"] = read_counters()
        if "close" not in at and now >= t_close:
            at["close"] = read_counters()

    if spec["loop"] == "open":
        drive.run_open(drv, mix.open_schedule(args.seconds, drive.DRAIN_S), t0=t0,
                       t_open=t_open, t_close=t_close, hooks=hooks)
    else:
        clients = int(spec["clients"])
        # enough requests for every client at one millisecond per token
        span = float(spec["ramp_s"]) + args.seconds
        budget = clients * (2 + int(math.ceil(span / (1e-3 * traffic.shortest(spec["output"])))))
        drive.run_closed(drv, mix.closed_schedule(budget), clients=clients,
                         stagger_s=float(spec.get("stagger_s", 0.0)), t0=t0,
                         t_open=t_open, t_close=t_close, hooks=hooks)
    hooks(time.perf_counter())
    drv.settle()
    trace = None
    if tracing_on:
        jax.profiler.stop_trace()
        if info["platform"] != "cpu":
            trace = tracing.reduce(tracing.find_xplane(trace_dir), mark_perf=state["mark"],
                                   start=t_open, end=t_close)
        shutil.rmtree(trace_dir, ignore_errors=True)

    stats = jax.devices()[0].memory_stats() or {}
    device = dict(info, memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    hist = eng.obs.registry.histogram("serve.queue_wait_ms")
    record = Record(
        cell=cell.name, sizes=sizes, peak=peak, setup_s=t_open - T_START,
        t_open=t_open, t_close=t_close,
        requests=sorted(drv.reqs.values(), key=lambda r: r.rid), steps=drv.steps,
        chunks=drv.chunks, gauges=drv.gauges,
        counters={c: (at["open"][c], at["close"][c]) for c in counters},
        compiles_in_window=compiles.count(t_open, t_close),
        queue_wait_ms={r.rid: v[0] for r in drv.reqs.values() if (v := hist.values(rid=r.rid))},
        trace=trace,
    )
    wanted = cell.per_layer if tracing_on else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cells.load_reader(roots, m.name)(record)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}

    window_due = record.due_in_window()
    failed = sum(1 for r in window_due if r.first is None)
    chosen = check.sample(drv, mix.check_rng(), int(spec["check_sample"]))
    pairs = check.served(drv, chosen)
    slab = eng.T
    drv.eng = None
    del eng
    gc.collect()
    readings = check.readings(ref, params, conf, pairs, slab_tokens=slab, control=control)
    limits = json.loads(cells.find_file(roots, "checks", cell.name, ".json").read_text())
    checks = {
        "logit_gap": {"value": readings["logit_gap"], "limit": limits["logit_gap"]["limit"]},
        "unanswered": {"value": failed, "limit": 0},
        "requests_checked": {"value": readings["requests_checked"], "min": 1},
    }
    if control:
        checks["control_gap"] = {"value": readings["control_gap"],
                                 "limit": limits["logit_gap"]["limit"]}
    correct = (
        readings["logit_gap"] <= limits["logit_gap"]["limit"]
        and readings["requests_checked"] >= 1
        and failed == 0
    )
    result = {"correct": bool(correct), "attempted": len(window_due), "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in trace.device_ops],
                               "idle_gaps": [list(x) for x in trace.idle_gaps]}
    result["checks"] = checks
    result["_notes"] = {
        "tokens_checked": readings["tokens_checked"],
        "tokens_past_slab": readings["tokens_past_slab"],
        "generator_late_ms_p99": _late_p99(record),
    }
    return result


def _late_p99(record: Record) -> float:
    late = sorted((r.submit - r.due) * 1e3 for r in record.due_in_window())
    return late[min(len(late) - 1, int(0.99 * len(late)))] if late else 0.0


def main(argv=None, *, allow_cpu: bool = False) -> int:
    args = parse(argv)
    result = run(args, allow_cpu=allow_cpu)
    notes = result.pop("_notes")
    print(f"generator: late p99 {notes['generator_late_ms_p99']:.3f} ms; "
          f"checked {notes['tokens_checked']} served tokens, "
          f"{notes['tokens_past_slab']} past the first slab", flush=True)
    for name, c in result["checks"].items():
        bound = f"limit {c['limit']}" if "limit" in c else f"at least {c['min']}"
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
