"""CI gate: paged-engine throughput must not regress vs the committed baseline.

Reads ``BENCH_pool.json`` (the smoke artifact the CI job just produced),
computes the paged/ggarray sequences-per-second ratio — both engines run on
the same machine in the same process, so the ratio self-normalizes away the
runner's absolute speed — and fails (exit 1) if it has dropped more than
``--tolerance`` (default 20%) below the committed baseline ratio in
``benchmarks/baselines/pool_smoke.json``.  Two floors are enforced:

* relative: ``ratio ≥ (1 − tolerance) · baseline_ratio`` — catches a
  scheduler/jit-cache regression even while the ratio is comfortably > 1;
* absolute: ``ratio ≥ 0.8`` — the ISSUE 6 acceptance bound (the paged
  engine must serve at least 0.8× ggarray's seqs/s, up from 0.21×).

The extent pool's zero-copy growth contract (ISSUE 7, DESIGN.md §8) is
gated too:

* hard: ``pool_grow_copied_bytes_{doubling,tz}`` and
  ``pool_serve_copied_bytes_{doubling,tz}`` must be **exactly 0** — a
  reintroduced full-pool copy fails CI deterministically (a missing row
  fails as well, so the gate cannot be dodged by dropping the bench);
* relative: the grow-step p95 advantage ``flat / max(extent)`` is a
  same-process self-normalizing ratio gated against the committed
  ``grow_step`` baseline with the same ``--tolerance``.

The telemetry artifact (``METRICS_pool.json``, registry snapshots of the
timed engines — ISSUE 8) supplies two more self-normalizing gates:

* ``ttft_p95_ratio`` — chunked ``serve.first_token_dispatch_ms`` p95 over
  monolithic p95 (host dispatch of the first token's sample),
  both from the same process; chunked admission exists to cut tail TTFT,
  so this ratio drifting *up* past
  ``max((1 + tolerance) · baseline, 0.5)`` fails (the absolute ceiling
  absorbs timer jitter on a tiny baseline — the chunked tail p95 is tens
  of ms in smoke mode — while still catching the real failure mode of
  chunking ceasing to help, which drives the ratio toward 1);
* ``utilization`` — chunked peak live tokens over peak pool capacity
  (gauge high-water marks); dropping below
  ``(1 − tolerance) · baseline`` means the pool got sparser.

The shared-prefix fleet (copy-on-write prefix caching, DESIGN.md §10)
contributes two more gates from the metrics artifact's ``prefix`` section:

* hard: ``hit_rate`` must be **exactly 1.0** — every identical prompt after
  the first must alias the cached slabs (a missing section fails too, so
  the gate cannot be dodged by dropping the scenario);
* relative: ``ttft_hit_ratio`` (full-hit TTFT over cold TTFT, same process,
  same jit cache) is a ceiling gate like ``ttft_p95_ratio`` — a fully
  cached prompt's first token comes from one decode step instead of the
  whole chunked prefill, so this ratio drifting up toward 1 means the
  cache stopped skipping prefill.

A missing metrics file or metric key fails, same as a missing bench row.

``--update`` rewrites the baseline from the current artifacts (a
deliberate, reviewed re-tune — commit the diff).

Usage::

    python benchmarks/check_regression.py [--bench BENCH_pool.json]
        [--metrics METRICS_pool.json]
        [--baseline benchmarks/baselines/pool_smoke.json]
        [--tolerance 0.2] [--update]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ABSOLUTE_FLOOR = 0.8  # ISSUE 6 acceptance: paged ≥ 0.8× ggarray seqs/s
TTFT_ABS_CEILING = 0.5  # chunked TTFT p95 must stay < 0.5× monolithic's
HIT_TTFT_ABS_CEILING = 0.5  # full-hit TTFT must stay < 0.5× cold TTFT


def _rows(path: str) -> dict[str, float]:
    with open(path) as f:
        payload = json.load(f)
    return {r["name"]: r["us_per_call"] for r in payload["rows"]}


def _telemetry(path: str) -> tuple[float, float, float, float, float] | str:
    """(ttft_p95_ratio, utilization, prefix_hit_rate, ttft_hit_ratio,
    masked_lane_waste) from METRICS_pool.json, or an error string."""
    try:
        with open(path) as f:
            engines = json.load(f)["engines"]
        chunked, mono = engines["chunked"], engines["monolithic"]
        first = "serve.first_token_dispatch_ms"
        ttft_ratio = chunked["histograms"][first]["p95"] / max(
            mono["histograms"][first]["p95"], 1e-12
        )
        util = chunked["gauges"]["pool.live_tokens"]["hwm"] / max(
            chunked["gauges"]["pool.capacity_tokens"]["hwm"], 1
        )
        prefix = engines["prefix"]
        hit_rate = float(prefix["hit_rate"])
        hit_ttft_ratio = float(prefix["ttft_hit_ratio"])
        # device counter plane (DESIGN.md §9.x): attend masked-lane waste —
        # a missing section fails, so the gate cannot be dodged
        masked_waste = float(engines["device"]["masked_lane_waste"])
    except (OSError, KeyError, TypeError) as e:
        return f"{path}: {type(e).__name__}: {e}"
    return ttft_ratio, util, hit_rate, hit_ttft_ratio, masked_waste


def main(argv: list[str] | None = None) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", default="BENCH_pool.json")
    ap.add_argument("--metrics", default="METRICS_pool.json")
    ap.add_argument(
        "--baseline", default=os.path.join(here, "baselines", "pool_smoke.json")
    )
    ap.add_argument("--tolerance", type=float, default=0.2)
    ap.add_argument("--update", action="store_true")
    args = ap.parse_args(argv)

    rows = _rows(args.bench)
    try:
        us_paged = rows["pool_paged_seqs_per_s"]
        us_gg = rows["pool_ggarray_seqs_per_s"]
    except KeyError as e:
        print(f"check_regression: {args.bench} is missing row {e}", file=sys.stderr)
        return 1
    # rows record µs per sequence, so throughput ratio inverts them
    ratio = us_gg / us_paged

    # zero-copy growth contract: every copied-bytes row must exist and be 0
    copy_rows = [
        f"pool_{kind}_copied_bytes_{sched}"
        for kind in ("grow", "serve")
        for sched in ("doubling", "tz")
    ]
    missing = [r for r in copy_rows if r not in rows]
    if missing:
        print(
            f"check_regression: {args.bench} is missing zero-copy gate "
            f"row(s) {missing}",
            file=sys.stderr,
        )
        return 1
    grow_ratio = None
    try:
        grow_ratio = rows["pool_grow_p95_us_flat"] / max(
            rows["pool_grow_p95_us_doubling"], rows["pool_grow_p95_us_tz"], 1e-12
        )
    except KeyError as e:
        print(f"check_regression: {args.bench} is missing row {e}", file=sys.stderr)
        return 1

    telemetry = _telemetry(args.metrics)
    if isinstance(telemetry, str):
        print(f"check_regression: telemetry gate unreadable — {telemetry}",
              file=sys.stderr)
        return 1
    ttft_ratio, util, hit_rate, hit_ttft_ratio, masked_waste = telemetry

    if args.update:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(
                {
                    "metric": "paged_vs_ggarray_seqs_per_s_ratio",
                    "value": round(ratio, 3),
                    "grow_step": {
                        "metric": "flat_over_extent_grow_p95_ratio",
                        "value": round(grow_ratio, 3),
                    },
                    "telemetry": {
                        "ttft_p95_ratio": round(ttft_ratio, 3),
                        "utilization": round(util, 3),
                        "source": "METRICS_pool.json",
                    },
                    "prefix": {
                        "hit_rate": round(hit_rate, 3),
                        "ttft_hit_ratio": round(hit_ttft_ratio, 3),
                        "source": "METRICS_pool.json",
                    },
                    "device": {
                        "masked_lane_waste": round(masked_waste, 4),
                        "source": "METRICS_pool.json",
                    },
                    "source": "benchmarks/bench_pool.py --smoke",
                },
                f,
                indent=2,
            )
            f.write("\n")
        print(
            f"check_regression: baseline updated to {ratio:.3f} "
            f"(grow-step ratio {grow_ratio:.3f}, ttft p95 ratio "
            f"{ttft_ratio:.3f}, utilization {util:.3f}, prefix hit rate "
            f"{hit_rate:.3f}, hit/cold ttft {hit_ttft_ratio:.3f}, "
            f"masked-lane waste {masked_waste:.4f})"
        )
        return 0

    with open(args.baseline) as f:
        baseline = json.load(f)
    base = baseline["value"]
    floor = (1.0 - args.tolerance) * base
    verdict = (
        f"paged/ggarray seqs/s ratio {ratio:.3f} "
        f"(baseline {base:.3f}, relative floor {floor:.3f}, "
        f"absolute floor {ABSOLUTE_FLOOR})"
    )
    if ratio < ABSOLUTE_FLOOR:
        print(f"check_regression: FAIL — below acceptance bound: {verdict}")
        return 1
    if ratio < floor:
        print(f"check_regression: FAIL — >{args.tolerance:.0%} regression: {verdict}")
        return 1

    copied = {r: rows[r] for r in copy_rows if rows[r] != 0.0}
    if copied:
        print(
            "check_regression: FAIL — extent growth copied pool bytes "
            f"(must be 0): {copied}"
        )
        return 1
    grow_verdict = f"grow-step p95 flat/extent ratio {grow_ratio:.3f}"
    grow_base = baseline.get("grow_step")
    if grow_base is not None:
        grow_floor = (1.0 - args.tolerance) * grow_base["value"]
        grow_verdict += f" (baseline {grow_base['value']:.3f}, floor {grow_floor:.3f})"
        if grow_ratio < grow_floor:
            print(
                f"check_regression: FAIL — grow-step regression: {grow_verdict}"
            )
            return 1
    tel_verdict = f"ttft p95 ratio {ttft_ratio:.3f}, utilization {util:.3f}"
    tel_base = baseline.get("telemetry")
    if tel_base is not None:
        ttft_ceil = max(
            (1.0 + args.tolerance) * tel_base["ttft_p95_ratio"], TTFT_ABS_CEILING
        )
        util_floor = (1.0 - args.tolerance) * tel_base["utilization"]
        tel_verdict += (
            f" (ttft ceiling {ttft_ceil:.3f}, utilization floor {util_floor:.3f})"
        )
        if ttft_ratio > ttft_ceil:
            print(
                "check_regression: FAIL — chunked TTFT tail regressed vs "
                f"monolithic: {tel_verdict}"
            )
            return 1
        if util < util_floor:
            print(
                f"check_regression: FAIL — pool utilization dropped: {tel_verdict}"
            )
            return 1
    # prefix caching (DESIGN.md §10): full-hit rate is a hard 1.0 gate, the
    # hit/cold TTFT ratio a ceiling gate like ttft_p95_ratio
    px_verdict = (
        f"prefix hit rate {hit_rate:.3f}, hit/cold ttft {hit_ttft_ratio:.3f}"
    )
    if hit_rate != 1.0:
        print(
            "check_regression: FAIL — shared-prefix fleet missed the cache "
            f"(hit rate must be exactly 1.0): {px_verdict}"
        )
        return 1
    px_base = baseline.get("prefix")
    if px_base is not None:
        px_ceil = max(
            (1.0 + args.tolerance) * px_base["ttft_hit_ratio"],
            HIT_TTFT_ABS_CEILING,
        )
        px_verdict += f" (ceiling {px_ceil:.3f})"
        if hit_ttft_ratio > px_ceil:
            print(
                "check_regression: FAIL — full-hit TTFT no longer beats cold "
                f"prefill: {px_verdict}"
            )
            return 1
    # device counter plane (DESIGN.md §9.x): masked-lane waste is a ratchet —
    # the attend walk reading lanes past kv_len may only get leaner; a jump
    # past baseline + tolerance means the page-walk gating regressed
    dev_verdict = f"masked-lane waste {masked_waste:.4f}"
    dev_base = baseline.get("device")
    if dev_base is not None:
        waste_ceil = (1.0 + args.tolerance) * dev_base["masked_lane_waste"]
        dev_verdict += (
            f" (baseline {dev_base['masked_lane_waste']:.4f}, "
            f"ceiling {waste_ceil:.4f})"
        )
        if masked_waste > waste_ceil:
            print(
                "check_regression: FAIL — attend masked-lane waste grew: "
                f"{dev_verdict}"
            )
            return 1
    print(
        f"check_regression: OK — {verdict}; {grow_verdict}; {tel_verdict}; "
        f"{px_verdict}; {dev_verdict}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
