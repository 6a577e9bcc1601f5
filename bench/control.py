"""Readings that set a cell's check limit: the program and the control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process (so set-up compiles once), this makes one run
of the cell as ``bench/run.py`` does, and reads beside the program's widest
logit gap the control's: the reference computed in the precision below the
configuration's, at the same positions of the same served requests.  The
benchmark's own runs never compute the control.

Prints one JSON line per seed, then a summary: the lower reading (the
largest program gap), the upper reading (the smallest control gap) and
their ratio.  The limit in ``bench/checks/<cell>.json`` is set between them.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None, *, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--root", action="append", default=[])
    args = ap.parse_args(argv)
    lower, upper = 0.0, float("inf")
    for seed in args.seeds:
        argv_run = ["--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seconds), "--trace", "0"]
        for r in args.root:
            argv_run += ["--root", r]
        res = run.run(run.parse(argv_run), allow_cpu=allow_cpu, control=True)
        gap = res["checks"]["logit_gap"]["value"]
        ctl = res["checks"]["control_gap"]["value"]
        lower, upper = max(lower, gap), min(upper, ctl)
        print(json.dumps({"seed": seed, "correct": res["correct"], "logit_gap": gap,
                          "control_gap": ctl, **res["_notes"],
                          "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds), "lower": lower,
                      "upper": upper, "ratio": upper / lower if lower else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
