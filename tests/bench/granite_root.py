"""A tiny benchmark root for the hybrid configuration: granite-4.0-h-small's
registry entry at hidden 64 (one period of 10 layers, 9 of 72 experts held,
the published Mamba-2 and expert widths), found by name through ``--root``
like any added cell.  Importing this module puts ``bench/`` on ``sys.path``.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchroot import BENCH, REPO, _write  # noqa: F401  (bench/ on sys.path)

CONFIG = json.loads((BENCH / "configs" / "granite-4.0-h-small.json").read_text())
TINY_GRANITE = dict(
    CONFIG,
    source="reduced granite-4.0-h-small for CPU tests",
    hidden_size=64,
    num_attention_heads=4,
    num_key_value_heads=1,
    mamba_n_heads=2,  # expand 2 × hidden 64 / head 64
    attention_multiplier=1 / 16,  # 1 / head_dim, as 1/128 is at head_dim 128
    vocab_size=512,
    reduced=CONFIG["reduced"] + ["hidden_size", "num_attention_heads",
                                 "num_key_value_heads", "mamba_n_heads", "vocab_size"],
    program_options={"expert_shards": 8, "cache_b0": 16, "attention_chunk": 256,
                     "attention_multiplier": 1 / 16, "remat": False},
    engine={"max_batch": 4, "pool_slabs": 24, "max_context": 64},
)
TINY_DOCS = {
    "loop": "closed",
    "clients": 3,
    "prompt": {"dist": "loguniform", "min": 8, "max": 40},
    "output": {"dist": "fixed", "value": 12},
    "block": 3,
    "ramp_s": 0.3,
    "check_sample": 3,
}
METRICS = ["setup_s", "itl_p90_ms", "output_tok_s"]
PER_LAYER = ["decode_batch_mean", "pool_copied_mb", "pool_held_over_live",
             "compiles_in_window", "hybrid_mfu_pct"]
CELL = "tiny_granite.docs"
# Set like a cell's limit (bench/checks/): above the bf16 program's widest
# gap and below the float8 control's, over the seeds in
# test_bench_granite_run.py (see there).
LIMIT = 0.3


def make_root(root: Path) -> Path:
    _write(root / "BENCHMARK.json", {
        "workloads": [{"name": CELL, "config": "tiny_granite", "traffic": "tiny_docs",
                       "chips": 1}],
        "end_to_end": [{"name": m, "unit": "u", "better": "lower", "source": "host_clock"}
                       for m in METRICS],
        "per_layer": [{"name": m, "unit": "u", "better": "lower", "source": "program_counter",
                       "layer": "x", "moves": "output_tok_s"} for m in PER_LAYER],
    })
    _write(root / "configs" / "tiny_granite.json", TINY_GRANITE)
    _write(root / "traffic" / "tiny_docs.json", TINY_DOCS)
    _write(root / "checks" / f"{CELL}.json", {"logit_gap": {"limit": LIMIT}})
    return root
