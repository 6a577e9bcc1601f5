"""A tiny benchmark root: the harness run end to end on the CPU.

The root holds its own ``BENCHMARK.json``, a configuration at a reduced size
(qwen2.5-3b's registry entry at hidden 128), an open and a closed mix, and
check limits — found by name through ``--root``, like any added cell.
Importing this module puts ``bench/`` on ``sys.path``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_CONFIG = {
    "source": "reduced qwen2.5-3b for CPU tests",
    "model_type": "qwen2",
    "hidden_act": "silu",
    "hidden_size": 128,
    "intermediate_size": 256,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0,
    "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
    "vocab_size": 512,
    "reduced": ["hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "vocab_size"],
    "registry": "qwen2.5-3b",
    "reference": "qwen2",
    "program_options": {"cache_b0": 16, "attention_chunk": 8, "remat": False},
    "engine": {"max_batch": 4, "pool_slabs": 24, "max_context": 64},
}
TINY_OPEN = {
    "loop": "open",
    "rate_per_s": 30.0,
    "prompt": {"dist": "loguniform", "min": 4, "max": 40},
    "output": {"dist": "loguniform", "min": 8, "max": 24},
    "block": 8,
    "ramp_s": 0.3,
    "check_sample": 3,
}
TINY_CLOSED = {
    "loop": "closed",
    "clients": 3,
    "prompt": {"dist": "loguniform", "min": 8, "max": 40},
    "output": {"dist": "fixed", "value": 12},
    "block": 3,
    "ramp_s": 0.3,
    "check_sample": 3,
}
METRICS = ["setup_s", "output_tok_s", "itl_p90_ms", "ttft_p50_ms"]
PER_LAYER = ["queue_wait_ms_p90", "decode_batch_mean", "pool_copied_mb",
             "pool_held_over_live", "compiles_in_window"]
# Set like a cell's limit (bench/checks/): above the program's widest gap
# (0.081 over seeds 1-3, 5-13 and 2**31 + 3 on the CPU) and below the float8
# control's gap on 12 of those 13 seeds (0.50-1.70; seed 11 reads 0.25, as a
# widest gap over ~50 tokens of a model this small can).  A wrong token
# reads 2-6.
LIMIT = 0.3


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(root: Path) -> Path:
    cells = [("tiny.open", "tiny_open"), ("tiny.closed", "tiny_closed")]
    _write(root / "BENCHMARK.json", {
        "workloads": [{"name": n, "config": "tiny", "traffic": t, "chips": 1} for n, t in cells],
        "end_to_end": [{"name": m, "unit": "u", "better": "lower", "source": "host_clock"}
                       for m in METRICS],
        "per_layer": [{"name": m, "unit": "u", "better": "lower", "source": "program_counter",
                       "layer": "x", "moves": "output_tok_s"} for m in PER_LAYER],
    })
    _write(root / "configs" / "tiny.json", TINY_CONFIG)
    _write(root / "traffic" / "tiny_open.json", TINY_OPEN)
    _write(root / "traffic" / "tiny_closed.json", TINY_CLOSED)
    for name, _ in cells:
        _write(root / "checks" / f"{name}.json", {"logit_gap": {"limit": LIMIT}})
    return root
