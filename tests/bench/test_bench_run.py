"""bench/run.py end to end on the CPU at a reduced size: one result line,
cells found by name from another root, and refusal without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchroot import BENCH, METRICS, PER_LAYER, REPO

import run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _json_lines(text: str) -> list[dict]:
    out = []
    for line in text.splitlines():
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    return out


def _main(capsys, root, cell, *, trace=0, seed=2**33 + 17):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1.0",
                   "--trace", str(trace), "--root", str(root)], allow_cpu=True)
    assert rc == 0
    return capsys.readouterr()


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_run_prints_one_result_line(capsys, tiny_root, cell):
    out = _main(capsys, tiny_root, cell)
    lines = _json_lines(out.out)
    assert len(lines) == 1
    assert out.out.strip().splitlines()[-1] == json.dumps(lines[0])
    res = lines[0]
    assert [k for k in res if k in KEYS] == KEYS
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == set(METRICS)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    # the numbers compared end standard error, each beside its limit
    tail = out.err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    assert res["checks"]["logit_gap"]["value"] <= res["checks"]["logit_gap"]["limit"]


def test_traced_run_reports_per_layer_metrics(capsys, tiny_root):
    res = _json_lines(_main(capsys, tiny_root, "tiny.open", trace=1).out)[-1]
    assert res["correct"] is True
    # the CPU has no device plane: trace readers find nothing and are left out
    assert set(res["metrics"]) == set(PER_LAYER)
    assert res["metrics"]["compiles_in_window"]["value"] == 0


def test_added_config_mix_and_reader_are_found_by_name(capsys, tiny_root):
    """A new cell from files alone: its configuration, mix and a metric
    reader live in another root; nothing under bench/ changes."""
    before = {p: p.read_bytes() for p in BENCH.rglob("*") if p.is_file() and "__pycache__" not in p.parts}
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "added.cell", "config": "added", "traffic": "added_mix", "chips": 1})
    spec["end_to_end"].append({"name": "requests_in_window", "unit": "count", "better": "higher",
                               "source": "host_clock", "workloads": ["added.cell"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    conf = json.loads((tiny_root / "configs" / "tiny.json").read_text())
    conf["engine"]["max_batch"] = 2
    (tiny_root / "configs" / "added.json").write_text(json.dumps(conf))
    mix = json.loads((tiny_root / "traffic" / "tiny_open.json").read_text())
    mix["rate_per_s"] = 6.0
    (tiny_root / "traffic" / "added_mix.json").write_text(json.dumps(mix))
    (tiny_root / "metrics" ).mkdir()
    (tiny_root / "metrics" / "requests_in_window.py").write_text(
        "def read(rec):\n    return len(rec.due_in_window())\n")
    (tiny_root / "checks" / "added.cell.json").write_text(json.dumps({"logit_gap": {"limit": 0.05}}))
    res = _json_lines(_main(capsys, tiny_root, "added.cell").out)[-1]
    assert res["correct"] is True
    assert res["metrics"]["requests_in_window"]["value"] == res["attempted"] > 0
    after = {p: p.read_bytes() for p in BENCH.rglob("*") if p.is_file() and "__pycache__" not in p.parts}
    assert after == before


def _subprocess(cwd, args, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_backend_is_refused(tiny_root):
    proc = _subprocess(REPO, ["--workload", "qwen2.5-3b.chat", "--seed", "1",
                              "--seconds", "1", "--trace", "0"], {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _json_lines(proc.stdout) == []
    assert "not a TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    (no program) exits non-zero and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _subprocess(tmp_path, ["--workload", "qwen1.5-0.5b.longdoc", "--seed", "1",
                                  "--seconds", "1", "--trace", "0"], {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _json_lines(proc.stdout) == []


def test_unknown_device_kind_has_no_peaks():
    assert run.peaks("TPU v5 lite", "tpu")["bf16_flops_per_s"] == 197e12
    with pytest.raises(run.Refused):
        run.peaks("TPU v99 imagined", "tpu")
