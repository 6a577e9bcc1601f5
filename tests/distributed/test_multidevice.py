"""Multi-device parity: the sharded (mesh + shard_map MoE) train step must
match the single-device run. Runs in a subprocess with 8 host devices so the
main test session keeps its real device count."""
import json
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_mesh_worker.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


@pytest.mark.parametrize("arch", ["dbrx-132b", "qwen2.5-3b"])
def test_sharded_train_step_matches_single_device(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, WORKER, arch],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"worker failed:\n{out.stdout}\n{out.stderr[-3000:]}"
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["devices"] == 8
    # Two tolerated effects: fp32 reduction-order skew, and (MoE archs) the
    # shard-local dispatch capacity — per-shard buffers drop at local
    # boundaries vs one global boundary, a documented semantic of the
    # production path (models/moe.py). Both stay well under these bounds.
    assert result["loss_diff"] < 2e-2, result
    assert result["param_max_diff"] < 5e-2, result


def test_elastic_reshard_across_mesh_shapes(tmp_path):
    """Checkpoint saved under a (2,4) mesh restores bit-exactly onto (4,2)
    and (1,1) meshes — the elastic-scaling path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, WORKER, "elastic", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"worker failed:\n{out.stdout}\n{out.stderr[-3000:]}"
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["elastic_max_diff"] == 0.0, result
