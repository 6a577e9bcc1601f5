"""``chip_smoke.py``'s phases on the CPU: reduced model, tiny sizes.

The hbm tilings — the ones the chip runs — execute here in interpret mode
(``REPRO_MEMORY_SPACE=hbm``), so the smoke's control flow, checks and
kernels are exercised before any chip time is spent.
"""
import importlib.util
import os

import jax
import pytest

from repro import configs
from repro.models import transformer

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def hbm(monkeypatch):
    monkeypatch.setenv("REPRO_MEMORY_SPACE", "hbm")


def test_serving_phases_run_on_cpu(smoke, hbm, capsys):
    cfg = configs.reduced("qwen2.5-3b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    prompts = smoke.make_prompts(0, 3, (5, 40), cfg.vocab_size)
    logits = smoke.phase_serving(params, cfg, prompts, 4, 2)
    assert logits.shape == (3, cfg.vocab_size)
    smoke.phase_pallas(
        params, cfg, prompts, 4, 2, logits, require_compiled=False
    )
    out = capsys.readouterr().out
    assert "phase A serving: ok requests=3" in out
    assert "phase B pallas: ok requests=3" in out


def test_grow_freeze_phases_run_on_cpu(smoke, hbm, capsys):
    smoke.phase_grow_freeze(nblocks=8, b0=8, wave=32, min_elems=1500, seed=0)
    smoke.phase_arena_freeze(narrays=8, slab=8, wave=32, min_elems=1500, seed=0)
    out = capsys.readouterr().out
    assert "phase C grow/freeze: ok" in out and "memory_space=hbm" in out
    assert "phase C arena grow/freeze: ok" in out


def test_main_refuses_a_cpu_backend(smoke, capsys):
    with pytest.raises(SystemExit, match="not a TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
