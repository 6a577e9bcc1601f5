"""The check decides ``correct``: a run with the served path broken
underneath reads ``correct: false``, once for each fault a serving cell can
have.  (A one-chip cell has no exchange between chips to leave out.)"""
from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

import run
from repro.serving import engine as engine_mod
from repro.serving import steps


@pytest.fixture()
def fresh_steps():
    """The engine's shared jit factories are cached per configuration; a
    patched step must not leak into, or out of, a cached executable."""
    engine_mod._decode_step_fn.cache_clear()
    engine_mod._prefill_chunk_fn.cache_clear()
    yield
    engine_mod._decode_step_fn.cache_clear()
    engine_mod._prefill_chunk_fn.cache_clear()


def _state_unchanged(monkeypatch):
    """The decode step hands back the K/V pool it was given: its appends are lost."""
    real = steps.decode_step

    def broken(params, token, caches, length, cfg, active=None):
        logits, _ = real(params, token, caches, length, cfg, active=active)
        return logits, caches

    monkeypatch.setattr(steps, "decode_step", broken)


def _half_batch(monkeypatch):
    """The decode step computes the lower half of its slots only; the upper
    half gets the lower half's logits."""
    real = steps.decode_step

    def broken(params, token, caches, length, cfg, active=None):
        logits, new = real(params, token, caches, length, cfg, active=active)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[: logits.shape[0] - half]), new

    monkeypatch.setattr(steps, "decode_step", broken)


def _token_altered(monkeypatch):
    """The sampler's first token of every batch it draws is one off."""
    real = engine_mod.sample

    def broken(key, logits, temperature=0.0):
        tok = real(key, logits, temperature)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1]).astype(jnp.int32)

    monkeypatch.setattr(engine_mod, "sample", broken)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


def _result(capsys, root, cell, seed):
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1.0",
                     "--trace", "0", "--root", str(root)], allow_cpu=True) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_broken_path_reads_not_correct(capsys, monkeypatch, fresh_steps, tiny_root, fault, cell):
    FAULTS[fault](monkeypatch)
    res = _result(capsys, tiny_root, cell, seed=2**32 + 101)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_sound_path_reads_correct(capsys, fresh_steps, tiny_root):
    res = _result(capsys, tiny_root, "tiny.open", seed=2**32 + 101)
    assert res["correct"] is True
