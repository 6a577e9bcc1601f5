"""Fault tolerance: crash → restart → bitwise-identical trajectory; elastic restore."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import reduced
from repro.train import loop as loop_mod
from repro.train import step as step_mod


CFG = reduced("qwen1.5-0.5b", n_layers=2)


def _loop(tmp, **kw):
    base = dict(steps=8, batch=2, seq=16, ckpt_dir=tmp, ckpt_every=3, log_every=100)
    base.update(kw)
    return loop_mod.LoopConfig(**base)


def test_crash_resume_matches_uninterrupted(tmp_path):
    # uninterrupted reference
    ref = loop_mod.run(CFG, _loop(str(tmp_path / "ref")))["losses"]

    # crashed run: fails at step 5 (after the step-3 checkpoint)
    d = str(tmp_path / "crash")
    with pytest.raises(RuntimeError, match="injected failure"):
        loop_mod.run(CFG, _loop(d, fail_at_step=5))
    # restart — resumes from step 3 and finishes
    out = loop_mod.run(CFG, _loop(d))
    assert out["start_step"] == 3
    np.testing.assert_array_equal(np.asarray(out["losses"]), np.asarray(ref[3:]))


def test_async_checkpoint_resume(tmp_path):
    d = str(tmp_path / "async")
    loop_mod.run(CFG, _loop(d, async_ckpt=True, steps=6))
    assert ckpt.latest_step(d) == 6


def test_checkpoint_roundtrip_exact(tmp_path):
    state = step_mod.init_train_state(jax.random.PRNGKey(0), CFG)
    path = ckpt.save(str(tmp_path), 7, state, metadata={"next_step": 7})
    restored, meta = ckpt.restore(str(tmp_path), 7, state)
    assert meta["next_step"] == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_onto_mesh_shardings(tmp_path):
    """A host-saved checkpoint restores under explicit (1,1) mesh shardings."""
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_host_mesh

    state = step_mod.init_train_state(jax.random.PRNGKey(1), CFG)
    ckpt.save(str(tmp_path), 1, state.params)
    mesh = make_host_mesh(1, 1)
    shardings = sh.param_shardings(state.params, CFG, mesh)
    restored, _ = ckpt.restore(str(tmp_path), 1, state.params, shardings=shardings)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partial_tmp_dir_is_ignored(tmp_path):
    d = tmp_path / "step_00000009.tmp"
    d.mkdir(parents=True)
    assert ckpt.latest_step(str(tmp_path)) is None


def test_grad_compression_error_feedback_converges():
    """int8-compressed grads with error feedback still reduce loss.

    Deflaked: a 6-step run compared single-step losses, which sat inside the
    quantization noise floor (~0.007 margin).  Run past the 5-step LR warmup
    and compare window means so one noisy step can't flip the verdict; the
    seed is fixed (LoopConfig.seed=0) so the trajectory is reproducible.
    """
    out = loop_mod.run(
        CFG, loop_mod.LoopConfig(steps=20, batch=2, seq=16, grad_compression=True, log_every=100)
    )
    losses = np.asarray(out["losses"])
    assert losses[-3:].mean() < losses[:3].mean()
    assert np.all(np.isfinite(losses))
