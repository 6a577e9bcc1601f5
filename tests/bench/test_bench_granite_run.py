"""The hybrid cell through ``bench/run.py`` on the CPU at a reduced size
(``granite_root``): one correct result line, a planted fault in the held
experts read ``correct: false``, and the hybrid readers and work counts.

The tiny root's check limit (``granite_root.LIMIT``, 0.3) lies between the
program's widest gap and the float8 control's, read with
``bench/control.py`` on the CPU over seeds 1, 2, 3 and 2**31 + 3: the
program 0.042-0.112, the control 0.977-1.587.
"""
from __future__ import annotations

import json

import pytest

import granite_root
import run
from harness import work_hybrid
from harness.drive import Chunk, Step
from harness.record import Record
from harness.tracing import Trace
from reference import granite_hybrid
from repro.configs.base import ModelConfig
from repro.serving import engine as engine_mod

SEED = 2**32 + 7


@pytest.fixture()
def groot(tmp_path):
    return granite_root.make_root(tmp_path / "groot")


@pytest.fixture()
def fresh_steps():
    """A patched step must not leak into, or out of, a cached executable."""
    engine_mod._decode_step_fn.cache_clear()
    engine_mod._prefill_chunk_fn.cache_clear()
    yield
    engine_mod._decode_step_fn.cache_clear()
    engine_mod._prefill_chunk_fn.cache_clear()


def _result(capsys, root, trace=0):
    assert run.main(["--workload", granite_root.CELL, "--seed", str(SEED), "--seconds", "1.0",
                     "--trace", str(trace), "--root", str(root)], allow_cpu=True) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_hybrid_cell_runs_and_reads_correct(capsys, fresh_steps, groot):
    res = _result(capsys, groot)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == set(granite_root.METRICS)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["logit_gap"]["value"] <= granite_root.LIMIT


def test_held_expert_offset_off_by_one_reads_not_correct(capsys, monkeypatch, fresh_steps, groot):
    """The program routes to experts 1 .. 9 while it holds 0 .. 8."""
    real = ModelConfig.held_experts.fget
    monkeypatch.setattr(ModelConfig, "held_experts",
                        property(lambda cfg: (real(cfg)[0] + 1, real(cfg)[1])))
    res = _result(capsys, groot)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_configuration_file_and_program_agree():
    """The cell's configuration, as ``run.py`` builds the program from it,
    holds the shapes the reference reads from the same file."""
    conf = json.loads((granite_root.BENCH / "configs" / "granite-4.0-h-small.json").read_text())
    cfg = run.model_config(conf)
    s = granite_hybrid.sizes(conf)
    assert cfg.n_layers == s["layers"] == 10 and cfg.n_periods == 1
    assert tuple("attention" if k == "attn" else k for k in cfg.layout) == s["kinds"]
    assert cfg.held_experts == (0, s["held"]) == (0, 9)
    assert cfg.moe.n_experts == s["experts"] == 72 and cfg.moe.top_k == s["top_k"] == 10
    assert cfg.moe.d_ff_expert == s["ff"] and cfg.moe.d_ff_shared == s["shared_ff"]
    assert cfg.ssm.n_ssm_heads(cfg.d_model) == s["m_heads"] == 128
    assert (cfg.ssm.head_dim, cfg.ssm.d_state, cfg.ssm.d_conv) == (
        s["m_head_dim"], s["m_state"], s["m_conv"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling,
            cfg.attention_multiplier) == (s["emb_mult"], s["res_mult"], s["logits_div"],
                                          s["attn_mult"])
    assert cfg.position_embedding == "nope" and cfg.mamba_ffn and cfg.moe.dropless
    # 4.83 GB of bf16 weights held on the chip, the cut's reckoning
    assert work_hybrid.param_bytes(s) == pytest.approx(4.83e9, rel=0.01)
    assert cfg.param_counts()["total"] == pytest.approx(2.41e9, rel=0.01)


def _sizes():
    conf = json.loads((granite_root.BENCH / "configs" / "granite-4.0-h-small.json").read_text())
    return granite_hybrid.sizes(conf)


def test_hybrid_work_counts_by_hand():
    s = _sizes()
    d, di = 4096, 8192
    mamba = d * (2 * di + 2 * 128 + 128) + di * d
    assert mamba == 102_236_160  # 102.3 M with the conv, norm and scalars
    ffn = 2 * (d * 72 + 10 * 9 / 72 * 3 * d * 768 + 3 * d * 1536)
    assert work_hybrid.ffn_flops_per_token(s) == pytest.approx(ffn)
    assert work_hybrid.kv_bytes_per_token(s) == 4096
    # a decode step: the weights once, 9 mixers' state (SSD rows in f32,
    # the conv window's last 3 columns in bf16) read and written per
    # sequence, each sequence's own K/V
    state = 128 * 64 * 128 * 4 + 3 * (di + 256) * 2
    ctxs = [2000, 6000]
    _, b = work_hybrid.decode_step(s, ctxs)
    assert b == pytest.approx(work_hybrid.param_bytes(s) + 2 * 2 * 9 * state + 8000 * 4096)


def _record(sizes, peak, modules=None):
    return Record(
        cell="x", sizes=sizes, peak=peak, setup_s=1.0, t_open=0.0, t_close=1.0,
        requests=[], steps=[Step(0, 0.5, [2000, 6000])],
        chunks=[Chunk(rid=0, t0=1024, live=1024, final=True, step=0)], gauges=[],
        counters={}, compiles_in_window=0, queue_wait_ms={},
        trace=Trace(start=0.0, end=1.0, busy_s=0.9, modules=modules or {}, device_ops=[],
                    idle_gaps=[]),
    )


def test_hybrid_mfu_reads_the_hybrid_work():
    read = run.cells.load_reader([], "hybrid_mfu_pct")
    s = _sizes()
    peak = run.peaks("TPU v5 lite", "tpu")
    flops = (work_hybrid.decode_step(s, [2000, 6000])[0]
             + work_hybrid.prefill_chunk(s, 1024, 1024, True))
    assert read(_record(s, peak)) == pytest.approx(100.0 * flops / peak["bf16_flops_per_s"])
    assert 0 < read(_record(s, peak)) < 100
    # a dense configuration's sizes have nothing for it to read
    assert read(_record({"d": 2048}, peak)) is None
    assert read(_record(s, None)) is None


def test_hybrid_decode_step_roofline_reads_the_hybrid_bytes():
    read = run.cells.load_reader([], "hybrid_decode_step_roofline")
    s = _sizes()
    peak = run.peaks("TPU v5 lite", "tpu")
    flops, nbytes = work_hybrid.decode_step(s, [2000, 6000])
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    assert least == nbytes / peak["hbm_bytes_per_s"]  # two sequences: bound by bytes
    # the step's device time twice its least time reads 50 %
    modules = {"jit_decode_step": [(0.2, 0.2 + 2 * least)],
               "jit_prefill_chunk": [(0.3, 0.4)]}
    assert read(_record(s, peak, modules)) == pytest.approx(50.0)
    # no decode program in the trace, no peaks, or a dense stack: nothing to read
    assert read(_record(s, peak)) is None
    assert read(_record(s, None, modules)) is None
    assert read(_record({"d": 2048}, peak, modules)) is None


def test_hybrid_prefill_chunk_ms_reads_the_chunk_programs():
    read = run.cells.load_reader([], "hybrid_prefill_chunk_ms")
    s = _sizes()
    modules = {"jit_prefill_chunk": [(0.1, 0.14), (0.5, 0.56), (1.2, 1.3)],
               "jit_decode_step": [(0.2, 0.21)]}
    # the third chunk starts after the window closes
    assert read(_record(s, None, modules)) == pytest.approx(50.0)
    assert read(_record(s, None)) is None
    assert read(_record({"d": 2048}, None, modules)) is None
