"""The yardstick: work counts at published widths against hand counts, and
the traffic generator's strata."""
from __future__ import annotations

import json

import numpy as np
import pytest

from benchroot import BENCH

from harness import traffic, work
from reference import qwen2


def _sizes(name: str) -> dict:
    return qwen2.sizes(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def test_kv_bytes_per_token_by_hand():
    # qwen2.5-3b: 36 layers × (K, V) × 2 heads × 128 × 2 B
    assert work.kv_bytes_per_token(_sizes("qwen2.5-3b")) == 36 * 2 * 2 * 128 * 2 == 36_864
    # qwen1.5-0.5b: 24 layers × (K, V) × 16 heads × 64 × 2 B
    assert work.kv_bytes_per_token(_sizes("qwen1.5-0.5b")) == 24 * 2 * 16 * 64 * 2 == 98_304


def test_matmul_params_per_token_by_hand():
    s = _sizes("qwen2.5-3b")
    layer = 2048 * (16 + 2 + 2) * 128 + 16 * 128 * 2048 + 3 * 2048 * 11008
    assert layer == 77_070_336
    head = 151_936 * 2048  # tied table used as the output head
    assert work.body_matmul_params(s) + work.head_params(s) == 36 * layer + head
    assert 3.08e9 < 36 * layer + head < 3.09e9


def test_param_bytes_by_hand():
    # qwen1.5-0.5b: 464 M parameters with the head tied, 0.93 GB in bf16
    nbytes = work.param_bytes(_sizes("qwen1.5-0.5b"))
    assert 0.92e9 < nbytes < 0.94e9
    # qwen2.5-3b: 6.17 GB of bf16 weights
    assert 6.16e9 < work.param_bytes(_sizes("qwen2.5-3b")) < 6.19e9


def test_decode_and_prefill_counts():
    s = _sizes("qwen2.5-3b")
    flops, nbytes = work.decode_step(s, [100, 2000])
    per_tok = 2 * (work.body_matmul_params(s) + work.head_params(s))
    assert flops == 2 * per_tok + 4 * 36 * 16 * 128 * 2100
    assert nbytes == work.param_bytes(s) + 2100 * 36_864
    # a chunk at t0 attends causally over t0 + 1 .. t0 + live keys
    f = work.prefill_chunk(s, 1024, 3, final=False)
    assert f == 3 * 2 * work.body_matmul_params(s) + 4 * 36 * 16 * 128 * (1025 + 1026 + 1027)
    assert work.prefill_chunk(s, 0, 1, final=True) - work.prefill_chunk(s, 0, 1, final=False) \
        == 2 * work.head_params(s)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.least_time(flops, nbytes, peak) == pytest.approx(nbytes / 819e9)


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_every_seed_offers_the_same_work(mix):
    """Lengths and gaps are the mix's own; the seed draws the prompt ids."""
    spec = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    a = traffic.Mix(spec, 2**33 + 1, 151_936)
    b = traffic.Mix(spec, 7, 151_936)
    if spec["loop"] == "open":
        ramp, window = spec["ramp_s"], 50.0
        sa, sb = a.open_schedule(window, 60.0), b.open_schedule(window, 60.0)
        inside = [p for p in sa if ramp <= p.due < ramp + window]
        # the window is one stratum of its own, spanning it exactly
        assert len(inside) == round(spec["rate_per_s"] * window)
        assert inside[0].due == ramp
        gaps = np.diff([p.due for p in sa])
        assert abs(gaps.mean() * spec["rate_per_s"] - 1) < 0.05
        # consecutive requests spread over the distribution
        first = sorted(p.prompt_len for p in inside[:8])
        assert first[0] < spec["prompt"]["median"] < first[-1]
    else:
        sa, sb = a.closed_schedule(160), b.closed_schedule(160)
    assert sa == sb
    assert a.prompt_ids(0, 50) != b.prompt_ids(0, 50)
    assert a.prompt_ids(3, 5) == traffic.Mix(spec, 2**33 + 1, 151_936).prompt_ids(3, 5)
    assert max(a.prompt_ids(3, 4000)) < 151_936
    for p in sa:
        assert traffic.shortest(spec["prompt"]) <= p.prompt_len <= traffic.longest(spec["prompt"])
        assert p.output_len <= traffic.longest(spec["output"])
