"""The one traffic generator: a mix file's parameters → a request schedule.

A mix (``traffic/<name>.json``) gives:

- ``loop``: ``"open"`` (requests due on a schedule, whether or not earlier
  ones finished) or ``"closed"`` (``clients`` callers, each sending its next
  request when the reply to its last one is ready);
- ``rate_per_s`` (open loop): Poisson arrivals;
- ``stagger_s`` (closed loop): the clients start one after another over
  this many seconds, so their prompts do not all arrive at once;
- ``prompt`` and ``output``: length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  ``{"dist": "loguniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``;
- ``block``: lengths and gaps are quantile midpoints in blocks of this
  many requests, each block in a well-spread order, so that any run of a
  block's length holds the whole distribution (an open loop's measured
  window is one block of its own);
- ``ramp_s``: seconds the loop runs before the measured window opens;
- ``check_sample``: finished requests the correctness check compares.

The seed draws the prompt token ids (uniform over the vocabulary), and the
weights; the lengths, gaps and their order are the mix's own, the same for
every seed.  A window turns over 7 to 40 requests, and an order drawn from
the seed changed the work of a window by 7-20 % between seeds (PERF.md).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at probabilities ``u`` of the distribution ``spec``."""
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(u.shape, float(spec["value"]))
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        x = np.exp(lo + u * (hi - lo))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", x.max())
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def longest(spec: dict) -> int:
    return int(spec["value"]) if spec["dist"] == "fixed" else int(spec["max"])


def shortest(spec: dict) -> int:
    return int(spec["value"]) if spec["dist"] == "fixed" else int(spec["min"])


# irrationals that step the low-discrepancy order of each drawn quantity
STEPS = {"prompt": (math.sqrt(5) - 1) / 2, "output": math.sqrt(2) - 1, "gap": math.sqrt(3) - 1}


def _strata(n: int, block: int, step: float) -> np.ndarray:
    """``n`` probabilities: each block of ``block`` holds the midpoints
    ``(j + 0.5) / block`` once, ordered by the Kronecker sequence
    ``frac(0.5 + k * step)``, so that any run of consecutive requests
    already spreads over the whole distribution."""
    k = np.arange(-(-n // block) * block)
    keys = np.modf(0.5 + k * step)[0].reshape(-1, block)
    ranks = np.argsort(np.argsort(keys, axis=1), axis=1)
    return ((ranks + 0.5) / block).reshape(-1)[:n]


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the mix plans it (``due`` only in an open loop,
    seconds after the loop starts)."""

    index: int
    prompt_len: int
    output_len: int
    due: float = 0.0


class Mix:
    """The schedule of one run; ``--seed`` draws its prompt ids."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = seed
        self.vocab = vocab
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.block = int(spec["block"])
        self.ramp_s = float(spec["ramp_s"])

    def _lengths(self, n: int, block: int):
        prompts = quantile(self.spec["prompt"], _strata(n, block, STEPS["prompt"]))
        outputs = quantile(self.spec["output"], _strata(n, block, STEPS["output"]))
        return prompts, outputs

    def open_schedule(self, window_s: float, drain_s: float) -> list[Planned]:
        """Poisson arrivals at ``rate_per_s`` (exponential gaps drawn by
        strata), due from the loop's start.  The ramp, the measured window
        and the drain each get a set of their own: the window's
        ``round(rate * window_s)`` requests are one stratum of lengths and
        gaps, with the gaps scaled to span the window exactly."""
        rate = float(self.spec["rate_per_s"])
        out: list[Planned] = []
        t = 0.0
        for span, block in ((self.ramp_s, self.block), (window_s, 0), (drain_s, self.block)):
            n = max(int(round(rate * span)), 1)
            block = block or n
            gaps = -np.log1p(-_strata(n, block, STEPS["gap"]))
            due = t + span * (np.cumsum(gaps) - gaps) / gaps.sum()
            prompts, outputs = self._lengths(n, block)
            out += [Planned(len(out) + i, int(prompts[i]), int(outputs[i]), float(due[i]))
                    for i in range(n)]
            t += span
        return out

    def closed_schedule(self, n: int) -> list[Planned]:
        """The first ``n`` requests the clients take, in order.  Each
        client's first request keeps a share of its output drawn by strata
        (the residual life of a request already running when the loop
        starts), so completions do not arrive in one burst."""
        clients = int(self.spec["clients"])
        prompts, outputs = self._lengths(n, self.block)
        share = _strata(clients, clients, STEPS["output"])
        floor = shortest(self.spec["output"])
        for c in range(min(clients, n)):
            outputs[c] = max(int(outputs[c] * share[c]), min(floor, 8))
        return [Planned(i, int(prompts[i]), int(outputs[i])) for i in range(n)]

    def prompt_ids(self, index: int, length: int) -> list[int]:
        rng = np.random.default_rng([self.seed, 3, index])
        return rng.integers(0, self.vocab, length).tolist()

    def check_rng(self) -> np.random.Generator:
        return np.random.default_rng([self.seed, 4])
