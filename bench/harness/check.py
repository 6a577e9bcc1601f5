"""Decide ``correct``: served tokens against the plain float32 reference.

After the window closes, a sample of finished requests drawn from the seed
(always with the longest among them) is run once through the reference
over its prompt and its served tokens.  At each served position the
reading is the gap by which the served token's reference logit lies below
the reference's best logit there; the run's number is the widest gap.  A
greedy server that computes what the model states reads a gap at rounding
level; a wrong K/V row, a lost append or an altered token reads far more.

The first served token checks the chunked prefill into the slab pool; the
rest check decode through the paged append and attend, past the slab
boundary wherever a checked sequence is longer than one slab.

``control=True`` also reads the control: the reference itself computed in
float8 (the precision below the configuration's bfloat16), at the same
positions, taking the reference gap of the token that float8 puts first.
"""
from __future__ import annotations

import numpy as np


def sample(drv, rng: np.random.Generator, k: int) -> list:
    """``k`` finished requests: the longest, then one drawn by ``rng`` from
    each of ``k - 1`` equal ranges of decode slots, so that every part of
    the batch is read (a fault in half of the slots cannot hide)."""
    done = [r for r in drv.reqs.values() if r.done and r.first is not None]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.stamps) + r.prompt_len, r.rid))
    pick = [done[-1]]
    slots = drv.eng.B
    for i in range(k - 1):
        lo, hi = i * slots // (k - 1), (i + 1) * slots // (k - 1)
        pool = [r for r in done[:-1] if lo <= drv.eng._requests[r.rid].slot < hi]
        if pool:
            pick.append(pool[int(rng.integers(len(pool)))])
    return pick


def served(drv, reqs: list) -> list[tuple[list[int], list[int]]]:
    """(prompt ids, served tokens) of each request, read from the device."""
    return [(drv.mix.prompt_ids(r.index, r.prompt_len), drv.tokens(r)) for r in reqs]


def readings(ref, params, config: dict, pairs, *, slab_tokens: int,
             control: bool = False) -> dict:
    """Reference readings over ``pairs`` of (prompt, served tokens)."""
    gap = 0.0
    ctl = 0.0
    tokens = 0
    past_slab = 0
    for prompt, toks in pairs:
        seq = prompt + toks[:-1]
        lp = len(prompt)
        rows = np.arange(lp - 1, lp - 1 + len(toks))
        g, logits, _ = ref.logits_at(params, config, seq, rows, toks)
        gap = max(gap, float(np.max(np.asarray(g))))
        tokens += len(toks)
        past_slab += int(np.sum(rows + 1 >= slab_tokens))
        if control:
            _, _, pick = ref.logits_at(params, config, seq, rows, toks, lowp=True)
            top = np.max(np.asarray(logits), axis=-1)
            chosen = np.take_along_axis(np.asarray(logits), np.asarray(pick)[:, None], -1)[:, 0]
            ctl = max(ctl, float(np.max(top - chosen)))
        del logits
    out = {"logit_gap": gap, "tokens_checked": tokens, "tokens_past_slab": past_slab,
           "requests_checked": len(pairs)}
    if control:
        out["control_gap"] = ctl
    return out
