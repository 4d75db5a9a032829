"""Decides a run's ``correct``: what the timed path served, against the
plain reference.

Once the window has closed and the system's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest
one, is run through ``reference.compare``: prompt + served tokens, at their
served lengths.  Each served token's logit lies some distance below the
reference's best at its position, in units of the logits' std; the mean
of these gaps over all compared tokens is compared with the cell's limit
(``limits/<workload>.json``).  The widest gap is reported beside it: it
swings from seed to seed by nature, too far to separate the control
(PERF.md).
Besides, no request may fail, none due in the window may stay unanswered,
and every finished request must carry as many tokens as it asked for.
A control's tokens (``reference.CONTROLS``) go through the same checks.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import reference
import weights
from model_spec import ModelSpec
from traffic_common import Request, host_rng

SAMPLE_TOKENS = 400   # served tokens compared, at least, where there are
SAMPLE_MIN = 4        # requests compared, at least, where there are
SAMPLE_MAX = 12       # requests compared, at most


def sample(requests: Sequence[Request], seed: int) -> List[Request]:
    done = [r for r in requests if r.finished and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.idx))
    order = host_rng(seed, 7).permutation(len(done))
    picked, total = [longest], len(longest.tokens)
    for i in order:
        if len(picked) >= SAMPLE_MAX or (
                total >= SAMPLE_TOKENS and len(picked) >= SAMPLE_MIN):
            break
        if done[i] is not longest:
            picked.append(done[i])
            total += len(done[i].tokens)
    return picked


def gaps(m: ModelSpec, seed: int, picked: Sequence[Request],
         control: Optional[str] = None) -> List[Dict[str, np.ndarray]]:
    return reference.compare(
        m, weights.root_key(seed),
        [{"prompt": r.prompt, "served": r.tokens} for r in picked], control)


def summary(gaps_: Sequence[np.ndarray]) -> Dict[str, float]:
    """Statistics of a run's gaps (all compared tokens together)."""
    g = np.concatenate(list(gaps_)) if gaps_ else np.zeros(0)
    if not g.size:
        return {}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.quantile(g, 0.99)),
            "share_over_0.05": float((g > 0.05).mean()),
            "share_over_0": float((g > 0).mean())}


def checks(limits: dict, picked: Sequence[Request], res, requests,
           unanswered: int) -> Dict[str, dict]:
    """Each compared number beside its limit; ``ok`` for each."""
    n = sum(len(g["gap"]) for g in res)
    mean = (float(sum(float(g["gap"].sum()) for g in res) / n) if n
            else float("nan"))
    wrong_len = sum(1 for r in requests
                    if r.finished and len(r.tokens) != r.max_new + 1)
    failed = sum(1 for r in requests if r.failed)
    lim = limits["gap_mean"]["limit"]
    return {
        "gap_mean": {"value": mean, "limit": lim, "ok": mean <= lim},
        "tokens_compared": {"value": n, "limit": limits["tokens_compared"],
                            "ok": n >= limits["tokens_compared"]},
        "failed": {"value": failed, "limit": 0, "ok": failed == 0},
        "unanswered": {"value": unanswered, "limit": 0, "ok": unanswered == 0},
        "wrong_length": {"value": wrong_len, "limit": 0, "ok": wrong_len == 0},
    }
