"""Shared pieces of the traffic generators.

Lengths are the distribution's quantiles at evenly spaced levels, so
every run gets the same multiset.  Their order is a stratified
permutation: each run of ``block`` consecutive requests holds one value
from each of ``block`` equal strata of the distribution, so that a window
sees a representative mix.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from math import erf, sqrt
from typing import List, Optional

import numpy as np


@dataclass
class Request:
    """One generated request: what the system receives, and when."""

    idx: int
    prompt: List[int]
    max_new: int           # tokens after the first (the prefill's) token
    send_at: float         # seconds after the window opens (backlog: 0)
    # filled in by the client
    sent_t: float = 0.0    # scheduled send time, host clock
    token_t: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done_t: float = 0.0
    failed: Optional[str] = None

    @property
    def finished(self) -> bool:
        return self.done_t > 0 and self.failed is None


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal CDF (bisection; exact to 1e-12)."""
    lo, hi = np.full_like(p, -12.0), np.full_like(p, 12.0)
    cdf = np.vectorize(lambda x: 0.5 * (1 + erf(x / sqrt(2))))
    for _ in range(80):
        mid = (lo + hi) / 2
        below = cdf(mid) < p
        lo, hi = np.where(below, mid, lo), np.where(below, hi, mid)
    return (lo + hi) / 2


def levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a lognormal, rounded to
    a multiple of ``grid`` (default 1) and clipped to ``[min, max]``;
    sorted ascending."""
    z = _norm_ppf(levels(n))
    x = dist["median"] * np.exp(dist["sigma"] * z)
    g = int(dist.get("grid", 1))
    return np.clip(g * np.rint(x / g), dist["min"], dist["max"]).astype(np.int64)


def reachable_lengths(dist: dict) -> range:
    """Every length ``lognormal_lengths`` can give, whatever the seed."""
    g = int(dist.get("grid", 1))
    return range(-(-int(dist["min"]) // g) * g, int(dist["max"]) + 1, g)


def stratified_order(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` (indices into sorted values) in which
    every run of ``block`` consecutive positions takes one index from each
    of ``block`` equal strata."""
    block = max(1, min(block, n))
    strata = [np.arange(n)[(np.arange(n) * block) // n == s] for s in range(block)]
    strata = [rng.permutation(s) for s in strata]
    out = []
    for b in range(max(len(s) for s in strata)):
        members = [s[b] for s in strata if b < len(s)]
        out.extend(rng.permutation(members))
    return np.asarray(out, np.int64)


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, size=int(n)).tolist()


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent host streams of one seed (lengths, order, token ids)."""
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])
