"""What one run leaves for the metric readers, and helpers they share.

A reader is ``metrics/<metric name>.py`` with ``read(run) -> float | None``;
``None`` means it found nothing to read, and the metric is left out.
"""

from __future__ import annotations

import bisect
import math
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from model_spec import ModelSpec
from trace_reduce import Trace, merge
from traffic_common import Request


@dataclass
class Run:
    workload: str
    model: ModelSpec
    serve: dict
    peaks: dict
    seconds: float
    requests: List[Request]
    t0: float                     # window opens (host clock, seconds)
    t1: float                     # window closes
    setup_s: float
    counters0: Dict[str, float]
    counters1: Dict[str, float]
    pages: List[Tuple[float, float, float]] = field(default_factory=list)
    trace: Optional[Trace] = None
    trace_offset_ns: Optional[float] = None  # trace_ns = host_s * 1e9 + offset

    def to_host_s(self, trace_ns: int) -> float:
        return (trace_ns - self.trace_offset_ns) / 1e9


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def tokens_in_window(run: Run) -> List[Tuple[Request, int, float]]:
    """(request, token index, time) of every token delivered in the window."""
    return [(r, i, t) for r in run.requests for i, t in enumerate(r.token_t)
            if run.t0 <= t < run.t1]


def decoding_spans(run: Run) -> List[Tuple[float, float]]:
    """Host-clock spans in which at least one request was decoding (from
    its first token to its last), clipped to the window."""
    spans = [(max(r.token_t[0], run.t0), min(r.token_t[-1], run.t1))
             for r in run.requests if len(r.token_t) > 1]
    return merge([(a, b) for a, b in spans if b > a])


def decoding_contexts(run: Run, t: float) -> List[int]:
    """The K/V length of each request that was decoding at host time
    ``t``: its prompt and the tokens delivered by then, but the last, which
    the step in flight feeds in."""
    out = []
    for r in run.requests:
        n = bisect.bisect_right(r.token_t, t)
        if 1 <= n <= r.max_new:
            out.append(len(r.prompt) + n - 1)
    return out


def mean(values: Sequence[float]) -> Optional[float]:
    return statistics.fmean(values) if values else None
