"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

``reduce(path)`` returns a :class:`Trace` holding, on one clock (the
profiler's, in nanoseconds):

- each device's op events (the ``XLA Ops`` line) and their busy union,
- each device's program executions (the ``XLA Modules`` line),
- the host's events (every thread of ``/host:CPU``), among them the
  benchmark's own clock marks (``bench_clock#<i>``), which tie the
  profiler's clock to the host's.

From these it gives the busy and idle time of a span, the device time of
the programs whose names match a pattern, the ops that took most time, and
the longest idle gaps with the host events that overlap them.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Span = Tuple[int, int]  # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MARK = "bench_clock"  # the benchmark's clock marks: no host work


@dataclass
class Device:
    name: str
    ops: List[Tuple[int, int, str]] = field(default_factory=list)
    modules: List[Tuple[int, int, str]] = field(default_factory=list)
    busy: List[Span] = field(default_factory=list)  # merged, sorted


@dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[int, int, str]]  # start, end, name; sorted by start
    window: Span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    # --------------------------------------------------------- busy / idle
    def busy_s(self, spans: Optional[List[Span]] = None) -> float:
        """Seconds in which an op ran, averaged over the devices; within
        ``spans`` (merged) when given, else within the whole window."""
        spans = merge(spans) if spans is not None else [self.window]
        if not self.devices:
            return 0.0
        return sum(overlap(d.busy, spans) for d in self.devices) / len(self.devices) / 1e9

    # ------------------------------------------------------------ programs
    def module_events(self, pattern: str) -> List[Tuple[int, int, str]]:
        rx = re.compile(pattern)
        return sorted((s, e, n) for d in self.devices for s, e, n in d.modules
                      if rx.search(n))

    def module_s(self, pattern: str) -> float:
        return sum(e - s for s, e, _ in self.module_events(pattern)) / 1e9

    def annotations(self, pattern: str) -> List[Tuple[int, int, str]]:
        rx = re.compile(pattern)
        return [h for h in self.host if rx.search(h[2])]

    # ----------------------------------------------------------- breakdown
    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took most device time, summed by program and op
        (``jit__decode_fn/fusion.218 bf16[6144,2,16,128]``).  Ops that hold
        other ops, such as a layer loop's ``while``, are left out: their
        time is their children's."""
        tot: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            ops = sorted(d.ops, key=lambda o: (o[0], -o[1]))
            starts = [m[0] for m in d.modules]
            for i, (s, e, n) in enumerate(ops):
                if i + 1 < len(ops) and ops[i + 1][0] < e:
                    continue  # a container: its children follow inside it
                j = bisect.bisect_right(starts, s) - 1
                mod = _plain_module(d.modules[j][2]) if j >= 0 else "?"
                tot[f"{mod}/{_short_op(n)}"] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9 / len(self.devices)] for n, t in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the first device inside the
        window, each named by the program before it and the host events
        that overlap it most."""
        if not self.devices:
            return []
        d = self.devices[0]
        gaps = [(a, b) for a, b in complement(d.busy, self.window) if b > a]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        mod_starts = [m[0] for m in d.modules]
        out = []
        for a, b in gaps:
            i = bisect.bisect_right(mod_starts, a) - 1
            before = d.modules[i][2] if i >= 0 else "trace start"
            host: Dict[str, int] = defaultdict(int)
            for s, e, n in self.host:
                if s >= b:
                    break
                if e > a and not n.startswith(MARK):
                    host[_plain(n)] += min(e, b) - max(s, a)
            names = [n for n, _ in sorted(host.items(), key=lambda kv: -kv[1])[:3]]
            out.append([f"after {_plain_module(before)}; host: "
                        f"{', '.join(names) or 'nothing'}",
                        (b - a) / 1e9])
        return out


def _plain_module(name: str) -> str:
    """``jit__decode_fn(18269495454191208069)`` → ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def _short_op(name: str) -> str:
    """``%fusion.218 = bf16[6144,2,16,128]{3,1,2,0:...} fusion(...)`` →
    ``fusion.218 bf16[6144,2,16,128]``."""
    m = re.match(r"%?(\S+) = (\w+\[[^\]]*\])?", name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def _plain(name: str) -> str:
    """Host event name without per-call numbering (``fold_in#12``)."""
    return re.sub(r"#\d+$", "", name)


def merge(spans) -> List[Span]:
    out: List[List[int]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: List[Span], b: List[Span]) -> int:
    """Total length of the intersection of two merged, sorted span lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def complement(busy: List[Span], window: Span) -> List[Span]:
    out, t = [], window[0]
    for s, e in busy:
        if e <= window[0] or s >= window[1]:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d.ops = [(int(e.start_ns), int(e.end_ns), e.name)
                             for e in line.events]
                elif line.name == "XLA Modules":
                    d.modules = sorted((int(e.start_ns), int(e.end_ns), e.name)
                                       for e in line.events)
            d.busy = merge((s, e) for s, e, _ in d.ops)
            devices.append(d)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events)
    host.sort()
    starts = [s for d in devices for s, _ in d.busy[:1]] + [h[0] for h in host[:1]]
    ends = [e for d in devices for _, e in d.busy[-1:]] + [max((h[1] for h in host), default=0)]
    window = (min(starts), max(ends)) if starts else (0, 0)
    return Trace(devices, host, window)
