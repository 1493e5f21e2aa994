"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The traced stretch is the benchmark's own ``bench.traced`` host span.  On
each device plane the ``XLA Ops`` line holds one event per operation that
ran.  Busy time is the union of those intervals inside the stretch,
averaged over the devices; the rest is idle.  Each idle gap is named by
the innermost of the benchmark's host spans that covers its middle
(``engine.step``, ``bench.wait``, ...), so an idle share says what the
host was doing meanwhile.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import List, Optional, Sequence, Tuple

#: The benchmark's host spans (see :mod:`bench.serve`).
HOST_SPANS = ("bench.traced", "bench.generate", "bench.submit",
              "engine.step", "bench.wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Trace:
    """Device ops (name, start, end) per device, and the host spans, in
    nanoseconds on the trace's clock, clipped to the traced stretch."""

    lo: int
    hi: int
    ops: List[List[Tuple[str, int, int]]]
    spans: List[Tuple[str, int, int]]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Union of op intervals in the stretch, averaged over devices."""
        if not self.ops:
            return 0.0
        total = sum(_length(union([(s, e) for _, s, e in dev]))
                    for dev in self.ops)
        return total / len(self.ops) * 1e-9

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the ops whose name matches ``pattern``,
        averaged over devices."""
        rx = re.compile(pattern)
        if not self.ops:
            return 0.0
        total = sum(e - s for dev in self.ops for n, s, e in dev
                    if rx.search(n))
        return total / len(self.ops) * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` ops that took the most device time of their own
        (a loop's time less the ops inside it), named by op and output
        shape with the instance number dropped, in seconds averaged over
        devices."""
        by = collections.Counter()
        for dev in self.ops:
            for name, t in self_times(dev):
                by[short_name(name)] += t
        k = max(len(self.ops), 1)
        return [[name, t / k * 1e-9] for name, t in by.most_common(n)]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle seconds on device 0, summed by the host span that covers
        each gap's middle, largest first."""
        if not self.ops:
            return []
        busy = union([(s, e) for _, s, e in self.ops[0]])
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.hi > t:
            gaps.append((t, self.hi))
        by = collections.Counter()
        for s, e in gaps:
            by[self.covering_span((s + e) // 2)] += e - s
        return [[name, t * 1e-9] for name, t in by.most_common(n)]

    def covering_span(self, t: int) -> str:
        best: Optional[Tuple[int, str]] = None
        for name, s, e in self.spans:
            if name != "bench.traced" and s <= t < e:
                if best is None or e - s < best[0]:
                    best = (e - s, name)
        return best[1] if best else "none"


def short_name(hlo: str) -> str:
    """``%copy.53 = bf16[16,160]{...} copy(...)`` -> ``%copy bf16[16,160]``."""
    op, _, rest = hlo.partition(" = ")
    op = re.sub(r"\.\d+$", "", op)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{op} {shape}".strip()


def self_times(ops: Sequence[Tuple[str, int, int]]
               ) -> List[Tuple[str, int]]:
    """Each op's duration less that of the ops nested inside it (the
    trace puts a loop's body ops inside the loop's own event)."""
    out: List[Tuple[str, int]] = []
    stack: List[list] = []          # [name, end, own time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    out.extend((n, t) for n, _, t in stack)
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def _clip(s: int, e: int, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler`` and clip it to
    the ``bench.traced`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns))
                                    for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    window = [(s, e) for n, s, e in spans if n == "bench.traced"]
    if not window:
        raise ValueError(f"{path}: no bench.traced span")
    lo, hi = window[0]
    ops = []
    for dev in devices:
        kept = []
        for name, s, e in dev:
            c = _clip(s, e, lo, hi)
            if c:
                kept.append((name, c[0], c[1]))
        ops.append(kept)
    return Trace(lo=lo, hi=hi, ops=ops, spans=spans)
