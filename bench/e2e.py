"""End-to-end metrics, taken by the benchmark on the host's clock.

Every request is timed from its due time, so a late generator or a stall
before submission shows.  Percentiles are numpy's linear interpolation
over every sample of the window; nothing is averaged over chunks.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

from bench.record import Record


def output_tok_s(rec: Record) -> float:
    """Output tokens emitted during the window over the window."""
    n = sum(1 for r in rec.requests.values() for t in r.token_times
            if t <= rec.t_end)
    return n / rec.seconds


def ttft_samples(rec: Record) -> List[float]:
    """Time to first token of every request due in the window; one with
    no first token by the window's end counts the time it has waited."""
    out = []
    for r in rec.requests.values():
        first = r.token_times[0] if r.token_times else None
        end = first if first is not None and first <= rec.t_end \
            else rec.t_end
        out.append(end - r.due)
    return out


def itl_samples(rec: Record) -> List[float]:
    """Every gap between consecutive tokens of one request, both emitted
    in the window."""
    out = []
    for r in rec.requests.values():
        ts = [t for t in r.token_times if t <= rec.t_end]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def ttft_p75_s(rec: Record) -> float:
    """p75: the highest percentile with ten samples beyond it in a window
    of some forty requests."""
    return float(np.percentile(ttft_samples(rec), 75))


def itl_p95_ms(rec: Record) -> float:
    return float(np.percentile(itl_samples(rec), 95)) * 1e3


#: Metrics read from the window; ``setup_s`` is timed by the harness.
WINDOW: Dict[str, Callable[[Record], float]] = {
    "output_tok_s": output_tok_s,
    "ttft_p75_s": ttft_p75_s,
    "itl_p95_ms": itl_p95_ms,
}
