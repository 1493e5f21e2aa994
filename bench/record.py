"""What one run recorded, as the per-layer metric readers see it."""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class Tick:
    """One executed engine tick, timed on the host around ``step()``.

    ``rows`` holds, per real row, ``(start, n)``: the row's first position
    this tick and how many positions it processed (decode: ``n == 1``).
    ``logit_rows`` counts the rows whose logits chose a token.
    """

    t0: float
    t1: float
    phase: str
    rows: List[Tuple[int, int]]
    logit_rows: int
    traced: bool = False

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.rows)

    @property
    def kv_lens(self) -> List[int]:
        return [s + n for s, n in self.rows]


@dataclasses.dataclass
class Req:
    """One request due in the window, timed from its due time."""

    rid: int
    due: float
    t_admit: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Record:
    model: Dict[str, Any]
    peaks: Dict[str, Any]
    t_start: float
    t_end: float
    ticks: List[Tick]
    requests: Dict[int, Req]
    compiles: int
    t_trace: Optional[float] = None   # host time the profiler started
    trace: Any = None   # bench.trace_reduce.Trace of the traced ticks

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def ticks_of(self, phase: str) -> List[Tick]:
        return [t for t in self.ticks if t.phase == phase]

    def median_tick_ms(self, phase: str) -> Optional[float]:
        ms = [t.ms for t in self.ticks_of(phase)]
        return statistics.median(ms) if ms else None

    @property
    def traced_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if t.traced]
