"""Arrival kind ``poisson``: an open loop at ``rate_per_s``.

The gaps are stratified like the lengths: each block of the mix's
``block`` gaps takes the exponential's quantile points in an order drawn
from the seed, so every block holds the same arrivals and its mean rate is
the mix's.  With ``"phases": [[seconds, factor], ...]`` the rate is
``rate_per_s * factor`` in each phase, the phases repeating: on/off bursts.
The unit-rate arrivals are then mapped through the cumulative rate, so the
same seed puts the same requests into the same phases.
"""
from __future__ import annotations

import numpy as np

from bench.generator import exponential_quantiles


class Arrivals:
    def __init__(self, mix, seed, stream, max_batch) -> None:
        rate = mix.get("rate_per_s")
        if not rate or rate <= 0:
            raise ValueError("a poisson mix needs rate_per_s > 0")
        self.stream = stream
        self.rate = float(rate)
        self.phases = [(float(s), float(f))
                       for s, f in mix.get("phases", [[1.0, 1.0]])]
        self.cycle = sum(s * f for s, f in self.phases)
        if self.cycle <= 0:
            raise ValueError("the phases must offer some load")
        self.rng = np.random.default_rng([seed, 2])
        self._gaps = exponential_quantiles(int(mix.get("block", 64)))
        self._queue: list = []
        self._unit = 0.0            # arrival time at factor 1
        self._next = self._draw()

    def _draw(self) -> float:
        if not self._queue:
            self._queue = list(self.rng.permutation(self._gaps))
        self._unit += self._queue.pop(0) / self.rate
        return self._warp(self._unit)

    def _warp(self, u: float) -> float:
        """The time at which the cumulative rate factor reaches ``u``."""
        n, rem = divmod(u, self.cycle)
        t = n * sum(s for s, _ in self.phases)
        for s, f in self.phases:
            if f > 0 and rem <= s * f:
                return t + rem / f
            rem -= s * f
            t += s
        return t

    def release(self, now_s: float, waiting: int):
        out = []
        while self._next <= now_s:
            out.append((self._next, self.stream.next()))
            self._next = self._draw()
        return out

    def next_due(self) -> float:
        return self._next
