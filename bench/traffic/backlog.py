"""Arrival kind ``backlog``: a queue that never runs dry.

``pending_per_row`` times the engine's ``max_batch`` requests are kept
waiting for admission, so the engine's rows never wait for work: an
offline job over a corpus.  A request is due when it is released.
"""
from __future__ import annotations


class Arrivals:
    def __init__(self, mix, seed, stream, max_batch) -> None:
        self.stream = stream
        self.pending = int(mix["pending_per_row"]) * max_batch

    def release(self, now_s: float, waiting: int):
        out = []
        while waiting + len(out) < self.pending:
            out.append((now_s, self.stream.next()))
        return out

    def next_due(self):
        return None
