"""The one general traffic generator: requests from a mix's parameters.

A mix (``bench/traffic/<name>.json``) gives the lengths of its requests and
names its arrival kind, a module ``bench/traffic/<kind>.py`` found by name
(:func:`bench.spec.arrival_kind`).  This module makes the requests; the
kind decides when each one is due.  So a new mix of a known kind is a data
file alone, and a new kind is one file more.

Lengths come in one or more classes (``"classes": [{"share", "prompt",
"output"}, ...]``, or ``"prompt"`` and ``"output"`` for one class), each
lognormal by median and sigma and clipped.  So that every seed offers the
same work, the lengths are not drawn at random: each block of ``block``
requests takes, per class, ``share * block`` quantile points
``(j + 0.5) / n`` of the distributions, paired (prompt with output) and
put in order by a generator of the mix's own, the same for every seed.
The seed only shuffles the requests within each run of ``shuffle``
consecutive ones (the whole block where the mix names none) and draws the
token ids.  A window that serves a few dozen requests then meets the same
sizes, and so the same work, whatever the seed.

Shared prefixes: with ``"prefix": {"length": {...}, "asks": k}`` each run
of ``k`` consecutive requests starts with the same document, its length
stratified in the same way and in the mix's own order, followed by the
request's own prompt.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List

import numpy as np

#: Seed of the generator that pairs and orders a mix's sizes: fixed, so
#: the sizes come in one order for every ``--seed``.
ORDER_SEED = 0


@dataclasses.dataclass
class Draw:
    """One request as generated: its prompt and its output budget."""

    prompt: np.ndarray
    max_new: int


def lognormal_quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` block quantile points of a clipped lognormal, as ints."""
    nd = statistics.NormalDist()
    vals = [dist["median"] * math.exp(dist["sigma"]
                                      * nd.inv_cdf((j + 0.5) / n))
            for j in range(n)]
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def exponential_quantiles(n: int) -> np.ndarray:
    """The ``n`` block quantile points of the unit exponential."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def classes(mix: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The mix's length classes; one with ``share`` 1 where it has none."""
    return mix.get("classes") or [{"share": 1.0, "prompt": mix["prompt"],
                                   "output": mix["output"]}]


class Stream:
    """An endless, seeded stream of requests for one mix."""

    def __init__(self, mix: Dict[str, Any], seed: int, vocab: int) -> None:
        self.vocab = vocab
        self.block = int(mix.get("block", 64))
        self.shuffle = int(mix.get("shuffle", self.block))
        if self.shuffle < 1 or self.block % self.shuffle:
            raise ValueError("shuffle must divide the block")
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(ORDER_SEED)
        self._sizes = []            # per class: (prompt points, outputs)
        for c in classes(mix):
            n = round(c["share"] * self.block)
            self._sizes.append((lognormal_quantiles(c["prompt"], n),
                                lognormal_quantiles(c["output"], n)))
        if sum(len(p) for p, _ in self._sizes) != self.block:
            raise ValueError("class shares must split a block exactly")
        self.prefix = mix.get("prefix")
        if self.prefix:
            self.asks = int(self.prefix["asks"])
            if self.block % self.asks:
                raise ValueError("a prefix's asks must divide the block")
            self._docs = lognormal_quantiles(self.prefix["length"],
                                             self.block // self.asks)
        self._queue: List[Draw] = []

    def _refill(self) -> None:
        pairs = []
        for p, o in self._sizes:
            pairs += zip(self.order.permutation(p),
                         self.order.permutation(o))
        pairs = [pairs[i] for i in self.order.permutation(len(pairs))]
        g = self.shuffle
        pairs = [pairs[i + j] for i in range(0, self.block, g)
                 for j in self.rng.permutation(g)]
        docs = []
        if self.prefix:
            for n in self.order.permutation(self._docs):
                doc = self._tokens(n)
                docs += [doc] * self.asks
        for i, (plen, out) in enumerate(pairs):
            prompt = self._tokens(plen)
            if docs:
                prompt = np.concatenate([docs[i], prompt])
            self._queue.append(Draw(prompt=prompt, max_new=int(out)))

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, int(n), dtype=np.int32)

    def next(self) -> Draw:
        if not self._queue:
            self._refill()
        return self._queue.pop(0)


def arrivals(mix: Dict[str, Any], seed: int, vocab: int, max_batch: int):
    """The mix's arrival kind, built over its stream of requests: an
    object whose ``release(now_s, waiting)`` returns the ``(due_s, Draw)``
    pairs due by ``now_s`` (seconds since the window opened), given the
    number of requests waiting for admission, and whose ``next_due()`` is
    the next due time, or None where the kind releases on demand."""
    from bench import spec
    kind = spec.arrival_kind(mix["arrival"])
    return kind.Arrivals(mix, seed, Stream(mix, seed, vocab), max_batch)
