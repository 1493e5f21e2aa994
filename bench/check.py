"""How ``correct`` is decided: the served tokens against the reference.

After the window has closed, a sample of the requests that the window
finished, drawn from the seed and always holding the longest, is run
through the float32 reference (``bench/reference/<kind>.py``): each prompt
with the tokens that were served after it.  At every served position the
gap is how far the served token's reference logit lies below the
reference's best.  Greedy decoding that computes what the reference
computes picks the best or a near-tie, so the widest gap over the sample
stays small; a wrong layer, a stale cache or an altered token lands far
below the best.  The mean gap over the sample is compared too: it
separates the int8 control from the program by more than the widest gap
does, which random weights' near-ties widen.  The limits are in
``bench/workloads/<cell>.json``, set from the readings recorded in
PERF.md.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def sample(finished: Sequence[dict], seed: int,
           tokens_wanted: int, max_requests: int) -> List[dict]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``tokens_wanted`` served tokens are in the sample."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (len(r["prompt"])
                                            + len(r["tokens"]), r["rid"]))
    longest = order[-1]
    rest = order[:-1]
    rng = np.random.default_rng([seed, 1])
    picked = [longest]
    n = len(longest["tokens"])
    for i in rng.permutation(len(rest)):
        if n >= tokens_wanted or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i]["tokens"])
    return picked


def sequences(reqs: Sequence[dict]):
    """Per request: the tokens the reference reads (prompt, then every
    served token but the last) and the rows whose logits chose the served
    tokens."""
    seqs, rows = [], []
    for r in reqs:
        p, t = np.asarray(r["prompt"]), np.asarray(r["tokens"], np.int32)
        seqs.append(np.concatenate([p, t[:-1]]).astype(np.int32))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(t)))
    return seqs, rows


def gaps(ref_rows: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Reference best minus the reference logit of each chosen token."""
    best = ref_rows.max(axis=-1)
    return best - ref_rows[np.arange(len(chosen)), chosen]


def served_gaps(ref: List[np.ndarray], reqs: Sequence[dict]) -> np.ndarray:
    return np.concatenate([gaps(lg, np.asarray(r["tokens"]))
                           for lg, r in zip(ref, reqs)]) \
        if reqs else np.zeros((0,))


def control_gaps(ref: List[np.ndarray],
                 low: List[np.ndarray]) -> np.ndarray:
    """At the same positions: the gap of the token that the lower
    precision puts first."""
    return np.concatenate([gaps(r, lo.argmax(axis=-1))
                           for r, lo in zip(ref, low)]) \
        if ref else np.zeros((0,))


def numbers(gap: np.ndarray, failed: int, limits: Dict) -> Dict[str, dict]:
    """Each number compared, with its limit and the side it must keep."""
    widest: Optional[float] = float(gap.max()) if len(gap) else None
    mean: Optional[float] = float(gap.mean()) if len(gap) else None
    return {
        "failed_requests": {"value": failed, "limit": 0, "at_most": True},
        "tokens_compared": {"value": int(len(gap)),
                            "limit": limits["tokens_compared_min"],
                            "at_most": False},
        "served_gap_max": {"value": widest,
                           "limit": limits["served_gap_max"],
                           "at_most": True},
        "served_gap_mean": {"value": mean,
                            "limit": limits["served_gap_mean"],
                            "at_most": True},
    }


def passed(nums: Dict[str, dict]) -> bool:
    for n in nums.values():
        v = n["value"]
        if v is None:
            return False
        if n["at_most"] and not v <= n["limit"]:
            return False
        if not n["at_most"] and not v >= n["limit"]:
            return False
    return True
