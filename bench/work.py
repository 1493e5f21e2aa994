"""Operations and bytes that a call needs, from its shapes alone.

The counts use what the work is, never how a program pads or gathers it:
a GEMM's rows are the real tokens of the tick, and decode attention reads
each row's keys and values up to its live ``kv_len``.  So two programs
that implement the same site differently are held to the same work.
Everything is in bf16 (2 bytes), the type the configurations serve in.

These are the calls every model kind is made of; what one tick of a kind
adds up to is in ``bench/models/<kind>.py``.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BYTES = 2


def gemm(m: int, k: int, n: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``(m, k) @ (k, n)``: read both operands once,
    write the product once."""
    return 2.0 * m * k * n, float(BYTES * (m * k + k * n + m * n))


def decode_attention(kv_lens: Iterable[int], heads: int, kv_heads: int,
                     head_dim: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's single-token attention for a batch of
    rows with these live lengths: QK and PV over each row's ``kv_len``
    positions, reading K and V up to it, plus q in and out."""
    kv_lens = list(kv_lens)
    total = float(sum(kv_lens))
    flops = 4.0 * heads * head_dim * total
    nbytes = BYTES * (2.0 * kv_heads * head_dim * total
                      + 2.0 * heads * head_dim * len(kv_lens))
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
