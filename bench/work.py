"""Operations and bytes that a call needs, from its shapes alone.

The counts use what the work is, never how a program pads or gathers it:
a GEMM's rows are the real tokens of the tick, and decode attention reads
each row's keys and values up to its live ``kv_len``.  So two programs
that implement the same site differently are held to the same work.
Everything is in bf16 (2 bytes), the type the configurations serve in.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

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


def layer_gemms(m: Dict) -> List[Tuple[int, int]]:
    """(K, N) of the GEMMs of one dense ``attn`` layer: q, k, v, o, and
    the gated MLP's in, gate and out."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, ff), (d, ff), (ff, d)]


def gemm_least_time(m: Dict, tokens: int, peaks: Dict) -> float:
    """Least seconds one tick's layer GEMMs could take on the chip, over
    its ``tokens`` real rows: each call bounded by the larger of its FLOPs
    over peak and its bytes over bandwidth."""
    t = 0.0
    for k, n in layer_gemms(m):
        f, b = gemm(tokens, k, n)
        t += m["num_hidden_layers"] * least_time(f, b, peaks)
    return t


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def layer_params(m: Dict) -> int:
    return sum(k * n for k, n in layer_gemms(m))


def tick_flops(m: Dict, rows, logit_rows: int) -> float:
    """Model FLOPs of one tick, ``rows`` as ``(start, n)`` real positions:
    per position 2 x the layer parameters it multiplies and QK and PV over
    its context, plus the head for each of ``logit_rows``."""
    layers = m["num_hidden_layers"]
    per_pos = 2.0 * layers * layer_params(m)
    attn = 4.0 * layers * m["num_attention_heads"] * m["head_dim"]
    f = 0.0
    for start, n in rows:
        contexts = n * start + n * (n + 1) // 2
        f += per_pos * n + attn * contexts
    return f + logit_rows * 2.0 * m["hidden_size"] * m["vocab_size"]


def attention_least_time(m: Dict, kv_lens, peaks: Dict) -> float:
    """Least seconds of one decode tick's attention over every layer."""
    f, b = decode_attention(kv_lens, m["num_attention_heads"],
                            m["num_key_value_heads"], m["head_dim"])
    return m["num_hidden_layers"] * least_time(f, b, peaks)
