"""Flash attention (online-softmax) Pallas kernel — train/prefill path.

SMA framing: attention is the canonical *hybrid* layer — two systolic-mode
GEMMs (q@k^T, p@v) separated by SIMD-mode work (scale, mask, online softmax).
A spatially-decoupled design pays an HBM round-trip for the (Sq, Skv) score
matrix; this kernel is the temporal integration of the three phases with the
intermediates pinned in VMEM, switching MXU->VPU->MXU per (q, kv) block pair.

Supports causal masking, sliding-window (local) attention
(recurrentgemma-style), and GQA via the KV-head index map — no KV replication
is materialized.

Grid: (B, Hq, Sq/bq, Skv/bkv), KV innermost with "arbitrary" semantics so the
running (m, l, acc) state is carried in VMEM scratch across KV steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # VPU lane width: scalar-per-row state is kept lane-broadcast


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_kv: int, n_kv: int, q_offset: int,
                  kv_len: int, out_dtype):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level schedule skip (the paper's PE active-mask, block granular):
    # causal => KV blocks entirely in the future contribute nothing;
    # window => KV blocks entirely before the window contribute nothing.
    q_start = iq * block_q + q_offset          # position of first query row
    kv_start = ik * block_kv
    run = jnp.bool_(True)
    if causal:
        run &= kv_start <= q_start + block_q - 1
    if window is not None:
        run &= kv_start + block_kv - 1 > q_start - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bkv, d)
        # systolic phase 1: scores
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # SIMD phase: mask + online softmax
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 1)
        mask = k_pos < kv_len  # padded keys are never valid
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)           # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # (bq, bkv)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        # systolic phase 2: weighted values, accumulated in VMEM
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv",
                     "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 256, block_kv: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Online-softmax attention.  q (B,Hq,Sq,D); k/v (B,Hkv,Skv,D)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}")
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    q_offset = skv - sq  # queries are end-aligned with the KV sequence

    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    pad_q = (-sq) % bq
    pad_kv = (-skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    n_kv = skv_p // bkv
    grid = (b, hq, sq_p // bq, n_kv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_kv=bkv, n_kv=n_kv, q_offset=q_offset,
        kv_len=skv, out_dtype=q.dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running denom l
            pltpu.VMEM((bq, d), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :]


def mxu_constraints(site) -> Optional[str]:
    """Hardware-path capability gate: both systolic passes (q@k^T, p@v)
    contract over head_dim, which must fill MXU half-lanes
    (``d % 64 == 0``) for the Mosaic lowering to be worth the mode switch.
    Misaligned sites ride the chunked-online-softmax SIMD path instead,
    with this reason recorded."""
    d = site.shapes[0][-1]
    if d % 64:
        return (f"shape:head_dim {d} not MXU-aligned "
                f"(hardware flash kernel needs d % 64 == 0)")
    return None
