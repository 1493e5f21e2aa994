"""Fused RMSNorm + GEMM Pallas kernel — the SMA prologue fusion.

Every transformer block starts with ``y = rmsnorm(x) @ W`` — a SIMD-mode
normalization feeding a systolic-mode projection.  A spatially-decoupled
schedule writes the normalized activations to HBM and reads them back
(2 × B·S·D bytes per block); this kernel is the paper's temporal integration
applied as a *prologue*: the row statistics are applied on the VPU to the
A-block already resident in VMEM, which then feeds the MXU directly — the
normalized matrix never exists in HBM.

Together with the epilogue fusion in ``sma_gemm`` this closes the mode-switch
loop: SIMD -> systolic -> SIMD with zero HBM round-trips, exactly the SMA
execution model.

The row inverse-RMS ``r = rsqrt(mean(x^2) + eps)`` is a cheap one-pass
reduction computed by the wrapper (XLA fuses it with the producer); the
kernel contracts ``(x * r * scale) @ W`` with a revolving f32 accumulator.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sma import EPILOGUES


def _norm_gemm_kernel(x_ref, r_ref, g_ref, w_ref, o_ref, acc_ref, *,
                      epilogue: str, n_k: int, out_dtype, precision):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # -- SIMD prologue: apply row stats + norm scale to the resident block --
    x = x_ref[...].astype(jnp.float32)
    a = (x * r_ref[...].astype(jnp.float32)
         * g_ref[...].astype(jnp.float32))
    # -- systolic phase ------------------------------------------------------
    acc_ref[...] += jax.lax.dot_general(
        a.astype(x_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=acc_ref.dtype)

    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        out = EPILOGUES[epilogue](acc_ref[...])
        o_ref[...] = out.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "eps", "block_m", "block_n", "block_k",
                     "interpret", "precision"))
def rmsnorm_gemm(x: jax.Array, scale: jax.Array, w: jax.Array, *,
                 epilogue: str = "none", eps: float = 1e-6,
                 block_m: Optional[int] = None, block_n: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: bool = False,
                 precision=None) -> jax.Array:
    """``epilogue(rmsnorm(x; scale) @ w)``.

    x: (..., M, K); scale: (K,); w: (K, N).  ``block_*=None`` resolves
    shape-aware blocks from :mod:`repro.kernels.autotune`.
    """
    orig_shape = x.shape
    k_dim = orig_shape[-1]
    m_total = 1
    for d in orig_shape[:-1]:
        m_total *= d
    x2 = x.reshape(m_total, k_dim)
    n_dim = w.shape[1]

    from repro.kernels.autotune import resolve_blocks
    block_m, block_n, block_k = resolve_blocks(
        m_total, n_dim, k_dim, x.dtype, block_m, block_n, block_k)

    # row statistics (one cheap fused reduction; f32)
    r = jax.lax.rsqrt(
        jnp.mean(jnp.square(x2.astype(jnp.float32)), axis=-1, keepdims=True)
        + eps)

    bm = min(block_m, m_total)
    bn = min(block_n, n_dim)
    bk = min(block_k, k_dim)
    pad_m = (-m_total) % bm
    pad_k = (-k_dim) % bk
    pad_n = (-n_dim) % bn
    if pad_m or pad_k:
        x2 = jnp.pad(x2, ((0, pad_m), (0, pad_k)))
        r = jnp.pad(r, ((0, pad_m), (0, 0)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    if pad_k:
        scale = jnp.pad(scale, (0, pad_k))
    mm, kk = x2.shape
    nn = w.shape[1]
    grid = (mm // bm, nn // bn, kk // bk)

    kernel = functools.partial(_norm_gemm_kernel, epilogue=epilogue,
                               n_k=grid[2], out_dtype=x.dtype,
                               precision=precision)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x block
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # row inv-rms
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),    # norm scale
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # W (stationary)
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, r, scale.reshape(1, -1), w)
    out = out[:m_total, :n_dim]
    return out.reshape(*orig_shape[:-1], n_dim)
