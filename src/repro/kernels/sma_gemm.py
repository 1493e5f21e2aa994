"""SMA GEMM: the paper's semi-broadcast weight-stationary dataflow on the MXU.

TPU adaptation of Sec. III-B / IV-C.  The mapping of the paper's structures:

=====================================  =====================================
paper (GPU substrate)                   this kernel (TPU substrate)
=====================================  =====================================
128x128 ``C_sub`` in the register file  (bm, bn) C accumulator in VMEM scratch
                                        — the *revolving accumulator*: stays
                                        resident across the whole K loop
B subtile stationary in PE buffers      (bk, bn) B block pinned in VMEM for
                                        the MXU pass (weight-stationary)
A element broadcast down a column       the MXU's internal operand broadcast
                                        across the systolic rows — the reason
                                        this dataflow is *native* here
LSMA asynchronous K x 8 x 8 macro-op    one grid step along the K ("arbitrary")
                                        dimension: flexible K, async w.r.t.
                                        the next block's DMA
double-buffered warp sets               Pallas's implicit two-stage pipeline:
                                        block k+1 DMAs HBM->VMEM while block k
                                        runs on the MXU
SIMD epilogue after sync                fused VPU epilogue (bias + activation)
                                        applied while C is still in VMEM —
                                        the temporal mode switch with zero
                                        HBM round-trip
=====================================  =====================================

Block shapes default to ``None`` — resolved per problem shape and dtype by
:func:`repro.kernels.autotune.heuristic_blocks` (multiples of the 128x128
MXU tile and the (8,128) VPU lane grid, clipped to the problem and shrunk to
fit VMEM with headroom for double buffering).  Explicit ``block_*``
arguments always win.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sma import EPILOGUES


def _sma_gemm_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
                     epilogue: str, n_k: int, out_dtype, precision):
    """One (i, j, k) grid step: C_block += A_block @ B_block (+ epilogue)."""
    k_idx = pl.program_id(2)

    # -- systolic phase -----------------------------------------------------
    # Revolving accumulator: zero it on the first K step only (the C block
    # never leaves VMEM between K steps — the paper's RF-resident C_sub).
    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Weight-stationary MXU pass: B block pinned, A streamed through.
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=acc_ref.dtype)

    # -- SIMD (epilogue) phase ----------------------------------------------
    # Temporal mode switch: on the last K step the VPU post-processes the
    # accumulator in place and the result is written once to HBM.
    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        out = acc_ref[...]
        if bias_ref is not None:
            out = out + bias_ref[...].astype(out.dtype)
        out = EPILOGUES[epilogue](out)
        o_ref[...] = out.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "block_m", "block_n", "block_k",
                     "interpret", "accum_dtype", "precision"))
def sma_gemm(a: jax.Array, b: jax.Array, *,
             bias: Optional[jax.Array] = None,
             epilogue: str = "none",
             block_m: Optional[int] = None, block_n: Optional[int] = None,
             block_k: Optional[int] = None,
             interpret: bool = False,
             accum_dtype: jnp.dtype = jnp.float32,
             precision=None) -> jax.Array:
    """``C = epilogue(A @ B + bias)`` via the SMA dataflow Pallas kernel.

    a: (..., M, K); b: (K, N); bias: (N,) or None.  Leading dims of ``a`` are
    collapsed into M (the paper's thread-block grid over the output).
    ``block_*=None`` resolves shape-aware blocks from
    :mod:`repro.kernels.autotune`.
    """
    orig_shape = a.shape
    m_total = 1
    for d in orig_shape[:-1]:
        m_total *= d
    k_dim = orig_shape[-1]
    a2 = a.reshape(m_total, k_dim)
    n_dim = b.shape[1]
    if b.shape[0] != k_dim:
        raise ValueError(f"A/B contraction mismatch: {a.shape} @ {b.shape}")

    from repro.kernels.autotune import resolve_blocks
    block_m, block_n, block_k = resolve_blocks(
        m_total, n_dim, k_dim, a.dtype, block_m, block_n, block_k)
    bm = min(block_m, m_total)
    bn = min(block_n, n_dim)
    bk = min(block_k, k_dim)
    if m_total % bm or n_dim % bn or k_dim % bk:
        # Fall back to padded grid via ceil-div; pad A/B (cheap, traced once).
        pad_m = (-m_total) % bm
        pad_k = (-k_dim) % bk
        pad_n = (-n_dim) % bn
        a2 = jnp.pad(a2, ((0, pad_m), (0, pad_k)))
        b = jnp.pad(b, ((0, pad_k), (0, pad_n)))
        if bias is not None:
            bias = jnp.pad(bias, (0, pad_n))
    mm, kk = a2.shape
    nn = b.shape[1]
    grid = (mm // bm, nn // bn, kk // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # A: streams along K
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # B: stationary per k
    ]
    inputs = [a2, b]
    if bias is not None:
        # (1, N) layout: TPU vector lanes want >=2D blocks.
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        inputs.append(bias.reshape(1, -1))
        kernel = functools.partial(_sma_gemm_kernel, epilogue=epilogue,
                                   n_k=grid[2], out_dtype=a.dtype,
                                   precision=precision)
    else:
        def kernel(a_ref, b_ref, o_ref, acc_ref):
            _sma_gemm_kernel(a_ref, b_ref, None, o_ref, acc_ref,
                             epilogue=epilogue, n_k=grid[2],
                             out_dtype=a.dtype, precision=precision)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), a.dtype),
        # Mosaic's MXU accumulates in 32 bits: a narrower ``accum_dtype``
        # (a bf16 einsum's preferred type) only names the output rounding.
        scratch_shapes=[pltpu.VMEM((bm, bn),
                                   jnp.promote_types(accum_dtype,
                                                     jnp.float32))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)

    out = out[:m_total, :n_dim]
    return out.reshape(*orig_shape[:-1], n_dim)


def mxu_alignment(m: int, n: int, k: int, dtype) -> Optional[str]:
    """Advisory MXU-alignment check for a GEMM site (lint hook, NOT a gate).

    Unlike the attention/recurrence kernels' ``kernel_constraints`` (which
    gate capability — see :meth:`Backend.supports`), ``sma_gemm`` pads any
    shape internally, so misalignment never blocks dispatch; it just wastes
    MXU cycles on padding.  The static analyzer's SMA004 lint consults this
    to flag shapes whose tiles are not multiples of the MXU/VPU lane grid.
    Returns ``None`` when aligned, else a human-readable reason.
    """
    from repro.kernels.autotune import MXU_TILE, _sublane
    sub = _sublane(jnp.dtype(dtype))
    issues = []
    if m % sub:
        issues.append(f"M={m} % sublane({sub})")
    if n % MXU_TILE:
        issues.append(f"N={n} % {MXU_TILE}")
    if k % MXU_TILE:
        issues.append(f"K={k} % {MXU_TILE}")
    if not issues:
        return None
    return ("padded tiles: " + ", ".join(issues)
            + f" nonzero for dtype {jnp.dtype(dtype).name}")
