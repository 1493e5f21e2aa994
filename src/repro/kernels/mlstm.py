"""Chunkwise-parallel mLSTM Pallas kernel (xLSTM matrix memory).

This kernel is the purest SMA showcase in the framework: *within one layer*
it alternates systolic-mode and SIMD-mode phases several times per chunk —

    SIMD    : cumulative log-gate scan (cumsum / cummax), decay matrices
    SYSTOLIC: S = q k^T                (intra-chunk interactions)
    SIMD    : stabilized decay masking (exp, causal tri mask)
    SYSTOLIC: (S . D) v, q C_prev      (intra + inter chunk outputs)
    SIMD    : denominator floor, normalization
    SYSTOLIC: C += (w . k)^T v         (state update for the next chunk)

all with the matrix memory C (d x d), normalizer n, and stabilizer m resident
in VMEM/SMEM across the whole sequence sweep.  A spatially-decoupled engine
would bounce the (L, L) interaction matrix and the state through HBM at every
mode change.

Math (stabilized chunkwise form; local index j in a chunk, state (C0, n0, m0)
from the previous chunk; b = cumsum(log f), a = log i - b,
g = max(m0, cummax(a)), m = b + g):

    h_j   = [ exp(m0 - g_j) q_j C0 + sum_{s<=j} exp(a_s - g_j) (q_j.k_s) v_s ]
            / max(|exp(m0 - g_j) q_j.n0 + sum_{s<=j} exp(a_s - g_j) q_j.k_s|,
                  exp(-m_j))
    C_L   = exp(m0 - g_L) C0 + sum_s exp(a_s - g_L) k_s v_s^T
    n_L   = exp(m0 - g_L) n0 + sum_s exp(a_s - g_L) k_s
    m_L   = b_L + g_L

which is algebraically identical to the sequential recurrence in
``ref.mlstm_ref`` (tests assert allclose).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, lf_ref, li_ref, o_ref,
                  c_ref, n_ref, m_ref, *,
                  chunk: int, n_chunks: int, scale: float, out_dtype):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[0, 0] = 0.0

    q = q_ref[0, 0].astype(jnp.float32) * scale    # (L, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (L, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (L, d)
    lf = lf_ref[0, 0].astype(jnp.float32)          # (L, 1)
    li = li_ref[0, 0].astype(jnp.float32)          # (L, 1)
    m0 = m_ref[0, 0]
    c0 = c_ref[...]                                # (d, d)
    n0 = n_ref[...]                                # (1, d)

    # ---- SIMD phase: stabilized gate scan -----------------------------------
    b_cum = jnp.cumsum(lf, axis=0)                 # (L, 1)
    a = li - b_cum
    g = jnp.maximum(m0, jax.lax.cummax(a, axis=0))  # (L, 1)
    m = b_cum + g
    decay0 = jnp.exp(m0 - g)                       # (L, 1) inter-chunk decay

    # ---- systolic phase: intra-chunk interactions ---------------------------
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)

    # ---- SIMD phase: causal stabilized decay mask ---------------------------
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    d_mat = jnp.where(col <= row, jnp.exp(a.T - g), 0.0)         # (L, L)
    sd = s * d_mat

    # ---- systolic phase: outputs --------------------------------------------
    intra = jax.lax.dot_general(sd, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    inter = decay0 * jax.lax.dot_general(
        q, c0, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    num = inter + intra                                           # (L, d)

    # ---- SIMD phase: normalization ------------------------------------------
    qn0 = jax.lax.dot_general(q, n0, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (L, 1)
    den_dot = decay0 * qn0 + jnp.sum(sd, axis=1, keepdims=True)
    den = jnp.maximum(jnp.abs(den_dot), jnp.exp(-m))
    o_ref[0, 0] = (num / den).astype(out_dtype)

    # ---- systolic phase: state update for the next chunk --------------------
    g_last = g[chunk - 1, 0]
    scale_c = jnp.exp(m0 - g_last)
    w = jnp.exp(a - g_last)                                       # (L, 1)
    wk = w * k
    c_ref[...] = scale_c * c0 + jax.lax.dot_general(
        wk, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[...] = scale_c * n0 + jnp.sum(wk, axis=0, keepdims=True)
    m_ref[0, 0] = b_cum[chunk - 1, 0] + g_last


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                    log_f: jax.Array, log_i: jax.Array, *,
                    chunk: int = 128, interpret: bool = False) -> jax.Array:
    """Chunkwise mLSTM.  q/k/v (B,H,S,D); log_f/log_i (B,H,S) -> (B,H,S,D)."""
    b, h, s_len, d = q.shape
    scale = d ** -0.5
    L = min(chunk, s_len)
    pad = (-s_len) % L
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        log_f = jnp.pad(log_f, ((0, 0), (0, 0), (0, pad)))
        # Padded steps must not contribute: i = 0 => log_i = -inf (use -1e30).
        log_i = jnp.pad(log_i, ((0, 0), (0, 0), (0, pad)),
                        constant_values=-1e30)
    sp = s_len + pad
    n_chunks = sp // L
    lf4 = log_f[..., None]
    li4 = log_i[..., None]
    grid = (b, h, n_chunks)

    kernel = functools.partial(_mlstm_kernel, chunk=L, n_chunks=n_chunks,
                               scale=scale, out_dtype=q.dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, L, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, L, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b_, h_, ic: (b_, h_, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, d), lambda b_, h_, ic: (b_, h_, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),    # matrix memory C
            pltpu.VMEM((1, d), jnp.float32),    # normalizer n
            pltpu.SMEM((1, 1), jnp.float32),    # stabilizer m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, lf4, li4)
    return out[:, :, :s_len, :]


def kernel_constraints(site) -> Optional[str]:
    """Capability gate shared by the hardware and interpreter paths: the
    Pallas kernel streams outputs only — final (C, n, m) state outputs ride
    the XLA path (identical math, tested allclose), so ``return_state=True``
    sites fall down the backend ladder with this reason recorded."""
    if site.extra("return_state"):
        return "param:return_state (state outputs ride the XLA path)"
    return None


def mxu_constraints(site) -> Optional[str]:
    """Hardware-path gate: the per-chunk (L, d) tiles must fill VPU
    sublanes (``d % 8 == 0``) for the Mosaic lowering."""
    d = site.shapes[0][-1]
    if d % 8:
        return (f"shape:head_dim {d} not sublane-aligned "
                f"(hardware mlstm kernel needs d % 8 == 0)")
    return None
