"""Pure-jnp oracles for every Pallas kernel.

Each function here is the semantic ground truth its kernel twin is tested
against (``tests/test_kernels.py`` sweeps shapes/dtypes with
``assert_allclose``).  They are also the **dry-run execution path**: on the
CPU backend (where Pallas TPU kernels cannot lower) ``kernels.ops`` dispatches
to these — identical math, shapes, and sharding behaviour, so the dry-run's
FLOP/byte/collective accounting stays representative of the TPU program.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sma import EPILOGUES


# --------------------------------------------------------------------------
# GEMM (sma_gemm oracle)
# --------------------------------------------------------------------------
def gemm_ref(a: jax.Array, b: jax.Array, *, bias: Optional[jax.Array] = None,
             epilogue: str = "none",
             accum_dtype: jnp.dtype = jnp.float32,
             precision=None) -> jax.Array:
    """C = epilogue(A @ B + bias), accumulated in ``accum_dtype``.

    As in the kernel, accumulation, bias and epilogue run in at least f32:
    a narrower ``accum_dtype`` (a bf16 einsum's preferred type) only names
    the output rounding.
    """
    accum_dtype = jnp.promote_types(accum_dtype, jnp.float32)
    out = jnp.matmul(a.astype(accum_dtype), b.astype(accum_dtype),
                     precision=precision)
    if bias is not None:
        out = out + bias.astype(accum_dtype)
    out = EPILOGUES[epilogue](out)
    return out.astype(a.dtype)


def rmsnorm_gemm_ref(x: jax.Array, scale: jax.Array, w: jax.Array, *,
                     epilogue: str = "none", eps: float = 1e-6,
                     precision=None) -> jax.Array:
    """epilogue(rmsnorm(x; scale) @ w) — norm_gemm oracle."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)
              * scale.astype(jnp.float32)).astype(x.dtype)
    out = jnp.matmul(normed.astype(jnp.float32), w.astype(jnp.float32),
                     precision=precision)
    out = EPILOGUES[epilogue](out)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (flash_attention / decode_attention oracles)
# --------------------------------------------------------------------------
def _gqa_expand(k: jax.Array, v: jax.Array, num_q_heads: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Repeat KV heads to match query heads (GQA)."""
    num_kv = k.shape[1]
    group = num_q_heads // num_kv
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    return k, v


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
            causal: bool = True, window: Optional[int] = None,
            scale: Optional[float] = None,
            bias: Optional[jax.Array] = None) -> jax.Array:
    """Full-softmax attention oracle.

    Shapes: q (B, Hq, Sq, D); k/v (B, Hkv, Skv, D); returns (B, Hq, Sq, D).
    ``window``: sliding-window size W — query t attends to [t-W+1, t]
    (local attention, recurrentgemma-style).  ``causal`` positions queries at
    the *end* of the KV sequence (Sq may be < Skv for decode).
    """
    orig_dtype = q.dtype
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k, v = _gqa_expand(k, v, q.shape[1])
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q32 * scale, k32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    sq, skv = q.shape[2], k.shape[2]
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)  # queries end-aligned
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v32)
    return out.astype(orig_dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *,
                         scale: Optional[float] = None) -> jax.Array:
    """Single-token GQA attention over a (possibly partially filled) cache.

    q (B, Hq, D); k/v_cache (B, Hkv, Smax, D); cache_len (B,) valid lengths.
    Returns (B, Hq, D).  Grouped-head einsums: the cache is never expanded
    to Hq (each KV head serves its g query rows directly) — this is both the
    oracle and the serving XLA path, where expansion would multiply cache
    bandwidth by the GQA group size.
    """
    orig_dtype = q.dtype
    b, hq, head_dim = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else head_dim ** -0.5
    q4 = q.reshape(b, hkv, g, head_dim).astype(jnp.float32) * scale
    logits = jnp.einsum("bhgd,bhkd->bhgk", q4,
                        k_cache.astype(jnp.float32))
    valid = (jnp.arange(k_cache.shape[2])[None, None, None, :]
             < cache_len[:, None, None, None])
    logits = jnp.where(valid, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, hq, head_dim).astype(orig_dtype)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        layer: jax.Array, block_table: jax.Array,
                        q_pos: jax.Array, kv_len: jax.Array, *,
                        window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """Block-table attention over a paged KV pool (decode + chunked prefill).

    q (B, C, Hq, D) — C query tokens per request (C=1 is plain decode);
    k/v_pool (L, NB, BS, Hkv*D) — the stacked token-major block pools of
    all layers (no batch axis), read at ``layer`` (a () int32); block_table
    (B, MB) int32 — per-request block ids, entries >= NB are unallocated
    padding; q_pos (B, C) — absolute positions of the query tokens; kv_len
    (B,) — valid cache length *including* this chunk.  Returns
    (B, C, Hq, D).

    Each request's pages come out of the pool in one indexed gather,
    ``pool[layer, block_table]``, already token-major (B, MB*BS, Hkv, D):
    no layer's pool is sliced out whole.  Grouped-head einsums like
    :func:`decode_attention_ref` (KV is never expanded to Hq).  Masking
    uses -1e30 rather than -inf so fully-masked rows (batch-padding rows
    with kv_len=0) stay finite instead of NaN.
    """
    orig_dtype = q.dtype
    b, c, hq, head_dim = q.shape
    _, nb, bs, width = k_pool.shape
    mb = block_table.shape[1]
    hkv = width // head_dim
    g = hq // hkv
    scale = scale if scale is not None else head_dim ** -0.5
    # Gather each request's pages; sentinel entries clamp into a real block
    # whose positions the validity mask below excludes.
    bt = jnp.clip(block_table, 0, nb - 1)
    k = k_pool[layer, bt].reshape(b, mb * bs, hkv, head_dim)
    v = v_pool[layer, bt].reshape(b, mb * bs, hkv, head_dim)
    q5 = q.reshape(b, c, hkv, g, head_dim).astype(jnp.float32) * scale
    logits = jnp.einsum("bchgd,bkhd->bchgk", q5, k.astype(jnp.float32))
    k_pos = jnp.arange(mb * bs)
    mask = k_pos[None, None, :] < kv_len[:, None, None]        # valid
    mask &= k_pos[None, None, :] <= q_pos[:, :, None]          # causal
    if window is not None:
        mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bchgk,bkhd->bchgd", probs, v.astype(jnp.float32))
    return out.reshape(b, c, hq, head_dim).astype(orig_dtype)


# --------------------------------------------------------------------------
# RG-LRU (recurrentgemma) oracle: h_t = a_t * h_{t-1} + u_t
# --------------------------------------------------------------------------
def rglru_ref(a: jax.Array, u: jax.Array,
              h0: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Diagonal linear recurrence oracle (sequential scan).

    a, u: (B, S, D) — per-step decay (0..1) and pre-gated input.
    Returns (h_seq (B, S, D), h_last (B, D)).
    """
    orig_dtype = u.dtype
    a32, u32 = a.astype(jnp.float32), u.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)

    def step(h, au):
        a_t, u_t = au
        h = a_t * h + u_t
        return h, h

    h_last, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                              (a32.swapaxes(0, 1), u32.swapaxes(0, 1)))
    return hs.swapaxes(0, 1).astype(orig_dtype), h_last


# --------------------------------------------------------------------------
# mLSTM (xLSTM) oracle: stabilized sequential recurrence.
# --------------------------------------------------------------------------
def mlstm_ref(q: jax.Array, k: jax.Array, v: jax.Array,
              log_f: jax.Array, log_i: jax.Array,
              ) -> jax.Array:
    """Matrix-memory LSTM oracle (sequential, log-space stabilized).

    Recurrence (xLSTM, arXiv:2405.04517):
        C_t = f_t C_{t-1} + i_t k_t v_t^T
        n_t = f_t n_{t-1} + i_t k_t
        h_t = C_t^T q_t / max(|n_t . q_t|, 1)
    with the exp-gate stabilizer m_t = max(log f_t + m_{t-1}, log i_t):
        f'_t = exp(log f_t + m_{t-1} - m_t),  i'_t = exp(log i_t - m_t).

    Shapes: q/k/v (B, H, S, D); log_f/log_i (B, H, S).  Returns (B, H, S, D).
    """
    orig_dtype = q.dtype
    b, h, s, d = q.shape
    scale = d ** -0.5
    q32 = q.astype(jnp.float32) * scale
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    lf = log_f.astype(jnp.float32)
    li = log_i.astype(jnp.float32)

    def step(carry, xs):
        c, n, m = carry  # c (B,H,D,D), n (B,H,D), m (B,H)
        q_t, k_t, v_t, lf_t, li_t = xs
        m_new = jnp.maximum(lf_t + m, li_t)
        f_t = jnp.exp(lf_t + m - m_new)[..., None]
        i_t = jnp.exp(li_t - m_new)[..., None]
        c = f_t[..., None] * c + i_t[..., None] * (k_t[..., None] * v_t[..., None, :])
        n = f_t * n + i_t * k_t
        num = jnp.einsum("bhde,bhd->bhe", c, q_t)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q_t)),
                          jnp.exp(-m_new))[..., None]
        return (c, n, m_new), num / den

    init = (jnp.zeros((b, h, d, d), jnp.float32),
            jnp.zeros((b, h, d), jnp.float32),
            jnp.zeros((b, h), jnp.float32))
    xs = (q32.transpose(2, 0, 1, 3), k32.transpose(2, 0, 1, 3),
          v32.transpose(2, 0, 1, 3), lf.transpose(2, 0, 1),
          li.transpose(2, 0, 1))
    _, hs = jax.lax.scan(step, init, xs)
    return hs.transpose(1, 2, 0, 3).astype(orig_dtype)
