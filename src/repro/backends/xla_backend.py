"""The ``xla`` backend — the SIMD-mode reference substrate.

Pure-jnp implementations of every kernel entry point, compiled by XLA.
Identical math and shapes to the Pallas kernels; this is the multi-pod
**dry-run** path (where the CPU backend cannot lower Mosaic kernels but
FLOP/byte/collective accounting must stay representative) and the universal
fallback that terminates every backend-preference ladder: it supports every
platform, dtype, and shape, which is exactly the paper's "flexible SIMD
substrate catches what the systolic array can't" role.

The memory-behaviour-preserving paths (``chunked_mha``, ``assoc_rglru``,
``mlstm_chunkwise``) lived in :mod:`repro.kernels.ops` before the backend
registry existed; they are re-homed here as this backend's implementations.
The plain oracles come from :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backends.base import Backend
from repro.core.modes import ExecMode
from repro.distributed.sharding import shard as _shard
from repro.kernels import ref as _ref

__all__ = ["XLA", "chunked_mha", "assoc_rglru", "mlstm_chunkwise"]


# --------------------------------------------------------------------------
# XLA-path variants that keep dry-run *memory* behaviour representative.
# --------------------------------------------------------------------------
def chunked_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool, window: Optional[int],
                scale: Optional[float],
                chunk: int = 1024, unroll: bool = False) -> jax.Array:
    """Online-softmax attention as a lax.scan over KV chunks.

    Semantically `ref.mha_ref`, but (a) never materializes the (Sq, Skv)
    score matrix — peak activation is (Sq, chunk) — and (b) uses grouped-head
    einsums so GQA never expands K/V to Hq heads (KV is read once, not
    group-size times).  This is the dry-run path: memory behaviour matches
    what the Pallas flash kernel does on TPU.
    """
    orig_dtype = q.dtype
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    q5 = q.reshape(b, hkv, g, sq, d).astype(jnp.float32) * scale
    q_pos = (jnp.arange(sq) + (skv - sq))[None, None, None, :, None]

    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_chunks = (skv + pad) // chunk
    kc = k.reshape(b, hkv, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        idx, k_blk, v_blk = xs
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q5,
                       k_blk.astype(jnp.float32))
        k_pos = idx * chunk + jnp.arange(chunk)[None, None, None, None, :]
        mask = k_pos < skv
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                       v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, hkv, g, sq, 1), -1e30, jnp.float32),
            jnp.zeros((b, hkv, g, sq, 1), jnp.float32),
            jnp.zeros((b, hkv, g, sq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init,
                                  (jnp.arange(n_chunks), kc, vc),
                                  unroll=unroll)
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, sq, d).astype(orig_dtype)


def assoc_rglru(a: jax.Array, u: jax.Array,
                h0: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """RG-LRU via associative scan: O(log S) depth on the XLA path.

    The recurrence h_t = a_t h_{t-1} + u_t is associative under
    (a1, u1) o (a2, u2) = (a1*a2, u1*a2 + u2), which XLA parallelizes —
    important for the 4k-train and 500k-decode dry-runs.
    """
    orig_dtype = u.dtype
    a32, u32 = a.astype(jnp.float32), u.astype(jnp.float32)
    if h0 is not None:
        # Fold h0 into the first step: h_1 = a_1 (h0) + u_1.
        u32 = u32.at[:, 0, :].add(a32[:, 0, :] * h0.astype(jnp.float32))

    def combine(left, right):
        al, ul = left
        ar, ur = right
        return al * ar, ul * ar + ur

    a_sc, h_sc = jax.lax.associative_scan(combine, (a32, u32), axis=1)
    return h_sc.astype(orig_dtype), h_sc[:, -1, :]


def mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                    log_f: jax.Array, log_i: jax.Array, *,
                    chunk: int, unroll: bool = False,
                    return_state: bool = False):
    """Chunkwise mLSTM in pure jnp — mirror of the Pallas kernel math.

    Same stabilized chunkwise algebra as ``kernels.mlstm`` (lax.scan over
    chunks carrying (C, n, m)); used on the XLA path so the dry-run's memory
    behaviour matches the TPU kernel (per-chunk (L, L) intermediates, never
    (S, S)) and so probe compiles can unroll the chunk loop for exact FLOP
    accounting.
    """
    orig_dtype = q.dtype
    b, h, s_len, d = q.shape
    scale = d ** -0.5
    L = min(chunk, s_len)
    pad = (-s_len) % L
    if pad:
        zpad = ((0, 0), (0, 0), (0, pad), (0, 0))
        q = jnp.pad(q, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        log_f = jnp.pad(log_f, ((0, 0), (0, 0), (0, pad)))
        log_i = jnp.pad(log_i, ((0, 0), (0, 0), (0, pad)),
                        constant_values=-1e30)
    sp = s_len + pad
    n_chunks = sp // L

    def split(t):  # (B,H,S,...) -> (n_chunks, B, H, L, ...)
        return t.reshape(b, h, n_chunks, L, *t.shape[3:]).swapaxes(0, 2) \
                .swapaxes(1, 2)

    # Pin the chunk-stack layout once: without this GSPMD re-lays-out every
    # per-iteration slice (measured 91 collective-permutes/layer on xLSTM —
    # EXPERIMENTS §Perf C2).
    fix = lambda t: _shard(t, None, "batch", None, None, "mlp")
    qc = fix(split(q.astype(jnp.float32) * scale))
    kc = fix(split(k.astype(jnp.float32)))
    vc = fix(split(v.astype(jnp.float32)))
    lfc = split(log_f.astype(jnp.float32))
    lic = split(log_i.astype(jnp.float32))
    tri = jnp.tril(jnp.ones((L, L), jnp.bool_))

    def step(carry, xs):
        c0, n0, m0 = carry               # (B,H,D,D), (B,H,D), (B,H)
        qq, kk, vv, lf, li = xs
        b_cum = jnp.cumsum(lf, axis=-1)                     # (B,H,L)
        a = li - b_cum
        g = jnp.maximum(m0[..., None], jax.lax.cummax(a, axis=2))
        m = b_cum + g
        decay0 = jnp.exp(m0[..., None] - g)                 # (B,H,L)
        s_mat = jnp.einsum("bhld,bhmd->bhlm", qq, kk)
        d_mat = jnp.where(tri, jnp.exp(a[:, :, None, :] - g[..., None]), 0.0)
        sd = s_mat * d_mat
        intra = jnp.einsum("bhlm,bhmd->bhld", sd, vv)
        inter = decay0[..., None] * jnp.einsum("bhld,bhde->bhle", qq, c0)
        num = inter + intra
        qn0 = jnp.einsum("bhld,bhd->bhl", qq, n0)
        den_dot = decay0 * qn0 + jnp.sum(sd, axis=-1)
        den = jnp.maximum(jnp.abs(den_dot), jnp.exp(-m))[..., None]
        out = num / den
        g_last = g[..., -1]
        scale_c = jnp.exp(m0 - g_last)
        w = jnp.exp(a - g_last[..., None])                  # (B,H,L)
        c_new = scale_c[..., None, None] * c0 + jnp.einsum(
            "bhld,bhle->bhde", w[..., None] * kk, vv)
        c_new = _shard(c_new, "batch", None, None, "mlp")  # stable carry
        n_new = scale_c[..., None] * n0 + jnp.sum(w[..., None] * kk, axis=2)
        m_new = b_cum[..., -1] + g_last
        return (c_new, n_new, m_new), _shard(out, "batch", None, None, "mlp")

    init = (jnp.zeros((b, h, d, d), jnp.float32),
            jnp.zeros((b, h, d), jnp.float32),
            jnp.zeros((b, h), jnp.float32))
    final, outs = jax.lax.scan(step, init, (qc, kc, vc, lfc, lic),
                               unroll=unroll)
    out = outs.swapaxes(0, 2).swapaxes(0, 1).reshape(b, h, sp, d)
    out = out[:, :, :s_len].astype(orig_dtype)
    if return_state:
        return out, final  # (C (B,H,D,D), n (B,H,D), m (B,H)) float32
    return out


# --------------------------------------------------------------------------
# Backend op table: the framework-wide per-op argument convention, with the
# kernel-backend-only knobs (block_*, autotune) accepted and ignored.
# --------------------------------------------------------------------------
def _op_sma_gemm(a, b, *, bias=None, epilogue="none",
                 accum_dtype=jnp.float32, precision=None,
                 block_m=None, block_n=None, block_k=None, autotune=False):
    del block_m, block_n, block_k, autotune  # tiling knobs: kernel-only
    return _ref.gemm_ref(a, b, bias=bias, epilogue=epilogue,
                         accum_dtype=accum_dtype, precision=precision)


def _op_rmsnorm_gemm(x, scale, w, *, epilogue="none", eps=1e-6,
                     precision=None, block_m=None, block_n=None,
                     block_k=None):
    del block_m, block_n, block_k
    return _ref.rmsnorm_gemm_ref(x, scale, w, epilogue=epilogue, eps=eps,
                                 precision=precision)


def _op_flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                        block_q=256, block_kv=512, unroll=False,
                        xla_chunk=1024):
    del block_q, block_kv
    return chunked_mha(q, k, v, causal=causal, window=window, scale=scale,
                       unroll=unroll, chunk=xla_chunk)


def _op_decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                         block_s=512):
    del block_s
    return _ref.decode_attention_ref(q, k_cache, v_cache, cache_len,
                                     scale=scale)


def _op_paged_decode_attention(q, k_pool, v_pool, layer, block_table,
                               q_pos, kv_len, *, window=None, scale=None,
                               block_s=512):
    del block_s  # kernel-backend tiling knob
    return _ref.paged_attention_ref(q, k_pool, v_pool, layer, block_table,
                                    q_pos, kv_len, window=window,
                                    scale=scale)


def _op_rglru_scan(a, u, h0=None, *, block_s=256, block_d=256):
    del block_s, block_d
    return assoc_rglru(a, u, h0)


def _op_mlstm_chunkwise(q, k, v, log_f, log_i, *, chunk=128, unroll=False,
                        return_state=False):
    return mlstm_chunkwise(q, k, v, log_f, log_i, chunk=chunk,
                           unroll=unroll, return_state=return_state)


XLA = Backend(
    "xla", ExecMode.SIMD,
    ops={
        "sma_gemm": _op_sma_gemm,
        "rmsnorm_gemm": _op_rmsnorm_gemm,
        "flash_attention": _op_flash_attention,
        "decode_attention": _op_decode_attention,
        "paged_decode_attention": _op_paged_decode_attention,
        "rglru_scan": _op_rglru_scan,
        "mlstm_chunkwise": _op_mlstm_chunkwise,
    },
    platforms=None,   # any
    dtypes=None,      # any
    description="pure-jnp reference paths compiled by XLA (universal "
                "SIMD-mode fallback; dry-run accounting path)",
)
