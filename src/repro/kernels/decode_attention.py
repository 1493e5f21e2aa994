"""Decode attention Pallas kernel: one new token vs. a long KV cache.

The decode step is the SIMD-mode-heavy end of serving: tiny GEMMs (one query
row per head group) against a huge cache — memory-bound, with per-request
variable lengths (control flow the paper's Sec. II calls GEMM-incompatible).
SMA treatment: the cache sweep runs as an online-softmax pipeline whose
per-block compute alternates a skinny MXU pass with VPU softmax updates, and
per-request ``cache_len`` drives *block-level skipping* (the active-PE mask of
the paper's systolic controller): blocks past the filled cache are never read
from HBM — with paged/ragged batches this is where decode bandwidth goes.

Layout: grid (B, Hkv, S/bs); each step computes the whole GQA head *group*
(g = Hq/Hkv query rows) for one KV head, so the MXU pass is (g, d) @ (d, bs).
``cache_len`` rides in scalar-prefetch SMEM (PrefetchScalarGridSpec).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, block_s: int, n_s: int, out_dtype):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    cache_len = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_start = ik * block_s

    @pl.when(kv_start < cache_len)  # block-level skip of the empty cache tail
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (g, d)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bs, d)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bs, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (g, bs)
        k_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < cache_len, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_s - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *,
                     scale: Optional[float] = None,
                     block_s: int = 512,
                     interpret: bool = False) -> jax.Array:
    """Single-token GQA attention over a KV cache.

    q (B, Hq, D); k/v_cache (B, Hkv, Smax, D); cache_len (B,) int32.
    Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}")
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    bs = min(block_s, smax)
    pad_s = (-smax) % bs
    if pad_s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    n_s = (smax + pad_s) // bs

    q4 = q.reshape(b, hkv, g, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, ik, lens: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b_, h, ik, lens: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b_, h, ik, lens: (b_, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h, ik, lens: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale, block_s=bs,
                               n_s=n_s, out_dtype=q.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q4, k_cache, v_cache)
    return out.reshape(b, hq, d)


def mxu_constraints(site) -> Optional[str]:
    """Capability gate for the *hardware* (Mosaic-lowered) path.

    The decode kernel's systolic pass is the skinny ``(g, d) @ (d, bs)``
    GEMM per KV head; the hardware path only takes sites whose head_dim
    fills MXU half-lanes (``d % 64 == 0``) — anything skinnier is routed
    down the backend ladder to the SIMD substrate (the paper's
    flexibility escape hatch), with this string as the recorded reason.
    The interpreter path has no such gate: the kernel itself pads.
    """
    d = site.shapes[0][-1]
    if d % 64:
        return (f"shape:head_dim {d} not MXU-aligned "
                f"(hardware decode kernel needs d % 64 == 0)")
    return None


def paged_constraints(site) -> Optional[str]:
    """Capability gate for ``paged_decode_attention`` on the kernel
    backends (both hardware and interpret).

    The kernel path gathers a request's pages and reuses this module's
    single-token decode kernel, so it only takes plain decode sites: a
    chunked-prefill tile (C > 1 query tokens) or a sliding-window site
    needs per-query causal/window masking the decode kernel does not
    express — those resolve down the ladder to the grouped-head SIMD path.
    """
    c = site.shapes[0][1]
    if c != 1:
        return (f"shape:chunked prefill tile (C={c}) needs per-query "
                f"masking (single-token decode kernel only)")
    if site.extra("window") is not None:
        return ("param:sliding-window masking runs on the SIMD paged "
                "path")
    return None
