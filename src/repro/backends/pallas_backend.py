"""The ``pallas`` and ``interpret`` backends — the systolic-mode substrate.

``pallas`` is the production path: compiled Pallas TPU kernels (MXU systolic
passes with fused VPU prologues/epilogues).  ``interpret`` runs the *same
kernel logic* through the Pallas interpreter on any platform — before the
backend registry this was a boolean threaded through every entry point; now
it is simply another registrant sharing this op table.

Capability checks implement the paper's efficiency/flexibility balance: the
systolic substrate takes only work it runs *well* (supported float dtypes;
MXU/VPU-aligned shapes for the hardware path), and everything else falls
back down the preference ladder to the SIMD substrate with the reason
recorded.  The shape gates are conservative policy, not kernel inability —
the kernels pad internally — and each lives next to its kernel (the
``mxu_constraints`` / ``kernel_constraints`` hooks in
:mod:`repro.kernels.*`), so kernel and capability knowledge evolve together.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.backends.base import Backend, OpSite
from repro.core.modes import ExecMode

__all__ = ["PALLAS", "INTERPRET", "SUPPORTED_DTYPES"]

#: Dtypes the Pallas kernels are written (and tested) for.
SUPPORTED_DTYPES = frozenset({"float32", "bfloat16", "float16"})


def _ops(interpret: bool):
    """Op table for the Pallas kernels, hardware (False) or interpreted
    (True).  Kernel modules are imported lazily at call time — both to keep
    backend resolution light and so tests may monkeypatch the module
    attributes."""

    def sma_gemm(a, b, *, bias=None, epilogue="none",
                 accum_dtype=jnp.float32, precision=None,
                 block_m=None, block_n=None, block_k=None, autotune=False):
        if autotune and (block_m is None or block_n is None
                         or block_k is None):
            from repro.kernels import autotune as _tune
            m = 1
            for d in a.shape[:-1]:
                m *= d
            bm, bn, bk = _tune.measured_blocks(
                m, b.shape[1], a.shape[-1], a.dtype, interpret=interpret)
            block_m, block_n, block_k = (block_m or bm, block_n or bn,
                                         block_k or bk)
        from repro.kernels.sma_gemm import sma_gemm as _kernel
        return _kernel(a, b, bias=bias, epilogue=epilogue,
                       block_m=block_m, block_n=block_n,
                       block_k=block_k, interpret=interpret,
                       accum_dtype=accum_dtype, precision=precision)

    def rmsnorm_gemm(x, scale, w, *, epilogue="none", eps=1e-6,
                     precision=None, block_m=None, block_n=None,
                     block_k=None):
        from repro.kernels.norm_gemm import rmsnorm_gemm as _kernel
        return _kernel(x, scale, w, epilogue=epilogue, eps=eps,
                       block_m=block_m, block_n=block_n,
                       block_k=block_k, interpret=interpret,
                       precision=precision)

    def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                        block_q=256, block_kv=512, unroll=False,
                        xla_chunk=1024):
        del unroll, xla_chunk  # SIMD-substrate knobs
        from repro.kernels.flash_attention import \
            flash_attention as _kernel
        return _kernel(q, k, v, causal=causal, window=window,
                       scale=scale, block_q=block_q,
                       block_kv=block_kv, interpret=interpret)

    def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                         block_s=512):
        from repro.kernels.decode_attention import \
            decode_attention as _kernel
        return _kernel(q, k_cache, v_cache, cache_len,
                       scale=scale, block_s=block_s, interpret=interpret)

    def paged_decode_attention(q, k_pool, v_pool, block_table, q_pos,
                               kv_len, *, window=None, scale=None,
                               block_s=512):
        # Constraints route chunked (C>1) and windowed sites to xla, so
        # here q is (B, 1, Hq, D) and the site is plain decode: gather the
        # request's pages into a contiguous per-request cache, then run the
        # existing decode kernel (its cache_len block-skip becomes the
        # page-tail skip).
        del q_pos, window
        from repro.kernels.decode_attention import \
            decode_attention as _kernel
        nb, hkv, bs, hd = k_pool.shape
        b = q.shape[0]
        bt = jnp.clip(block_table, 0, nb - 1)
        k = k_pool[bt].transpose(0, 2, 1, 3, 4).reshape(b, hkv, -1, hd)
        v = v_pool[bt].transpose(0, 2, 1, 3, 4).reshape(b, hkv, -1, hd)
        # Temporary: XLA names the kernel's instruction after the scope
        # that directly encloses it.  Inside the serving layer scan that
        # was the body's ``closed_call`` before sites were scoped, and the
        # benchmark's decode-attention roofline still finds the kernel by
        # that name.  ROADMAP §3 ("A scope kept only for a benchmark
        # reader"): the reader moves onto the kernel's scope path and
        # this scope goes, in one change.
        with jax.named_scope("closed_call"):
            out = _kernel(q[:, 0], k, v, kv_len.astype(jnp.int32),
                          scale=scale, block_s=block_s,
                          interpret=interpret)
        return out[:, None]

    def rglru_scan(a, u, h0=None, *, block_s=256, block_d=256):
        from repro.kernels.rglru import rglru_scan as _kernel
        return _kernel(a, u, h0, block_s=block_s, block_d=block_d,
                       interpret=interpret)

    def mlstm_chunkwise(q, k, v, log_f, log_i, *, chunk=128, unroll=False,
                        return_state=False):
        del unroll, return_state  # declined via kernel_constraints -> xla
        from repro.kernels.mlstm import mlstm_chunkwise as _kernel
        return _kernel(q, k, v, log_f, log_i, chunk=chunk,
                       interpret=interpret)

    return {
        "sma_gemm": sma_gemm,
        "rmsnorm_gemm": rmsnorm_gemm,
        "flash_attention": flash_attention,
        "decode_attention": decode_attention,
        "paged_decode_attention": paged_decode_attention,
        "rglru_scan": rglru_scan,
        "mlstm_chunkwise": mlstm_chunkwise,
    }


def _constraints(hardware: bool):
    """Per-op capability checks, sourced from the kernel modules.

    ``hardware=True`` adds the MXU/VPU alignment gates that only matter when
    the kernel actually lowers to Mosaic; the interpreter executes any shape
    the kernel logic can express.
    """

    def decode_attention(site: OpSite):
        from repro.kernels.decode_attention import mxu_constraints
        return mxu_constraints(site) if hardware else None

    def rglru_scan(site: OpSite):
        from repro.kernels.rglru import mxu_constraints
        return mxu_constraints(site) if hardware else None

    def flash_attention(site: OpSite):
        from repro.kernels.flash_attention import mxu_constraints
        return mxu_constraints(site) if hardware else None

    def mlstm_chunkwise(site: OpSite):
        from repro.kernels import mlstm as _mod  # module: no name collision
        why = _mod.kernel_constraints(site)
        if why is None and hardware:
            why = _mod.mxu_constraints(site)
        return why

    def paged_decode_attention(site: OpSite):
        import repro.kernels.decode_attention as _mod  # module, not the fn
        why = _mod.paged_constraints(site)
        if why is None and hardware:
            why = _mod.mxu_constraints(site)
        return why

    return {
        "decode_attention": decode_attention,
        "paged_decode_attention": paged_decode_attention,
        "rglru_scan": rglru_scan,
        "flash_attention": flash_attention,
        "mlstm_chunkwise": mlstm_chunkwise,
    }


PALLAS = Backend(
    "pallas", ExecMode.SYSTOLIC,
    ops=_ops(interpret=False),
    platforms=frozenset({"tpu"}),
    dtypes=SUPPORTED_DTYPES,
    constraints=_constraints(hardware=True),
    description="compiled Pallas TPU kernels (MXU systolic passes, fused "
                "VPU epilogues) — the production path",
)

INTERPRET = Backend(
    "interpret", ExecMode.SYSTOLIC,
    ops=_ops(interpret=True),
    platforms=None,  # the interpreter runs anywhere
    dtypes=SUPPORTED_DTYPES,
    constraints=_constraints(hardware=False),
    description="Pallas kernels under the interpreter — kernel-logic "
                "validation on any platform",
)
