"""Public kernel entry points, routed through the backend registry.

Dispatch policy (the framework-wide contract): every entry point resolves
its execution backend through :mod:`repro.backends.registry` —

* ``backend="pallas"``   — compiled Pallas TPU kernels (the production
  systolic-mode path).
* ``backend="interpret"``— the same kernels under the Pallas interpreter
  (kernel-logic validation on any platform).  The legacy boolean
  ``interpret=True`` still forces this backend and wins over any
  ``backend=`` preference.
* ``backend="xla"``      — the pure-jnp SIMD-mode reference paths
  (:mod:`repro.kernels.ref` plus the memory-representative variants in
  :mod:`repro.backends.xla_backend`), compiled by XLA.  This is the
  multi-pod **dry-run** path and the universal fallback.
* ``backend=None``/"auto" — the mode ladder: pallas where capable, xla
  otherwise.
* ``backend=("name", ...)`` — an explicit ordered preference ladder; any
  :func:`repro.backends.register_backend` registrant is selectable here
  (and via ``SMAOptions.backend``) with no edits to this module.

Resolution is capability-checked per call *site* (op, shapes, dtypes,
platform): a backend that cannot take a site — wrong platform, unsupported
dtype, non-MXU-aligned shape — is skipped with the reason recorded (plan
reports surface these in their ``backends`` section), and the ladder
terminates on ``xla``, which takes everything.  Every entry point takes the
same arguments under every backend, so models are written once against this
module.

:mod:`repro.compiler` targets this contract from the other direction: its
dispatcher executes traced jaxprs and routes every SYSTOLIC-anchored GEMM
(the ``(..., K) @ (K, N)`` LSMA macro-op shape) through :func:`sma_gemm`
with the same knobs, so compiled models and hand-written models share one
dispatch policy.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.options import current_options
from repro.backends import base as _base
from repro.backends import registry as _registry
from repro.obs import trace as _obs_trace
from repro.resilience import faults as _faults
from repro.resilience import guard as _guard

#: Back-compat aliases: these memory-representative XLA paths lived here
#: before the backend registry re-homed them into
#: :mod:`repro.backends.xla_backend`.  Resolved lazily (PEP 562) to avoid a
#: circular import when the backend module loads first.
_LEGACY_XLA_ALIASES = {
    "_chunked_mha_xla": "chunked_mha",
    "_assoc_rglru_xla": "assoc_rglru",
    "_mlstm_chunkwise_xla": "mlstm_chunkwise",
}


def __getattr__(name: str):
    if name in _LEGACY_XLA_ALIASES:
        from repro.backends import xla_backend
        return getattr(xla_backend, _LEGACY_XLA_ALIASES[name])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _knobs(**explicit: Any) -> Dict[str, Any]:
    """One-read resolution of every kernel knob left unset (``None``)
    against the ambient ``repro.options`` context — the single
    configuration path, shared by all entry points.  Explicit kwargs
    (including falsy ones: ``interpret=False``, ``autotune=False``) always
    beat the ambient value; only ``None`` means *inherit*.

    Resolution happens when the call executes, i.e. at trace time if the
    caller is inside ``jax.jit``: the resolved knobs are baked into that
    trace, and later calls hitting jit's cache will NOT see a changed
    ambient context (``sma_jit`` avoids this by keying its cache on the
    resolved options).
    """
    o = current_options()
    out = {k: (getattr(o, k) if v is None else v)
           for k, v in explicit.items()}
    for flag in ("interpret", "autotune"):
        if flag in out:
            out[flag] = bool(out[flag])
    return out


def _guarded(op: str, site_args: Tuple[Any, ...], backend: Any,
             interpret: bool, make_call, *, attrs: Any = None,
             check_numerics: Optional[str] = None,
             recompute=None, **extras: Any):
    """Failover-guarded kernel launch — the runtime half of the paper's
    in-situ mode switch.

    Resolves the site down its backend-preference ladder
    (:func:`repro.backends.registry.select_backend`, which also skips
    quarantined rungs), fires any injected faults, and catches
    runtime-class failures (``XlaRuntimeError``/OOM, ``NotImplementedError``,
    injected chaos — see :func:`repro.resilience.guard.is_runtime_failure`):
    the failing ``(op, signature, backend)`` tuple is quarantined so later
    calls skip it with zero retry attempts, and the launch retries on the
    next rung, always terminating on the universal ``xla`` backend (whose
    failures, and every non-runtime-class error, propagate).  Outputs pass
    through the ``check_numerics`` numeric guard.
    """
    site = _base.OpSite.from_args(op, site_args, **extras)
    ladder: Any = _registry.normalize_preference(backend, interpret)
    while True:
        be, _ = _registry.select_backend(site, ladder)
        try:
            _faults.maybe_raise(op, be.name)
            span_attrs = attrs(be) if callable(attrs) else dict(attrs or {})
            out = _launch(op, be, make_call(be), **span_attrs)
            out = _faults.corrupt(op, be.name, out)
        except Exception as exc:
            if be.name == "xla" or not _guard.is_runtime_failure(exc):
                raise
            ladder = _guard.next_rung(ladder, be.name)
            _guard.note_runtime_fallback(op, site, be.name, exc,
                                         retry_on=ladder)
            continue
        return _guard.check_numerics_value(
            op, be.name, out,
            recompute if be.name != "xla" else None, check_numerics)


def _launch(op: str, be: _base.Backend, call, **attrs: Any):
    """Run one kernel launch under the name scope ``op``, so every device
    op the launch emits (a page gather, a re-layout, the kernel itself)
    carries the site's name in the compiled program's metadata.  A span
    is recorded when a profile scope or profiler session is active,
    tagged with the resolved :class:`ExecMode` and backend so the exported
    trace lands on the right systolic/SIMD lane; ``attrs`` carries the
    launch-shaping decisions (block sizes, autotune)."""
    with jax.named_scope(op), _obs_trace.span(
            f"kernel.{op}", cat="kernel", mode=be.mode.value,
            backend=be.name, **attrs) as sp:
        out = call()
        return out if sp is None else sp.block(out)


def _mesh_routable(a: jax.Array, b: jax.Array, mesh: Any) -> bool:
    """True when a resolved ``mesh`` knob should route this GEMM through the
    SUMMA collective path: a real multi-device mesh and the LSMA macro-op
    shape (``(..., K) @ (K, N)``)."""
    if mesh is None or mesh is False:
        return False
    if getattr(b, "ndim", 0) != 2 or getattr(a, "ndim", 0) < 2:
        return False
    try:
        from repro.distributed.summa import summa_grid
        _, _, pr, pc = summa_grid(mesh)
    except (TypeError, AttributeError):
        return False
    return pr * pc > 1


def sma_gemm(a: jax.Array, b: jax.Array, *,
             bias: Optional[jax.Array] = None,
             epilogue: str = "none",
             backend: Any = None,
             interpret: Optional[bool] = None,
             accum_dtype: jnp.dtype = jnp.float32,
             precision=None,
             block_m: Optional[int] = None, block_n: Optional[int] = None,
             block_k: Optional[int] = None,
             autotune: Optional[bool] = None,
             mesh: Any = None,
             check_numerics: Optional[str] = None) -> jax.Array:
    """Fused GEMM + bias + activation (the LSMA macro-op).

    Every knob left unset (``None``) resolves from the ambient
    :func:`repro.api.options.current_options` — this entry point is a thin
    shim over the framework-wide :class:`SMAOptions` configuration path.
    ``block_*=None`` then falls back to the shape-aware table in
    :mod:`repro.kernels.autotune`; ``autotune=True`` additionally runs the
    measured search (cached per shape/dtype) on the kernel backends.

    ``mesh`` (a :class:`jax.sharding.Mesh`, or ``SMAOptions.mesh`` via the
    ambient options) routes the call through the multi-device SUMMA
    collective GEMM (:func:`repro.distributed.summa.sma_gemm_sharded`) with
    comm/compute overlap; ``mesh=False`` forces the single-device local
    path (used by the sharded path itself for its per-step tile GEMMs).
    """
    kn = _knobs(backend=backend, interpret=interpret, precision=precision,
                block_m=block_m, block_n=block_n, block_k=block_k,
                autotune=autotune, mesh=mesh, check_numerics=check_numerics)
    mesh_kn = kn.pop("mesh")
    checknum = kn.pop("check_numerics")
    if _mesh_routable(a, b, mesh_kn):
        from repro.distributed.summa import sma_gemm_sharded
        return sma_gemm_sharded(a, b, mesh=mesh_kn, bias=bias,
                                epilogue=epilogue,
                                accum_dtype=accum_dtype,
                                precision=kn["precision"],
                                backend=kn["backend"],
                                interpret=kn["interpret"],
                                block_m=kn["block_m"], block_n=kn["block_n"],
                                block_k=kn["block_k"])
    pref, interp = kn.pop("backend"), kn.pop("interpret")

    def make_call(be):
        return lambda: be.op("sma_gemm")(a, b, bias=bias, epilogue=epilogue,
                                         accum_dtype=accum_dtype, **kn)

    def attrs(be):
        if _obs_trace.current_tracer() is None:
            return {}
        m = 1
        for d in a.shape[:-1]:
            m *= int(d)
        n, k = int(b.shape[-1]), int(b.shape[0])
        out: Dict[str, Any] = {"m": m, "n": n, "k": k,
                               "epilogue": epilogue,
                               "autotune": kn["autotune"]}
        if be.name != "xla":
            # The kernel backends tile; record the blocks the launch
            # resolves to (explicit knobs win, heuristic table fills the
            # rest).
            from repro.kernels import autotune as _autotune
            out["blocks"] = list(_autotune.resolve_blocks(
                m, n, k, a.dtype, kn["block_m"], kn["block_n"],
                kn["block_k"]))
        return out

    def recompute():
        return _registry.get_backend("xla").op("sma_gemm")(
            a, b, bias=bias, epilogue=epilogue, accum_dtype=accum_dtype,
            **kn)

    return _guarded("sma_gemm", (a, b), pref, interp, make_call,
                    attrs=attrs, check_numerics=checknum,
                    recompute=recompute)


def rmsnorm_gemm(x: jax.Array, scale: jax.Array, w: jax.Array, *,
                 epilogue: str = "none", eps: float = 1e-6,
                 backend: Any = None,
                 interpret: Optional[bool] = None,
                 precision=None,
                 block_m: Optional[int] = None, block_n: Optional[int] = None,
                 block_k: Optional[int] = None,
                 check_numerics: Optional[str] = None) -> jax.Array:
    """Fused SIMD-prologue norm + systolic GEMM (SMA prologue fusion).

    Unset knobs resolve from the ambient options, as in :func:`sma_gemm`.
    """
    kn = _knobs(backend=backend, interpret=interpret, precision=precision,
                block_m=block_m, block_n=block_n, block_k=block_k,
                check_numerics=check_numerics)
    checknum = kn.pop("check_numerics")
    pref, interp = kn.pop("backend"), kn.pop("interpret")

    def make_call(be):
        return lambda: be.op("rmsnorm_gemm")(x, scale, w, epilogue=epilogue,
                                             eps=eps, **kn)

    def recompute():
        return _registry.get_backend("xla").op("rmsnorm_gemm")(
            x, scale, w, epilogue=epilogue, eps=eps, **kn)

    return _guarded("rmsnorm_gemm", (x, scale, w), pref, interp, make_call,
                    attrs={"epilogue": epilogue}, check_numerics=checknum,
                    recompute=recompute)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    backend: Any = None,
                    interpret: Optional[bool] = None,
                    block_q: int = 256, block_kv: int = 512,
                    unroll: bool = False,
                    xla_chunk: int = 1024) -> jax.Array:
    """Online-softmax attention (train/prefill)."""
    kn = _knobs(backend=backend, interpret=interpret)
    return _guarded(
        "flash_attention", (q, k, v), kn["backend"], kn["interpret"],
        lambda be: lambda: be.op("flash_attention")(
            q, k, v, causal=causal, window=window, scale=scale,
            block_q=block_q, block_kv=block_kv, unroll=unroll,
            xla_chunk=xla_chunk),
        attrs={"blocks": [block_q, block_kv], "causal": causal})


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *,
                     scale: Optional[float] = None,
                     backend: Any = None,
                     interpret: Optional[bool] = None,
                     block_s: int = 512) -> jax.Array:
    """Single-token GQA attention over a KV cache (decode)."""
    kn = _knobs(backend=backend, interpret=interpret)
    return _guarded(
        "decode_attention", (q, k_cache, v_cache), kn["backend"],
        kn["interpret"],
        lambda be: lambda: be.op("decode_attention")(
            q, k_cache, v_cache, cache_len, scale=scale, block_s=block_s),
        attrs={"blocks": [block_s]})


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           q_pos: jax.Array, kv_len: jax.Array, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           backend: Any = None,
                           interpret: Optional[bool] = None,
                           block_s: int = 512) -> jax.Array:
    """Block-table GQA attention over a paged KV pool (serving).

    q (B, C, Hq, D) — C query tokens per request (C=1: decode; C>1: a
    chunked-prefill tile); k/v_pool (NB, Hkv, BS, D) — the global block
    pool; block_table (B, MB) int32 per-request page ids (entries >= NB
    are unallocated); q_pos (B, C) absolute query positions; kv_len (B,)
    valid lengths including this chunk.  Returns (B, C, Hq, D).

    The kernel backends take single-token non-windowed sites (page gather
    + the existing decode kernel, so block-level cache-tail skipping is
    preserved); chunked and windowed sites resolve down the ladder to the
    grouped-head SIMD path (:func:`repro.kernels.ref.paged_attention_ref`).
    """
    kn = _knobs(backend=backend, interpret=interpret)
    # The site is the float operands only: the int32 block table would
    # fail every kernel backend's dtype gate.
    return _guarded(
        "paged_decode_attention", (q, k_pool, v_pool),
        kn["backend"], kn["interpret"],
        lambda be: lambda: be.op("paged_decode_attention")(
            q, k_pool, v_pool, block_table, q_pos, kv_len,
            window=window, scale=scale, block_s=block_s),
        attrs={"blocks": [block_s], "chunk": int(q.shape[1]),
               "window": window},
        window=window)


def rglru_scan(a: jax.Array, u: jax.Array,
               h0: Optional[jax.Array] = None, *,
               backend: Any = None,
               interpret: Optional[bool] = None,
               block_s: int = 256, block_d: int = 256,
               ) -> Tuple[jax.Array, jax.Array]:
    """Gated linear recurrence h_t = a_t h_{t-1} + u_t (RG-LRU core)."""
    kn = _knobs(backend=backend, interpret=interpret)
    return _guarded(
        "rglru_scan", (a, u), kn["backend"], kn["interpret"],
        lambda be: lambda: be.op("rglru_scan")(a, u, h0, block_s=block_s,
                                               block_d=block_d),
        attrs={"blocks": [block_s, block_d]})


def mlstm_chunkwise(q: jax.Array, k: jax.Array, v: jax.Array,
                    log_f: jax.Array, log_i: jax.Array, *,
                    chunk: int = 128,
                    backend: Any = None,
                    interpret: Optional[bool] = None,
                    unroll: bool = False,
                    return_state: bool = False):
    """Chunkwise-parallel mLSTM (xLSTM matrix memory).

    ``return_state=True`` additionally returns the final (C, n, m) state —
    the prefill path for xLSTM serving.  The Pallas kernels stream outputs
    only, so state-returning sites resolve to the ``xla`` backend via the
    capability check (identical math, tested allclose).
    """
    kn = _knobs(backend=backend, interpret=interpret)
    return _guarded(
        "mlstm_chunkwise", (q, k, v), kn["backend"], kn["interpret"],
        lambda be: lambda: be.op("mlstm_chunkwise")(
            q, k, v, log_f, log_i, chunk=chunk, unroll=unroll,
            return_state=return_state),
        attrs={"chunk": chunk, "return_state": return_state},
        return_state=return_state)
