"""``sma_jit`` / :class:`Engine` — the SMA stack's single front door.

``compiler.compile_model`` bound a model to ONE trace of fixed shapes: every
new batch or sequence length paid the full trace → lower → plan → rewrite
pipeline again, and callers had to manage the resulting ``CompiledModel``
objects by hand.  The engine makes the compiled pipeline behave like
``jax.jit``:

* ``repro.sma_jit(fn, options=...)`` returns an :class:`Engine` — a callable
  that lazily compiles on first call and caches executables keyed by the
  **abstract signature** ``(pytree structure, per-leaf (shape, dtype,
  weak_type), static kwargs, resolved options)``.  Steady-state calls with a
  signature already seen skip trace/plan/rewrite entirely and go straight to
  the cached executable (shape-polymorphic caching: one engine serves every
  batch size, each compiled once).
* cache hits/misses and compile wall-time are tracked per entry and per
  engine; every cached :class:`CompiledModel`'s plan report carries an
  ``"engine"`` section with its hit count and amortized compile time, so the
  report never hides the compile bill.
* ``static_argnames`` marks keyword arguments as compile-time constants
  (hashable, baked into the trace), exactly like ``jax.jit``.

Example::

    import repro

    @repro.sma_jit
    def mlp(x, w1, w2):
        return jnp.tanh(x @ w1) @ w2

    mlp(x8, w1, w2)    # compiles (miss) for batch 8
    mlp(x8, w1, w2)    # cache hit: zero re-trace/re-plan work
    mlp(x64, w1, w2)   # new signature -> compiles once for batch 64
    mlp.stats          # EngineStats(misses=2, hits=1, ...)

    with repro.options(backend="interpret"):
        mlp(x8, w1, w2)  # different resolved options -> its own entry
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.api_util import shaped_abstractify as _abstractify

from repro.api.options import SMAOptions, resolve_options
from repro.obs import metrics as _metrics
from repro.obs import trace as _obs_trace
from repro.resilience import faults as _faults
from repro.resilience import guard as _res_guard

__all__ = ["Engine", "EngineStats", "sma_jit", "abstract_signature"]


def abstract_signature(flat_leaves) -> Tuple[Any, ...]:
    """Per-leaf ``(shape, dtype, weak_type)`` triples — the shape-polymorphic
    half of the cache key (mirrors ``jax.jit``'s signature abstraction)."""
    sig = []
    for leaf in flat_leaves:
        try:
            aval = _abstractify(leaf)
        except (TypeError, ValueError) as exc:
            raise TypeError(
                f"sma_jit argument leaf {leaf!r} is not a JAX type; mark "
                f"the containing keyword argument static via "
                f"sma_jit(..., static_argnames=...)") from exc
        sig.append((tuple(aval.shape), str(aval.dtype),
                    bool(getattr(aval, "weak_type", False))))
    return tuple(sig)


@dataclasses.dataclass
class EngineStats:
    """Cache + compile accounting for one engine."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    compile_time_s: float = 0.0

    @property
    def calls(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        return self.hits / self.calls if self.calls else 0.0

    @property
    def amortized_compile_s(self) -> float:
        """Compile seconds amortized over every call so far — the number
        that should trend to ~0 in steady-state serving."""
        return self.compile_time_s / self.calls if self.calls else 0.0

    def asdict(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "calls": self.calls, "hit_rate": self.hit_rate,
                "compile_time_s": self.compile_time_s,
                "amortized_compile_s": self.amortized_compile_s}


@dataclasses.dataclass
class _CacheEntry:
    compiled: Any                  # compiler.dispatch.CompiledModel
    hits: int = 0
    compile_time_s: float = 0.0


#: The engine-boundary ``check_numerics="fallback"`` overlay: recompute the
#: whole call on the pure reference path, with the guard off (the recompute
#: must not recurse) and fusion off (the spatially-decoupled baseline).
_REFERENCE_FALLBACK = SMAOptions(backend="xla", interpret=False,
                                 fuse_runtime=False, check_numerics="off")


class Engine:
    """Shape-polymorphic compile cache around the SMA compiler pipeline.

    Construct via :func:`sma_jit`.  Follows JAX's usual single-thread-per-
    trace model: concurrent calls from multiple threads may duplicate a
    compile for the same signature (last write wins) but never corrupt the
    cache.
    """

    def __init__(self, fn: Callable, *, options: Optional[SMAOptions] = None,
                 static_argnames: Tuple[str, ...] = (),
                 name: Optional[str] = None) -> None:
        functools.update_wrapper(self, fn,
                                 assigned=("__module__", "__name__",
                                           "__qualname__", "__doc__"),
                                 updated=())
        self.fn = fn
        self.options = options
        self.static_argnames = tuple(static_argnames)
        self.name = name or getattr(fn, "__name__", None) or "model"
        self.stats = EngineStats()
        # Insertion/use-ordered: ``max_cache_entries`` evicts from the front
        # (least recently used), hits ``move_to_end``.
        self._cache: "collections.OrderedDict[Any, _CacheEntry]" = \
            collections.OrderedDict()

    # ------------------------------------------------------------- keying
    def _split_static(self, kwargs: Dict[str, Any]):
        static = {}
        dynamic = dict(kwargs)
        for name in self.static_argnames:
            if name in dynamic:
                static[name] = dynamic.pop(name)
        return static, dynamic

    def _key(self, args, dyn_kwargs, static, opts: SMAOptions):
        flat, in_tree = jax.tree_util.tree_flatten((args, dyn_kwargs))
        try:
            static_key = tuple(sorted(static.items()))
            hash(static_key)
        except TypeError as exc:
            raise TypeError(
                f"static argument values must be hashable, got {static!r}"
            ) from exc
        return (in_tree, abstract_signature(flat), static_key,
                opts.cache_key())

    # ------------------------------------------------------------ compile
    def _lookup(self, args, kwargs, overlay: Optional[SMAOptions] = None
                ) -> Tuple[_CacheEntry, Dict[str, Any], bool, SMAOptions]:
        opts = resolve_options(self.options, overlay)
        static, dyn_kwargs = self._split_static(kwargs)
        key = self._key(args, dyn_kwargs, static, opts)
        entry = self._cache.get(key)
        if entry is not None:
            # Hot path: counters only — report stamping happens lazily when
            # the report is read (CompiledModel.report refresh hook).
            self._cache.move_to_end(key)
            self.stats.hits += 1
            entry.hits += 1
            _metrics.inc("engine.cache_hits")
            return entry, dyn_kwargs, True, opts

        from repro.compiler.dispatch import compile_with_options
        fn = functools.partial(self.fn, **static) if static else self.fn
        t0 = time.perf_counter()
        with _obs_trace.span("engine.compile", cat="engine",
                             engine=self.name), _faults.compile_scope():
            # Compile-time fault probe: ``engine.compile`` specs (kind
            # compile_error via the scope above, or runtime_error/latency)
            # model a signature whose kernels fail to build.
            _faults.maybe_raise("engine.compile", self.name)
            compiled = compile_with_options(fn, *args, name=self.name,
                                            options=opts, **dyn_kwargs)
        dt = time.perf_counter() - t0
        entry = _CacheEntry(compiled=compiled, compile_time_s=dt)
        # The one shared stamping path: compile(), the report property, and
        # any obs snapshot all read CompiledModel.report, which re-runs this
        # hook — hit counts and amortized compile time are always current.
        compiled.report_refresh = functools.partial(
            self._refresh_report, entry)
        self._cache[key] = entry
        self.stats.misses += 1
        self.stats.compile_time_s += dt
        _metrics.inc("engine.cache_misses")
        _metrics.observe("engine.compile_s", dt)
        limit = opts.max_cache_entries or 0
        while limit > 0 and len(self._cache) > limit:
            self._cache.popitem(last=False)
            self.stats.evictions += 1
            _metrics.inc("engine.cache_evictions")
        return entry, dyn_kwargs, False, opts

    def _refresh_report(self, entry: _CacheEntry,
                        rep: Dict[str, Any]) -> None:
        """Restamp the live sections of one entry's plan report (called on
        every ``CompiledModel.report`` access)."""
        calls = max(entry.hits + 1, 1)
        rep["engine"] = {
            "cache_hits": entry.hits,
            "compile_time_s": entry.compile_time_s,
            "amortized_compile_s": entry.compile_time_s / calls,
            "engine_stats": self.stats.asdict(),
        }
        # The measured half of the plan: aggregate the active (or most
        # recent) profile window into a ``runtime`` section next to the
        # static ``mode_switches``/``mode_flop_histogram`` numbers.  The
        # profile scope is the attribution boundary — runs of other engines
        # inside the same scope contribute to the same timeline.
        tracer = _obs_trace.last_tracer()
        if tracer is not None and tracer.events:
            rep["runtime"] = tracer.runtime_section()
        rep["resilience"] = _res_guard.resilience_section()

    # ------------------------------------------------------------- public
    def _run(self, args, kwargs,
             overlay: Optional[SMAOptions] = None) -> Tuple[Any, bool]:
        """Lookup + execute + engine-boundary numeric guard.

        The guard here sees *concrete* outputs even under ``jit=True``
        (kernel-site checks are skipped on tracers), so ``check_numerics=
        "fallback"`` can recompute the whole call on the reference path —
        done via a re-lookup with an ``xla`` overlay, which compiles (and
        caches) its own entry and never recurses further.
        """
        with _obs_trace.span("engine.lookup", cat="engine"):
            entry, dyn_kwargs, hit, opts = self._lookup(args, kwargs,
                                                        overlay)
        with _obs_trace.span("engine.dispatch", cat="engine"):
            out = entry.compiled(*args, **dyn_kwargs)
        policy = opts.check_numerics
        if policy in (None, "off"):
            return out, hit
        recompute = None
        if overlay is None:
            recompute = lambda: self._run(args, kwargs,  # noqa: E731
                                          _REFERENCE_FALLBACK)[0]
        out = _res_guard.check_numerics_value(
            f"engine.{self.name}", "engine", out, recompute, policy)
        return out, hit

    def __call__(self, *args, **kwargs):
        if (_obs_trace.current_tracer() is None
                and not _obs_trace.recording()):
            return self._run(args, kwargs)[0]
        with _obs_trace.span("engine.call", cat="engine",
                             engine=self.name) as sp:
            out, hit = self._run(args, kwargs)
            if sp is not None:      # None: the profiler stopped meanwhile
                sp.annotate(cache="hit" if hit else "miss")
                out = sp.block(out)
            return out

    def compile(self, *args, **kwargs):
        """Compile (or fetch) the executable for this signature WITHOUT
        running it — arguments may be ``jax.ShapeDtypeStruct`` placeholders.
        Returns the cached :class:`CompiledModel`."""
        return self._lookup(args, kwargs)[0].compiled

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def clear_cache(self) -> None:
        self._cache.clear()

    @property
    def report(self) -> Dict[str, Any]:
        """Engine-level report: cache stats + one summary per entry."""
        entries = []
        for key, entry in self._cache.items():
            in_tree, sig, static_key, _ = key
            entries.append({
                "signature": [list(s) for s in sig],
                "static": [list(kv) for kv in static_key],
                "cache_hits": entry.hits,
                "compile_time_s": entry.compile_time_s,
                "fused_sites": len(entry.compiled.fused_sites),
                "mode_switches":
                    entry.compiled.summary.mode_switches,
                "diagnostics": {
                    k: entry.compiled.report_data.get(
                        "diagnostics", {}).get(k, 0)
                    for k in ("errors", "warnings", "infos")
                },
                "backends": entry.compiled.report_data.get("backends"),
            })
        return {"engine": self.name, "cache": self.stats.asdict(),
                "entries": entries}

    def __repr__(self) -> str:
        return (f"Engine({self.name}, entries={len(self._cache)}, "
                f"hits={self.stats.hits}, misses={self.stats.misses})")


def sma_jit(fn: Optional[Callable] = None, *,
            options: Optional[SMAOptions] = None,
            static_argnames=(), name: Optional[str] = None):
    """Decorate ``fn`` with the SMA engine (shape-polymorphic compile cache).

    Usable bare (``@sma_jit``), parametrized (``@sma_jit(options=...)``),
    or as a call (``engine = sma_jit(fn, options=...)``).  ``options`` is a
    partial :class:`SMAOptions` overlay resolved against the ambient
    ``repro.options(...)`` context at each call.
    """
    if isinstance(static_argnames, str):
        static_argnames = (static_argnames,)

    def wrap(f: Callable) -> Engine:
        return Engine(f, options=options,
                      static_argnames=tuple(static_argnames), name=name)

    return wrap if fn is None else wrap(fn)
