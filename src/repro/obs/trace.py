"""Contextvar-scoped span/event tracer — the runtime half of the SMA story.

The compiler's plan reports describe what the stack *intends* to do; this
module records what it actually *did*: engine calls and compiles, each
compile stage, dispatcher mode regions, kernel launches (with their chosen
backend, :class:`~repro.core.modes.ExecMode`, and resolved block sizes), and
the serving/training drivers' steps.  The contract:

* **Strictly off by default.**  No tracer is installed unless the program is
  inside a :func:`profile` scope; every instrumentation site reduces to one
  ``ContextVar.get()`` returning ``None``, one check that no JAX profiler
  session is recording, and a shared no-op context manager, so disabled
  tracing costs well under a microsecond per site and records nothing.
* **On the profiler's clock.**  Whenever a JAX profiler session is
  recording (``jax.profiler.trace`` / ``start_trace``, or an xprof capture),
  every span also opens a ``jax.profiler.TraceAnnotation`` of the same name
  carrying its scalar arguments, with or without a :func:`profile` scope.
  The program's spans then sit in the profiler trace beside the device ops
  they launched, so each idle gap on the device can be attributed to the
  host work that was running.
* **Never part of the compile-cache key.**  Tracing state lives in a
  contextvar here, NOT in :class:`repro.api.options.SMAOptions` — enabling a
  profile can never fragment the engine's executable cache (asserted in
  ``tests/test_obs.py``).
* **Honest about async dispatch.**  JAX dispatch is asynchronous: a span
  around an un-synchronized kernel call measures *enqueue* wall time, not
  device time.  ``profile(sync=True)`` inserts ``jax.block_until_ready`` at
  span boundaries (where the value is concrete) so walls are device-honest;
  every event carries a ``synced`` flag so the export layer can label
  async-dispatch walls as such.

Usage::

    with repro.profile(path="trace.json", sync=True) as prof:
        engine(x)                       # spans recorded
    prof.runtime_section()              # measured per-mode time + switches
    print(prof.timeline_text())         # two-lane ASCII mode timeline
    # trace.json is Chrome-trace JSON: open in Perfetto / chrome://tracing
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "profile", "span", "current_tracer",
           "last_tracer", "recording"]

#: True while a JAX profiler session is recording (a C++ flag read).
recording = TraceAnnotation.is_enabled


def _scalars(args: Dict[str, Any]) -> Dict[str, Any]:
    """The arguments a profiler event can carry: numbers and strings."""
    return {k: v for k, v in args.items()
            if isinstance(v, (int, float, str))}


class _Annotation:
    """A span seen only by the JAX profiler (no :func:`profile` scope is
    active): the same ``annotate``/``block`` surface as :class:`Span`."""

    __slots__ = ("_me",)

    def __init__(self, name: str, args: Dict[str, Any]) -> None:
        self._me = TraceAnnotation(name, **_scalars(args))

    def __enter__(self) -> "_Annotation":
        self._me.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._me.__exit__(*exc)

    def annotate(self, **attrs: Any) -> None:
        self._me.set_metadata(**_scalars(attrs))

    def block(self, value: Any) -> Any:
        return value


class Span:
    """One open span.  Created by :meth:`Tracer.span`; appended to the
    tracer's event list (as a Chrome-trace-shaped dict) when the ``with``
    scope exits."""

    __slots__ = ("tracer", "name", "cat", "mode", "args", "_start",
                 "_profiled")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 mode: Optional[str], args: Dict[str, Any]) -> None:
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.mode = mode
        self.args = args
        self._start = 0.0
        self._profiled: Optional[_Annotation] = None

    @property
    def sync(self) -> bool:
        return self.tracer.sync

    def annotate(self, **attrs: Any) -> None:
        """Attach attributes discovered mid-span (e.g. cache hit/miss)."""
        self.args.update(attrs)
        if self._profiled is not None:
            self._profiled.annotate(**attrs)

    def block(self, value: Any) -> Any:
        """Synchronize on ``value`` at the span boundary when the tracer is
        in ``sync`` mode, so the recorded wall is device time rather than
        async-dispatch enqueue time.  Tracers (abstract values inside a
        ``jax.jit`` / ``lax.scan`` trace) cannot be blocked on — those spans
        keep their enqueue walls and are marked unsynced."""
        if not self.tracer.sync:
            return value
        try:
            import jax
            jax.block_until_ready(value)
            self.args.setdefault("synced", True)
        except Exception:
            self.args["synced"] = False
        return value


class Tracer:
    """An in-memory event buffer with a monotonic clock.

    Events are plain dicts already shaped like Chrome-trace ``"X"`` slices
    (``name``/``cat``/``ts``/``dur`` in microseconds, plus the SMA-specific
    ``mode`` used for lane assignment and the mode-timeline aggregation).
    """

    def __init__(self, path: Optional[str] = None, sync: bool = False
                 ) -> None:
        self.path = path
        self.sync = sync
        self.events: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self.total_us: Optional[float] = None

    # ------------------------------------------------------------- clock
    def now_us(self) -> float:
        """Microseconds since the tracer was created."""
        return (time.perf_counter() - self._t0) * 1e6

    # ----------------------------------------------------------- writing
    def add_event(self, name: str, *, cat: str = "host", ts: float,
                  dur: float, mode: Optional[str] = None,
                  **args: Any) -> None:
        """Append one completed slice (used by aggregating instrumentation
        like the dispatcher's SIMD-region tracking, which cannot use a
        ``with`` scope)."""
        self.events.append({"name": name, "cat": cat, "ts": ts, "dur": dur,
                            "mode": mode, "args": args})

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "host",
             mode: Optional[str] = None, **args: Any) -> Iterator[Span]:
        sp = Span(self, name, cat, mode, dict(args))
        if recording():
            sp._profiled = _Annotation(name, args).__enter__()
        sp._start = self.now_us()
        try:
            yield sp
        finally:
            end = self.now_us()
            if sp._profiled is not None:
                sp._profiled.__exit__(None, None, None)
            if not self.sync:
                sp.args.setdefault("synced", False)
            self.events.append({"name": sp.name, "cat": sp.cat,
                                "ts": sp._start, "dur": end - sp._start,
                                "mode": sp.mode, "args": sp.args})

    def instant(self, name: str, *, cat: str = "host", **args: Any) -> None:
        """A zero-duration marker event."""
        self.events.append({"name": name, "cat": cat, "ts": self.now_us(),
                            "dur": 0.0, "mode": None, "ph": "i",
                            "args": args})

    # ----------------------------------------------------------- reading
    def chrome_trace(self) -> Dict[str, Any]:
        from repro.obs.export import chrome_trace
        return chrome_trace(self.events)

    def save(self, path: Optional[str] = None) -> str:
        from repro.obs.export import write_chrome_trace
        target = path or self.path
        if target is None:
            raise ValueError("no path given to Tracer.save and the tracer "
                             "was created without one")
        write_chrome_trace(self.events, target)
        return target

    def runtime_section(self) -> Dict[str, Any]:
        from repro.obs.export import runtime_section
        return runtime_section(self.events, sync=self.sync,
                               total_us=self.total_us)

    def timeline_text(self, width: int = 64) -> str:
        from repro.obs.export import render_mode_timeline
        return render_mode_timeline(self.runtime_section(), width=width)

    def __repr__(self) -> str:
        return (f"Tracer(events={len(self.events)}, sync={self.sync}, "
                f"path={self.path!r})")


_ACTIVE: contextvars.ContextVar[Optional[Tracer]] = contextvars.ContextVar(
    "repro_obs_tracer", default=None)

#: The most recent tracer (active or already closed) — lets plan reports
#: stamp their ``runtime`` section after the ``profile`` scope has exited.
_LAST: Optional[Tracer] = None


def current_tracer() -> Optional[Tracer]:
    """The tracer installed by an enclosing :func:`profile`, else ``None``.
    This is THE fast path every instrumentation site starts with."""
    return _ACTIVE.get()


def last_tracer() -> Optional[Tracer]:
    """The active tracer if any, else the most recently closed one."""
    return _ACTIVE.get() or _LAST


@contextlib.contextmanager
def profile(path: Optional[str] = None, *, sync: bool = False
            ) -> Iterator[Tracer]:
    """Record spans for everything inside the scope.

    ``path`` (optional) writes a Chrome-trace JSON on exit — load it in
    Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``: systolic and
    SIMD work render as two pseudo-thread lanes, so the paper's temporal
    mode schedule is literally visible as one lane going quiet while the
    other runs.  ``sync=True`` blocks at span boundaries for device-honest
    walls (adds synchronization overhead; off by default).

    Tracing state never touches :class:`~repro.api.options.SMAOptions`, so
    profiling cannot fragment any engine's compile cache.
    """
    global _LAST
    tracer = Tracer(path=path, sync=sync)
    token = _ACTIVE.set(tracer)
    _LAST = tracer
    try:
        yield tracer
    finally:
        tracer.total_us = tracer.now_us()
        _ACTIVE.reset(token)
        if path is not None:
            tracer.save(path)


#: Reusable no-op context manager for disabled-tracing call sites
#: (``contextlib.nullcontext`` is stateless, hence shareable).
_NULL = contextlib.nullcontext()


def span(name: str, *, cat: str = "host", mode: Optional[str] = None,
         **args: Any):
    """``with obs.span(...) as sp`` — records iff a profile is active, and
    lands in the JAX profiler's trace iff a profiler session is recording.

    Disabled cost is one contextvar read, one :func:`recording` check and a
    shared ``nullcontext``; ``sp`` is ``None`` when disabled, so
    conditional annotations read ``if sp is not None: sp.annotate(...)``.
    """
    tracer = _ACTIVE.get()
    if tracer is not None:
        return tracer.span(name, cat=cat, mode=mode, **args)
    if recording():
        return _Annotation(name, args)
    return _NULL
