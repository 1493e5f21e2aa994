"""Guarded execution: runtime failure classification, failover accounting,
numeric guards, and the plan report's ``resilience`` section.

The failover *loop* lives at the launch site (:mod:`repro.kernels.ops`);
this module supplies its policy pieces:

* :func:`is_runtime_failure` — which exceptions mean "this backend cannot
  run this site right now" (retry the next rung) vs a programming error
  (propagate).  Runtime-class: ``JaxRuntimeError`` (incl. XLA's
  ``RESOURCE_EXHAUSTED`` / OOM texts), ``NotImplementedError``, and
  :class:`~repro.resilience.faults.InjectedFault`.
* :func:`note_runtime_fallback` — one call per failed rung: quarantines the
  ``(op, signature, backend)`` tuple, bumps metrics, records an event, and
  warns once per (op, backend) so chaos logs stay readable.
* :func:`check_numerics_value` — the ``SMAOptions.check_numerics`` policy
  (``"off" | "log" | "raise" | "fallback"``) applied to one launch output;
  under ``"fallback"`` the site recomputes on the reference ``xla`` path.
* :func:`resilience_section` — the runtime-fallback/numeric/quarantine
  ledger stamped into plan reports next to the static ``backends`` section,
  so *forced* mode switches are as inspectable as planned ones.

Counters are mirrored into :mod:`repro.obs.metrics` (the asserted surface)
and kept locally for the report section (surviving ``metrics.reset()``).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from jax.errors import JaxRuntimeError

from repro.obs import metrics as _metrics
from repro.resilience import quarantine as _quarantine
from repro.resilience.faults import InjectedFault

__all__ = ["is_runtime_failure", "note_runtime_fallback", "next_rung",
           "check_numerics_value", "resilience_section", "record_event",
           "warn_once", "RetryPolicy", "reset", "EVENTS"]

NUMERIC_POLICIES = ("off", "log", "raise", "fallback")

#: Substrings in a RuntimeError message that mark an XLA runtime failure
#: even when the exception type is opaque.
_RUNTIME_MESSAGE_MARKS = ("RESOURCE_EXHAUSTED", "out of memory", "OOM",
                          "INTERNAL:", "UNIMPLEMENTED")


def is_runtime_failure(exc: BaseException) -> bool:
    """True when ``exc`` is a runtime-class launch failure worth retrying on
    the next backend rung (vs a programming error that must propagate)."""
    if isinstance(exc, (InjectedFault, NotImplementedError)):
        return True
    if isinstance(exc, JaxRuntimeError):
        return True
    if isinstance(exc, (RuntimeError, MemoryError)):
        msg = str(exc)
        return isinstance(exc, MemoryError) or \
            any(mark in msg for mark in _RUNTIME_MESSAGE_MARKS)
    return False


def next_rung(ladder: Sequence[str], failed: str) -> Tuple[str, ...]:
    """The remaining preference ladder after ``failed`` — always non-empty,
    terminating on the universal ``xla`` rung."""
    ladder = tuple(ladder)
    if failed in ladder:
        ladder = ladder[ladder.index(failed) + 1:]
    return ladder or ("xla",)


# --------------------------------------------------------------------------
# Event ledger (feeds the report's ``resilience`` section)
# --------------------------------------------------------------------------
EVENTS: "collections.deque[Dict[str, Any]]" = collections.deque(maxlen=256)
_COUNTS: Dict[str, float] = {}
_WARNED: set = set()
_LOCK = threading.Lock()


def _count(name: str, n: float = 1) -> None:
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n
    _metrics.inc(f"resilience.{name}", n)


def record_event(kind: str, **fields: Any) -> None:
    EVENTS.append({"kind": kind, **fields})


def warn_once(key: str, message: str) -> None:
    """Warn the first time ``key`` is seen — repeated runtime fallbacks in a
    serving loop (or a chaos run) would otherwise flood the log."""
    with _LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    warnings.warn(message, RuntimeWarning, stacklevel=3)


def note_runtime_fallback(op: str, site: Any, backend: str,
                          exc: BaseException,
                          retry_on: Sequence[str]) -> None:
    """Account one failed rung: quarantine, count, record, warn-once."""
    reason = f"runtime:{type(exc).__name__} on '{backend}'"
    _quarantine.add(op, site.shapes, site.dtypes, backend,
                    reason=f"{type(exc).__name__}: {exc}")
    _count("runtime_fallbacks")
    _count("failover_attempts")
    _metrics.inc(f"resilience.runtime_fallback.{op}")
    record_event("runtime_fallback", op=op, backend=backend,
                 reason=reason, error=str(exc),
                 shapes=[list(s) for s in site.shapes],
                 retry_on=list(retry_on))
    warn_once(f"runtime_fallback:{op}:{backend}",
              f"{op} failed at runtime on backend '{backend}' "
              f"({type(exc).__name__}: {exc}); quarantined, retrying on "
              f"{tuple(retry_on)} (further occurrences suppressed)")


# --------------------------------------------------------------------------
# Numeric guards
# --------------------------------------------------------------------------
def _nonfinite_leaves(value: Any) -> List[str]:
    """Names of non-finite concrete float leaves in ``value`` (empty under
    tracing — abstract values cannot be inspected; the engine boundary
    re-checks concrete outputs)."""
    import jax
    import jax.numpy as jnp
    from jax import core as jax_core

    bad: List[str] = []
    leaves_paths = jax.tree_util.tree_flatten_with_path(value)[0]
    for path, leaf in leaves_paths:
        if isinstance(leaf, jax_core.Tracer):
            continue
        if not hasattr(leaf, "dtype") or \
                not jnp.issubdtype(leaf.dtype, jnp.inexact):
            continue
        if not bool(jnp.isfinite(leaf).all()):
            bad.append(jax.tree_util.keystr(path) or "<out>")
    return bad


def check_numerics_value(op: str, backend: str, value: Any,
                         recompute: Optional[Callable[[], Any]],
                         policy: Optional[str]) -> Any:
    """Apply the ``check_numerics`` policy to one launch output.

    ``recompute`` re-runs the site on the reference ``xla`` path (used by
    ``"fallback"``); sites without one degrade ``"fallback"`` to raising,
    so a poisoned value never silently propagates.
    """
    if policy in (None, "off"):
        return value
    if policy not in NUMERIC_POLICIES:
        raise ValueError(f"check_numerics={policy!r} "
                         f"(one of {NUMERIC_POLICIES})")
    bad = _nonfinite_leaves(value)
    if not bad:
        return value
    _count("numeric_events")
    record_event("numeric_guard", op=op, backend=backend, leaves=bad,
                 policy=policy)
    msg = (f"{op} produced non-finite output on backend '{backend}' "
           f"(leaves {bad})")
    if policy == "log":
        warn_once(f"numeric:{op}:{backend}", msg + " [check_numerics=log]")
        return value
    if policy == "raise" or recompute is None:
        raise FloatingPointError(msg)
    warn_once(f"numeric:{op}:{backend}",
              msg + "; recomputing on the xla reference path")
    out = recompute()
    _count("numeric_fallbacks")
    _metrics.inc(f"resilience.numeric_fallback.{op}")
    return out


# --------------------------------------------------------------------------
# Serving policy + report section
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry + backoff for failure-isolated serving.

    ``max_retries`` is per *request*: a poisoned request is evicted (marked
    failed) once its budget is spent, while other slots keep decoding.
    ``deadline_s`` is the watchdog bound on one admit/tick (soft: an XLA
    launch cannot be preempted mid-flight, so an overrun is counted and
    warned rather than interrupted).
    """

    max_retries: int = 1
    backoff_s: float = 0.0
    deadline_s: Optional[float] = None


def resilience_section(*, max_events: int = 20) -> Dict[str, Any]:
    """The runtime resilience ledger for plan reports.

    Process-scoped by design (like the backend registry and the quarantine
    it reports on): one section shows every forced fallback since the last
    :func:`reset`, refreshed on each report read.
    """
    with _LOCK:
        counts = dict(_COUNTS)
    events = list(EVENTS)
    injected: Dict[str, int] = {}
    snap = _metrics.snapshot()["counters"]
    for name, n in snap.items():
        if name.startswith("resilience.injected."):
            injected[name.rsplit(".", 1)[1]] = int(n)
    quarantined = _quarantine.entries()
    return {
        "enabled": bool(counts or events or quarantined or injected),
        "runtime_fallbacks": int(counts.get("runtime_fallbacks", 0)),
        "failover_attempts": int(counts.get("failover_attempts", 0)),
        "numeric_events": int(counts.get("numeric_events", 0)),
        "numeric_fallbacks": int(counts.get("numeric_fallbacks", 0)),
        "quarantine_skips": int(snap.get("resilience.quarantine_skips", 0)),
        "quarantine": quarantined,
        "injected_faults": injected,
        "events": events[-max_events:],
    }


def reset() -> None:
    """Clear quarantine, events, counters, and warn-once state — recovery
    (and test isolation) in one call."""
    _quarantine.reset()
    EVENTS.clear()
    with _LOCK:
        _COUNTS.clear()
        _WARNED.clear()
