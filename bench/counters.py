"""The program's counters and histograms, as the per-layer readers find
them.

``repro.obs.metrics`` keeps them for the life of the process.  A run's
set-up compiles the step programs by calling the engine's two programs
directly, never ``ServeEngine.step``, and the check after the window runs
the reference alone, so every ``serving.*`` count and observation in the
process is the window's.  One the program does not keep is absent, and a
reader of it returns nothing.
"""
from __future__ import annotations

from typing import Any, Dict


def window() -> Dict[str, float]:
    from repro.obs import metrics
    return metrics.snapshot()["counters"]


def histograms() -> Dict[str, Dict[str, Any]]:
    """``{name: {count, total, mean, min, max, p50, p99}}``."""
    from repro.obs import metrics
    return metrics.snapshot()["histograms"]
