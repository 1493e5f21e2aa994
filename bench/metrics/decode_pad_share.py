"""Scheduler: share of the decode ticks' batch rows that were padding
(copies of a real row up to the power-of-two bucket, doing a row's full
work for nothing), over the window: program counters
``serving.pad_rows.decode`` over that plus ``serving.rows.decode``."""
from bench import counters


def read(rec):
    c = counters.window()
    pad = c.get("serving.pad_rows.decode")
    real = c.get("serving.rows.decode")
    if pad is None or real is None or not pad + real:
        return None
    return 100.0 * pad / (pad + real)
