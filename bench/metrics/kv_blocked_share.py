"""KV cache: share of the window's ticks at which the queue's head stayed
queued because the pool could not reserve its KV budget (prompt plus
``max_new_tokens``) while a row was free: program counters
``serving.admit_blocked.kv`` over ``serving.ticks``."""
from bench import counters


def read(rec):
    c = counters.window()
    blocked, ticks = c.get("serving.admit_blocked.kv"), c.get("serving.ticks")
    if blocked is None or not ticks:
        return None
    return 100.0 * blocked / ticks
