"""KV cache: batch rows left empty per tick because the pool could not
reserve the queue head's KV budget, over the window: program counter
``serving.kv_blocked_rows`` (the free rows at each tick whose head stayed
queued for KV) over ``serving.ticks``.  Unlike ``kv_blocked_share`` it
falls when freed budget admits some of the waiting requests but not all."""
from bench import counters


def read(rec):
    c = counters.window()
    rows, ticks = c.get("serving.kv_blocked_rows"), c.get("serving.ticks")
    if rows is None or not ticks:
        return None
    return rows / ticks
