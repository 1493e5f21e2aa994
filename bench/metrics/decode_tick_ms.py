"""Model step: median host time of a decode tick (``step()`` returns after
copying the tick's logits to the host)."""


def read(rec):
    return rec.median_tick_ms("decode")
