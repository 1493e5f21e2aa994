"""Scheduler: real rows per decode tick, before padding to the bucket."""


def read(rec):
    ticks = rec.ticks_of("decode")
    return sum(len(t.rows) for t in ticks) / len(ticks) if ticks else None
