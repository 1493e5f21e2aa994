"""KV cache: share of the reserved KV budget (prompt plus
``max_new_tokens`` per admitted request) not yet written, at the ticks
whose queue head stayed queued for KV, over the window: one less the
totals of the program's histograms ``serving.kv.positions_in_use`` over
``serving.kv.positions_reserved``.  What reservation holds back from the
waiting requests; nothing to read where the pool never blocked."""
from bench import counters


def read(rec):
    h = counters.histograms()
    used = h.get("serving.kv.positions_in_use")
    reserved = h.get("serving.kv.positions_reserved")
    if used is None or reserved is None or not reserved["total"]:
        return None
    return 100.0 * (1.0 - used["total"] / reserved["total"])
