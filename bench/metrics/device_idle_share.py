"""Device: share of the traced stretch in which no operation ran."""


def read(rec):
    tr = rec.trace
    if tr is None or not tr.ops or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
