"""Device: model FLOPs of the prompt and output tokens that the traced
ticks processed, over the traced stretch times the chip's bf16 peak; the
FLOPs of a tick are the configuration's kind's (``bench/models/<kind>.py``)."""
from bench import spec


def read(rec):
    tr = rec.trace
    ticks = rec.traced_ticks
    if tr is None or not tr.ops or not ticks or not tr.window_s:
        return None
    kind = spec.model_kind(rec.model)
    flops = sum(kind.tick_flops(rec.model, t.rows, t.logit_rows)
                for t in ticks)
    return 100.0 * flops / (tr.window_s * rec.peaks["bf16_flops_per_s"])
