"""Device: model FLOPs of the prompt and output tokens that the traced
ticks processed, over the traced stretch times the chip's bf16 peak."""
from bench import work


def read(rec):
    tr = rec.trace
    ticks = rec.traced_ticks
    if tr is None or not tr.ops or not ticks or not tr.window_s:
        return None
    flops = sum(work.tick_flops(rec.model, t.rows, t.logit_rows)
                for t in ticks)
    return 100.0 * flops / (tr.window_s * rec.peaks["bf16_flops_per_s"])
