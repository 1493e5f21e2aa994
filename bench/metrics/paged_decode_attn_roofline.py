"""Kernels: least time of the traced decode ticks' attention (K and V up
to each row's live ``kv_len``, plus q and out) over the device time of the
decode-attention Pallas calls in the trace.  The page gather that runs
before the kernel is not in the kernel's time.  The attention of a tick is
the configuration's kind's (``bench/models/<kind>.py``)."""
from bench import spec

#: How the trace names the decode-attention Pallas calls.
KERNEL = r"^%closed_call(\.\d+)? = .*tpu_custom_call"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    t_kernel = tr.op_seconds(KERNEL)
    if not t_kernel:
        return None
    kind = spec.model_kind(rec.model)
    least = sum(kind.attention_least_time(rec.model, t.kv_lens, rec.peaks)
                for t in rec.traced_ticks if t.phase == "decode")
    return 100.0 * least / t_kernel
