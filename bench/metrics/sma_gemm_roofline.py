"""Kernels: least time of the traced ticks' layer GEMMs (each call the
larger of FLOPs over peak and bytes over bandwidth, from real rows) over
the device time of the ``sma_gemm`` Pallas calls and of the fusions that
stage each call's weight out of the stacked layer parameters.  The head
runs in ``rmsnorm_gemm`` and is in neither.  The GEMMs of a tick are the
configuration's kind's (``bench/models/<kind>.py``)."""
from bench import spec

#: How the trace names the ``sma_gemm`` Pallas calls and the fusions that
#: stage their weights.
KERNEL = r"^%(sma_gemm|dynamic-slice_bitcast_fusion)(\.\d+)? = "


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    t_kernel = tr.op_seconds(KERNEL)
    if not t_kernel:
        return None
    kind = spec.model_kind(rec.model)
    least = sum(kind.gemm_least_time(rec.model, t.tokens, rec.peaks)
                for t in rec.traced_ticks)
    return 100.0 * least / t_kernel
