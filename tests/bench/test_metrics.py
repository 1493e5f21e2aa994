"""Each per-layer reader on a hand-made record: the number it reads, and
nothing where the run holds nothing to read."""
import pytest

from bench import spec
from bench import trace_reduce as TR
from bench.record import Record, Tick
from conftest import CONFIGS, tiny_config

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
NS = 1_000_000     # 1 ms in the trace's nanoseconds


def _record(trace, config=CONFIGS[0]):
    ticks = [Tick(0.0, 0.010, "prefill", [(0, 32), (0, 20)], 1, True),
             Tick(0.010, 0.014, "decode", [(20, 1), (32, 1)], 2, True),
             Tick(0.014, 0.020, "decode", [(21, 1)], 1, True)]
    return Record(model=tiny_config(config), peaks=PEAKS, t_start=0.0,
                  t_end=0.02, ticks=ticks, requests={}, compiles=0,
                  trace=trace)


def _trace():
    ops = [("%sma_gemm.1 = bf16[1] custom-call()", 0, 4 * NS),
           ("%dynamic-slice_bitcast_fusion.2 = bf16[1] fusion()", 4 * NS,
            6 * NS),
           ("%closed_call.3 = bf16[1] custom-call() tpu_custom_call",
            12 * NS, 13 * NS)]
    return TR.Trace(lo=0, hi=20 * NS, ops=[ops],
                    spans=[("bench.traced", 0, 20 * NS)])


def read(name, rec):
    return spec.metric_reader(name).read(rec)


def test_host_readers():
    rec = _record(None)
    assert read("decode_rows.mean", rec) == pytest.approx(1.5)
    assert read("decode_tick_ms", rec) == pytest.approx(5.0)
    assert read("prefill_tick_ms", rec) == pytest.approx(10.0)
    assert read("window_compiles", rec) == 0


@pytest.mark.parametrize("config", CONFIGS)
def test_trace_readers(config):
    rec = _record(_trace(), config)
    # Busy 0-6 ms and 12-13 ms of 20.
    assert read("device_idle_share", rec) == pytest.approx(100 * 13 / 20)
    # The work counts are the configuration's kind's.
    kind = spec.model_kind(rec.model)
    least = sum(kind.gemm_least_time(rec.model, t.tokens, PEAKS)
                for t in rec.ticks)
    assert read("sma_gemm_roofline", rec) == pytest.approx(
        100 * least / 6e-3)
    attn = sum(kind.attention_least_time(rec.model, t.kv_lens, PEAKS)
               for t in rec.ticks if t.phase == "decode")
    assert read("paged_decode_attn_roofline", rec) == pytest.approx(
        100 * attn / 1e-3)
    flops = sum(kind.tick_flops(rec.model, t.rows, t.logit_rows)
                for t in rec.ticks)
    assert read("step_mfu", rec) == pytest.approx(100 * flops / 20e-3 / 1e12)


@pytest.mark.parametrize("name", ["device_idle_share", "step_mfu",
                                  "sma_gemm_roofline",
                                  "paged_decode_attn_roofline"])
def test_nothing_to_read(name):
    assert read(name, _record(None)) is None
    empty = TR.Trace(lo=0, hi=NS, ops=[], spans=[])
    assert read(name, _record(empty)) is None
