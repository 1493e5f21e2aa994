"""The trace reduction: busy-interval union, per-kernel time, idle gaps
named by the host span that covers them; on hand-made intervals and on a
short trace recorded on a TPU v5e."""
import gzip
import pathlib
import shutil

import pytest

from bench import trace_reduce as TR

DATA = pathlib.Path(__file__).parent / "data"
SAMPLE = DATA / "stablelm-decode.xplane.pb.gz"


def _trace():
    # A loop holding two ops, then a kernel after an idle gap.
    ops = [("%while.1 = s32[] while()", 0, 100),
           ("%sma_gemm.3 = bf16[16,2048] custom-call()", 10, 30),
           ("%copy.5 = bf16[4,4] copy()", 40, 60),
           ("%closed_call.2 = bf16[1] custom-call() tpu_custom_call",
            150, 180)]
    spans = [("bench.traced", 0, 200), ("engine.step", 0, 120),
             ("bench.generate", 120, 130), ("engine.step", 130, 190),
             ("bench.wait", 190, 200)]
    return TR.Trace(lo=0, hi=200, ops=[ops], spans=spans)


def test_union_merges_overlaps():
    assert TR.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_busy_and_kernel_time():
    t = _trace()
    assert t.window_s == pytest.approx(200e-9)
    assert t.busy_s == pytest.approx(130e-9)        # 0-100 and 150-180
    assert t.op_seconds(r"^%sma_gemm") == pytest.approx(20e-9)
    assert t.op_seconds(r"^%closed_call.*tpu_custom_call") == \
        pytest.approx(30e-9)


def test_self_time_and_short_names():
    by = dict(TR.self_times(_trace().ops[0]))
    assert by["%while.1 = s32[] while()"] == 60     # 100 less 20 and 20
    top = dict(_trace().top_ops())
    assert top["%while s32[]"] == pytest.approx(60e-9)
    assert top["%sma_gemm bf16[16,2048]"] == pytest.approx(20e-9)


def test_idle_gaps_named_by_covering_span():
    gaps = dict(_trace().idle_gaps())
    # 100-150: middle 125 in bench.generate; 180-200: middle 190 in
    # bench.wait (starts there; the step ends at 190).
    assert gaps == {"bench.generate": pytest.approx(50e-9),
                    "bench.wait": pytest.approx(20e-9)}


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "sample.xplane.pb"
    with gzip.open(SAMPLE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return TR.load(str(path))


def test_recorded_trace(sample):
    assert len(sample.ops) == 1 and sample.ops[0]
    assert 0 < sample.busy_s <= sample.window_s
    # The union counts each instant once: no more than the ops' sum.
    assert sample.busy_s <= sum(e - s for _, s, e in sample.ops[0]) * 1e-9
    gemm = sample.op_seconds(r"^%sma_gemm(\.\d+)? = ")
    attn = sample.op_seconds(r"^%closed_call(\.\d+)? = .*tpu_custom_call")
    assert 0 < gemm < sample.busy_s and 0 < attn < sample.busy_s
    gaps = sample.idle_gaps()
    assert {g for g, _ in gaps} <= set(TR.HOST_SPANS) | {"none"}
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(sample.window_s - sample.busy_s, rel=1e-6)
    spans = {n for n, _, _ in sample.spans}
    assert {"bench.traced", "engine.step"} <= spans
