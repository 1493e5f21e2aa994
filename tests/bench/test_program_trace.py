"""What the program writes into a profiler trace: its spans, each device
op's name scope, and the readings built on them; plus the readers of the
program's counters.  On hand-made traces, on the trace recorded on a TPU
v5e before the program had scopes, and on one recorded with them."""
import gzip
import json
import pathlib
import shutil
import sys

import pytest

from bench import program_trace as PT
from bench import spec
from bench import trace_reduce as TR

DATA = pathlib.Path(__file__).parent / "data"
DECODE = "jit(m_paged_decode)/layers/while/body"
PREFILL = "jit(m_paged_prefill)/layers/while/body"


def _hand_made():
    ops = [("%copy.1 = bf16[2] copy()", 40, 100),
           ("%closed_call.1 = bf16[1] custom-call() tpu_custom_call",
            100, 200),
           ("%copy.2 = bf16[3] copy()", 200, 250),
           ("%fusion.3 = bf16[4] fusion()", 250, 300),
           ("%copy.4 = bf16[5] copy()", 600, 700),
           ("%copy.9 = bf16[6] copy()", 700, 720)]
    scopes = [f"{DECODE}/closed_call/paged_decode_attention/gather",
              f"{DECODE}/closed_call/paged_decode_attention/closed_call/"
              "pallas_call",
              f"{DECODE}/dynamic_slice",
              f"{DECODE}/closed_call/kv_write/scatter",
              f"{PREFILL}/dynamic_update_slice",
              ""]
    bench = [("bench.traced", 0, 1000), ("engine.step", 0, 400),
             ("bench.generate", 400, 500), ("engine.step", 500, 900)]
    program = [("serving.admit", 0, 10), ("serving.tick.decode", 10, 400),
               ("serving.pack", 10, 30), ("engine.call", 30, 60),
               ("serving.fetch", 60, 380), ("serving.sample", 380, 400),
               ("serving.admit", 500, 520),
               ("serving.tick.prefill", 520, 900),
               ("serving.fetch", 600, 850)]
    trace = TR.Trace(lo=0, hi=1000, ops=[ops], spans=bench)
    return PT.ProgramTrace(trace=trace, scopes=[scopes],
                           spans=[(n, s, e, {}) for n, s, e in program])


def test_tick_host_time():
    # Decode: 390 less a fetch of 320, plus an admission of 10: 80 ns;
    # prefill: 380 - 250 + 20 = 150 ns.  Median 115 ns.
    assert PT.tick_host_ms(_hand_made()) == pytest.approx(115e-6)


def test_device_time_by_scope_per_tick():
    pt = _hand_made()
    # Gather 60 and kernel 100 in the decode program, one decode tick.
    assert PT.decode_attn_site_ms(pt) == pytest.approx(160e-6)
    # The scan's own slice (50) and update (100) over two ticks; the page
    # write inside the body is the layer's own work.
    assert PT.layer_state_ms(pt) == pytest.approx(75e-6)


def test_idle_gaps_named_by_program_spans():
    gaps = dict(_hand_made().idle_gaps())
    # 0-40 (middle in serving.pack), 300-600 (bench.generate), 720-1000
    # (in the prefill tick, after its fetch).
    assert gaps == {"serving.pack": pytest.approx(40e-9),
                    "bench.generate": pytest.approx(300e-9),
                    "serving.tick.prefill": pytest.approx(280e-9)}


def test_nothing_to_read():
    pt = _hand_made()
    bare = PT.ProgramTrace(trace=pt.trace, scopes=[[""] * 6], spans=[])
    for read in (PT.tick_host_ms, PT.decode_attn_site_ms,
                 PT.layer_state_ms):
        assert read(bare) is None
    no_scopes = PT.ProgramTrace(trace=pt.trace, scopes=[[""] * 6],
                                spans=pt.spans)
    assert PT.decode_attn_site_ms(no_scopes) is None
    assert PT.layer_state_ms(no_scopes) is None


def _load(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "sample.xplane.pb"
    with gzip.open(DATA / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return PT.load(str(path))


@pytest.fixture(scope="module")
def before_scopes(tmp_path_factory):
    return _load(tmp_path_factory, "stablelm-decode.xplane.pb.gz")


def test_scopes_of_a_trace_recorded_before_scopes(before_scopes):
    pt = before_scopes
    assert len(pt.scopes) == len(pt.trace.ops) == 1
    assert len(pt.scopes[0]) == len(pt.trace.ops[0])
    body = "jit(runner)/while/body"
    # The page gather (its copy and its fusion), the layer scan's
    # slices, and the copies XLA inserted, which carry no scope.
    assert pt.self_seconds(
        lambda sc: sc == f"{body}/closed_call/gather") == \
        pytest.approx(0.158, abs=0.001)
    assert pt.self_seconds(lambda sc: sc == f"{body}/dynamic_slice") == \
        pytest.approx(0.061, abs=0.001)
    assert pt.self_seconds(lambda sc: sc == "") == \
        pytest.approx(0.025, abs=0.001)
    # No program spans then, and no readings on them.
    assert pt.spans == []
    assert PT.tick_host_ms(pt) is None


def _counters(monkeypatch, observed=(), **values):
    from repro.obs import metrics
    registry = metrics.MetricsRegistry()
    for name, v in values.items():
        registry.inc(name.replace("__", "."), v)
    for name, v in observed:
        registry.observe(name, v)
    monkeypatch.setattr(metrics, "snapshot", registry.snapshot)


def test_counter_readers(monkeypatch):
    _counters(monkeypatch, serving__pad_rows__decode=30,
              serving__rows__decode=90, serving__admit_blocked__kv=12,
              serving__kv_blocked_rows=60, serving__ticks=48,
              observed=[("serving.kv.positions_in_use", 300),
                        ("serving.kv.positions_reserved", 1000),
                        ("serving.kv.positions_in_use", 500),
                        ("serving.kv.positions_reserved", 1000)])
    assert spec.metric_reader("decode_pad_share").read(None) == \
        pytest.approx(25.0)
    assert spec.metric_reader("kv_blocked_share").read(None) == \
        pytest.approx(25.0)
    assert spec.metric_reader("kv_blocked_rows.mean").read(None) == \
        pytest.approx(1.25)
    # 800 of the 2000 reserved positions written at the blocked ticks.
    assert spec.metric_reader("kv_reserved_unused_share").read(None) == \
        pytest.approx(60.0)


def test_kv_readers_where_the_pool_never_blocks(monkeypatch):
    # Counters kept, no blocked tick: no rows lost, and no reservation
    # observed to read.
    _counters(monkeypatch, serving__admit_blocked__kv=0,
              serving__kv_blocked_rows=0, serving__ticks=48)
    assert spec.metric_reader("kv_blocked_rows.mean").read(None) == 0.0
    assert spec.metric_reader("kv_reserved_unused_share").read(None) is None


@pytest.mark.parametrize("name", ["decode_pad_share", "kv_blocked_share",
                                  "kv_blocked_rows.mean",
                                  "kv_reserved_unused_share"])
def test_counter_readers_with_nothing_to_read(monkeypatch, name):
    # A program without the counters (the parent of this change): nothing.
    _counters(monkeypatch, serving__ticks=48)
    assert spec.metric_reader(name).read(None) is None
    # Counters kept but no tick ran: nothing either.
    _counters(monkeypatch, serving__pad_rows__decode=0,
              serving__rows__decode=0, serving__admit_blocked__kv=0,
              serving__kv_blocked_rows=0, serving__ticks=0)
    assert spec.metric_reader(name).read(None) is None


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """Two decode ticks of stablelm-1.6b.decode-backlog with the program's
    spans and scopes (TPU v5 lite)."""
    return _load(tmp_path_factory, "stablelm-decode-scoped.xplane.pb.gz")


def test_recorded_spans_split_each_tick(scoped):
    ticks = scoped.ticks("decode")
    assert [t[3] for t in ticks] == [
        {"rows": 12, "bucket": 16, "pad": 4, "tick": 388},
        {"rows": 12, "bucket": 16, "pad": 4, "tick": 389}]
    for _, s, e, _ in ticks:
        inside = {n: (cs, ce) for n, cs, ce, _ in scoped.spans
                  if s <= cs and ce <= e}
        assert set(inside) == {"serving.tick.decode", "serving.pack",
                               "engine.call", "engine.lookup",
                               "engine.dispatch", "serving.fetch",
                               "serving.sample"}
        call = inside["engine.call"]
        for child in ("engine.lookup", "engine.dispatch"):
            assert call[0] <= inside[child][0] <= inside[child][1] <= \
                call[1]
    assert sum(n == "serving.admit" for n, _, _, _ in scoped.spans) == 2


def test_recorded_ops_carry_the_program_scopes(scoped):
    scopes = {sc for dev in scoped.scopes for sc in dev if sc}
    program = "jit(stablelm_1_6b_paged_decode)"
    # The step program, and the eager slice of its logits' real rows.
    assert {sc.split("/")[0] for sc in scopes} == {program,
                                                   "jit(dynamic_slice)"}
    layer = f"{program}/layers/while/body/closed_call"
    assert f"{layer}/paged_decode_attention/closed_call/pallas_call" in \
        scopes
    assert f"{layer}/kv_write/scatter" in scopes
    # The kernels keep the HLO names that the roofline readers match.
    kernel = dict(zip((n for n, _, _ in scoped.trace.ops[0]),
                      scoped.scopes[0]))
    assert any(n.startswith("%closed_call") and "tpu_custom_call" in n
               and s.endswith("paged_decode_attention/closed_call/"
                              "pallas_call") for n, s in kernel.items())
    assert scoped.trace.op_seconds(r"^%sma_gemm(\.\d+)? = ") > 0


def test_recorded_readings(scoped):
    assert PT.tick_host_ms(scoped) == pytest.approx(2.99, abs=0.01)
    # Gather 0.167 s and kernel 0.064 s over two decode ticks.
    assert PT.decode_attn_site_ms(scoped) == pytest.approx(115.8, abs=0.1)
    assert PT.layer_state_ms(scoped) == pytest.approx(52.6, abs=0.1)


def test_recorded_idle_named_by_program_spans(scoped):
    bench = dict(scoped.trace.idle_gaps())
    gaps = dict(scoped.idle_gaps())
    assert set(bench) == {"engine.step"}
    # Every idle second inside the step falls in a program span: mostly
    # the wait for the logits, the rest while the next batch is packed.
    assert set(gaps) == {"serving.fetch", "serving.pack"}
    assert sum(gaps.values()) == pytest.approx(bench["engine.step"])
    assert gaps["serving.fetch"] > 0.9 * bench["engine.step"]


def test_recorded_readings_in_one_object(scoped):
    got = PT.readings(scoped)
    assert got["ticks"] == {"decode": 2}
    assert got["tick_host_ms"] == PT.tick_host_ms(scoped)
    assert got["decode_attn_site_ms"] == PT.decode_attn_site_ms(scoped)
    assert got["idle_in_step_named_share"] == pytest.approx(1.0)
    assert 0 < got["ops_without_scope_s"] < got["busy_s"] < got["window_s"]
    json.dumps(got)


def test_program_readings_cli_reads_before_the_trace_goes(
        monkeypatch, tmp_path, capsys):
    """The CLI reads the trace when the run reduces it, before run_cell
    deletes it, and keeps a gzipped copy."""
    from bench import program_readings, run
    from bench import trace_reduce
    path = tmp_path / "t.xplane.pb"
    with gzip.open(DATA / "stablelm-decode-scoped.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    original = trace_reduce.load

    def fake_main(argv):
        assert argv[-2:] == ["--trace", "1"]
        trace = trace_reduce.load(str(path))
        assert isinstance(trace, TR.Trace)
        path.unlink()
        print("{}")
        return 0

    monkeypatch.setattr(run, "main", fake_main)
    monkeypatch.setattr(sys, "path", list(sys.path))
    keep = tmp_path / "kept"
    assert program_readings.main(["--workload", "w", "--seed", "7",
                                  "--seconds", "1", "--keep",
                                  str(keep)]) == 0
    assert trace_reduce.load is original
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith("PROGRAM_TRACE ")
    found = json.loads(last.split(" ", 1)[1])
    assert found["ticks"] == {"decode": 2}
    assert found["kept"] == str(keep / "w-7.xplane.pb.gz")
    assert gzip.open(found["kept"]).read(4)
