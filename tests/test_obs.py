"""Observability tests: ``repro.profile`` span tracing through
engine -> dispatch -> kernel, the no-fragmentation guarantee (tracing is
never part of a compile-cache key), Chrome-trace export schema, the
``runtime`` plan-report section (measured mode timeline), and the metrics
registry.
"""
import json

import jax
import jax.numpy as jnp
import pytest

import repro
from repro.api import SMAOptions, sma_jit
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import timing as obs_timing
from repro.obs import trace as obs_trace

KEY = jax.random.PRNGKey(0)

#: interpret = the systolic-mode substrate that runs on CPU, so traces show
#: real systolic/SIMD alternation regardless of the CI backend env default.
INTERP = SMAOptions(backend="interpret")


def _sandwich_engine():
    """x @ w1 -> softmax (SIMD) -> @ w2: statically 2 mode switches."""
    w1 = jax.random.normal(KEY, (16, 16), jnp.float32) * 0.25
    w2 = jax.random.normal(jax.random.PRNGKey(1), (16, 16),
                           jnp.float32) * 0.25
    engine = sma_jit(lambda x: jax.nn.softmax(x @ w1) @ w2,
                     options=INTERP, name="sandwich")
    return engine, jnp.ones((8, 16), jnp.float32)


# ===========================================================================
# Span tracing
# ===========================================================================
class TestTracing:
    def test_spans_nest_engine_dispatch_kernel(self):
        engine, x = _sandwich_engine()
        with repro.profile() as prof:
            engine(x)
        names = {e["name"] for e in prof.events}
        assert {"engine.call", "engine.compile", "compile.trace",
                "compile.lower", "compile.plan", "compile.rewrite",
                "dispatch.sma_gemm", "kernel.sma_gemm",
                "dispatch.simd_region"} <= names
        call = next(e for e in prof.events if e["name"] == "engine.call")
        for e in prof.events:
            if e["name"].startswith(("kernel.", "dispatch.", "compile.")):
                assert e["ts"] >= call["ts"] - 1e-6
                assert e["ts"] + e["dur"] <= \
                    call["ts"] + call["dur"] + 1e-6
        kernel = next(e for e in prof.events
                      if e["name"] == "kernel.sma_gemm")
        assert kernel["mode"] == "systolic"
        assert kernel["args"]["backend"] == "interpret"
        assert kernel["args"]["blocks"]  # resolved block sizes recorded
        assert call["args"]["cache"] == "miss"

    def test_second_call_is_traced_as_cache_hit(self):
        engine, x = _sandwich_engine()
        engine(x)
        with repro.profile() as prof:
            engine(x)
        call = next(e for e in prof.events if e["name"] == "engine.call")
        assert call["args"]["cache"] == "hit"
        assert not any(e["name"] == "engine.compile" for e in prof.events)

    def test_sync_mode_marks_spans_synced(self):
        engine, x = _sandwich_engine()
        engine(x)
        with repro.profile(sync=True) as prof:
            engine(x)
        call = next(e for e in prof.events if e["name"] == "engine.call")
        assert call["args"]["synced"] is True
        sec = prof.runtime_section()
        assert sec["sync"] is True
        assert "device" in sec["wall_basis"]

    def test_serve_spans(self):
        import numpy as np

        import repro.configs as C
        from repro.launch.serve import Request, Server
        from repro.models import lm
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        params, _ = lm.init(KEY, cfg)
        server = Server(cfg, params, slots=2, cache_size=32,
                        options=SMAOptions(backend="xla"))
        req = Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                      max_new_tokens=2)
        with repro.profile() as prof:
            assert server.admit(req)
            server.tick()
        names = {e["name"] for e in prof.events}
        assert {"serve.admit", "serve.warmup",
                "serving.tick.decode"} <= names


# ===========================================================================
# Disabled tracing: zero events, zero cache fragmentation
# ===========================================================================
class TestDisabled:
    def test_no_tracer_outside_profile_scope(self):
        assert obs_trace.current_tracer() is None
        with repro.profile() as prof:
            assert obs_trace.current_tracer() is prof
        assert obs_trace.current_tracer() is None

    def test_disabled_records_no_events(self):
        engine, x = _sandwich_engine()
        engine(x)
        assert obs_trace.current_tracer() is None  # nothing recording

    def test_profile_does_not_fragment_compile_cache(self):
        """THE cache-key invariant: enabling tracing must not recompile."""
        engine, x = _sandwich_engine()
        engine(x)
        assert engine.cache_size == 1
        with repro.profile():
            engine(x)
        engine(x)
        assert engine.cache_size == 1
        assert engine.stats.misses == 1 and engine.stats.hits == 2

    def test_tracing_absent_from_options_cache_key(self):
        key_fields = INTERP.cache_key()
        assert not any("trace" in str(f) or "profile" in str(f)
                       for f in key_fields)


# ===========================================================================
# Chrome-trace export
# ===========================================================================
class TestChromeTrace:
    def test_schema_and_roundtrip(self, tmp_path):
        engine, x = _sandwich_engine()
        path = tmp_path / "trace.json"
        with repro.profile(path=str(path)):
            engine(x)
        doc = json.loads(path.read_text())  # round-trips
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        for ev in events:
            assert ev["ph"] in ("X", "M", "i")
            assert isinstance(ev["pid"], int)
            assert isinstance(ev["tid"], int)
            if ev["ph"] == "X":
                assert isinstance(ev["ts"], float)
                assert isinstance(ev["dur"], float)
                assert ev["dur"] >= 0.0

    def test_systolic_and_simd_lanes_present(self, tmp_path):
        engine, x = _sandwich_engine()
        with repro.profile() as prof:
            engine(x)
        events = prof.chrome_trace()["traceEvents"]
        lanes = {ev["args"]["name"] for ev in events
                 if ev["ph"] == "M" and ev["name"] == "thread_name"}
        assert lanes == {"host", "systolic mode", "simd mode", "comm mode"}
        tids = {ev["tid"] for ev in events if ev["ph"] == "X"}
        assert obs_export.LANES["systolic"] in tids  # kernel slices
        assert obs_export.LANES["simd"] in tids      # dispatch regions
        assert obs_export.LANES["host"] in tids      # engine/compile


# ===========================================================================
# The runtime plan-report section (measured mode timeline)
# ===========================================================================
class TestRuntimeSection:
    def test_per_mode_times_sum_to_total(self):
        t = obs_trace.Tracer()
        t.add_event("k1", cat="kernel", ts=0.0, dur=10.0, mode="systolic")
        t.add_event("r1", cat="dispatch", ts=12.0, dur=8.0, mode="simd")
        t.add_event("k2", cat="kernel", ts=20.0, dur=10.0, mode="systolic")
        sec = obs_export.runtime_section(t.events)
        assert sec["per_mode_us"]["systolic"] == pytest.approx(20.0)
        assert sec["per_mode_us"]["simd"] == pytest.approx(8.0)
        # per-mode walls sum to ~the window (2us switch gap unattributed)
        assert sum(sec["per_mode_us"].values()) == \
            pytest.approx(sec["total_us"] - 2.0)
        assert sec["mode_switches"] == 2
        assert sec["switch_overhead_us"] == pytest.approx(2.0)

    def test_nested_spans_resolve_innermost_wins(self):
        t = obs_trace.Tracer()
        t.add_event("region", cat="dispatch", ts=0.0, dur=30.0, mode="simd")
        t.add_event("kernel", cat="kernel", ts=10.0, dur=10.0,
                    mode="systolic")
        sec = obs_export.runtime_section(t.events)
        assert sec["per_mode_us"]["simd"] == pytest.approx(20.0)
        assert sec["per_mode_us"]["systolic"] == pytest.approx(10.0)
        assert sec["mode_switches"] == 2  # simd -> systolic -> simd

    def test_runtime_switches_match_static_plan(self):
        """Acceptance bar: on a cache-hit steady-state call, the measured
        mode-switch count equals the static plan's ``mode_switches``."""
        engine, x = _sandwich_engine()
        engine(x)  # compile + warm
        engine(x)
        with repro.profile(sync=True) as prof:
            engine(x)  # ONE steady-state call
        compiled = engine.compile(x)
        static = compiled.summary.mode_switches
        assert static == 2
        assert prof.runtime_section()["mode_switches"] == static
        rep = compiled.report
        assert rep["runtime"]["mode_switches"] == static
        assert rep["runtime"]["kernel_spans"] >= 2
        json.dumps(rep)  # the stamped report stays JSON-clean

    def test_report_restamped_lazily_on_access(self):
        engine, x = _sandwich_engine()
        engine(x)
        rep = engine.compile(x).report
        hits_then = rep["engine"]["cache_hits"]
        engine(x)
        engine(x)
        rep = engine.compile(x).report
        assert rep["engine"]["cache_hits"] == hits_then + 3
        stats = rep["engine"]["engine_stats"]
        assert stats["misses"] == 1
        assert rep["engine"]["amortized_compile_s"] <= \
            rep["engine"]["compile_time_s"]

    def test_render_text_includes_runtime_timeline(self):
        from repro.compiler.report import render_text
        engine, x = _sandwich_engine()
        engine(x)
        with repro.profile(sync=True):
            engine(x)
        text = render_text(engine.compile(x).report)
        assert "runtime (measured)" in text
        assert "runtime mode timeline" in text
        assert "engine cache" in text

    def test_timeline_text_renders_two_lanes(self):
        engine, x = _sandwich_engine()
        with repro.profile() as prof:
            engine(x)
        text = prof.timeline_text()
        assert "systolic" in text and "simd" in text
        assert "mode switches (runtime)" in text


# ===========================================================================
# Metrics registry
# ===========================================================================
class TestMetrics:
    def test_snapshot_and_reset(self):
        reg = obs_metrics.MetricsRegistry()
        reg.inc("a")
        reg.inc("a", 2)
        reg.observe("lat", 1.0)
        reg.observe("lat", 3.0)
        snap = reg.snapshot()
        assert snap["counters"]["a"] == 3
        assert snap["histograms"]["lat"]["count"] == 2
        assert snap["histograms"]["lat"]["mean"] == pytest.approx(2.0)
        assert snap["histograms"]["lat"]["min"] == 1.0
        assert snap["histograms"]["lat"]["max"] == 3.0
        reg.reset()
        assert reg.snapshot() == {"counters": {}, "histograms": {}}

    def test_engine_feeds_global_metrics(self):
        obs_metrics.reset()
        engine, x = _sandwich_engine()
        engine(x)
        engine(x)
        snap = obs_metrics.snapshot()
        assert snap["counters"]["engine.cache_misses"] == 1
        assert snap["counters"]["engine.cache_hits"] == 1
        assert snap["histograms"]["engine.compile_s"]["count"] == 1
        assert any(k.startswith("backend.chosen.")
                   for k in snap["counters"])

    def test_snapshot_is_a_copy(self):
        reg = obs_metrics.MetricsRegistry()
        reg.inc("x")
        snap = reg.snapshot()
        snap["counters"]["x"] = 999
        assert reg.snapshot()["counters"]["x"] == 1


# ===========================================================================
# Shared benchmark timer
# ===========================================================================
class TestTiming:
    def test_timeit_semantics(self):
        calls = []

        def fn(v):
            calls.append(v)
            return jnp.asarray(v)

        t = obs_timing.timeit(fn, 1.0, iters=3, warmup=2)
        assert t >= 0.0
        assert len(calls) == 5  # 2 warmup + 3 timed

    def test_cold_timing_no_warmup(self):
        calls = []

        def fn():
            calls.append(1)
            return jnp.zeros(())

        obs_timing.timeit(fn, iters=1, warmup=0, sync_each=True)
        assert len(calls) == 1

    def test_iters_validated(self):
        with pytest.raises(ValueError):
            obs_timing.timeit(lambda: None, iters=0)
