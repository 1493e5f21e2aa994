"""The program's spans on the JAX profiler's clock, the name scopes of its
compiled step programs, and the serving counters, on a tiny engine on the
CPU."""
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro
import repro.configs as C
from repro.api import SMAOptions
from repro.models import lm
from repro.obs import metrics
from repro.obs import trace as obs_trace
from repro.serving import CacheConfig, Request, ServeEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def model():
    cfg = C.reduced(C.get_config("stablelm-1.6b"))
    params, _ = lm.init(KEY, cfg)
    return cfg, params


def _engine(model, max_batch=2, cache=CacheConfig(8, 32, 64)):
    cfg, params = model
    return ServeEngine(cfg, params, cache=cache, max_batch=max_batch,
                       options=SMAOptions(backend="xla"))


def _submit(eng, rid, prompt_len, new):
    eng.submit(Request(rid=rid, prompt=np.arange(1, prompt_len + 1,
                                                 dtype=np.int32),
                       max_new_tokens=new))


def _host_spans(logdir):
    """(name, start, end, args) of every program span in the trace."""
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("serving.", "engine.")):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_step_spans_land_in_the_profiler_trace_nested(model, tmp_path):
    eng = _engine(model)
    for rid in range(3):
        _submit(eng, rid, 5, 3)
    eng.step()                  # compiles prefill, outside the trace
    eng.step()                  # compiles decode
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    for want in ("serving.admit", "serving.tick.decode", "serving.pack",
                 "engine.call", "engine.lookup", "engine.dispatch",
                 "serving.fetch", "serving.sample"):
        assert names.count(want) == 1, (want, names)
    by = {s[0]: s for s in spans}
    tick = by["serving.tick.decode"]
    assert by["serving.admit"][2] <= tick[1]
    for child in ("serving.pack", "engine.call", "serving.fetch",
                  "serving.sample"):
        assert _inside(by[child], tick), child
    for child in ("engine.lookup", "engine.dispatch"):
        assert _inside(by[child], by["engine.call"]), child
    order = [by[n][1] for n in ("serving.pack", "engine.call",
                                "serving.fetch", "serving.sample")]
    assert order == sorted(order)
    # Two rows decode, the third request waits for a row.
    assert tick[3] == {"rows": 2, "bucket": 2, "pad": 0, "tick": 3}
    assert by["serving.admit"][3] == {"admitted": 0, "blocked": "rows"}
    assert by["engine.call"][3]["cache"] == "hit"


def test_a_profile_scope_and_the_profiler_both_see_a_span(tmp_path):
    with jax.profiler.trace(str(tmp_path)), repro.profile() as prof:
        with obs_trace.span("serving.pack", cat="serve", rows=3) as sp:
            sp.annotate(bucket=4, shape=[1, 2])
    assert [e["name"] for e in prof.events] == ["serving.pack"]
    assert prof.events[0]["args"]["shape"] == [1, 2]
    (span,) = _host_spans(tmp_path)
    assert span[0] == "serving.pack"
    assert span[3] == {"rows": 3, "bucket": 4}      # scalars only


def test_nothing_recorded_with_neither(model):
    assert not obs_trace.recording()
    assert obs_trace.current_tracer() is None
    assert obs_trace.span("serving.pack", rows=1) is obs_trace._NULL
    last = obs_trace.last_tracer()
    eng = _engine(model)
    _submit(eng, 0, 5, 2)
    eng.run()
    assert obs_trace.last_tracer() is last


def _op_names(compiled):
    text = compiled.executable.as_text()
    module = re.search(r"HloModule (\S+?),", text).group(1)
    return module, set(re.findall(r'op_name="([^"]*)"', text))


def test_step_programs_carry_named_scopes(model):
    eng = _engine(model)
    _submit(eng, 0, 5, 3)
    eng.run()
    for phase, engine in eng.engines.items():
        (entry,) = engine._cache.values()
        module, names = _op_names(entry.compiled)
        program = f"stablelm_1_6b_smoke_paged_{phase}"
        assert module == f"jit_{program}"
        assert all(n.startswith(f"jit({program})/") for n in names
                   if n.startswith("jit("))
        joined = "\n".join(names)
        for scope in ("/embed/", "/layers/while/", "/kv_write/",
                      "/paged_decode_attention/", "/sma_gemm",
                      "/head/"):
            assert scope in joined, (phase, scope)
        # The page writes are in the layer scan, under their own scope.
        assert re.search(r"/layers/while/body/.*/kv_write/scatter", joined)


def test_rows_and_padding_counted_by_phase(model):
    metrics.reset()
    eng = _engine(model, max_batch=4)
    for rid in range(3):
        _submit(eng, rid, 3, 3)
    assert eng.run() == 3       # one prefill tick, two decode ticks
    c = metrics.snapshot()["counters"]
    assert c["serving.rows.prefill"] == 3
    assert c["serving.pad_rows.prefill"] == 1      # 3 rows, bucket 4
    assert c["serving.rows.decode"] == 6
    assert c["serving.pad_rows.decode"] == 2
    assert c["serving.admit_blocked.kv"] == 0
    assert c["serving.admit_blocked.rows"] == 0
    assert c["serving.kv_blocked_rows"] == 0


def test_admission_blocks_counted_by_reason(model):
    """Two rows, a pool of three blocks of 8: requests 0 and 1 take a
    block each and both rows, so request 2 (24 positions, three blocks)
    waits for a row at ticks 1-2; request 0 finishes at tick 2, and
    request 2 then waits for blocks while request 1 decodes (ticks 3-5)."""
    metrics.reset()
    eng = _engine(model, max_batch=2, cache=CacheConfig(8, 3, 32))
    _submit(eng, 0, 3, 2)
    _submit(eng, 1, 3, 5)
    _submit(eng, 2, 20, 4)
    assert eng.run() == 9
    assert len(eng.done) == 3
    c = metrics.snapshot()["counters"]
    assert c["serving.admit_blocked.rows"] == 2
    assert c["serving.admit_blocked.kv"] == 3
    assert c["serving.ticks"] == 9
    # Request 1 holds one row, so each kv-blocked tick leaves one free.
    assert c["serving.kv_blocked_rows"] == 3
    h = metrics.snapshot()["histograms"]
    # At each kv-blocked tick request 1 holds its 8-position budget and
    # has written 4, 5, then 6 positions.
    assert h["serving.kv.positions_reserved"]["count"] == 3
    assert h["serving.kv.positions_reserved"]["max"] == 8
    assert (h["serving.kv.positions_in_use"]["min"],
            h["serving.kv.positions_in_use"]["max"]) == (4, 6)


def test_kv_stats_reserved_against_in_use():
    from repro.serving.kv_cache import PagedKVCache
    kv = PagedKVCache(CacheConfig(8, 16, 64), max_batch=3)
    assert kv.admit(0, 10, 6)          # 16 positions, 2 blocks
    assert kv.admit(2, 3, 2)           # 5 positions, 1 block
    lengths = np.array([12, 0, 4], np.int32)
    st = kv.stats(lengths)
    assert st["positions_reserved"] == 21
    assert st["positions_in_use"] == 16
    assert st["resident_positions"] == 24
    kv.release(0)
    assert kv.stats(lengths)["positions_reserved"] == 5
    assert "positions_in_use" not in kv.stats()
