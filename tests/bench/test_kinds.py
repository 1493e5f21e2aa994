"""A configuration's model kind picks every piece of the benchmark that
knows a block's layout (``bench/models/<kind>.py``,
``bench/reference/<kind>.py``), so a kind is added by new files alone.

The dense kind is pinned to what the benchmark read before the kinds were
split out of the shared modules: the same weights bit for bit, and the
same work counts, so no reading of either cell moves."""
import hashlib
import json
import shutil
import time

import jax
import numpy as np
import pytest

from bench import spec
from bench import trace_reduce as TR
from bench.record import Record, Tick
from conftest import PEAKS, tiny_config

SEED = 2 ** 33 + 3
#: Read from the dense configurations before the split: a digest of the
#: program's weights at ``tiny`` size from :data:`SEED`; and at full size,
#: with the v5e's peaks, each count at the inputs of :func:`_counts`.
PINNED = {
    "stablelm-1.6b": {
        "digest": "7ac6d3182d739a9192cfc7b13dfa70a90c16e21ffc83439faf89b6"
                  "f78aefdd65",
        "gemm_least_time": {1: 0.0030136057435897435,
                            16: 0.0030482642051282054,
                            32: 0.0030852332307692307},
        "tick_flops": 171005902848.0,
        "attention_least_time": 0.0007617059633699633},
    "mistral-nemo-12b": {
        "digest": "29cf12345d8439f74e6ae72824a11620079e0631c17dd320c47b50"
                  "467f2b24f5",
        "gemm_least_time": {1: 0.0033299004639804636,
                            16: 0.003346216947496947,
                            32: 0.003363621196581197},
        "tick_flops": 186878935040.0,
        "attention_least_time": 7.964444444444445e-05},
}


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _counts(conf) -> dict:
    kind, peaks = spec.model_kind(conf), spec.peaks("TPU v5 lite")
    return {
        "gemm_least_time": {t: kind.gemm_least_time(conf, t, peaks)
                            for t in (1, 16, 32)},
        "tick_flops": kind.tick_flops(
            conf, [(0, 32), (1000, 32), (2047, 1), (500, 1)], 3),
        "attention_least_time": kind.attention_least_time(
            conf, [1, 100, 1020, 2048], peaks)}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_weights_are_pinned(name):
    conf = tiny_config(name)
    params = spec.model_kind(conf).program_params(SEED, conf)
    assert _digest(params) == PINNED[name]["digest"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_work_counts_are_pinned(name):
    want = dict(PINNED[name])
    del want["digest"]
    assert _counts(spec.config(name)) == want


#: Appended to a copy of a kind's module: each name of the contract
#: records its calls in ``calls.txt`` beside the module, then does what
#: the copy does.
RECORD_CALLS = '''

def _recorded(name, f):
    import pathlib

    def call(*args, **kwargs):
        with open(pathlib.Path(__file__).with_name("calls.txt"), "a") as out:
            out.write(name + "\\n")
        return f(*args, **kwargs)
    return call


for _name in NAMES:
    globals()[_name] = _recorded(_name, globals()[_name])
'''


def _add_kind(root, kind, source):
    """Copy the ``source`` kind's two modules as ``kind``, recording the
    calls of the contract's names."""
    for sub, names in (("models", ("expected", "program_params",
                                   "gemm_least_time", "tick_flops",
                                   "attention_least_time", "tiny")),
                       ("reference", ("Reference",))):
        text = (spec.BENCH / sub / f"{source}.py").read_text()
        (root / "bench" / sub / f"{kind}.py").write_text(
            text + f"\nNAMES = {names!r}\n" + RECORD_CALLS)


def _benchmark_with(tmp_path, kind):
    """A copy of the benchmark with one more configuration, of ``kind``,
    and one cell of it on the first cell's mix: new files and entries."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.benchmark()
    old = bench["workloads"][0]
    conf = spec.config(old["config"])
    conf = dict(conf, name="new-model",
                model=dict(conf["model"], kind=kind))
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(conf))
    cell = f"new-model.{old['traffic']}"
    shutil.copy(spec.BENCH / "workloads" / f"{old['name']}.json",
                tmp_path / "bench/workloads" / f"{cell}.json")
    bench["configs"].append(dict(bench["configs"][0], name="new-model",
                                 file="bench/configs/new-model.json"))
    bench["workloads"].append(dict(old, name=cell, config="new-model"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def test_a_kind_is_added_by_files_alone(tmp_path, monkeypatch):
    """Copy the dense kind as ``new_kind``, name it in a new
    configuration, and see every piece that knows a block's layout take
    the copy: the cell, the program's config and weights, the cut, the
    reference and the three work readers, with no other file touched."""
    from bench import run, serve
    from conftest import tiny_cell
    cell_name = _benchmark_with(tmp_path, "new_kind")
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "BENCH", tmp_path / "bench")
    _add_kind(tmp_path, "new_kind", "dense")
    calls = tmp_path / "bench/models/calls.txt"
    ref_calls = tmp_path / "bench/reference/calls.txt"

    cell = spec.cell(cell_name)
    assert spec.kind(cell.config) == "new_kind"
    assert spec.model_kind(cell.config).__file__ == str(
        tmp_path / "bench/models/new_kind.py")
    serve.model_config(tiny_config("new-model"))
    assert calls.read_text().split() == ["tiny", "expected"]

    calls.unlink()
    out = run.run_cell(tiny_cell(cell_name), 2 ** 31 + 7, 3.0, False,
                       jax.devices("cpu")[:1], PEAKS, time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert set(calls.read_text().split()) == {"tiny", "expected",
                                              "program_params"}
    assert ref_calls.read_text().split() == ["Reference"]

    calls.unlink()
    ticks = [Tick(0.0, 0.01, "prefill", [(0, 32)], 1, True),
             Tick(0.01, 0.02, "decode", [(32, 1), (7, 1)], 2, True)]
    ms = 1_000_000
    trace = TR.Trace(lo=0, hi=20 * ms, ops=[[
        ("%sma_gemm.1 = bf16[1] custom-call()", 0, 4 * ms),
        ("%closed_call.2 = bf16[1] custom-call() tpu_custom_call", 12 * ms,
         13 * ms)]], spans=[("bench.traced", 0, 20 * ms)])
    rec = Record(model=tiny_config("new-model"), peaks=PEAKS, t_start=0.0,
                 t_end=0.02, ticks=ticks, requests={}, compiles=0,
                 trace=trace)
    for metric in ("sma_gemm_roofline", "paged_decode_attn_roofline",
                   "step_mfu"):
        assert spec.metric_reader(metric).read(rec) > 0
    assert calls.read_text().split() == ["tiny", "gemm_least_time",
                                         "gemm_least_time",
                                         "attention_least_time",
                                         "tick_flops", "tick_flops"]


@pytest.mark.parametrize("present", [(), ("models",)],
                         ids=["no-module", "no-reference"])
def test_an_unknown_kind_is_refused(tmp_path, monkeypatch, present):
    cell_name = _benchmark_with(tmp_path, "no_such_kind")
    for sub in present:
        shutil.copy(spec.BENCH / sub / "dense.py",
                    tmp_path / "bench" / sub / "no_such_kind.py")
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "BENCH", tmp_path / "bench")
    missing = "reference" if present else "models"
    with pytest.raises(ValueError,
                       match=f"no bench/{missing}/no_such_kind.py"):
        spec.cell(cell_name)
