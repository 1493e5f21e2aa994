"""The benchmark finds every piece by its name, so a later change adds a
configuration, a mix, a cell or a per-layer metric by adding files; and a
run refuses to print a result off the chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

BENCH = spec.benchmark()
ROOT = spec.ROOT


def _stems(sub, suffix):
    return sorted(p.name[:-len(suffix)]
                  for p in (spec.BENCH / sub).glob(f"*{suffix}"))


def test_every_file_is_named_in_the_benchmark():
    assert _stems("configs", ".json") == sorted(
        c["name"] for c in BENCH["configs"])
    assert _stems("traffic", ".json") == sorted(
        {w["traffic"] for w in BENCH["workloads"]})
    assert _stems("workloads", ".json") == sorted(
        w["name"] for w in BENCH["workloads"])
    assert _stems("metrics", ".py") == sorted(
        m["name"] for m in BENCH["per_layer"])
    for kind in _stems("traffic", ".py"):
        assert callable(spec.arrival_kind(kind).Arrivals)
    for w in BENCH["workloads"]:
        assert spec.traffic(w["traffic"])["arrival"] in _stems(
            "traffic", ".py")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(name):
    cell = spec.cell(name)
    assert cell.chips == 1
    assert cell.config["name"] in {c["name"] for c in BENCH["configs"]}
    assert cell.checks["limits"]["served_gap_max"] > 0
    assert cell.checks["limits"]["served_gap_mean"] > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]).read)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_cells_report_what_it_moves(metric):
    for cell in metric["workloads"]:
        reported = {m["name"] for m in spec.cell(cell).end_to_end}
        assert metric["moves"] in reported, (metric["name"], cell)
        assert metric["name"] in {m["name"] for m in
                                  spec.cell(cell).per_layer}


#: What a model kind's two modules give (``bench/spec.py`` says what each
#: name is).
KIND_NAMES = {"models": ("expected", "program_params", "layer",
                         "gemm_least_time", "tick_flops",
                         "attention_least_time", "tiny"),
              "reference": ("Reference",)}


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_every_config_kind_resolves_to_both_modules(name):
    conf = spec.config(name)
    for sub, mod in (("models", spec.model_kind(conf)),
                     ("reference", spec.reference(conf))):
        assert mod.__file__ == str(spec.BENCH / sub /
                                   f"{spec.kind(conf)}.py")
        for attr in KIND_NAMES[sub]:
            assert callable(getattr(mod, attr)), (sub, attr)


def test_config_files_state_their_cuts():
    for entry in BENCH["configs"]:
        conf = json.loads((ROOT / entry["file"]).read_text())
        assert conf["reduced"] == entry["reduced"]
        assert conf["source"] == entry["source"]
        assert conf["serving"]["num_blocks"] > 0
        assert conf["torch_dtype"] == "bfloat16"


def test_unknown_device_kind_refused():
    with pytest.raises(spec.UnknownDevice):
        spec.peaks("TPU v4")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_off_the_chip():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "not 'tpu'" in out.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


#: An arrival kind a later change might add: one request every half
#: second, whatever the engine is doing.
NEW_KIND = """
class Arrivals:
    def __init__(self, mix, seed, stream, max_batch):
        self.stream, self.n = stream, 0

    def release(self, now_s, waiting):
        out = []
        while self.n * 0.5 <= now_s:
            out.append((self.n * 0.5, self.stream.next()))
            self.n += 1
        return out

    def next_due(self):
        return self.n * 0.5
"""


def test_a_new_cell_and_metric_need_only_new_files(tmp_path, monkeypatch):
    """Add a configuration, an arrival kind, a mix, a cell and a per-layer
    metric the way a later change would: new files and new entries, no
    file edited; then run the new cell past the look for a chip."""
    import time

    import jax

    from bench import run
    from conftest import PEAKS, tiny_cell
    shutil.copytree(spec.BENCH, tmp_path / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    old = bench["workloads"][0]
    conf = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(
        dict(conf, name="new-model")))
    (tmp_path / "bench/traffic/every_half_second.py").write_text(NEW_KIND)
    mix = dict(spec.traffic(old["traffic"]), arrival="every_half_second")
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/workloads/new-model.new-mix.json").write_text(
        (spec.BENCH / "workloads" / f"{old['name']}.json").read_text())
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(rec):\n    return float(len(rec.requests))\n")
    bench["configs"].append(dict(bench["configs"][0], name="new-model",
                                 file="bench/configs/new-model.json"))
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({"name": "new_metric", "unit": "count",
                               "better": "lower", "source": "host_clock",
                               "layer": "engine", "moves": "output_tok_s",
                               "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "BENCH", tmp_path / "bench")
    cell = spec.cell("new-model.new-mix")
    assert cell.config["name"] == "new-model"
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    out = run.run_cell(tiny_cell("new-model.new-mix"), 2 ** 31 + 5, 3.0,
                       False, jax.devices("cpu")[:1], PEAKS,
                       time.perf_counter())
    # Requests due every half second of a 3 s window: 6 of them.
    assert out["attempted"] == 6
    assert out["correct"] is True, out["checks"]
