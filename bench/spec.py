"""Where the benchmark finds each piece: by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own, found by name, so a later change adds a
configuration, a mix, a cell or a per-layer metric by adding files:

* ``bench/configs/<config>.json``: the model as it runs, its source, what
  was cut and assumed, and the serving settings; its ``model.kind``
  (``dense`` where it names none) picks the next two;
* ``bench/models/<kind>.py``: what the benchmark knows of that kind's
  block layout: ``expected(conf)``, the ``ModelConfig`` fields the program
  must match; ``program_params(seed, conf)``, the program's weights from
  the seed, and ``layer(key, conf, index)``, one layer of them;
  ``gemm_least_time(conf, tokens, peaks)``,
  ``tick_flops(conf, rows, logit_rows)`` and
  ``attention_least_time(conf, kv_lens, peaks)``, the work the rooflines
  and ``step_mfu`` count; ``tiny(conf, control=False)``, the cut the CPU
  tests run;
* ``bench/reference/<kind>.py``: ``Reference(conf, seed, seq_len)``, the
  plain forward whose ``logits(seqs, rows, quants)`` decides ``correct``;
* ``bench/traffic/<traffic>.json``: the parameters that the one general
  generator (:mod:`bench.generator`) reads;
* ``bench/traffic/<kind>.py``: one module per arrival kind, named by a
  mix's ``arrival``;
* ``bench/workloads/<cell>.json``: the cell's correctness limits and the
  size of the sample the reference checks;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class UnknownDevice(Exception):
    """The device kind has no entry in ``bench/peaks.json``."""


def _json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    entry = _entry(benchmark()["configs"], name, "configuration")
    return _json(ROOT / entry["file"])


def traffic(name: str) -> Dict[str, Any]:
    return _json(BENCH / "traffic" / f"{name}.json")


def checks(cell_name: str) -> Dict[str, Any]:
    return _json(BENCH / "workloads" / f"{cell_name}.json")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py``; its ``read(record)`` returns
    the metric's value, or None when the run holds nothing to read."""
    return _module("metrics", name)


def arrival_kind(name: str):
    """The module ``bench/traffic/<name>.py``; its ``Arrivals`` class says
    when each request of a mix of that kind is due."""
    if not (BENCH / "traffic" / f"{name}.py").is_file():
        raise ValueError(f"unknown arrival {name!r}: no "
                         f"bench/traffic/{name}.py")
    return _module("traffic", name)


def model_kind(conf: Dict[str, Any]):
    """The module ``bench/models/<kind>.py`` of a configuration's kind."""
    return _module("models", kind(conf))


def reference(conf: Dict[str, Any]):
    """The module ``bench/reference/<kind>.py`` of a configuration's kind."""
    return _module("reference", kind(conf))


def kind(conf: Dict[str, Any]) -> str:
    """The configuration's model kind, refused unless both of its modules
    are there."""
    name = conf.get("model", {}).get("kind", "dense")
    for sub in ("models", "reference"):
        if not (BENCH / sub / f"{name}.py").is_file():
            raise ValueError(f"unknown model kind {name!r}: no "
                             f"bench/{sub}/{name}.py")
    return name


def _module(sub: str, name: str):
    path = BENCH / sub / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entry(entries: List[Dict[str, Any]], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, read from the files."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    checks: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def cell(name: str) -> Cell:
    bench = benchmark()
    entry = _entry(bench["workloads"], name, "workload")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in e2e_names]
    conf = config(entry["config"])
    kind(conf)
    return Cell(name=name, chips=entry["chips"], config=conf,
                traffic=traffic(entry["traffic"]), checks=checks(name),
                end_to_end=e2e, per_layer=layer)
