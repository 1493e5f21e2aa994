"""Run one benchmark cell on the chip and print its result line.

From the root of a checkout::

    python3 bench/run.py --workload stablelm-1.6b.decode-backlog \\
        --seed 1234 --seconds 40 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; :mod:`bench.spec` says where each lives.
Set-up makes the weights from ``--seed`` on the device, builds
``ServeEngine`` and compiles every step program the configuration uses;
the window then serves the mix for ``--seconds``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the last
:data:`TRACE_SECONDS` of the window run under the profiler and the result
carries the per-layer metrics, the device's busy time and a breakdown.
After the window, a sample of the finished requests is checked against
the float32 reference of the configuration's kind (:mod:`bench.check`).

The last line of standard output is one JSON object; the numbers compared
for ``correct`` come last in it and in the last lines of standard error.
The run exits non-zero and prints no result where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind missing from
``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: Seconds at the end of the window that a ``--trace 1`` run profiles.
TRACE_SECONDS = 4.0


class Refused(Exception):
    """No result may be printed: the message says why."""


def compile_cache() -> str:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``<checkout>/.jax_cache``, a fixed path so that a later
    run of the same checkout finds what an earlier one compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def find_chips(chips: int):
    """The devices, refused unless they are TPUs, at least ``chips`` of
    them, of a kind ``bench/peaks.json`` knows."""
    import jax
    from bench import spec
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX platform is {devices[0].platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    try:
        peaks = spec.peaks(devices[0].device_kind)
    except spec.UnknownDevice as exc:
        raise Refused(str(exc)) from exc
    return devices[:chips], peaks


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peaks: Dict[str, Any], t_process: float) -> Dict[str, Any]:
    """Set up, serve the window, check, and build the result line."""
    from bench import check, e2e, serve, spec, trace_reduce
    from bench.generator import arrivals

    conf = cell.config
    engine = serve.build(conf, seed)
    serve.warm_up(engine)
    offered = arrivals(cell.traffic, seed, conf["vocab_size"],
                       engine.max_batch)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = serve.Window(engine, offered, seconds,
                       trace_dir=trace_dir,
                       trace_s=min(TRACE_SECONDS, seconds / 2))
    setup_s = time.perf_counter() - t_process
    rec = win.run(conf, peaks)
    finished = win.finished(rec)
    failed = len(engine.failed)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    engine.state = engine.params = None     # free before the reference
    del engine, win
    gc.collect()
    limits = cell.checks["limits"]
    picked = check.sample(finished, seed, cell.checks["sample_tokens"],
                          cell.checks["sample_requests"])
    seqs, rows = check.sequences(picked)
    ref = spec.reference(conf).Reference(conf, seed,
                                         conf["serving"]["max_seq_len"])
    gaps = check.served_gaps(ref.logits(seqs, rows)[None], picked)
    nums = check.numbers(gaps, failed, limits)
    correct = check.passed(nums)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown: Optional[Dict[str, Any]] = None
    if not trace:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else \
                e2e.WINDOW[m["name"]](rec)
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        rec.trace = trace_reduce.load(files[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        breakdown = {"device_ops": rec.trace.top_ops(10),
                     "idle_gaps": rec.trace.idle_gaps(10)}
    out = {"correct": correct, "attempted": len(rec.requests),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": n["value"], "limit": n["limit"],
                         "must_be": "at most" if n["at_most"]
                         else "at least"}
                     for k, n in nums.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell named in BENCHMARK.json's workloads")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights, prompts and arrivals")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window's end, print per-layer "
                         "metrics")
    args = ap.parse_args(argv)
    here = str(ROOT / "bench")
    sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise Refused(f"no src/repro in {ROOT}: run from the root of "
                          f"a checkout")
        from bench import spec
        try:
            cell = spec.cell(args.workload)
        except (KeyError, FileNotFoundError, ValueError) as exc:
            raise Refused(f"no such cell: {exc}") from exc
        compile_cache()
        devices, peaks = find_chips(cell.chips)
    except Refused as exc:
        print(f"bench: refused: {exc}", file=sys.stderr, flush=True)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, peaks, T_PROCESS)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} ({c['must_be']} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
