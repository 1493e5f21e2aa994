"""Readings that the correctness limits are set from, on the chip.

From the root of a checkout, on the machine with the chip::

    python3 bench/control.py --workload stablelm-1.6b.decode-backlog \\
        --seconds 20 --seeds 101 102 103 [--fault half_batch]

One process builds the cell's engine once.  For each seed it makes the
weights, serves a window of the cell's own traffic at its own load, and
checks the finished requests as a benchmark run does.  On the same sample
it also runs the control: the reference computed in int8
(``bench/reference/<kind>.py``), read at every served position as the gap
of the token the int8 forward puts first, and held to the cell's limits
like the program.  One JSON line per seed gives both gaps, widest and
mean (the program's are lower readings of the limits, the control's
upper ones), and whether each is ``correct``.  ``--fault`` plants one of
:mod:`bench.faults` under the timed path first; the program's numbers
are then the fault's.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float, engine, peaks) -> dict:
    """Serve one window with the seed's weights, then read both gaps."""
    from bench import check, spec
    from bench.generator import arrivals
    from bench.serve import Window
    conf = cell.config
    engine.state = engine.params = None
    gc.collect()
    engine.params = spec.model_kind(conf).program_params(seed, conf)
    engine.reset()
    win = Window(engine, arrivals(cell.traffic, seed, conf["vocab_size"],
                                  engine.max_batch), seconds)
    rec = win.run(conf, peaks)
    finished = win.finished(rec)
    failed = len(engine.failed)
    engine.state = engine.params = None
    gc.collect()
    picked = check.sample(finished, seed, cell.checks["sample_tokens"],
                          cell.checks["sample_requests"])
    seqs, rows = check.sequences(picked)
    t0 = time.perf_counter()
    ref = spec.reference(conf).Reference(conf, seed,
                                         conf["serving"]["max_seq_len"])
    lg = ref.logits(seqs, rows, quants=(None, "int8"))
    limits = cell.checks["limits"]
    gaps = {"served": check.served_gaps(lg[None], picked),
            "control": check.control_gaps(lg[None], lg["int8"])}
    served = check.numbers(gaps["served"], failed, limits)
    control = check.numbers(gaps["control"], 0, limits)
    return {"workload": cell.name, "seed": seed, "requests": len(picked),
            "correct": check.passed(served),
            "control_correct": check.passed(control),
            "readings": {f"{k}_gap_{f.__name__}": float(f(g)) if len(g)
                         else None for k, g in gaps.items()
                         for f in (np.max, np.mean)},
            "checks": {k: n["value"] for k, n in served.items()},
            "control_checks": {k: n["value"] for k, n in control.items()},
            "limits": {k: n["limit"] for k, n in served.items()},
            "reference_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", help="plant this fault of bench/faults.py")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import faults, run, serve, spec
    cell = spec.cell(args.workload)
    run.compile_cache()
    devices, peaks = run.find_chips(cell.chips)
    engine = serve.build(cell.config, args.seeds[0])
    serve.warm_up(engine)
    if args.fault:
        faults.FAULTS[args.fault](engine)
    for seed in args.seeds:
        out = readings(cell, seed, args.seconds, engine, peaks)
        print(json.dumps(dict(out, fault=args.fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
