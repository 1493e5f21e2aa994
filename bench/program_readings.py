"""Run one cell as ``bench/run.py --trace 1`` does, and print after its
result line what the program's spans and scopes read in the traced
stretch (:func:`bench.program_trace.readings`).

From the root of a checkout, on the chip::

    python3 bench/program_readings.py --workload stablelm-1.6b.decode-backlog \\
        --seed 1234 --seconds 51 [--keep DIR]

The last line of standard output is ``PROGRAM_TRACE {...}``; the line
before it is the cell's result line.  ``--keep DIR`` also copies the
trace there, gzipped, as ``<workload>-<seed>.xplane.pb.gz``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to copy the gzipped trace into")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import program_trace, run, trace_reduce

    found = {}
    reduce_load = trace_reduce.load

    def load(path):
        # run_cell reduces the trace here and deletes it after: read the
        # program's part of it first.
        trace_reduce.load = reduce_load
        pt = program_trace.load(path)
        found.update(program_trace.readings(pt))
        if args.keep:
            keep = pathlib.Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            dst = keep / f"{args.workload}-{args.seed}.xplane.pb.gz"
            with open(path, "rb") as src, gzip.open(dst, "wb") as out:
                shutil.copyfileobj(src, out)
            found["kept"] = str(dst)
        return pt.trace

    trace_reduce.load = load
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc == 0:
        print("PROGRAM_TRACE " + json.dumps(found), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
