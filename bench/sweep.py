"""Find the knee of an open-loop mix once, by a sweep on the chip.

From the root of a checkout, on the machine with the chip::

    python3 bench/sweep.py --workload <cell of a poisson mix> \\
        --seconds 30 --seed 7 --rates 1 1.5 2 2.5 3

One process builds the cell's engine once and serves a window of the
mix at each rate in turn.  For each rate it prints the requests due,
those finished, the waiting queue in the window's second and last
quarters, and the tails.  The knee is the highest rate whose queue does
not grow over the window; the cell runs at a fixed rate below it, written
into its traffic file.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import e2e, run, serve, spec
    from bench.generator import arrivals
    cell = spec.cell(args.workload)
    run.compile_cache()
    devices, peaks = run.find_chips(cell.chips)
    engine = serve.build(cell.config, args.seed)
    serve.warm_up(engine)
    for rate in args.rates:
        engine.reset()
        mix = dict(cell.traffic, rate_per_s=rate)
        offered = arrivals(mix, args.seed, cell.config["vocab_size"],
                           engine.max_batch)
        win = serve.Window(engine, offered, args.seconds)
        rec = win.run(cell.config, peaks)
        waiting = []            # (time, requests due and not admitted)
        for t in rec.ticks:
            waiting.append((t.t1 - rec.t_start, sum(
                1 for r in rec.requests.values()
                if r.due <= t.t1 and (r.t_admit is None
                                      or r.t_admit > t.t1))))
        q = [(s / args.seconds, w) for s, w in waiting]
        second = [w for f, w in q if 0.25 <= f < 0.5]
        last = [w for f, w in q if f >= 0.75]
        print(json.dumps({
            "rate_per_s": rate, "due": len(rec.requests),
            "finished": len(win.finished(rec)),
            "waiting_q2": statistics.mean(second) if second else None,
            "waiting_q4": statistics.mean(last) if last else None,
            "ttft_p75_s": e2e.ttft_p75_s(rec),
            "itl_p95_ms": e2e.itl_p95_ms(rec),
            "output_tok_s": e2e.output_tok_s(rec)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
