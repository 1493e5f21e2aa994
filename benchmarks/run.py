"""Benchmark harness: one function per paper table/figure + kernel/roofline
rows.  Prints ``name,us_per_call,derived`` CSV, then the claims scoreboard.

``--bench-json [PATH]`` runs the kernel-bench smoke set (fused-vs-unfused
GEMM chains + fusion accounting) and writes it as JSON — by default
``BENCH_kernels.json`` at the repo root, the perf baseline future PRs
regress against.  ``--bench-full`` includes the heavier attention / rglru /
mlstm rows in the JSON as well.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# Support both `python -m benchmarks.run` and `python benchmarks/run.py`.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)


#: (fast suffix, baseline suffix) pairs the bench gate enforces: the fast
#: row must not be slower than baseline * slack.
_CHECK_PAIRS = ((".fused", ".unfused"), (".cached", ".percall"),
                (".overlap", ".noverlap"))


def check_chain_rows(rows, *, slack: float = 1.25) -> int:
    """Enforce the acceptance bars: every ``.fused`` chain row must be no
    slower than its ``.unfused`` counterpart times ``slack``, and every
    engine ``.cached`` row must beat its per-call-compile ``.percall``
    baseline the same way (cache-hit dispatch overhead must stay amortized).

    The slack is deliberately coarse: shared CI runners jitter by tens of
    percent, while a genuine regression (an extra materialization on the
    fused path; a re-trace on the cached path) erases the whole margin and
    then some — this is a tripwire for the pathological case, not a
    high-resolution perf gate.  Returns the number of violations."""
    by_name = {name: us for name, us, _ in rows}
    bad = 0
    for name, us in sorted(by_name.items()):
        for fast, base_sfx in _CHECK_PAIRS:
            if not name.endswith(fast):
                continue
            base = by_name.get(name[:-len(fast)] + base_sfx)
            if base is None:
                continue
            ok = us <= base * slack
            print(f"# check {name}: {fast[1:]} {us:.1f}us vs "
                  f"{base_sfx[1:]} {base:.1f}us "
                  f"-> {'ok' if ok else 'REGRESSION'}")
            bad += 0 if ok else 1
    return bad


def check_backend_rows(rows, baseline_path: str, *, slack: float = 3.0
                       ) -> int:
    """Gate the per-backend kernel rows against the *committed* baseline.

    The ``backend.<op>.<shape>.<name>`` rows time each registered backend on
    one fixed GEMM site.  Unlike the paired fused/cached checks (measured
    interleaved in one process), this compares across runs/hosts, so the
    slack is very coarse — it exists to trip on a pathological kernel-path
    regression (the ``interpret`` row IS the Pallas kernel logic on CPU CI;
    on TPU the ``pallas`` row joins it), not to resolve small drift.  Rows
    present on only one side (e.g. ``pallas`` appearing once CI gains a TPU
    leg) are skipped.  Returns the number of violations.
    """
    try:
        with open(baseline_path) as f:
            baseline = {r["name"]: r["us_per_call"]
                        for r in json.load(f).get("rows", [])}
    except (OSError, ValueError):
        print(f"# no committed baseline at {baseline_path}; "
              f"backend rows not gated")
        return 0
    bad = 0
    for name, us, _ in rows:
        if not name.startswith("backend."):
            continue
        base = baseline.get(name)
        if base is None:
            print(f"# check {name}: no baseline row (new backend) -> ok")
            continue
        ok = us <= base * slack
        print(f"# check {name}: {us:.1f}us vs committed {base:.1f}us "
              f"(slack x{slack}) -> {'ok' if ok else 'REGRESSION'}")
        bad += 0 if ok else 1
    return bad


def write_bench_json(path: str, *, full: bool = False,
                     check: bool = False, suite: str = "kernels") -> None:
    """Run the kernel benches and write ``{schema, meta, rows}`` JSON.

    ``suite="sharded"`` runs the SUMMA scaling rows instead (launch the
    process with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so
    the 2- and 4-device meshes exist); the ``--bench-check`` gate then
    enforces overlapped <= non-overlapped * slack at every mesh size.
    ``suite="serve"`` runs the continuous-batching serving rows (Poisson
    arrivals, sma vs fcfs scheduling); the gate enforces sma switches/token
    <= fcfs at every rate plus throughput vs the committed baseline.
    """
    import jax

    from benchmarks import kernel_bench

    if suite == "serve":
        from benchmarks import serve_bench
        rows = serve_bench.serve_rows()
        suite_name = "serve"
    elif suite == "sharded":
        if jax.device_count() < 4:
            print(f"# note: only {jax.device_count()} device(s) — set "
                  f"XLA_FLAGS=--xla_force_host_platform_device_count=4 "
                  f"for the full 1/2/4 scaling sweep")
        rows = kernel_bench.sharded_paths()
        suite_name = "sharded"
    else:
        rows = kernel_bench.all_rows() if full else kernel_bench.smoke_rows()
        suite_name = "full" if full else "smoke"
    baseline_violations = 0
    if check and suite == "kernels":
        baseline_violations = check_backend_rows(rows, path)
    elif check and suite == "serve":
        # Serve throughput always gates against the *committed* baseline,
        # even when the run writes its JSON elsewhere (the CI leg does).
        from benchmarks import serve_bench
        baseline_violations = serve_bench.check_serve_baseline(
            rows, os.path.join(_REPO_ROOT, "BENCH_serve.json"))
    payload = {
        "schema": 1,
        "meta": {
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "suite": suite_name,
        },
        "rows": [{"name": name, "us_per_call": us, "derived": derived}
                 for name, us, derived in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.4f}")
    print(f"# wrote {len(rows)} rows -> {path}")
    if check:
        if suite == "serve":
            from benchmarks import serve_bench
            violations = serve_bench.check_serve_rows(rows)
        else:
            violations = check_chain_rows(rows)
        if violations or baseline_violations:
            raise SystemExit(
                "bench check failed: fused chain slower than unfused, "
                "cached slower than percall, overlapped sharded GEMM "
                "slower than non-overlapped, SMA scheduler out-switching "
                "FCFS, or a row regressed vs the committed baseline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-kernels", action="store_true",
                    help="skip wall-clock kernel benches (CPU-heavy)")
    ap.add_argument("--bench-json", nargs="?", const=os.path.join(
                        _REPO_ROOT, "BENCH_kernels.json"),
                    default=None, metavar="PATH",
                    help="run the kernel-bench smoke set and write it as "
                         "JSON (default path: BENCH_kernels.json at the "
                         "repo root)")
    ap.add_argument("--bench-full", action="store_true",
                    help="with --bench-json: include the heavy kernel rows")
    ap.add_argument("--bench-check", action="store_true",
                    help="with --bench-json/--bench-sharded: fail (exit 1) "
                         "if any fused chain row is slower than its unfused "
                         "baseline, or any overlapped sharded row slower "
                         "than its non-overlapped reference")
    ap.add_argument("--bench-sharded", nargs="?", const=os.path.join(
                        _REPO_ROOT, "BENCH_gemm_sharded.json"),
                    default=None, metavar="PATH",
                    help="run the SUMMA sharded-GEMM scaling rows (needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=4) and write them as JSON (default path: "
                         "BENCH_gemm_sharded.json at the repo root)")
    ap.add_argument("--bench-serve", nargs="?", const=os.path.join(
                        _REPO_ROOT, "BENCH_serve.json"),
                    default=None, metavar="PATH",
                    help="run the continuous-batching serving rows (Poisson "
                         "arrivals, sma vs fcfs scheduling) and write them "
                         "as JSON (default path: BENCH_serve.json at the "
                         "repo root)")
    ap.add_argument("--analyze", nargs="*", default=None, metavar="ARCH",
                    help="run the static plan verifier + SMA lint pass "
                         "(python -m repro.analysis) over the named "
                         "architectures (none = --all) instead of "
                         "benchmarks; exits nonzero on error diagnostics")
    ap.add_argument("--analyze-check", action="store_true",
                    help="with --analyze: gate against the committed "
                         "golden baseline (GOLDEN_diagnostics.json)")
    ap.add_argument("--compile-report", action="store_true",
                    help="emit one jaxpr->SMA plan report (JSON) per model "
                         "family instead of running benchmarks")
    ap.add_argument("--report-dir", default=None,
                    help="with --compile-report: also write one "
                         "<arch>.plan.json per family into this directory")
    ap.add_argument("--report-seq", type=int, default=512,
                    help="sequence length for --compile-report tracing")
    ap.add_argument("--report-reduced", action="store_true",
                    help="trace reduced (smoke) configs instead of full "
                         "scale")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="profile the benchmark run with repro.obs and "
                         "write Chrome-trace JSON (Perfetto-loadable) here")
    args, _ = ap.parse_known_args()

    import contextlib

    import repro
    from repro.launch.common import use_compile_cache

    use_compile_cache()

    with repro.profile(path=args.trace_out) if args.trace_out \
            else contextlib.nullcontext():
        _dispatch(args)
    if args.trace_out:
        print(f"# wrote trace -> {args.trace_out}")


def _dispatch(args) -> None:
    if args.bench_serve:
        write_bench_json(args.bench_serve, check=args.bench_check,
                         suite="serve")
        return

    if args.bench_sharded:
        write_bench_json(args.bench_sharded, check=args.bench_check,
                         suite="sharded")
        return

    if args.bench_json:
        write_bench_json(args.bench_json, full=args.bench_full,
                         check=args.bench_check)
        return

    if args.analyze is not None:
        from repro.analysis.cli import main as analysis_main
        argv = list(args.analyze) or ["--all"]
        argv += ["--seq", str(args.report_seq)]
        if args.report_reduced:
            argv.append("--reduced")
        if args.analyze_check:
            argv.append("--check")
        raise SystemExit(analysis_main(argv))

    if args.compile_report:
        from benchmarks import compile_report
        compile_report.run(args.report_dir, seq_len=args.report_seq,
                           reduced=args.report_reduced)
        return

    from benchmarks import paper_figs, roofline_report

    rows = []
    claims = []
    for name, fn in paper_figs.ALL_FIGS.items():
        r, c = fn()
        rows += r
        claims += c

    if not args.skip_kernels:
        from benchmarks import kernel_bench
        rows += kernel_bench.all_rows()

    rows += roofline_report.csv_rows()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.4f}")

    print("\n# paper-claims scoreboard (claim, paper, ours, |delta|%)")
    for metric, paper, ours in claims:
        delta = abs(ours - paper) / abs(paper) * 100 if paper else 0.0
        print(f"# {metric}: paper={paper:.3f} ours={ours:.3f} "
              f"delta={delta:.1f}%")


if __name__ == "__main__":
    main()
