"""Size a configuration's paged KV pool to the chip, ahead of time.

On the machine with the chip, from the root of a checkout::

    python3 bench/size_pool.py --config stablelm-1.6b [--write]

Compiles every step program that the configuration's engine uses (decode
at each row bucket, prefill at each) for two pool sizes, without running
them, and reads each program's device footprint from
``memory_analysis()``: arguments + outputs - aliased + temporaries + code.
The footprint grows linearly with the pool, so two sizes give each
program's line; ``num_blocks`` is the largest pool at which every line
stays under the device's ``bytes_limit`` less :data:`MARGIN`.  That pool
is then compiled once more to confirm it.  ``--write`` stores the count
and the margin in the configuration file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: Share of the device's ``bytes_limit`` left free: the allocator's
#: fragmentation and the host-to-device copies of each tick's inputs.
MARGIN = 0.06
PROBES = (256, 512)
#: Refits before the pool is given up as not fitting.
ROUNDS = 4


def footprint(compiled) -> int:
    ma = compiled.executable.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def programs(engine, conf, num_blocks: int):
    """Compile each step program for a pool of ``num_blocks``; yield
    (name, footprint bytes)."""
    import jax
    import jax.numpy as jnp
    from bench import serve
    from repro.serving import CacheConfig
    from repro.serving import model as smodel
    srv = conf["serving"]
    cache = CacheConfig(block_size=srv["block_size"], num_blocks=num_blocks,
                        max_seq_len=srv["max_seq_len"])
    state = jax.eval_shape(lambda: smodel.init_state(
        engine.cfg, engine.max_batch, cache))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          engine.params)
    sds = jax.ShapeDtypeStruct
    sc = engine.sched.config
    prefill_max = min(engine.max_batch, sc.max_prefill_batch)
    for phase, top, c in (("decode", engine.max_batch, 1),
                          ("prefill", prefill_max, sc.prefill_chunk)):
        for b in serve.buckets(top):
            args = [params, state,
                    sds((b, cache.max_blocks_per_req), jnp.int32),
                    sds((b,), jnp.int32)]
            if phase == "prefill":
                args.append(sds((b,), jnp.int32))
            args.append({"tokens": sds((b, c), jnp.int32)})
            yield f"{phase}[{b}]", footprint(
                engine.engines[phase].compile(*args))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from bench import run, serve, spec
    run.compile_cache()
    entry = [c for c in spec.benchmark()["configs"]
             if c["name"] == args.config][0]
    conf = spec.config(args.config)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("size_pool: needs the chip", file=sys.stderr)
        return 2
    limit = dev.memory_stats()["bytes_limit"]
    budget = int(limit * (1 - MARGIN))
    small = dict(conf, serving=dict(conf["serving"], num_blocks=16))
    engine = serve.build(small, 0)
    fits = {}
    per = {}
    for nb in PROBES:
        for name, nbytes in programs(engine, conf, nb):
            per.setdefault(name, []).append(nbytes)
            print(f"{name} num_blocks {nb}: {nbytes} bytes", flush=True)
    points = {name: [(nb, f) for nb, f in zip(PROBES, fs)]
              for name, fs in per.items()}
    for _ in range(ROUNDS):
        # Each program's line through its two newest points, solved for
        # the budget; a program that grows faster than linearly (the
        # batch-1 decode step re-lays out the pools) needs a second look.
        for name, pts in points.items():
            (n0, f0), (n1, f1) = pts[-2:]
            fits[name] = int(n1 + (budget - f1) * (n1 - n0) / (f1 - f0))
        num_blocks = min(fits.values())
        worst = 0
        for name, nbytes in programs(engine, conf, num_blocks):
            points[name].append((num_blocks, nbytes))
            worst = max(worst, nbytes)
            print(f"{name} num_blocks {num_blocks}: {nbytes} bytes",
                  flush=True)
        if worst <= budget:
            break
    print(json.dumps({"config": args.config, "bytes_limit": limit,
                      "margin": MARGIN, "budget": budget,
                      "num_blocks": num_blocks, "largest_program": worst,
                      "fits_by_program": fits}), flush=True)
    if worst > budget:
        print("size_pool: the chosen pool does not fit", file=sys.stderr)
        return 1
    if args.write:
        path = ROOT / entry["file"]
        conf["serving"]["num_blocks"] = num_blocks
        conf["serving"]["num_blocks_rule"] = (
            f"largest pool whose every step program fits bytes_limit "
            f"{limit} less {MARGIN:.0%} by ahead-of-time memory_analysis "
            f"(bench/size_pool.py); largest program {worst} bytes")
        with open(path, "w") as f:
            json.dump(conf, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
