"""Bring-up smoke: the serving path end to end on a TPU, and nothing else.

Run from the root of a checkout (no ``PYTHONPATH`` needed)::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded GEMM only

One chip: stablelm-1.6b at its published widths (24 layers, d_model 2048,
32 heads of 64, d_ff 5632, vocab 100352, bf16 activations, random weights
from ``--seed``) serves 8 requests through ``repro.serving.ServeEngine`` on
the ``pallas`` backend, twice: a warm-up pass that compiles every batch
bucket, then a timed pass that must repeat it token for token.  The same
requests then run on the ``xla`` backend as the reference, fed the same
tokens: the logits each request's first and second tokens were sampled
from must agree within ``LOGIT_RTOL`` of the reference's max |logit|, and
the tokens must match unless the reference's top two are closer than the
measured deviation allows.
Every GEMM and every decode attention site must run a Pallas kernel; the
one allowed decline is chunked-prefill attention, printed with its reason.

Four chips: ``distributed.sma_gemm_sharded`` on a 2x2 mesh at
8192x2048 @ 2048x5632 (bf16, bias, gelu), overlapped and not, against
single-chip ``ops.sma_gemm`` on the same inputs.

The script exits non-zero and prints no result when JAX finds no TPU, when
``REPRO_BACKEND`` or ``REPRO_FAULTS`` is set, when it is not beside
``src/repro``, or when any check fails.  A passing run ends with one JSON
line naming the device.  Its timings are smoke timings, not benchmark
numbers.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "stablelm-1.6b"
#: 576 blocks of 16 positions hold 8 requests of up to 1152 positions.
CACHE = {"block_size": 16, "num_blocks": 576, "max_seq_len": 1152}
MAX_BATCH = 8
N_REQUESTS = 8
PROMPT_LENS = (256, 1024)
MAX_NEW = 64
#: Tokens compared with the reference per request: the first comes from a
#: prefill tick, the second from a decode tick.
COMPARED_TOKENS = 2
#: Logits of the two backends, max |pallas - xla| over the reference's max
#: |logit|: about four bf16 ulps (2**-8 to 2**-7 of a value each) at the
#: largest logit.  Both paths multiply bf16 operands with f32 accumulation
#: but round to bf16 in different places (K blocking, fused epilogues) on
#: the way through 24 layers, and the logits themselves are bf16; a wrong
#: kernel is off by order one.
LOGIT_RTOL = 3e-2
#: The sharded GEMM against the single-chip kernel, max |diff| over max
#: |ref|.  SUMMA rounds each K-panel's partial product to bf16.
GEMM_RTOL = 2e-2
SUMMA_SHAPE = (8192, 2048, 5632)   # M, K, N
#: The state tuple is donated: without it a decode step at batch 1 needs
#: 17.7 GB (ahead-of-time memory analysis), more than the chip holds.
DONATE_STATE = (1,)


class SmokeFailure(Exception):
    """A check failed; the message says which."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


def bring_up(chips: int):
    """Refuse every setting that would hide the device, then find it."""
    for var in ("REPRO_BACKEND", "REPRO_FAULTS"):
        check(not os.environ.get(var),
              f"{var}={os.environ.get(var)!r} is set; it would pin a "
              f"backend or inject faults.  Unset it.")
    src = os.path.join(ROOT, "src")
    check(os.path.isdir(os.path.join(src, "repro")),
          f"no src/repro beside {os.path.basename(__file__)}: run it from "
          f"the root of a checkout")
    sys.path.insert(0, src)
    import jax
    from repro.launch.common import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX platform is {devices[0].platform!r}, not 'tpu': this smoke "
          f"runs on the chip only")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    say(f"device {devices[0].device_kind}, count {len(devices)}, "
        f"platform {devices[0].platform}, jax {jax.__version__}")
    say(f"compile cache {cache_dir}")
    return jax, devices


# --------------------------------------------------------------------------
# One chip: ServeEngine
# --------------------------------------------------------------------------
def make_prompts(vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def recording_engine_class():
    from repro.serving import ServeEngine

    class RecordingEngine(ServeEngine):
        """Keeps the logits row each request's first ``COMPARED_TOKENS``
        tokens were sampled from (every emission samples, then emits).

        With ``forced`` (request id -> tokens) it records its own choice
        but emits the forced token, so a reference run feeds the same
        tokens as the run it checks and every compared row sees the same
        history.
        """

        def __init__(self, *args, forced=None, **kwargs):
            super().__init__(*args, **kwargs)
            self.forced = forced
            self.rows = {}
            self.choices = {}
            self._row = None

        def _sample(self, np_row):
            self._row = np_row
            return super()._sample(np_row)

        def _emit(self, req, tok):
            rows = self.rows.setdefault(req.rid, [])
            choices = self.choices.setdefault(req.rid, [])
            if len(rows) < COMPARED_TOKENS:
                rows.append(self._row.copy())
                choices.append(tok)
            if self.forced is not None:
                tok = self.forced[req.rid][len(req.out_tokens)]
            super()._emit(req, tok)

        def reset(self):
            super().reset()
            self.rows = {}
            self.choices = {}

    return RecordingEngine


def serve_pass(jax, engine, prompts, max_new: int) -> dict:
    """Submit every prompt and step until the queue and active set drain."""
    from repro.serving import Request

    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    misses = sum(e.stats.misses for e in engine.engines.values())
    t0 = time.perf_counter()
    ticks = 0
    while engine.queue or engine.active:
        engine.step()
        ticks += 1
        check(ticks <= 10_000, "the engine did not drain in 10000 ticks")
    jax.block_until_ready(engine.state)
    wall = time.perf_counter() - t0
    return {
        "ticks": ticks, "wall_s": wall,
        "compiles": sum(e.stats.misses for e in engine.engines.values())
        - misses,
        "done": len(engine.done), "failed": dict(
            (rid, r.error) for rid, r in engine.failed.items()),
        "tokens": {rid: list(r.out_tokens) for rid, r in engine.done.items()},
        "rows": dict(engine.rows), "choices": dict(engine.choices),
    }


def report_engines(engine, backend: str) -> None:
    """Print compiles per (engine, bucket) and every backend resolution;
    fail on any site that left the requested backend, except
    chunked-prefill attention."""
    for name, eng in engine.engines.items():
        for entry in eng.report["entries"]:
            bucket = entry["signature"][-1][0][0]  # tokens (bucket, width)
            be = entry["backends"]
            say(f"engine {name} bucket {bucket}: compile "
                f"{entry['compile_time_s']:.3f} s, {entry['cache_hits']} hits"
                f"; sites {be['num_sites']} chosen {be['chosen']}")
            check(len(be["sites"]) == be["num_sites"],
                  f"{name} bucket {bucket}: report lists "
                  f"{len(be['sites'])} of {be['num_sites']} sites")
            check(not be["interpret"], f"{name} compiled in interpret mode")
            for site in be["sites"]:
                if site["backend"] == backend:
                    continue
                chunked = (site["op"] == "paged_decode_attention"
                           and site["shapes"][0][1] > 1)
                say(f"  decline {site['op']} {site['shapes'][0]} -> "
                    f"{site['backend']}: {site['fallback_reason']}")
                check(chunked, f"{name} bucket {bucket}: {site['op']} "
                               f"{site['shapes'][0]} ran on "
                               f"{site['backend']}, not {backend}")


def run_backend(jax, cfg, params, prompts, backend: str, max_new: int,
                passes: int, forced=None) -> dict:
    from repro.api import SMAOptions
    from repro.resilience.guard import resilience_section
    from repro.serving import CacheConfig

    engine = recording_engine_class()(
        cfg, params, cache=CacheConfig(**CACHE), max_batch=MAX_BATCH,
        forced=forced,
        options=SMAOptions(backend=backend, check_numerics="raise",
                           donate_argnums=DONATE_STATE))
    runs = []
    for i in range(passes):
        if i:
            engine.reset()
        run = serve_pass(jax, engine, prompts, max_new)
        runs.append(run)
        label = "warm-up" if i == 0 and passes > 1 else "pass"
        say(f"{backend} {label} {i}: {run['done']} done, "
            f"{len(run['failed'])} failed, "
            f"{sum(map(len, run['tokens'].values()))} tokens, "
            f"{run['ticks']} ticks, {run['compiles']} compiles inside")
        check(not run["failed"], f"{backend}: requests failed: "
                                 f"{run['failed']}")
        check(run["done"] == len(prompts),
              f"{backend}: {run['done']} of {len(prompts)} requests done")
        for rid, toks in run["tokens"].items():
            check(len(toks) == max_new and all(
                0 <= t < cfg.vocab_size for t in toks),
                f"{backend}: request {rid} gave {len(toks)} tokens, or "
                f"tokens outside the vocabulary")
    report_engines(engine, backend)
    compile_s = sum(e.stats.compile_time_s for e in engine.engines.values())
    compiles = sum(e.stats.misses for e in engine.engines.values())
    say(f"{backend} compiles {compiles}, compile seconds {compile_s:.3f}")
    res = resilience_section()
    say(f"{backend} runtime_fallbacks {res['runtime_fallbacks']}, "
        f"numeric_events {res['numeric_events']}, "
        f"quarantine {res['quarantine']}")
    check(res["runtime_fallbacks"] == 0,
          f"{backend}: {res['runtime_fallbacks']} runtime fallbacks: "
          f"{res['events']}")
    check(res["numeric_events"] == 0,
          f"{backend}: {res['numeric_events']} numeric events")
    engine.state = None   # free the pools before the next engine
    return {"runs": runs, "compile_s": compile_s, "compiles": compiles}


def serve_smoke(jax, seed: int) -> None:
    import numpy as np
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config(ARCH)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.jit(lambda k: lm.init(k, cfg)[0])(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    pbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    say(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads x {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, activations {cfg.dtype}, params "
        f"{cfg.param_dtype} {pbytes} bytes, init {init_s:.3f} s")
    prompts = make_prompts(cfg.vocab_size, seed)
    say(f"requests {len(prompts)}, prompt lengths "
        f"{[len(p) for p in prompts]}, max_new_tokens {MAX_NEW}")

    pal = run_backend(jax, cfg, params, prompts, "pallas", MAX_NEW, 2)
    warm, steady = pal["runs"]
    check(steady["tokens"] == warm["tokens"],
          "pallas: the timed pass did not repeat the warm-up's tokens")
    ntok = sum(map(len, steady["tokens"].values()))
    say(f"smoke timing (not a benchmark): steady pass {steady['wall_s']:.3f} "
        f"s wall, {ntok} tokens, {ntok / steady['wall_s']:.1f} tokens/s, "
        f"{steady['compiles']} compiles inside")

    # The reference is fed pallas's tokens, so a near-tie that the two
    # backends break differently cannot make the histories diverge.
    ref = run_backend(jax, cfg, params, prompts, "xla", COMPARED_TOKENS, 1,
                      forced=steady["tokens"])
    (ref_run,) = ref["runs"]
    worst, same, ties, first_same = 0.0, 0, 0, 0
    for rid in range(len(prompts)):
        for i in range(COMPARED_TOKENS):
            g = np.asarray(steady["rows"][rid][i], np.float32)
            w = np.asarray(ref_run["rows"][rid][i], np.float32)
            tok, ref_tok = steady["tokens"][rid][i], ref_run["choices"][rid][i]
            diff = float(np.max(np.abs(g - w)))
            dev_i = diff / float(np.max(np.abs(w)))
            top2 = np.sort(w)[-2:]
            margin = float(top2[1] - top2[0])
            worst = max(worst, dev_i)
            say(f"request {rid} token {i}: pallas {tok} xla {ref_tok}, "
                f"max |dlogit| {diff:.4e} = {dev_i:.3e} of max |logit|, "
                f"reference top-2 margin {margin:.4e}")
            if tok == ref_tok:
                same += 1
                first_same += i == 0
                continue
            # Every logit moved by at most ``diff``: the backends can rank
            # two tokens differently only when the reference's margin is
            # within 2 * diff, and pallas's token must then be one of them.
            check(margin <= 2 * diff and w[tok] >= top2[1] - 2 * diff,
                  f"request {rid} token {i}: pallas chose {tok} "
                  f"(reference logit {w[tok]:.4e}), xla {ref_tok} "
                  f"({top2[1]:.4e}), beyond the measured deviation {diff:.4e}")
            say(f"  near tie: margin {margin:.4e} <= 2 x {diff:.4e}")
            ties += 1
    n = len(prompts) * COMPARED_TOKENS
    say(f"logits vs xla: max deviation {worst:.3e} of max |logit| "
        f"(tolerance {LOGIT_RTOL:.0e}); tokens identical {same}/{n}, "
        f"near ties {ties}/{n}; first tokens identical "
        f"{first_same}/{len(prompts)}")
    check(worst <= LOGIT_RTOL,
          f"logits deviate {worst:.3e} of max |logit| > {LOGIT_RTOL:.0e}")

    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    say(f"memory peak_bytes_in_use {peak}, bytes_limit {limit}")
    engines_s = pal["compile_s"] + ref["compile_s"]
    say(f"compile total: {pal['compiles'] + ref['compiles']} engine "
        f"compiles {engines_s:.3f} s; set-up (weight init, whose compile "
        f"dominates it, + engine compiles) {init_s + engines_s:.3f} s")


# --------------------------------------------------------------------------
# Four chips: SUMMA
# --------------------------------------------------------------------------
def summa_smoke(jax, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.backends import registry
    from repro.distributed.summa import sma_gemm_sharded
    from repro.kernels import ops
    from repro.launch.mesh import fake_mesh
    from repro.resilience.guard import resilience_section

    m, k, n = SUMMA_SHAPE
    chips, backend = 4, "pallas"
    mesh = fake_mesh(chips)
    say(f"mesh {dict(mesh.shape)} over devices "
        f"{[d.id for d in mesh.devices.flat]}")
    ka, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    rows, cols = mesh.axis_names
    a = jax.device_put(jax.random.normal(ka, (m, k), jnp.bfloat16),
                       NamedSharding(mesh, P(rows, cols)))
    b = jax.device_put(
        (jax.random.normal(kb, (k, n), jnp.float32) * k ** -0.5)
        .astype(jnp.bfloat16), NamedSharding(mesh, P(rows, cols)))
    bias = jax.device_put(jax.random.normal(kc, (n,), jnp.bfloat16),
                          NamedSharding(mesh, P(cols)))
    for name, x in (("A", a), ("B", b), ("bias", bias)):
        per_dev = {s.device.id: s.data.nbytes for s in x.addressable_shards}
        say(f"{name} {x.shape} {x.dtype}: bytes per device {per_dev} "
            f"(total {x.nbytes})")
        if name != "bias":   # bias is split over columns, kept per row
            check(sorted(per_dev.values()) == [x.nbytes // chips] * chips,
                  f"{name} is not spread over {chips} devices: {per_dev}")

    outs = {}
    with registry.record_sites() as sites:
        for overlap in (True, False):
            t0 = time.perf_counter()
            y = sma_gemm_sharded(a, b, mesh=mesh, bias=bias,
                                 epilogue="gelu", overlap=overlap,
                                 backend=backend)
            jax.block_until_ready(y)
            outs[overlap] = y
            say(f"sma_gemm_sharded overlap={overlap}: {y.shape} {y.dtype}, "
                f"first call {time.perf_counter() - t0:.3f} s")
    one = jax.devices()[0]
    ref = ops.sma_gemm(jax.device_put(a, one), jax.device_put(b, one),
                       bias=jax.device_put(bias, one), epilogue="gelu",
                       backend=backend, mesh=False)
    chosen = sorted({(s["op"], s["backend"]) for s in sites})
    say(f"local GEMM sites resolved {len(sites)} times: {chosen}")
    check(all(s["backend"] == backend for s in sites),
          f"a sharded GEMM step left {backend}: {chosen}")
    ref_np = np.asarray(ref, np.float32)
    scale = float(np.max(np.abs(ref_np)))
    for overlap, y in outs.items():
        got = np.asarray(y, np.float32)
        check(bool(np.isfinite(got).all()),
              f"overlap={overlap}: non-finite output")
        dev_o = float(np.max(np.abs(got - ref_np))) / scale
        say(f"overlap={overlap} vs single-chip sma_gemm: max |diff| / max "
            f"|ref| {dev_o:.3e} (tolerance {GEMM_RTOL:.0e})")
        check(dev_o <= GEMM_RTOL,
              f"overlap={overlap} deviates {dev_o:.3e} > {GEMM_RTOL:.0e}")
    same = bool(np.array_equal(np.asarray(outs[True]),
                               np.asarray(outs[False])))
    say(f"overlap and reference schedules bitwise equal: {same}")
    res = resilience_section()
    say(f"runtime_fallbacks {res['runtime_fallbacks']}")
    check(res["runtime_fallbacks"] == 0,
          f"{res['runtime_fallbacks']} runtime fallbacks: {res['events']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve stablelm-1.6b; 4: only the sharded GEMM "
                         "on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and GEMM inputs")
    args = ap.parse_args(argv)
    try:
        jax, devices = bring_up(args.chips)
        if args.chips == 4:
            summa_smoke(jax, args.seed)
        else:
            serve_smoke(jax, args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAIL: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
