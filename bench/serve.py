"""The system under test, driven through ``ServeEngine.submit`` / ``step``.

Set-up builds the weights on the device from the seed, builds the engine
as the configuration states it, and compiles every (phase, bucket) step
program the configuration can use by calling the engine's two ``sma_jit``
programs on dummy inputs of those shapes; no serving pass runs before the
window.  The window then offers the cell's traffic for ``seconds`` and
times every token on the host when ``step()`` returns it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import spec
from bench.record import Record, Req, Tick

#: Serving state is the engine call's argument 1: donated, so a step
#: updates the KV pools in place (without it a batch-1 decode step of
#: stablelm-1.6b needs 17.7 GB).
DONATE_STATE = (1,)


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the fields that the file's kind (``bench/models/<kind>.py``)
    says its published sizes fix."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["model"]["repro_config"]),
                              **conf["model"]["overrides"])
    want = spec.model_kind(conf).expected(conf)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['name']}: the program's config {got} is "
                         f"not the file's {want}")
    return cfg


def build(conf: Dict[str, Any], seed: int, cfg=None):
    """Weights from the seed and the engine, as the configuration says."""
    from repro.api import SMAOptions
    from repro.serving import CacheConfig, SchedulerConfig, ServeEngine
    cfg = cfg or model_config(conf)
    params = spec.model_kind(conf).program_params(seed, conf)
    jax.block_until_ready(params)
    srv = conf["serving"]
    engine = ServeEngine(
        cfg, params,
        cache=CacheConfig(block_size=srv["block_size"],
                          num_blocks=srv["num_blocks"],
                          max_seq_len=srv["max_seq_len"]),
        max_batch=srv["max_batch"],
        sched=SchedulerConfig(**srv["scheduler"]),
        options=SMAOptions(donate_argnums=DONATE_STATE))
    return engine


def buckets(n: int) -> List[int]:
    """The engine's row buckets up to ``n``: powers of two, and ``n``."""
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [n]


def warm_up(engine) -> int:
    """Compile (or load from the persistent cache) every step program the
    configuration uses: decode at each row bucket up to ``max_batch``,
    prefill at each bucket up to ``max_prefill_batch``, and the slice of
    each bucket's logits at every row count it serves.  Each step runs once
    on sentinel block tables, whose writes drop.  Returns the compile
    count."""
    sc = engine.sched.config
    chunk = sc.prefill_chunk
    width = engine.cache.max_blocks_per_req
    sentinel = engine.cache.num_blocks
    prefill_max = min(engine.max_batch, sc.max_prefill_batch)
    for phase, top, c in (("decode", engine.max_batch, 1),
                          ("prefill", prefill_max, chunk)):
        lo = 0
        for b in buckets(top):
            bt = jnp.full((b, width), sentinel, jnp.int32)
            cl = jnp.zeros((b,), jnp.int32)
            batch = {"tokens": jnp.zeros((b, c), jnp.int32)}
            args = (engine.params, engine.state, bt, cl)
            if phase == "prefill":
                args += (jnp.zeros((b,), jnp.int32),)
            logits, engine.state, _ = engine.engines[phase](*args, batch)
            # A tick takes its real rows out of the bucket's logits by an
            # eager slice, a program of its own for each row count that
            # the bucket serves: warm those too, or they compile in the
            # window.
            for n in range(lo + 1, b + 1):
                jax.block_until_ready(logits[:n])
            lo = b
    compiles = sum(e.stats.misses for e in engine.engines.values())
    engine.state = None     # free the pools before reset makes new ones
    engine.reset()
    return compiles


@dataclasses.dataclass
class Window:
    """Offers one cell's traffic to the engine for ``seconds``: one loop
    that submits whatever the mix's arrival kind releases and steps the
    engine while it has work."""

    engine: Any
    arrivals: Any                      # generator.arrivals(...) of the mix
    seconds: float
    trace_dir: Optional[str] = None    # profile the window's last seconds
    trace_s: float = 0.0

    def run(self, model: Dict[str, Any], peaks: Dict[str, Any]) -> Record:
        from repro.serving import Request
        eng = self.engine
        misses0 = sum(e.stats.misses for e in eng.engines.values())
        reqs: Dict[int, Req] = {}
        ticks: List[Tick] = []
        rid = 0
        traced, span = False, None
        t_start = time.perf_counter()
        t_end = t_start + self.seconds
        t_trace = t_end - self.trace_s
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if self.trace_dir and not traced and now >= t_trace:
                jax.profiler.start_trace(self.trace_dir)
                span = TraceAnnotation("bench.traced")
                span.__enter__()
                traced = True
                t_trace = time.perf_counter()
            with TraceAnnotation("bench.generate"):
                new = self.arrivals.release(now - t_start, len(eng.queue))
            with TraceAnnotation("bench.submit"):
                for due_s, d in new:
                    req = Request(rid=rid, prompt=d.prompt,
                                  max_new_tokens=d.max_new)
                    req.t_submit = t_start + due_s
                    reqs[rid] = Req(rid=rid, due=req.t_submit)
                    eng.submit(req)
                    rid += 1
            if not (eng.queue or eng.active):
                due = self.arrivals.next_due()
                wait = (t_end if due is None else min(t_start + due, t_end)
                        ) - time.perf_counter()
                with TraceAnnotation("bench.wait"):
                    if wait > 0:
                        time.sleep(wait)
                continue
            before = {r.rid: (r.prefilled, len(r.out_tokens or ()))
                      for r in list(eng.active.values()) + eng.queue}
            ticks_before = eng.sched.ticks
            t0 = time.perf_counter()
            with TraceAnnotation("engine.step"):
                out = eng.step()
            t1 = time.perf_counter()
            for r in out:
                reqs[r].token_times.append(t1)
            if eng.sched.ticks == ticks_before:
                continue
            rows = []
            for r, (p0, n0) in before.items():
                req = eng.active.get(r) or eng.done.get(r)
                if req is None:
                    continue
                if reqs[r].t_admit is None and req.t_admit is not None:
                    reqs[r].t_admit = req.t_admit
                dp = req.prefilled - p0
                if dp > 0:
                    rows.append((p0, dp))
                elif len(req.out_tokens or ()) > n0 and n0 > 0:
                    rows.append((len(req.prompt) + n0 - 1, 1))
            ticks.append(Tick(t0=t0, t1=t1,
                              phase=eng.sched.stats()["current_phase"],
                              rows=rows, logit_rows=len(out),
                              traced=traced))
        if span is not None:
            span.__exit__(None, None, None)
        rec = Record(model=model, peaks=peaks, t_start=t_start, t_end=t_end,
                     ticks=ticks, requests=reqs,
                     compiles=sum(e.stats.misses
                                  for e in eng.engines.values()) - misses0,
                     t_trace=t_trace if traced else None)
        if traced:
            jax.profiler.stop_trace()
        return rec

    def finished(self, rec: Record) -> List[dict]:
        """Requests done by the window's end, with what they served."""
        out = []
        for r in self.engine.done.values():
            times = rec.requests[r.rid].token_times
            if r.out_tokens and times and times[-1] <= rec.t_end:
                out.append({"rid": r.rid, "prompt": np.asarray(r.prompt),
                            "tokens": list(r.out_tokens)})
        return out
