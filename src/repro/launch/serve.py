"""DEPRECATED serving driver — a shim over :mod:`repro.serving`.

The slot-based ``Server`` grew into the continuous-batching
:class:`repro.serving.ServeEngine` (paged KV cache, chunked prefill, SMA
mode-batching scheduler).  This module keeps the old surface working for
one release: ``Server`` delegates every operation to a ``ServeEngine``
configured for slot-equivalent behaviour —

* ``slots`` rows, each able to hold a full ``cache_size`` token budget in
  KV blocks (so admission succeeds exactly when a slot is free, like the
  old dense per-slot cache);
* ``admit`` runs the whole prompt prefill before returning and emits no
  token (the old warmup), ``tick`` decodes one token for every active
  request and re-feeds the last prompt token first (the old first-tick
  semantics) — outputs are tick-for-tick compatible;
* the same fault sites (``serve.admit`` / ``serve.tick``), ``serve.*``
  counters, retry/evict/watchdog behaviour, and legacy trace span names.

Each ``Server`` construction emits one :class:`DeprecationWarning` pointed
at the caller.  Migrate to::

    from repro.serving import ServeEngine, Request
    eng = ServeEngine(cfg, params, ...)
    eng.submit(Request(rid=0, prompt=..., max_new_tokens=8))
    while eng.queue or eng.active:
        eng.step()
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro._deprecation import warn_deprecated
from repro.api import SMAOptions
from repro.configs.base import ModelConfig, get_config, reduced
from repro.launch.common import use_compile_cache
from repro.models import lm
from repro.models.layers import Runtime
from repro.obs import trace as _obs_trace
from repro.resilience.guard import RetryPolicy
from repro.serving import CacheConfig, Request, ServeEngine

__all__ = ["Request", "Server", "main"]

#: Block size the shim provisions its slot-equivalent pools with.
_BLOCK = 16


class Server:
    """Deprecated slot-based facade over :class:`ServeEngine`."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 cache_size: int = 256, rt: Optional[Runtime] = None,
                 options: Optional[SMAOptions] = None,
                 temperature: float = 0.0, seed: int = 0,
                 retry: Optional[RetryPolicy] = None) -> None:
        warn_deprecated(
            "repro.launch.serve.Server is deprecated; use "
            "repro.serving.ServeEngine (continuous batching over a paged "
            "KV cache) instead")
        self.cfg = cfg
        self.slots = slots
        self.cache_size = cache_size
        # Slot-equivalent provisioning: every slot can hold a full
        # cache_size budget, so block pressure never rejects a request the
        # old dense per-slot cache would have taken.
        blocks_per_slot = -(-cache_size // _BLOCK)
        cache = CacheConfig(block_size=_BLOCK,
                            num_blocks=slots * blocks_per_slot,
                            max_seq_len=cache_size)
        self.core = ServeEngine(cfg, params, cache=cache, max_batch=slots,
                                rt=rt, options=options,
                                temperature=temperature, seed=seed,
                                retry=retry)

    # ------------------------------------------------------ old surface
    @property
    def params(self):
        return self.core.params

    @property
    def rt(self) -> Runtime:
        return self.core.rt

    @property
    def active(self) -> Dict[int, Request]:
        return self.core.active

    @property
    def done(self) -> Dict[int, Request]:
        return self.core.done

    @property
    def failed(self) -> Dict[int, Request]:
        return self.core.failed

    @property
    def retry(self) -> RetryPolicy:
        return self.core.retry

    @property
    def temperature(self) -> float:
        return self.core.temperature

    @property
    def cache_len(self):
        return self.core.cache_len

    @property
    def engine(self):
        """The decode-phase ``sma_jit`` engine (stats/cache accessors)."""
        return self.core.engines["decode"]

    def free_slots(self) -> List[int]:
        return self.core.free_rows()

    def admit(self, req: Request) -> bool:
        """Old admission contract: True when the request was consumed
        (admitted with its prompt fully prefilled, trivially completed, or
        rejected as ``failed``); False only when no slot is free."""
        return self.core.admit_sync(req)

    def tick(self) -> Dict[int, int]:
        """Decode one token for every active request."""
        if not self.core.active:
            return {}
        with _obs_trace.span("serve.tick", cat="serve",
                             active=len(self.core.active)):
            return self.core.decode_tick()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a runtime trace of the serve loop and "
                         "write Chrome-trace JSON (Perfetto-loadable) here")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_batch=args.slots,
                         temperature=args.temperature)

    rng = np.random.RandomState(0)
    for i in range(args.requests):
        req = Request(rid=i,
                      prompt=rng.randint(0, cfg.vocab_size, size=(6,))
                      .astype(np.int32),
                      max_new_tokens=args.max_new)
        status = engine.submit(req)
        if status == "failed":
            print(f"[serve] rejected request {req.rid}: {req.error}")
    t0 = time.time()
    with _obs_trace.profile(path=args.trace_out) if args.trace_out \
            else contextlib.nullcontext() as prof:
        ticks = engine.run()
    dt = time.time() - t0
    print(f"[serve] {len(engine.done)} done / {len(engine.failed)} failed "
          f"of {args.requests} requests, {ticks} engine ticks, "
          f"{dt:.2f}s ({ticks / max(dt, 1e-9):.1f} ticks/s)")
    sched = engine.sched.stats()
    print(f"[serve] scheduler({sched['policy']}): {sched['ticks']} ticks, "
          f"{sched['mode_switches']} mode switches")
    for name, eng in engine.engines.items():
        st = eng.stats
        print(f"[serve] {name} engine cache: {st.hits} hits / "
              f"{st.misses} compiles, compile {st.compile_time_s:.2f}s")
    if args.trace_out:
        print(f"[serve] wrote trace -> {args.trace_out}")
        print(prof.timeline_text())


if __name__ == "__main__":
    main()
