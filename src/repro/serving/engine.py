"""ServeEngine: continuous batching over a paged KV cache.

The serving tentpole: a request-level scheduler on top of the paged model
steps (:mod:`repro.serving.model`), replacing the slot-based
``launch.serve.Server`` (now a deprecation shim over this class).  What
changed, and why it matters for the paper's SMA story:

* **Continuous admission** — requests join mid-flight: every tick first
  drains the FIFO queue into free rows (a row + its KV blocks), so a new
  request starts prefilling while earlier ones are still decoding.  No
  stop-the-world batch boundaries.
* **Paged KV** — :class:`repro.serving.kv_cache.PagedKVCache` hands out
  fixed-size blocks; admission is all-or-nothing against the *request
  budget* (prompt + max_new), so decode can never overflow mid-flight, and
  eviction returns blocks to the pool immediately.
* **Mode batching** — prefill chunks are systolic-mode GEMM work, decode
  steps are SIMD-mode cache sweeps.  The
  :class:`repro.serving.scheduler.ModeScheduler` groups same-mode ticks so
  the temporal SMA substrate switches modes per *run of ticks*, not per
  request.  Each tick runs under a mode-tagged span
  (``serving.tick.prefill`` / ``serving.tick.decode``) so
  ``obs.runtime_section`` measures the realized switch count; inside it,
  ``serving.pack``, ``engine.call``, ``serving.fetch`` and
  ``serving.sample`` split the tick's host work, and ``serving.admit``
  covers the admission and planning before it.  Under a JAX profiler
  session these spans sit on the device trace's clock.
* **One compile per (phase, bucket)** — both phases run through
  ``sma_jit`` engines; batches are padded to power-of-two row buckets and
  prefill chunks to a fixed width, so the set of abstract signatures is
  small and every tick after the first per bucket is a cache hit.

Failure isolation carries over from the old server verbatim: per-row
non-finite containment with bounded retries, whole-tick retry on runtime
failures, block-freeing eviction past the budget, and the soft watchdog —
same fault sites (``serve.admit`` / ``serve.tick``) and the same
``serve.*`` counters, so existing chaos harnesses keep working.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import SMAOptions, sma_jit
from repro.configs.base import ModelConfig
from repro.models.layers import Runtime
from repro.obs import metrics as _metrics
from repro.obs import trace as _obs_trace
from repro.resilience import faults as _faults
from repro.resilience.guard import (RetryPolicy, is_runtime_failure,
                                    record_event, warn_once)
from repro.serving import model as smodel
from repro.serving.kv_cache import CacheConfig, PagedKVCache
from repro.serving.scheduler import ModeScheduler, SchedulerConfig, TickPlan

__all__ = ["Request", "ServeEngine"]

#: Counters every engine keeps (present from construction, so a zero is
#: a reading): real and padding rows per tick phase, the ticks at which
#: the queue's head stayed queued, by what it waited for, and the rows
#: left free at the ticks the KV pool blocked it.
COUNTERS = ("serving.rows.prefill", "serving.rows.decode",
            "serving.pad_rows.prefill", "serving.pad_rows.decode",
            "serving.admit_blocked.kv", "serving.admit_blocked.rows",
            "serving.kv_blocked_rows")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    out_tokens: Optional[List[int]] = None
    slot: int = -1               # engine row while active
    #: ``pending`` → ``active`` → ``done`` | ``failed`` (rejected at admit
    #: or evicted mid-decode; ``error`` says why).
    status: str = "pending"
    error: Optional[str] = None
    retries: int = 0
    # --- serving ledger (engine-managed) ---------------------------------
    prefilled: int = 0           # prompt tokens already prefilled
    #: emit the first token from the prefill logits (continuous path); the
    #: deprecated slot API instead re-feeds the last prompt token on the
    #: first decode tick (legacy warmup semantics).
    emit_first: bool = True
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None


class ServeEngine:
    """Continuous-batching engine: paged KV + SMA mode-batching scheduler."""

    def __init__(self, cfg: ModelConfig, params, *,
                 cache: Optional[CacheConfig] = None,
                 max_batch: int = 8,
                 sched: Optional[SchedulerConfig] = None,
                 rt: Optional[Runtime] = None,
                 options: Optional[SMAOptions] = None,
                 temperature: float = 0.0, seed: int = 0,
                 retry: Optional[RetryPolicy] = None) -> None:
        self.cfg = cfg
        self.params = params
        self.rt = rt or Runtime(remat=False)
        self.cache = cache or CacheConfig()
        self.max_batch = max_batch
        self.sched = ModeScheduler(sched)
        self.temperature = temperature
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        self.retry = retry or RetryPolicy()

        self.kv = PagedKVCache(self.cache, max_batch)
        self.state = smodel.init_state(cfg, max_batch, self.cache)
        self.cache_len = np.zeros((max_batch,), np.int32)  # host-side truth
        self._pooled = frozenset(smodel.pooled_positions(cfg))

        self.queue: List[Request] = []
        self.active: Dict[int, Request] = {}
        self.done: Dict[int, Request] = {}
        self.failed: Dict[int, Request] = {}

        legacy = SMAOptions(backend=self.rt.backend,
                            interpret=self.rt.interpret or None)
        self.options = legacy.overlay(options).replace(jit=True)
        for name in COUNTERS:
            _metrics.inc(name, 0)
        # One engine per phase.  Batches are padded to pow2 row buckets and
        # prefill chunks to scheduler.prefill_chunk, so each phase has one
        # compile per bucket and every later tick is a cache hit.
        self.engines = {
            "decode": sma_jit(
                lambda p, s, bt, cl, b: smodel.paged_decode_step(
                    p, s, bt, cl, cfg, self.rt, b),
                options=self.options,
                name=f"{cfg.name}.paged_decode"),
            "prefill": sma_jit(
                lambda p, s, bt, cl, nt, b: smodel.paged_prefill_step(
                    p, s, bt, cl, nt, cfg, self.rt, b),
                options=self.options,
                name=f"{cfg.name}.paged_prefill"),
        }

    # ------------------------------------------------------------------ rows
    def free_rows(self) -> List[int]:
        used = {r.slot for r in self.active.values()}
        return [i for i in range(self.max_batch) if i not in used]

    def _by_row(self) -> Dict[int, Request]:
        return {r.slot: r for r in self.active.values()}

    def _prefill_reqs(self) -> List[Request]:
        return [r for r in self.active.values()
                if r.prefilled < len(r.prompt)]

    def _decode_reqs(self) -> List[Request]:
        return [r for r in self.active.values()
                if r.prefilled >= len(r.prompt)]

    # ------------------------------------------------------------- admission
    def _validate(self, req: Request) -> bool:
        """Terminal validation; True when the request was consumed (failed
        or trivially done) without taking capacity."""
        if len(req.prompt) == 0:
            self._fail(req, "empty prompt (nothing to decode from)")
            return True
        why = self.kv.admission_error(len(req.prompt), req.max_new_tokens)
        if why is not None:
            self._fail(req, why)
            return True
        if req.max_new_tokens <= 0:
            req.out_tokens = []
            req.status = "done"
            self.done[req.rid] = req
            return True
        return False

    def submit(self, req: Request) -> str:
        """Continuous-path entry: validate and enqueue.  Admission happens
        on the next :meth:`step`.  Returns the request's status."""
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        if self._validate(req):
            return req.status
        self.queue.append(req)
        return req.status

    def try_admit(self, req: Request, *, emit_first: bool = True) -> bool:
        """Place a validated request into a free row, reserving its whole
        KV-block budget.  False = transient capacity pressure (no row or no
        blocks right now); terminal problems raise via :meth:`_validate`
        having been called first."""
        free = self.free_rows()
        if not free:
            return False
        row = free[0]
        if not self.kv.admit(row, len(req.prompt), req.max_new_tokens):
            return False
        now = time.perf_counter()
        req.slot = row
        req.out_tokens = []
        req.status = "active"
        req.prefilled = 0
        req.emit_first = emit_first
        req.t_admit = now
        if req.t_submit is not None:
            _metrics.observe("serving.queue_wait_s", now - req.t_submit)
        self._zero_row(row)
        self.active[req.rid] = req
        _metrics.inc("serving.admitted")
        return True

    def _admit_from_queue(self) -> Tuple[int, Optional[str]]:
        """Drain the FIFO head into free rows — every tick, so requests
        join mid-flight (continuous batching).  Returns how many were
        admitted, and what the head that stayed queued waits for:
        ``"rows"`` (every row busy), ``"kv"`` (the pool cannot reserve its
        budget) or None (the queue drained)."""
        admitted = 0
        while self.queue:
            head = self.queue[0]
            if self._validate(head):
                self.queue.pop(0)
                continue
            if not self.try_admit(head):
                return admitted, "kv" if self.free_rows() else "rows"
            self.queue.pop(0)
            admitted += 1
        return admitted, None

    def admit_sync(self, req: Request) -> bool:
        """Legacy slot-API admission (the deprecated ``Server.admit``):
        validate, take a row, and run the whole prompt prefill before
        returning.  No first token is emitted — the first decode tick
        re-feeds the last prompt token, exactly like the old warmup.

        Returns True when the request was consumed (admitted / trivially
        done / rejected as failed) and False only when no capacity is free.
        """
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        if self._validate(req):
            return True
        t0 = time.perf_counter()
        if not self.try_admit(req, emit_first=False):
            return False
        with _obs_trace.span("serve.admit", cat="serve", rid=req.rid,
                             slot=req.slot, prompt_len=len(req.prompt)):
            try:
                _faults.maybe_raise("serve.admit")
                with _obs_trace.span("serve.warmup", cat="serve",
                                     rid=req.rid, slot=req.slot,
                                     tokens=len(req.prompt)):
                    while (req.status == "active"
                           and req.prefilled < len(req.prompt)):
                        plan = self.sched.plan([req.slot], [])
                        self._run_plan(plan)
            except Exception as exc:
                if not is_runtime_failure(exc):
                    raise
                self._evict(req, f"warmup failed: "
                                 f"{type(exc).__name__}: {exc}")
        self._watchdog("serve.admit", time.perf_counter() - t0)
        return True

    # ----------------------------------------------------------------- ticks
    def step(self) -> Dict[int, int]:
        """One scheduler tick: admit, plan one same-mode batch, run it.

        Returns ``{rid: token}`` for tokens emitted this tick.
        """
        with _obs_trace.span("serving.admit", cat="serve") as sp:
            admitted, blocked = self._admit_from_queue()
            prefill_rows = [r.slot for r in self._prefill_reqs()]
            decode_rows = sorted(r.slot for r in self._decode_reqs())
            plan = self.sched.plan(prefill_rows, decode_rows)
            if sp is not None:
                sp.annotate(admitted=admitted, blocked=blocked or "none")
        if plan.phase == "idle":
            return {}
        if blocked is not None:
            _metrics.inc(f"serving.admit_blocked.{blocked}")
            if blocked == "kv":
                _metrics.inc("serving.kv_blocked_rows",
                             len(self.free_rows()))
                kv = self.kv.stats(self.cache_len)
                _metrics.observe("serving.kv.positions_in_use",
                                 kv["positions_in_use"])
                _metrics.observe("serving.kv.positions_reserved",
                                 kv["positions_reserved"])
        t0 = time.perf_counter()
        out: Dict[int, int] = {}
        try:
            _faults.maybe_raise("serve.tick")
            out = self._run_plan(plan)
        except Exception as exc:
            if not is_runtime_failure(exc):
                raise
            self._tick_failed(exc, plan.rows)
        self._watchdog("serve.tick", time.perf_counter() - t0)
        return out

    def decode_tick(self) -> Dict[int, int]:
        """Legacy slot-API tick: decode one token for every decode-ready
        request (no prefill interleave — the deprecated ``Server.tick``)."""
        decode_rows = sorted(r.slot for r in self._decode_reqs())
        if not decode_rows:
            return {}
        plan = self.sched.plan([], decode_rows)
        t0 = time.perf_counter()
        out: Dict[int, int] = {}
        try:
            _faults.maybe_raise("serve.tick")
            out = self._run_plan(plan)
        except Exception as exc:
            if not is_runtime_failure(exc):
                raise
            self._tick_failed(exc, plan.rows)
        self._watchdog("serve.tick", time.perf_counter() - t0)
        return out

    def run(self, *, max_ticks: int = 100_000) -> int:
        """Drive :meth:`step` until all submitted work drains.  Returns the
        number of executed ticks."""
        ticks = 0
        while (self.queue or self.active) and ticks < max_ticks:
            self.step()
            ticks += 1
        return ticks

    def _run_plan(self, plan: TickPlan) -> Dict[int, int]:
        """Execute one planned tick under its mode-tagged span.  The span's
        ``mode`` tag is what ``obs.runtime_section`` collapses into
        systolic/SIMD segments — the measured mode-switch count of the
        serve loop."""
        if plan.switched:
            _metrics.inc("serving.mode_switches")
        _metrics.inc("serving.ticks")
        n, bucket = len(plan.rows), self._bucket(len(plan.rows))
        with _obs_trace.span(f"serving.tick.{plan.phase}", cat="serve",
                             mode=plan.mode, rows=n, bucket=bucket,
                             pad=bucket - n, tick=self.sched.ticks):
            if plan.phase == "prefill":
                return self._prefill_tick(list(plan.rows))
            return self._decode_tick(list(plan.rows))

    # ------------------------------------------------------------- internals
    def _bucket(self, n: int) -> int:
        """The power-of-two row bucket a tick of ``n`` rows runs at."""
        b = 1 << max(0, n - 1).bit_length() if n > 1 else 1
        return min(self.max_batch, b)

    def _padded_rows(self, phase: str,
                     rows: List[int]) -> Tuple[np.ndarray, int]:
        """The tick's rows padded to its bucket with copies of the first
        (full work, results dropped); counted per phase."""
        pad = self._bucket(len(rows)) - len(rows)
        _metrics.inc(f"serving.rows.{phase}", len(rows))
        _metrics.inc(f"serving.pad_rows.{phase}", pad)
        return np.asarray(rows + [rows[0]] * pad, np.int32), pad

    def _gather(self, rows_padded: np.ndarray) -> Tuple[Any, ...]:
        """Batch-row view of the state: paged pools pass through whole (no
        batch axis), per-row recurrent entries gather the tick's rows."""
        out = []
        for p, entry in enumerate(self.state):
            if p in self._pooled:
                out.append(entry)
            else:
                out.append(jax.tree.map(lambda s: s[:, rows_padded], entry))
        return tuple(out)

    def _scatter(self, new_state: Tuple[Any, ...], rows: List[int],
                 good_idx: List[int]) -> None:
        """Write back a tick's results.  Pools are accepted wholesale (a
        retried row re-writes the same positions, nothing else reads past
        its kv_len); recurrent rows are scattered back only for healthy
        requests, so a poisoned row keeps its pre-tick state."""
        state = list(self.state)
        gi = np.asarray(good_idx, np.int32)
        gr = np.asarray([rows[i] for i in good_idx], np.int32)
        for p, entry in enumerate(new_state):
            if p in self._pooled:
                state[p] = entry
            elif len(good_idx):
                state[p] = jax.tree.map(
                    lambda old, new: old.at[:, gr].set(new[:, gi]),
                    self.state[p], entry)
        self.state = tuple(state)

    def _batch_of(self, toks: np.ndarray) -> Dict[str, jax.Array]:
        toks_j = jnp.asarray(toks)
        if self.cfg.input_mode == "embeds":
            return {"embeds": smodel.token_embeds(self.params, self.cfg,
                                                  toks_j)}
        return {"tokens": toks_j}

    def _sample(self, np_row: np.ndarray) -> int:
        if self.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            row = np_row / self.temperature
            return int(jax.random.categorical(sub, jnp.asarray(row)))
        return int(np.argmax(np_row))

    def _emit(self, req: Request, tok: int) -> None:
        now = time.perf_counter()
        req.out_tokens.append(tok)
        if req.t_last is None:
            if req.t_submit is not None:
                _metrics.observe("serving.ttft_s", now - req.t_submit)
            req.t_first = now
        else:
            _metrics.observe("serving.itl_s", now - req.t_last)
        req.t_last = now
        _metrics.inc("serving.tokens")
        if len(req.out_tokens) >= req.max_new_tokens:
            self._finish(req)

    def _finish(self, req: Request) -> None:
        req.status = "done"
        self.done[req.rid] = req
        self.active.pop(req.rid, None)
        self.kv.release(req.slot)

    def _fetch(self, logits: jax.Array, n: int) -> np.ndarray:
        """The real rows' logits on the host: the tick's wait on the
        device."""
        with _obs_trace.span("serving.fetch", cat="serve"):
            return np.asarray(logits[:n], np.float32)

    def _prefill_tick(self, rows: List[int]) -> Dict[int, int]:
        by_row = self._by_row()
        reqs = [by_row[r] for r in rows]
        c = self.sched.config.prefill_chunk
        with _obs_trace.span("serving.pack", cat="serve"):
            rows_padded, pad = self._padded_rows("prefill", rows)
            bucket = len(rows_padded)
            toks = np.zeros((bucket, c), np.int32)
            n_tok = np.zeros((bucket,), np.int32)
            chunk_n: List[int] = []
            for i, req in enumerate(reqs):
                m = min(c, len(req.prompt) - req.prefilled)
                toks[i, :m] = req.prompt[req.prefilled:req.prefilled + m]
                n_tok[i] = m
                chunk_n.append(m)
            bt = np.vstack([self.kv.table_rows(rows),
                            self.kv.sentinel_rows(pad)])
            cl = np.concatenate([self.cache_len[rows],
                                 np.zeros((pad,), np.int32)])
            args = (self.params, self._gather(rows_padded), jnp.asarray(bt),
                    jnp.asarray(cl), jnp.asarray(n_tok),
                    self._batch_of(toks))
        logits, new_state, _ = self.engines["prefill"](*args)
        np_logits = self._fetch(logits, len(rows))
        with _obs_trace.span("serving.sample", cat="serve"):
            good_idx = [i for i in range(len(rows))
                        if np.isfinite(np_logits[i]).all()]
            bad = [reqs[i] for i in range(len(rows)) if i not in good_idx]
            self._scatter(new_state, rows, good_idx)
            out: Dict[int, int] = {}
            for i in good_idx:
                req = reqs[i]
                self.cache_len[req.slot] += chunk_n[i]
                req.prefilled += chunk_n[i]
                if req.prefilled >= len(req.prompt) and req.emit_first:
                    tok = self._sample(np_logits[i])
                    self._emit(req, tok)
                    out[req.rid] = tok
            for req in bad:
                self._charge_retry(req, "non-finite logits")
        return out

    def _decode_tick(self, rows: List[int]) -> Dict[int, int]:
        by_row = self._by_row()
        # Defense in depth behind the admit-time budget reservation: a row
        # whose cache filled anyway (state poked by a chaos harness) is
        # evicted with a clear error instead of writing past its blocks.
        for r in list(rows):
            req = by_row[r]
            if int(self.cache_len[r]) >= self.kv.capacity_of(r):
                self._evict(req, f"KV cache exhausted mid-decode "
                                 f"(cache_size={self.cache.max_seq_len})")
                rows.remove(r)
        if not rows:
            return {}
        reqs = [by_row[r] for r in rows]
        with _obs_trace.span("serving.pack", cat="serve"):
            rows_padded, pad = self._padded_rows("decode", rows)
            bucket = len(rows_padded)
            toks = np.zeros((bucket, 1), np.int32)
            for i, req in enumerate(reqs):
                toks[i, 0] = (req.out_tokens[-1] if req.out_tokens
                              else int(req.prompt[-1]))
            bt = np.vstack([self.kv.table_rows(rows),
                            self.kv.sentinel_rows(pad)])
            cl = np.concatenate([self.cache_len[rows],
                                 np.zeros((pad,), np.int32)])
            args = (self.params, self._gather(rows_padded), jnp.asarray(bt),
                    jnp.asarray(cl), self._batch_of(toks))
        logits, new_state, _ = self.engines["decode"](*args)
        np_logits = self._fetch(logits, len(rows))
        with _obs_trace.span("serving.sample", cat="serve"):
            # Containment: rows whose logits went non-finite are poisoned
            # — only healthy rows advance (state scatter + cache_len), and
            # the poisoned requests are charged a bounded retry.  Healthy
            # rows are never held back by a sick neighbour.
            good_idx = [i for i in range(len(rows))
                        if np.isfinite(np_logits[i]).all()]
            bad = [reqs[i] for i in range(len(rows)) if i not in good_idx]
            self._scatter(new_state, rows, good_idx)
            out: Dict[int, int] = {}
            for i in good_idx:
                req = reqs[i]
                self.cache_len[req.slot] += 1
                tok = self._sample(np_logits[i])
                self._emit(req, tok)
                out[req.rid] = tok
            for req in bad:
                self._charge_retry(req, "non-finite logits")
        return out

    # -------------------------------------------------------- failure paths
    def _tick_failed(self, exc: BaseException, rows: Tuple[int, ...]
                     ) -> None:
        """The whole batched step failed (engine runtime error / injected
        chaos): charge every participating request one retry, back off, and
        let the next tick re-attempt from the unchanged pre-tick state."""
        _metrics.inc("serve.tick_failures")
        record_event("serve_tick_failed", error=str(exc),
                     active=len(self.active))
        warn_once(f"serve_tick:{type(exc).__name__}",
                  f"serve tick failed ({type(exc).__name__}: {exc}); "
                  f"retrying active requests (bounded by RetryPolicy)")
        by_row = self._by_row()
        for r in rows:
            req = by_row.get(r)
            if req is not None:
                self._charge_retry(req, f"tick failed: "
                                        f"{type(exc).__name__}: {exc}")
        if self.retry.backoff_s > 0:
            time.sleep(self.retry.backoff_s)

    def _charge_retry(self, req: Request, why: str) -> None:
        req.retries += 1
        _metrics.inc("serve.retries")
        if req.retries > self.retry.max_retries:
            self._evict(req, f"{why} (after {req.retries - 1} retries)")

    def _zero_row(self, row: int) -> None:
        """Reset one row's recurrent state and length (pool blocks need no
        reset on admit: every position below kv_len is freshly written)."""
        self.cache_len[row] = 0
        state = list(self.state)
        for p, entry in enumerate(state):
            if p not in self._pooled:
                state[p] = jax.tree.map(
                    lambda s: s.at[:, row].set(jnp.zeros_like(s[:, row])),
                    entry)
        self.state = tuple(state)

    def _scrub_blocks(self, blocks: List[int]) -> None:
        """Zero a freed request's pool blocks.  Needed on *eviction* only:
        attention masks every position past kv_len, but a NaN value row
        would still poison the weighted sum (0 * NaN = NaN), so poisoned
        blocks must not re-enter the free list dirty."""
        if not blocks:
            return
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        state = list(self.state)
        for p, entry in enumerate(state):
            if p in self._pooled:
                state[p] = jax.tree.map(
                    lambda s: s.at[:, idx].set(0.0), entry)
        self.state = tuple(state)

    def _evict(self, req: Request, error: str) -> None:
        """Remove a poisoned request mid-flight: scrub + free its blocks,
        zero its row, and mark it failed.  Neighbours keep decoding."""
        self.active.pop(req.rid, None)
        if req.slot >= 0:
            self._scrub_blocks(self.kv.blocks_of(req.slot))
            self.kv.release(req.slot)
            self._zero_row(req.slot)
        _metrics.inc("serve.evictions")
        record_event("serve_evicted", rid=req.rid, slot=req.slot,
                     error=error)
        self._fail(req, error)

    def _fail(self, req: Request, error: str) -> None:
        req.status = "failed"
        req.error = error
        self.failed[req.rid] = req
        _metrics.inc("serve.requests_failed")

    def _watchdog(self, what: str, elapsed_s: float) -> None:
        """Soft deadline: XLA launches cannot be preempted, so an overrun
        is counted and warned (once per site), not interrupted."""
        deadline = self.retry.deadline_s
        if deadline is None or elapsed_s <= deadline:
            return
        _metrics.inc("serve.watchdog_exceeded")
        warn_once(f"serve_watchdog:{what}",
                  f"{what} took {elapsed_s:.3f}s "
                  f"(RetryPolicy.deadline_s={deadline}); the launch cannot "
                  f"be preempted — counted as serve.watchdog_exceeded")

    # ------------------------------------------------------------- lifecycle
    def reset(self) -> None:
        """Return to an empty engine without dropping compiled signatures —
        benchmark loops reuse one engine across policies/rates so compiles
        amortize."""
        self.kv = PagedKVCache(self.cache, self.max_batch)
        self.state = smodel.init_state(self.cfg, self.max_batch, self.cache)
        self.cache_len = np.zeros((self.max_batch,), np.int32)
        self.queue.clear()
        self.active.clear()
        self.done.clear()
        self.failed.clear()
        self.sched.reset()
        self.key = jax.random.PRNGKey(self.seed)

    def stats(self) -> dict:
        eng = {name: {"hits": e.stats.hits, "misses": e.stats.misses,
                      "compile_time_s": e.stats.compile_time_s}
               for name, e in self.engines.items()}
        return {"kv": self.kv.stats(self.cache_len),
                "scheduler": self.sched.stats(),
                "engines": eng,
                "requests": {"queued": len(self.queue),
                             "active": len(self.active),
                             "done": len(self.done),
                             "failed": len(self.failed)}}
