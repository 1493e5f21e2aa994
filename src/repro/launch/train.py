"""Training driver: end-to-end, fault-tolerant, arch-selectable.

Production behaviours demonstrated here (and exercised by tests/examples):

* auto-resume from the latest checkpoint (params + optimizer + data cursor +
  error-feedback state travel together; atomic commits survive crashes),
* elastic restart — the checkpoint is mesh-independent; restoring onto a
  different device count just changes the shardings handed to ``restore``,
* optional int8+error-feedback gradient compression on the DP all-reduce,
* deterministic, stateless data addressing (any host can build any batch).

On this CPU container it runs the reduced configs (examples/train_lm.py);
on a TPU pod the same file drives the full mesh with ``--mesh pod``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional

import jax

from repro.api import SMAOptions, sma_jit
from repro.configs.base import ModelConfig, get_config, reduced
from repro.data.pipeline import DataConfig, DataPipeline, PipelineState
from repro.distributed.sharding import rules_for, use_rules
from repro.checkpoint.manager import CheckpointManager
from repro.launch.common import use_compile_cache
from repro.models import lm
from repro.models.layers import Runtime
from repro.obs import trace as _obs_trace
from repro.optim import adamw
from repro.optim import compress as gcomp


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    # Simulated fault injection: checkpoint and halt after this step (the
    # resume test restarts from here and must match an uninterrupted run
    # bit-exactly — schedules/data addressing key off the global step).
    halt_at_step: Optional[int] = None
    grad_compression: bool = False
    seed: int = 0
    peak_lr: float = 3e-3
    remat: bool = True


def make_step(cfg: ModelConfig, rt: Runtime, ocfg: adamw.AdamWConfig,
              rules, mesh_axes, *, grad_compression: bool,
              options: Optional[SMAOptions] = None):
    """Build the train step on the ``sma_jit`` front door.

    The engine traces the full fwd+bwd+optimizer program through the SMA
    compiler (systolic GEMMs — including the backward-pass projections —
    dispatch via ``sma_gemm``), jits the dispatched executable, and caches
    it per abstract signature: step 2..N are pure cache hits, and a
    seq-len/batch change (curriculum schedules) compiles once instead of
    silently re-tracing every step.
    """
    def step(params, opt_state, ef, batch):
        with use_rules(rules, mesh_axes):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: lm.loss_fn(p, cfg, rt, batch), has_aux=True)(params)
            if grad_compression:
                grads, ef = gcomp.roundtrip(grads, ef)
            params, opt_state, om = adamw.update(grads, opt_state, params,
                                                 ocfg)
        return params, opt_state, ef, {**metrics, **om}

    # donate params/opt_state/ef so XLA updates them in place (same peak
    # memory as the pre-engine jax.jit(step, donate_argnums=(0, 1, 2))).
    # ``options`` is the supported configuration path; the deprecated
    # Runtime.backend/.interpret fields fold in underneath (back-compat).
    legacy = SMAOptions(backend=rt.backend, interpret=rt.interpret or None)
    eng_opts = legacy.overlay(options).replace(jit=True,
                                               donate_argnums=(0, 1, 2))
    return sma_jit(step, options=eng_opts, name=f"{cfg.name}.train_step")


def train(cfg: ModelConfig, loop: TrainLoopConfig,
          rt: Optional[Runtime] = None,
          mesh: Optional[jax.sharding.Mesh] = None,
          options: Optional[SMAOptions] = None) -> Dict[str, Any]:
    rt = rt or Runtime(remat=loop.remat)
    rules = rules_for(cfg, mesh, batch_size=loop.global_batch,
                      kind="train") if mesh is not None else None
    mesh_axes = mesh.axis_names if mesh is not None else ()

    key = jax.random.PRNGKey(loop.seed)
    params, _ = lm.init(key, cfg)
    opt_state = adamw.init(params)
    ef = gcomp.init_error(params) if loop.grad_compression else {}
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=loop.seq_len,
                      global_batch=loop.global_batch, seed=loop.seed,
                      input_mode=cfg.input_mode, d_model=cfg.d_model,
                      num_vision_tokens=cfg.num_vision_tokens)
    pipe = DataPipeline(dcfg)
    start_step = 0

    mgr = (CheckpointManager(loop.checkpoint_dir)
           if loop.checkpoint_dir else None)
    if mgr is not None and mgr.latest_step() is not None:
        state_like = {"params": params, "opt": opt_state, "ef": ef,
                      "data": pipe.state.to_dict()}
        start_step, restored = mgr.restore(state_like)
        params, opt_state, ef = (restored["params"], restored["opt"],
                                 restored["ef"])
        pipe.state = PipelineState.from_dict(restored["data"])
        print(f"[train] resumed from step {start_step}")

    ocfg = adamw.AdamWConfig(peak_lr=loop.peak_lr,
                             warmup_steps=max(loop.steps // 10, 1),
                             total_steps=loop.steps)
    step_fn = make_step(cfg, rt, ocfg, rules, mesh_axes,
                        grad_compression=loop.grad_compression,
                        options=options)

    history = []
    t0 = time.time()
    for i in range(start_step, loop.steps):
        batch = next(pipe)
        with _obs_trace.span("train.step", cat="train", step=i):
            params, opt_state, ef, metrics = step_fn(params, opt_state, ef,
                                                     batch)
        if (i + 1) % loop.log_every == 0 or i == loop.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = i + 1
            m["wall_s"] = round(time.time() - t0, 2)
            history.append(m)
            print(f"[train] step {i+1:5d} loss={m['loss']:.4f} "
                  f"acc={m.get('accuracy', 0):.3f} "
                  f"gnorm={m.get('grad_norm', 0):.2f}", flush=True)
        if mgr is not None and (i + 1) % loop.checkpoint_every == 0:
            mgr.save(i + 1, {"params": params, "opt": opt_state, "ef": ef,
                             "data": pipe.state.to_dict()})
        if loop.halt_at_step is not None and (i + 1) == loop.halt_at_step:
            if mgr is not None and (i + 1) % loop.checkpoint_every != 0:
                mgr.save(i + 1, {"params": params, "opt": opt_state,
                                 "ef": ef, "data": pipe.state.to_dict()})
            if mgr is not None:
                mgr.wait()
            print(f"[train] simulated fault: halted at step {i + 1}")
            return {"history": history, "params": params,
                    "engine": step_fn.stats.asdict()}
    if mgr is not None:
        mgr.save(loop.steps, {"params": params, "opt": opt_state, "ef": ef,
                              "data": pipe.state.to_dict()})
        mgr.wait()
    return {"history": history, "params": params,
            "engine": step_fn.stats.asdict()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--out", default=None, help="write history JSON here")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    loop = TrainLoopConfig(steps=args.steps, seq_len=args.seq_len,
                           global_batch=args.batch,
                           checkpoint_dir=args.checkpoint_dir,
                           grad_compression=args.grad_compression,
                           peak_lr=args.lr)
    result = train(cfg, loop)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result["history"], f, indent=1)


if __name__ == "__main__":
    main()
