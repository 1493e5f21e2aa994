"""AdamW optimizer with global-norm clipping and LR schedules (own impl).

Functional, pytree-native, shard-transparent: optimizer state inherits the
parameters' sharding (FSDP'd moments come for free under jit), so ZeRO-style
optimizer-state sharding is a property of the parameter specs, not special
code here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    end_lr_ratio: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    schedule: str = "cosine"  # cosine | linear | constant


def lr_at(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    if cfg.schedule == "cosine":
        decay = cfg.end_lr_ratio + (1 - cfg.end_lr_ratio) * 0.5 * (
            1.0 + jnp.cos(jnp.pi * frac))
    elif cfg.schedule == "linear":
        decay = 1.0 - (1.0 - cfg.end_lr_ratio) * frac
    else:
        decay = jnp.ones_like(frac)
    return cfg.peak_lr * warm * decay


def init(params) -> Dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def update(grads, state: Dict, params, cfg: AdamWConfig
           ) -> Tuple[Dict, Dict, Dict[str, jax.Array]]:
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m_new = cfg.b1 * m + (1 - cfg.b1) * g32
        v_new = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g32)
        mhat = m_new / b1c
        vhat = v_new / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return ((p.astype(jnp.float32) - lr * delta).astype(p.dtype),
                m_new, v_new)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v)
           for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"m": new_m, "v": new_v, "step": step}, metrics


def state_specs(param_specs) -> Dict:
    """Optimizer-state logical specs mirror the parameter specs (ZeRO)."""
    return {
        "m": param_specs,
        "v": param_specs,
        "step": (),
    }
