"""GPipe-style pipeline parallelism over a named mesh axis.

Optional parallelism feature for depth-dominated configs (deepseek-67b's 95
layers): stages hold contiguous layer groups; microbatches stream through a
``shard_map`` program whose stage-to-stage handoff is a single
``jax.lax.ppermute`` per tick — the canonical TPU-native pipeline transfer.

Schedule: GPipe with M microbatches over P stages costs (M + P - 1) ticks;
bubble fraction (P-1)/(M+P-1).  ``pipeline_apply`` is deliberately
forward-only-generic: it pipelines any per-stage function (a layer-group
forward, or a full fwd+bwd step function for 1F1B-style training at the
caller's discretion).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh: Mesh, axis: str,
                   stage_params, x_micro: jax.Array) -> jax.Array:
    """Run microbatches through pipeline stages laid out along ``axis``.

    stage_fn(params_slice, x) -> y : one stage's compute (same shape in/out).
    stage_params: pytree with a leading stage axis (len == mesh[axis]).
    x_micro: (M, micro_batch, ...) microbatched input (replicated; stage 0
    consumes it in order).
    Returns (M, micro_batch, ...) outputs (valid on the last stage,
    replicated back to all for convenience).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)

    def per_stage(params, xs):
        # params: this stage's slice (leading axis 1); xs: full microbatches.
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])           # current carry (one microbatch)
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (when in range); others use recv.
            inject = xs[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(stage == 0,
                             jnp.where(t < n_micro, inject, jnp.zeros_like(inject)),
                             buf)
            y = stage_fn(params, x_in)
            # pass to the next stage (ring; last stage's send wraps unused)
            nxt = jax.lax.ppermute(
                y, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits microbatch (t - (P-1)) at tick t
            emit_idx = t - (n_stages - 1)
            valid = jnp.logical_and(stage == n_stages - 1, emit_idx >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(emit_idx, 0), 0),
                lambda o: o, outs)
            return (nxt, outs)

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # Replicate the last stage's outputs to every shard.  ``tiled=True``
        # concatenates the per-stage (M, ...) buffers along the existing
        # leading axis — a (P*M, ...) layout whose stage-s block sits at
        # rows [s*M, (s+1)*M) — matching the out_specs=P() stitching
        # convention (no new stacked axis to reconcile with the spec).
        outs_all = jax.lax.all_gather(outs, axis, tiled=True)  # (P*M, ...)
        return jax.lax.slice_in_dim(
            outs_all, (n_stages - 1) * n_micro, n_stages * n_micro, axis=0)

    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(param_specs, P()),
                       out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
