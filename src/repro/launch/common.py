"""Shared launch machinery: abstract params, input specs, step builders.

Used by dryrun.py (lower+compile on the production mesh), train.py, serve.py
and the benchmarks.  Everything here is allocation-free for the full-size
configs: parameters and inputs are ``jax.ShapeDtypeStruct`` trees until a
launcher decides to materialize them.
"""
from __future__ import annotations

import os
import pathlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import MeshRules, rules_for, use_rules
from repro.models import lm
from repro.models.layers import Runtime
from repro.optim import adamw

DECODE_MARGIN = 16  # cache capacity beyond seq_len (keeps dims TP-divisible)

#: The persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: ``.jax_cache`` at the root of the checkout (src/repro/launch/ is
#: three levels below it).
DEFAULT_COMPILE_CACHE = (pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a launcher process.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and this
    sets nothing.  Otherwise the cache goes to :data:`DEFAULT_COMPILE_CACHE`,
    a fixed path, so a later process of the same checkout finds what an
    earlier one compiled.  Called from each launcher's ``main()``; nothing
    turns the cache on at import.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Abstract parameter / state trees + logical specs
# ---------------------------------------------------------------------------
def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    """Logical-axis spec tree, captured without allocating parameters."""
    box: Dict[str, Any] = {}

    def trace() -> Any:
        params, specs = lm.init(jax.random.PRNGKey(0), cfg)
        box["specs"] = specs
        return params

    jax.eval_shape(trace)
    return box["specs"]


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg)[0])


def abstract_opt_state(aparams):
    return jax.eval_shape(adamw.init, aparams)


def _is_logical_leaf(x) -> bool:
    """A spec leaf is a (possibly empty) tuple of axis names / None."""
    return isinstance(x, tuple) and all(
        isinstance(a, str) or a is None for a in x)


def logical_to_pspec(spec_tree, rules: MeshRules, mesh_axes) -> Any:
    """Tuple-of-logical-names tree -> PartitionSpec tree."""
    def conv(leaf):
        if leaf == ():
            return P()
        return rules.spec(*leaf, mesh_axes=mesh_axes)

    return jax.tree.map(conv, spec_tree, is_leaf=_is_logical_leaf)


def opt_pspecs(p_pspecs) -> Dict[str, Any]:
    return {"m": p_pspecs, "v": p_pspecs, "step": P()}


# ---------------------------------------------------------------------------
# Input specs per (arch x shape): ShapeDtypeStruct stand-ins, no allocation
# ---------------------------------------------------------------------------
def batch_abstract(cfg: ModelConfig, shape: ShapeConfig
                   ) -> Tuple[Dict[str, Any], Dict[str, Tuple]]:
    """(ShapeDtypeStructs, logical specs) for one step's data batch."""
    b = shape.global_batch
    s = 1 if shape.kind == "decode" else shape.seq_len
    sd = jax.ShapeDtypeStruct
    act = cfg.activation_dtype
    batch: Dict[str, Any] = {}
    specs: Dict[str, Tuple] = {}
    seq_ax = None if shape.kind == "decode" else "seq"
    if cfg.input_mode == "embeds":
        batch["embeds"] = sd((b, s, cfg.d_model), act)
        specs["embeds"] = ("batch", seq_ax, "embed_act")
    elif cfg.input_mode == "tokens+vision":
        nv = cfg.num_vision_tokens if shape.kind != "decode" else 0
        batch["tokens"] = sd((b, s - nv), jnp.int32)
        specs["tokens"] = ("batch", seq_ax)
        if shape.kind != "decode":
            batch["vision_embeds"] = sd((b, nv, cfg.d_model), act)
            specs["vision_embeds"] = ("batch", None, "embed_act")
    else:
        batch["tokens"] = sd((b, s), jnp.int32)
        specs["tokens"] = ("batch", seq_ax)
    if shape.kind == "train":
        batch["labels"] = sd((b, shape.seq_len), jnp.int32)
        specs["labels"] = ("batch", "seq")
    return batch, specs


def decode_state_abstract(cfg: ModelConfig, shape: ShapeConfig):
    cache_size = shape.seq_len + DECODE_MARGIN
    return jax.eval_shape(
        lambda: lm.init_state(cfg, shape.global_batch, cache_size))


# ---------------------------------------------------------------------------
# Step builders (the functions the dry-run lowers and the drivers run)
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, rt: Runtime, ocfg: adamw.AdamWConfig,
                    rules: Optional[MeshRules], mesh_axes=()):
    def train_step(params, opt_state, batch):
        with use_rules(rules, mesh_axes):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: lm.loss_fn(p, cfg, rt, batch), has_aux=True)(params)
            new_params, new_opt, opt_metrics = adamw.update(
                grads, opt_state, params, ocfg)
        return new_params, new_opt, {**metrics, **opt_metrics}

    return train_step


def make_decode_step(cfg: ModelConfig, rt: Runtime,
                     rules: Optional[MeshRules], mesh_axes=()):
    def serve_step(params, state, cache_len, batch):
        with use_rules(rules, mesh_axes):
            return lm.decode_step(params, state, cache_len, cfg, rt, batch)

    return serve_step


def make_prefill_step(cfg: ModelConfig, rt: Runtime, cache_size: int,
                      rules: Optional[MeshRules], mesh_axes=()):
    def serve_step(params, batch):
        with use_rules(rules, mesh_axes):
            return lm.prefill(params, cfg, rt, batch, cache_size=cache_size)

    return serve_step


# ---------------------------------------------------------------------------
# The full lowering plan for one (arch x shape x mesh) cell
# ---------------------------------------------------------------------------
def build_cell(cfg: ModelConfig, shape: ShapeConfig,
               mesh: jax.sharding.Mesh, *,
               rt: Optional[Runtime] = None,
               sequence_parallel: bool = False,
               remat: bool = True):
    """Returns (jitted_fn, example_args) ready for .lower(*args).

    ``example_args`` are ShapeDtypeStructs with shardings attached via the
    jit in_shardings, so ``.lower`` never allocates.
    """
    rt = rt or Runtime(remat=remat, sequence_parallel=sequence_parallel)
    rules = rules_for(cfg, mesh, batch_size=shape.global_batch,
                      kind=shape.kind, sequence_parallel=sequence_parallel)
    axes = mesh.axis_names

    p_specs = logical_to_pspec(param_specs(cfg), rules, axes)
    p_sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    aparams = abstract_params(cfg)
    b_abs, b_logical = batch_abstract(cfg, shape)
    b_pspec = logical_to_pspec(b_logical, rules, axes)
    b_sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), b_pspec)

    if shape.kind == "train":
        ocfg = adamw.AdamWConfig()
        step = make_train_step(cfg, rt, ocfg, rules, axes)
        o_pspecs = opt_pspecs(p_specs)
        o_sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), o_pspecs)
        aopt = abstract_opt_state(aparams)
        fn = jax.jit(step,
                     in_shardings=(p_sharding, o_sharding, b_sharding),
                     out_shardings=(p_sharding, o_sharding, None),
                     donate_argnums=(0, 1))
        args = (aparams, aopt, b_abs)
    elif shape.kind == "decode":
        step = make_decode_step(cfg, rt, rules, axes)
        s_logical = lm.state_specs(cfg)
        s_pspec = logical_to_pspec(s_logical, rules, axes)
        astate = decode_state_abstract(cfg, shape)
        s_sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), s_pspec)
        len_sharding = NamedSharding(
            mesh, rules.spec("batch", mesh_axes=axes))
        fn = jax.jit(step,
                     in_shardings=(p_sharding, s_sharding, len_sharding,
                                   b_sharding),
                     out_shardings=(None, s_sharding, len_sharding),
                     donate_argnums=(1,))
        alen = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        args = (aparams, astate, alen, b_abs)
    else:  # prefill
        cache_size = shape.seq_len + DECODE_MARGIN
        step = make_prefill_step(cfg, rt, cache_size, rules, axes)
        fn = jax.jit(step, in_shardings=(p_sharding, b_sharding))
        args = (aparams, b_abs)
    return fn, args
