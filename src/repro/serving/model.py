"""Paged-state model steps: decode and chunked prefill over block tables.

The serving twins of :func:`repro.models.lm.decode_step` / ``prefill``:
same block structure (stacked groups under ``lax.scan``, per-pattern-position
state entries), but attention layers keep their KV in the *global* paged
pool.  State entries for ``attn``/``local`` positions are
``{"k","v"}: (num_groups, num_blocks, block_size, num_kv_heads * head_dim)``,
token-major (one row of ``Hkv*D`` per cached position) with NO batch axis;
which blocks of the pool belong to which request is carried by the
``block_table`` argument.  Recurrent positions (RG-LRU / mLSTM / sLSTM) keep
their dense per-row state exactly as in ``lm.init_state``.

Data flow through the layer scan: the pools ride the scan's *carry* and are
written and read at the group's layer index (an ``xs`` of
``arange(num_groups)``); no layer's pool is sliced out of the stack or
stacked back.  The stacked block params and the recurrent states are the
scan's ``xs``/``ys``.  With the state donated, each step updates the pools
in place: the only pool-sized instructions left are the writes' scatters.

Two entry points, one per serving phase (and per ``sma_jit`` cache family):

* :func:`paged_decode_step` — one token per row, SIMD-heavy (memory-bound
  cache sweep, tiny GEMMs).
* :func:`paged_prefill_step` — a C-token chunk per row with per-row valid
  counts ``n_tokens``, systolic-heavy (all projections/MLPs are (B*C, D)
  GEMMs).  Rows whose chunk is shorter than C are masked: their pool writes
  drop (sentinel block ids), their recurrent state merges are suppressed
  per-token, and the returned logits are taken at each row's last *valid*
  position.

Pool writes are copy-free scatters: position ``p`` of a row in group ``l``
lands at ``pool[l, table[row, p // bs], p % bs]``; out-of-budget or padding
writes carry the sentinel block id (== num_blocks) and drop
(``mode="drop"`` — note jnp would *wrap* a -1, so the sentinel is
one-past-the-end, never -1).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import attention, moe as moe_lib, recurrent
from repro.models.layers import (Runtime, gated_mlp_apply, rmsnorm_apply)
from repro.serving.kv_cache import CacheConfig

__all__ = ["init_state", "paged_decode_step", "paged_prefill_step",
           "token_embeds"]


def init_state(cfg: ModelConfig, max_batch: int, cache: CacheConfig,
               dtype=None) -> Tuple[Any, ...]:
    """Serving state pytree, one entry per pattern position.

    ``attn``/``local`` positions hold ``{"k", "v"}`` token-major paged
    pools of shape ``(num_groups, num_blocks, block_size,
    num_kv_heads * head_dim)``; the layer scan carries them whole and
    indexes them at the group's layer.  Recurrent positions hold dense
    per-row states (as in ``lm.init_state``), stacked over groups."""
    dtype = dtype or cfg.activation_dtype
    pool_shape = (cfg.num_groups, cache.num_blocks, cache.block_size,
                  cfg.num_kv_heads * cfg.resolved_head_dim)
    state = []
    for btype in cfg.block_pattern:
        if btype in ("attn", "local"):
            state.append({"k": jnp.zeros(pool_shape, dtype),
                          "v": jnp.zeros(pool_shape, dtype)})
        elif btype == "rglru":
            state.append(jax.tree.map(
                lambda z: jnp.broadcast_to(z, (cfg.num_groups,) + z.shape),
                recurrent.rglru_block_init_state(cfg, max_batch, dtype)))
        elif btype == "mlstm":
            state.append(jax.tree.map(
                lambda z: jnp.broadcast_to(z, (cfg.num_groups,) + z.shape),
                recurrent.mlstm_block_init_state(cfg, max_batch, dtype)))
        elif btype == "slstm":
            state.append(jax.tree.map(
                lambda z: jnp.broadcast_to(z, (cfg.num_groups,) + z.shape),
                recurrent.slstm_block_init_state(cfg, max_batch, dtype)))
        else:
            raise ValueError(f"unknown block type {btype}")
    return tuple(state)


def pooled_positions(cfg: ModelConfig) -> Tuple[int, ...]:
    """Pattern positions whose state entry is a paged pool (no batch axis),
    decided by the block type.  The layer scan carries these entries; the
    engine passes them through its row gather/scatter whole."""
    return tuple(p for p, bt in enumerate(cfg.block_pattern)
                 if bt in ("attn", "local"))


def token_embeds(params: dict, cfg: ModelConfig,
                 toks: jax.Array) -> jax.Array:
    """Decoder-input embeddings for embeds-mode families (see the old
    ``Server._token_embeds``): the model's own table when the checkpoint
    has one, else a deterministic one-hot by token id mod d_model."""
    table = params.get("embed")
    if table is not None:
        return table["table"].astype(cfg.activation_dtype)[toks]
    return jax.nn.one_hot(toks % cfg.d_model, cfg.d_model,
                          dtype=cfg.activation_dtype)


def _embed(params: dict, cfg: ModelConfig,
           batch: Dict[str, jax.Array]) -> jax.Array:
    if cfg.input_mode == "embeds":
        return batch["embeds"].astype(cfg.activation_dtype)
    return params["embed"]["table"].astype(cfg.activation_dtype)[
        batch["tokens"]]


def _pool_write(pool: jax.Array, layer: jax.Array, block_table: jax.Array,
                pos: jax.Array, val: jax.Array,
                valid: Optional[jax.Array] = None) -> jax.Array:
    """Scatter a chunk's K or V into the stacked pool, in place.

    pool (L, NB, BS, Hkv*D), the scan's carried pool; layer () int32, the
    group being run; block_table (B, MB); pos (B, C) absolute positions;
    val (B, C, Hkv, D).  Each token writes one row of ``Hkv*D`` at
    ``pool[layer, block, pos % BS]``.  ``valid`` (B, C) masks writes by
    routing them to the sentinel block (dropped).
    """
    _, nb, bs, _ = pool.shape
    mb = block_table.shape[1]
    blk = jnp.take_along_axis(block_table,
                              jnp.clip(pos // bs, 0, mb - 1), axis=1)
    # Positions past the table (can't happen for budget-allocated rows;
    # CAN happen for padding rows) and masked positions write nowhere.
    blk = jnp.where(pos // bs < mb, blk, nb)
    if valid is not None:
        blk = jnp.where(valid, blk, nb)
    rows = val.reshape(val.shape[:-2] + (-1,)).astype(pool.dtype)
    return pool.at[layer, blk, pos % bs].set(rows, mode="drop")


def _attn_ffn(bparams: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Post-attention norm2 + MLP/MoE residual (shared by both phases)."""
    h2 = rmsnorm_apply(bparams["norm2"], x)
    if cfg.moe is not None:
        y2, _ = moe_lib.moe_apply(bparams["ffn"], h2, cfg)
    else:
        y2 = gated_mlp_apply(bparams["ffn"], h2)
    return x + y2


def _paged_attn(bparams: dict, x: jax.Array, bstate: dict,
                layer: jax.Array, block_table: jax.Array, q_pos: jax.Array,
                kv_len: jax.Array, cfg: ModelConfig, *,
                window: Optional[int],
                valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, dict]:
    """Attention over the paged pool for a (B, C, D) chunk (C=1: decode).

    Writes the chunk's K/V into the stacked pools at ``layer`` (masked
    writes drop), then runs the block-table attention op on the same
    layer.  Returns (residual y (B, C, D), the updated stacked pools)."""
    b, c, _ = x.shape
    h = rmsnorm_apply(bparams["norm1"], x)
    q, k, v = attention._project_qkv(bparams["mixer"], h, cfg, q_pos)
    with jax.named_scope("kv_write"):
        new_k = _pool_write(bstate["k"], layer, block_table, q_pos, k,
                            valid)
        new_v = _pool_write(bstate["v"], layer, block_table, q_pos, v,
                            valid)
    out = kops.paged_decode_attention(
        q, new_k, new_v, layer, block_table, q_pos,
        kv_len.astype(jnp.int32), window=window)
    y = jnp.einsum("...f,fd->...d", out.reshape(b, c, -1),
                   bparams["mixer"]["wo"].astype(x.dtype))
    return y, {"k": new_k, "v": new_v}


def _chunk_mixer_scan(decode_fn, bparams: dict, h: jax.Array, bstate,
                      n_tokens: jax.Array, cfg: ModelConfig, rt: Runtime
                      ) -> Tuple[jax.Array, Any]:
    """Run a single-token recurrent mixer over a (B, C, D) chunk.

    ``lax.scan`` over the C tokens of the chunk, merging state per token
    only for rows where the token is valid (t < n_tokens) — the same
    masked-merge containment the decode tick uses, applied at chunk
    granularity.  Outputs at invalid positions are garbage and discarded
    by the caller's last-valid gather.
    """
    b = h.shape[0]

    def tok_body(carry, xs):
        st = carry
        x_t, t = xs                       # x_t (B, D)
        y, ns = decode_fn(bparams["mixer"], x_t[:, None], st, cfg, rt)
        keep = t < n_tokens               # (B,)
        ns = jax.tree.map(
            lambda new, old: jnp.where(
                keep.reshape((b,) + (1,) * (new.ndim - 1)), new, old),
            ns, st)
        return ns, y[:, 0]

    toks = (h.swapaxes(0, 1), jnp.arange(h.shape[1]))
    new_state, ys = jax.lax.scan(tok_body, bstate, toks,
                                 unroll=rt.scan_unroll)
    return ys.swapaxes(0, 1), new_state


def _prefill_block(bparams: dict, btype: str, x: jax.Array, bstate,
                   layer: jax.Array, block_table: jax.Array,
                   q_pos: jax.Array, kv_len: jax.Array, valid: jax.Array,
                   n_tokens: jax.Array, cfg: ModelConfig, rt: Runtime
                   ) -> Tuple[jax.Array, Any]:
    if btype in ("attn", "local"):
        window = cfg.window if btype == "local" else None
        y, new_cache = _paged_attn(bparams, x, bstate, layer, block_table,
                                   q_pos, kv_len, cfg, window=window,
                                   valid=valid)
        return _attn_ffn(bparams, x + y, cfg), new_cache
    h = rmsnorm_apply(bparams["norm1"], x)
    if btype == "rglru":
        y, ns = _chunk_mixer_scan(recurrent.rglru_block_decode, bparams, h,
                                  bstate, n_tokens, cfg, rt)
        x = x + y
        h2 = rmsnorm_apply(bparams["norm2"], x)
        return x + gated_mlp_apply(bparams["ffn"], h2), ns
    if btype == "mlstm":
        y, ns = _chunk_mixer_scan(recurrent.mlstm_block_decode, bparams, h,
                                  bstate, n_tokens, cfg, rt)
        return x + y, ns
    if btype == "slstm":
        y, ns = _chunk_mixer_scan(recurrent.slstm_block_decode, bparams, h,
                                  bstate, n_tokens, cfg, rt)
        return x + y, ns
    raise ValueError(btype)


def _decode_block(bparams: dict, btype: str, x: jax.Array, bstate,
                  layer: jax.Array, block_table: jax.Array,
                  cache_len: jax.Array, cfg: ModelConfig, rt: Runtime
                  ) -> Tuple[jax.Array, Any]:
    if btype in ("attn", "local"):
        window = cfg.window if btype == "local" else None
        y, new_cache = _paged_attn(bparams, x, bstate, layer, block_table,
                                   cache_len[:, None], cache_len + 1,
                                   cfg, window=window)
        return _attn_ffn(bparams, x + y, cfg), new_cache
    h = rmsnorm_apply(bparams["norm1"], x)
    if btype == "rglru":
        y, ns = recurrent.rglru_block_decode(bparams["mixer"], h, bstate,
                                             cfg, rt)
        x = x + y
        h2 = rmsnorm_apply(bparams["norm2"], x)
        return x + gated_mlp_apply(bparams["ffn"], h2), ns
    if btype == "mlstm":
        y, ns = recurrent.mlstm_block_decode(bparams["mixer"], h, bstate,
                                             cfg, rt)
        return x + y, ns
    if btype == "slstm":
        y, ns = recurrent.slstm_block_decode(bparams["mixer"], h, bstate,
                                             cfg, rt)
        return x + y, ns
    raise ValueError(btype)


def _layer_scan(block, x: jax.Array, params: dict, state: Tuple[Any, ...],
                cfg: ModelConfig, rt: Runtime
                ) -> Tuple[jax.Array, Tuple[Any, ...]]:
    """Run ``block(bparams, btype, x, bstate, layer)`` over every group.

    The pooled positions' stacked pools ride the scan's carry beside
    ``x`` and are written and read at ``layer`` in place; the stacked
    block params, the recurrent positions' per-group states and the layer
    index are the scan's ``xs``, the new recurrent states its ``ys``.
    Returns (x, the new state tuple)."""
    pools = {p: state[p] for p in pooled_positions(cfg)}
    per_layer = tuple(None if p in pools else s for p, s in enumerate(state))

    def group_body(carry, xs):
        x, pools = carry
        gparams, gstate, layer = xs
        pools, gstate = dict(pools), list(gstate)
        for p, btype in enumerate(cfg.block_pattern):
            entries = pools if p in pools else gstate
            x, entries[p] = block(gparams[p], btype, x, entries[p], layer)
        return (x, pools), tuple(gstate)

    with jax.named_scope("layers"):
        (x, pools), per_layer = jax.lax.scan(
            group_body, (x, pools),
            (params["blocks"], per_layer,
             jnp.arange(cfg.num_groups, dtype=jnp.int32)),
            unroll=rt.scan_unroll)
    return x, tuple(pools[p] if p in pools else s
                    for p, s in enumerate(per_layer))


def _head(params: dict, x: jax.Array) -> jax.Array:
    x = rmsnorm_apply(params["final_norm"], x)
    return jnp.einsum("...d,dv->...v", x,
                      params["head"]["w"].astype(x.dtype))


def paged_decode_step(params: dict, state: Tuple[Any, ...],
                      block_table: jax.Array, cache_len: jax.Array,
                      cfg: ModelConfig, rt: Runtime,
                      batch: Dict[str, jax.Array]
                      ) -> Tuple[jax.Array, Tuple[Any, ...], jax.Array]:
    """One token per row against the paged pool.

    block_table (B, MB) int32; cache_len (B,) — the position this step
    writes; batch tokens (B, 1) (or embeds).  Returns (logits (B, Vpad),
    new_state, cache_len + 1).
    """
    with jax.named_scope("embed"):
        x = _embed(params, cfg, batch)                  # (B, 1, D)

    def block(bparams, btype, x, bstate, layer):
        return _decode_block(bparams, btype, x, bstate, layer, block_table,
                             cache_len, cfg, rt)

    x, new_state = _layer_scan(block, x, params, state, cfg, rt)
    with jax.named_scope("head"):
        logits = _head(params, x)
    return logits[:, 0], new_state, cache_len + 1


def paged_prefill_step(params: dict, state: Tuple[Any, ...],
                       block_table: jax.Array, cache_len: jax.Array,
                       n_tokens: jax.Array, cfg: ModelConfig, rt: Runtime,
                       batch: Dict[str, jax.Array]
                       ) -> Tuple[jax.Array, Tuple[Any, ...], jax.Array]:
    """One prefill chunk per row: C prompt tokens, ``n_tokens`` (B,) valid.

    Rows with n_tokens < C are padded (pool writes of padding positions
    drop; recurrent merges are suppressed per token).  Returns (logits at
    each row's last valid position (B, Vpad), new_state,
    cache_len + n_tokens).
    """
    with jax.named_scope("embed"):
        x = _embed(params, cfg, batch)                  # (B, C, D)
    b, c, _ = x.shape
    q_pos = cache_len[:, None] + jnp.arange(c)[None, :]       # (B, C)
    valid = jnp.arange(c)[None, :] < n_tokens[:, None]        # (B, C)
    kv_len = cache_len + n_tokens

    def block(bparams, btype, x, bstate, layer):
        return _prefill_block(bparams, btype, x, bstate, layer, block_table,
                              q_pos, kv_len, valid, n_tokens, cfg, rt)

    x, new_state = _layer_scan(block, x, params, state, cfg, rt)
    with jax.named_scope("head"):
        last = jnp.clip(n_tokens - 1, 0, c - 1)               # (B,)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = _head(params, x_last)                        # (B, 1, Vpad)
    return logits[:, 0], new_state, cache_len + n_tokens
