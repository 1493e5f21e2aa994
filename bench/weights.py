"""Random weights from the seed, made by the benchmark and not the program.

Every leaf is drawn from its own key, ``fold_in`` of the seed's key with
the leaf's index, and rounded to bf16, the type the checkpoints store.  So
the program's tree (all layers in one jitted call, on the device) and the
reference's one-layer-at-a-time copy hold the same values, and neither
takes anything from the other.

This module holds what every model kind shares: the seed's key, the draw,
and the leaves outside the layers.  Each kind's layers and the tree the
program takes are in ``bench/models/<kind>.py``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

#: Leaf indices of the leaves outside the layers.
EMBED, FINAL_NORM, HEAD = 100, 101, 102
#: The program pads its vocabulary to a multiple of this.
VOCAB_PAD = 256
DTYPE = jnp.bfloat16


def root_key(seed: int) -> jax.Array:
    """A key from all 64 bits of the seed (``PRNGKey`` keeps only 32)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def draw(key: jax.Array, shape: tuple, is_scale: bool) -> jax.Array:
    """A norm scale 1 + N(0, 0.01), or a projection N(0, 1/fan_in)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if is_scale:
        return (1.0 + 0.1 * z).astype(DTYPE)
    return (z * shape[0] ** -0.5).astype(DTYPE)


def embed(key: jax.Array, m: Dict[str, Any]) -> jax.Array:
    d, vocab = m["hidden_size"], m["vocab_size"]
    z = jax.random.normal(jax.random.fold_in(key, EMBED), (vocab, d),
                          jnp.float32)
    return z.astype(DTYPE)


def final_norm(key: jax.Array, m: Dict[str, Any]) -> jax.Array:
    return draw(jax.random.fold_in(key, FINAL_NORM), (m["hidden_size"],),
                True)


def head(key: jax.Array, m: Dict[str, Any]) -> jax.Array:
    return draw(jax.random.fold_in(key, HEAD),
                (m["hidden_size"], m["vocab_size"]), False)
