"""Random weights from the seed, made by the benchmark and not the program.

Every leaf of every layer is drawn from its own key, ``fold_in`` of the
seed's key with the leaf's index and then the layer's, and rounded to bf16,
the type the checkpoints store.  So the program's stacked tree (all layers
in one jitted call, on the device) and the reference's one-layer-at-a-time
copy hold the same values, and neither takes anything from the other.

Shapes follow the configuration file's published sizes; the tree is laid
out as the program's dense ``attn`` block takes it.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

#: (group, leaf) of one layer, in the order their keys are folded.
LAYER_LEAVES = (("norm1", "scale"), ("mixer", "wq"), ("mixer", "wk"),
                ("mixer", "wv"), ("mixer", "wo"), ("norm2", "scale"),
                ("ffn", "wi"), ("ffn", "wg"), ("ffn", "wo"))
#: Leaf indices of the leaves outside the layers.
EMBED, FINAL_NORM, HEAD = 100, 101, 102
#: The program pads its vocabulary to a multiple of this.
VOCAB_PAD = 256
DTYPE = jnp.bfloat16


def dims(m: Dict[str, Any]) -> Tuple[int, int, int, int, int, int, int]:
    """(d, ff, heads, kv_heads, head_dim, vocab, layers) of a config file."""
    return (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["vocab_size"], m["num_hidden_layers"])


def root_key(seed: int) -> jax.Array:
    """A key from all 64 bits of the seed (``PRNGKey`` keeps only 32)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def layer_shapes(m: Dict[str, Any]) -> Dict[Tuple[str, str], tuple]:
    d, ff, hq, hkv, hd, _, _ = dims(m)
    return {("norm1", "scale"): (d,), ("mixer", "wq"): (d, hq * hd),
            ("mixer", "wk"): (d, hkv * hd), ("mixer", "wv"): (d, hkv * hd),
            ("mixer", "wo"): (hq * hd, d), ("norm2", "scale"): (d,),
            ("ffn", "wi"): (d, ff), ("ffn", "wg"): (d, ff),
            ("ffn", "wo"): (ff, d)}


def _draw(key: jax.Array, shape: tuple, is_scale: bool) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if is_scale:
        return (1.0 + 0.1 * z).astype(DTYPE)
    return (z * shape[0] ** -0.5).astype(DTYPE)


def layer(key: jax.Array, m: Dict[str, Any], index) -> Dict[str, dict]:
    """One layer's leaves (bf16), as a nested dict."""
    out: Dict[str, dict] = {}
    for i, (group, leaf) in enumerate(LAYER_LEAVES):
        shape = layer_shapes(m)[(group, leaf)]
        k = jax.random.fold_in(jax.random.fold_in(key, i), index)
        out.setdefault(group, {})[leaf] = _draw(k, shape, leaf == "scale")
    return out


def embed(key: jax.Array, m: Dict[str, Any]) -> jax.Array:
    d, vocab = m["hidden_size"], m["vocab_size"]
    z = jax.random.normal(jax.random.fold_in(key, EMBED), (vocab, d),
                          jnp.float32)
    return z.astype(DTYPE)


def final_norm(key: jax.Array, m: Dict[str, Any]) -> jax.Array:
    return _draw(jax.random.fold_in(key, FINAL_NORM), (m["hidden_size"],),
                 True)


def head(key: jax.Array, m: Dict[str, Any]) -> jax.Array:
    return _draw(jax.random.fold_in(key, HEAD),
                 (m["hidden_size"], m["vocab_size"]), False)


def program_params(seed: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree, made on the device in one jitted
    call: every layer stacked on a leading axis, as ``lax.scan`` takes it."""
    if m["vocab_size"] % VOCAB_PAD:
        raise ValueError(f"vocab_size {m['vocab_size']} is not a multiple "
                         f"of {VOCAB_PAD}; the program would pad the head")
    layers = m["num_hidden_layers"]

    @jax.jit
    def make(key):
        stacked = jax.vmap(lambda i: layer(key, m, i))(jnp.arange(layers))
        return {"embed": {"table": embed(key, m)},
                "blocks": (stacked,),
                "final_norm": {"scale": final_norm(key, m)},
                "head": {"w": head(key, m)}}

    return make(root_key(seed))
