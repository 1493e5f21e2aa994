"""The dense ``attn`` kind: what the benchmark knows of its block's layout.

A configuration file whose ``model.kind`` is ``dense``, or names no kind,
runs the program's dense ``attn`` block: RMSNorm, q/k/v projections with
no bias, rotary embedding, grouped-query attention, the output projection,
RMSNorm and a SwiGLU MLP.  Its plain forward is
``bench/reference/dense.py``.  This module gives, for that layout:

* :func:`expected`: the ``ModelConfig`` fields the program must match;
* :func:`program_params`: the program's parameter tree from the seed, and
  :func:`layer`: one layer of it, as the reference draws it;
* :func:`gemm_least_time`, :func:`tick_flops`,
  :func:`attention_least_time`: the work that the rooflines and
  ``step_mfu`` count;
* :func:`tiny`: the configuration cut to the size the CPU tests run.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import weights as W
from bench import work

#: (group, leaf) of one layer, in the order their keys are folded.
LAYER_LEAVES = (("norm1", "scale"), ("mixer", "wq"), ("mixer", "wk"),
                ("mixer", "wv"), ("mixer", "wo"), ("norm2", "scale"),
                ("ffn", "wi"), ("ffn", "wg"), ("ffn", "wo"))
#: The published sizes cut to CPU size for a served run, and the larger
#: cut at which a test reads the int8 control; every other key stays the
#: file's, and the head layout (MHA or GQA 4:1) the original's.
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "head_dim": 16, "vocab_size": 256,
        "num_hidden_layers": 2}
CONTROL = {"hidden_size": 512, "intermediate_size": 1408,
           "num_attention_heads": 4, "head_dim": 128, "vocab_size": 32768,
           "num_hidden_layers": 8}


def expected(m: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ModelConfig`` fields that the file's published
    sizes fix."""
    return {"d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
            "num_heads": m["num_attention_heads"],
            "num_kv_heads": m["num_key_value_heads"],
            "resolved_head_dim": m["head_dim"],
            "vocab_size": m["vocab_size"],
            "num_layers": m["num_hidden_layers"],
            "rope_theta": m["rope_theta"],
            "block_pattern": ("attn",), "moe": None,
            "param_dtype": m["torch_dtype"], "dtype": m["torch_dtype"]}


def dims(m: Dict[str, Any]) -> Tuple[int, int, int, int, int, int, int]:
    """(d, ff, heads, kv_heads, head_dim, vocab, layers) of a config file."""
    return (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["vocab_size"], m["num_hidden_layers"])


def layer_shapes(m: Dict[str, Any]) -> Dict[Tuple[str, str], tuple]:
    d, ff, hq, hkv, hd, _, _ = dims(m)
    return {("norm1", "scale"): (d,), ("mixer", "wq"): (d, hq * hd),
            ("mixer", "wk"): (d, hkv * hd), ("mixer", "wv"): (d, hkv * hd),
            ("mixer", "wo"): (hq * hd, d), ("norm2", "scale"): (d,),
            ("ffn", "wi"): (d, ff), ("ffn", "wg"): (d, ff),
            ("ffn", "wo"): (ff, d)}


def layer(key: jax.Array, m: Dict[str, Any], index) -> Dict[str, dict]:
    """One layer's leaves (bf16), as a nested dict: each from its own key,
    ``fold_in`` of the seed's key with the leaf's index, then the layer's."""
    out: Dict[str, dict] = {}
    for i, (group, leaf) in enumerate(LAYER_LEAVES):
        shape = layer_shapes(m)[(group, leaf)]
        k = jax.random.fold_in(jax.random.fold_in(key, i), index)
        out.setdefault(group, {})[leaf] = W.draw(k, shape, leaf == "scale")
    return out


def program_params(seed: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree, made on the device in one jitted
    call: every layer stacked on a leading axis, as ``lax.scan`` takes it."""
    if m["vocab_size"] % W.VOCAB_PAD:
        raise ValueError(f"vocab_size {m['vocab_size']} is not a multiple "
                         f"of {W.VOCAB_PAD}; the program would pad the head")
    layers = m["num_hidden_layers"]

    @jax.jit
    def make(key):
        stacked = jax.vmap(lambda i: layer(key, m, i))(jnp.arange(layers))
        return {"embed": {"table": W.embed(key, m)},
                "blocks": (stacked,),
                "final_norm": {"scale": W.final_norm(key, m)},
                "head": {"w": W.head(key, m)}}

    return make(W.root_key(seed))


def layer_gemms(m: Dict) -> List[Tuple[int, int]]:
    """(K, N) of the GEMMs of one layer: q, k, v, o, and the gated MLP's
    in, gate and out."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, ff), (d, ff), (ff, d)]


def layer_params(m: Dict) -> int:
    return sum(k * n for k, n in layer_gemms(m))


def gemm_least_time(m: Dict, tokens: int, peaks: Dict) -> float:
    """Least seconds one tick's layer GEMMs could take on the chip, over
    its ``tokens`` real rows: each call bounded by the larger of its FLOPs
    over peak and its bytes over bandwidth."""
    t = 0.0
    for k, n in layer_gemms(m):
        f, b = work.gemm(tokens, k, n)
        t += m["num_hidden_layers"] * work.least_time(f, b, peaks)
    return t


def tick_flops(m: Dict, rows, logit_rows: int) -> float:
    """Model FLOPs of one tick, ``rows`` as ``(start, n)`` real positions:
    per position 2 x the layer parameters it multiplies and QK and PV over
    its context, plus the head for each of ``logit_rows``."""
    layers = m["num_hidden_layers"]
    per_pos = 2.0 * layers * layer_params(m)
    attn = 4.0 * layers * m["num_attention_heads"] * m["head_dim"]
    f = 0.0
    for start, n in rows:
        contexts = n * start + n * (n + 1) // 2
        f += per_pos * n + attn * contexts
    return f + logit_rows * 2.0 * m["hidden_size"] * m["vocab_size"]


def attention_least_time(m: Dict, kv_lens, peaks: Dict) -> float:
    """Least seconds of one decode tick's attention over every layer."""
    f, b = work.decode_attention(kv_lens, m["num_attention_heads"],
                                 m["num_key_value_heads"], m["head_dim"])
    return m["num_hidden_layers"] * work.least_time(f, b, peaks)


def tiny(m: Dict[str, Any], control: bool = False) -> Dict[str, Any]:
    """A copy of a configuration file cut to :data:`TINY`, or to
    :data:`CONTROL` where ``control``, with the program's overrides to
    match."""
    sizes = CONTROL if control else TINY
    out = copy.deepcopy(m)
    gqa = out["num_attention_heads"] // out["num_key_value_heads"]
    out.update(sizes)
    out["num_key_value_heads"] = sizes["num_attention_heads"] // gqa
    out["model"]["overrides"].update(
        num_groups=sizes["num_hidden_layers"], d_model=sizes["hidden_size"],
        d_ff=sizes["intermediate_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=out["num_key_value_heads"],
        head_dim=sizes["head_dim"], vocab_size=sizes["vocab_size"])
    return out
