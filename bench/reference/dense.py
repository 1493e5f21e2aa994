"""Plain float32 forward of the dense ``attn`` decoder, for the check.

The block as the configuration files state it: RMSNorm, q/k/v projections
with no bias, rotary embedding over the full head width (rotate-half, the
published Llama/Mistral convention), causal grouped-query attention, the
output projection, RMSNorm, and a SwiGLU MLP, each with a residual; then a
final RMSNorm and the untied head.  Every matrix product runs in float32
under ``precision="highest"``.  It imports nothing of the program: the
weights come from the seed, one layer at a time, drawn as
``bench/models/dense.py`` draws the program's.

``quant="int8"`` computes the same forward with every projection, MLP and
head product in int8, weights quantized per output column and activations
per row, symmetric, accumulated in int32: the step below the configured
bf16 that a later change might take.  It serves as the control, which the
check has to refuse.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
#: Queries per attention block: bounds the score matrix to
#: heads x QBLOCK x seq_len floats.
QBLOCK = 512


def _mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    if quant is None:
        return jnp.dot(x, w, precision=HIGHEST)
    if quant != "int8":
        raise ValueError(f"unknown quant {quant!r}")
    ws = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    xs = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    wq = jnp.round(w / jnp.where(ws > 0, ws, 1.0)).astype(jnp.int8)
    xq = jnp.round(x / jnp.where(xs > 0, xs, 1.0)).astype(jnp.int8)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x (S, H, hd); rotate-half over the full head width."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal GQA.  q (S, Hq, hd); k, v (S, Hkv, hd) -> (S, Hq, hd)."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        qb = qb.reshape(QBLOCK, hkv, g, hd)
        sc = jnp.einsum("qngd,knd->ngqk", qb, k,
                        precision=HIGHEST) * hd ** -0.5
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        ob = jnp.einsum("ngqk,knd->qngd", p, v, precision=HIGHEST)
        return ob.reshape(QBLOCK, hq, hd)

    out = jax.lax.map(block, jnp.arange(s // QBLOCK))
    return out.reshape(s, hq, hd)


class Reference:
    """The forward over whole sequences, padded to ``seq_len`` (a
    multiple of :data:`QBLOCK`), layer by layer over all of them."""

    def __init__(self, m: Dict, seed: int, seq_len: int) -> None:
        self.m = m
        self.key = W.root_key(seed)
        self.seq_len = -(-seq_len // QBLOCK) * QBLOCK
        kind = spec.model_kind(m)
        d, ff, hq, hkv, hd, vocab, layers = kind.dims(m)
        eps, theta = m["rms_norm_eps"], m["rope_theta"]

        def layer_fwd(x, w, quant):
            f32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
            s = x.shape[0]
            pos = jnp.arange(s)
            h = _rmsnorm(x, f32["norm1"]["scale"], eps)
            mx = f32["mixer"]
            q = _rope(_mm(h, mx["wq"], quant).reshape(s, hq, hd), pos, theta)
            k = _rope(_mm(h, mx["wk"], quant).reshape(s, hkv, hd), pos,
                      theta)
            v = _mm(h, mx["wv"], quant).reshape(s, hkv, hd)
            a = _attention(q, k, v).reshape(s, hq * hd)
            x = x + _mm(a, mx["wo"], quant)
            h = _rmsnorm(x, f32["norm2"]["scale"], eps)
            fw = f32["ffn"]
            y = jax.nn.silu(_mm(h, fw["wg"], quant)) * _mm(h, fw["wi"], quant)
            return x + _mm(y, fw["wo"], quant)

        def head_fwd(x, rows, scale, w, quant):
            h = _rmsnorm(x[rows], scale.astype(jnp.float32), eps)
            return _mm(h, w.astype(jnp.float32), quant)

        self._layer = jax.jit(layer_fwd, static_argnames="quant")
        self._head = jax.jit(head_fwd, static_argnames="quant")
        self._gen_layer = jax.jit(lambda key, i: kind.layer(key, m, i))
        self._embed = jax.jit(lambda key: W.embed(key, m))
        self._final = jax.jit(lambda key: (W.final_norm(key, m),
                                           W.head(key, m)))
        self.layers = layers

    def _inputs(self, seqs: Sequence[np.ndarray]) -> List[jax.Array]:
        table = self._embed(self.key)
        xs = []
        for s in seqs:
            if len(s) > self.seq_len:
                raise ValueError(f"sequence of {len(s)} > {self.seq_len}")
            toks = np.zeros((self.seq_len,), np.int32)
            toks[:len(s)] = s
            xs.append(table[jnp.asarray(toks)].astype(jnp.float32))
        return xs

    def logits(self, seqs: Sequence[np.ndarray],
               rows: Sequence[np.ndarray],
               quants: Sequence[Optional[str]] = (None,)
               ) -> Dict[Optional[str], List[np.ndarray]]:
        """Logits (float32, host) at ``rows[i]`` of sequence ``i``, for each
        precision in ``quants`` (None: the float32 reference)."""
        x0 = self._inputs(seqs)
        xs = {qt: list(x0) for qt in quants}
        del x0
        for i in range(self.layers):
            w = self._gen_layer(self.key, i)
            for qt in quants:
                xs[qt] = [self._layer(x, w, qt) for x in xs[qt]]
            del w
        scale, head = self._final(self.key)
        out = {}
        for qt in quants:
            out[qt] = [np.asarray(self._head(x, jnp.asarray(r, jnp.int32),
                                             scale, head, qt))
                       for x, r in zip(xs[qt], rows)]
        return out
