"""RG-LRU linear-recurrence Pallas kernel (recurrentgemma / Griffin).

The RG-LRU is the modern incarnation of the paper's GEMM-*incompatible*
class: massively parallel across (batch, channels) but sequential in time —
exactly the kind of op the paper shows dying on a GEMM-only accelerator
(its CRF example).  SMA treatment: run it in **SIMD mode** on the VPU with the
hidden state resident in VMEM, streaming (a, u) blocks through the same
memory pipeline the systolic kernels use — a pure mode-switch, no host
round-trip, no GEMM contortions.

Computes  h_t = a_t * h_{t-1} + u_t  over (B, S, D):
grid (B, S/bs, D/bd) with the time dimension "arbitrary"; the carry h lives
in a VMEM scratch; within a block the recurrence runs as an unrolled
``fori_loop`` of VPU FMAs over (1, bd) rows.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, u_ref, h0_ref, o_ref, hlast_ref, h_ref, *,
                  block_s: int, n_s: int, out_dtype):
    is_ = pl.program_id(2)  # time is the innermost ("arbitrary") grid dim

    @pl.when(is_ == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)   # (bs, bd)
    u = u_ref[0].astype(jnp.float32)   # (bs, bd)

    def step(t, h):
        h = a[t][None, :] * h + u[t][None, :]
        o_ref[0, t, :] = h[0].astype(out_dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h

    @pl.when(is_ == n_s - 1)
    def _final():
        hlast_ref[...] = h.astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_d", "interpret"))
def rglru_scan(a: jax.Array, u: jax.Array,
               h0: Optional[jax.Array] = None, *,
               block_s: int = 256, block_d: int = 256,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Gated linear recurrence h_t = a_t h_{t-1} + u_t.

    a, u: (B, S, D); h0: (B, D) or None.  Returns (h_seq, h_last).
    """
    b, s, d = a.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), a.dtype)
    bs = min(block_s, s)
    bd = min(block_d, d)
    pad_s = (-s) % bs
    pad_d = (-d) % bd
    if pad_s or pad_d:
        # Pad with a=1, u=0 (identity recurrence) so h_last stays exact.
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d)),
                    constant_values=1 if pad_s else 0)
        a = a.at[:, :, d:].set(0) if pad_d else a
        u = jnp.pad(u, ((0, 0), (0, pad_s), (0, pad_d)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d)))
    sp, dp = s + pad_s, d + pad_d
    n_s = sp // bs
    # Time innermost so the VMEM carry sweeps t for one (batch, d-block) pair
    # before moving to the next; (b, d) blocks are independent ("parallel").
    grid = (b, dp // bd, n_s)

    kernel = functools.partial(_rglru_kernel, block_s=bs, n_s=n_s,
                               out_dtype=a.dtype)
    h_seq, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda b_, id_, is_: (b_, is_, id_)),
            pl.BlockSpec((1, bs, bd), lambda b_, id_, is_: (b_, is_, id_)),
            pl.BlockSpec((1, bd), lambda b_, id_, is_: (b_, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bd), lambda b_, id_, is_: (b_, is_, id_)),
            pl.BlockSpec((1, bd), lambda b_, id_, is_: (b_, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, dp), a.dtype),
            jax.ShapeDtypeStruct((b, dp), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, u, h0)
    return h_seq[:, :s, :d], h_last[:, :d]


def mxu_constraints(site) -> Optional[str]:
    """Hardware-path capability gate: the recurrence streams (1, bd) rows
    through the VPU, so the channel dim must fill sublanes (``D % 8 == 0``)
    to lower efficiently.  Misaligned sites fall down the backend ladder to
    the associative-scan SIMD path with this reason recorded; the
    interpreter path accepts any D (the kernel pads)."""
    d = site.shapes[0][-1]
    if d % 8:
        return (f"shape:channel dim {d} not VPU sublane-aligned "
                f"(hardware rglru kernel needs D % 8 == 0)")
    return None
