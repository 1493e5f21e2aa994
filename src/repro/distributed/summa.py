"""SUMMA sharded GEMM with comm/compute overlap — the scale-out macro-op.

The paper's temporal integration is a single-chip story: keep the systolic
array busy by fusing the SIMD work into the GEMM's residency window.  At
mesh scale the analogous efficiency lever is hiding *collective* traffic
behind FMACS: a multi-device GEMM spends its time either multiplying tiles
or waiting for the next tile to arrive, and a schedule that broadcasts tile
``t+1`` while tile ``t`` multiplies pays for communication exactly once —
at step 0.  (The WSE-2 SUMMA case study referenced in PAPERS.md measures
this structure directly: a per-step broadcast of ~201 cycles hidden under
an ~11k-cycle tile GEMM — "broadcast is COMPLETELY HIDDEN".)

Algorithm (textbook SUMMA on a ``(pr, pc)`` process grid):

* ``A`` is block-distributed ``(M/pr, K/pc)``, ``B`` ``(K/pr, N/pc)``, and
  the output ``C`` ``(M/pr, N/pc)`` — the same 2-D block layout the
  production meshes in :mod:`repro.launch.mesh` use for weights.
* The contraction runs over ``S = lcm(pr, pc)`` K-panels.  At step ``t``
  the column that owns A-panel ``t`` broadcasts it along its row, the row
  that owns B-panel ``t`` broadcasts it along its column, and every device
  accumulates ``A_panel @ B_panel`` into its C block with the local
  :func:`repro.kernels.ops.sma_gemm` (so the per-step tile GEMM runs on
  whatever backend the ambient options resolve — the same dispatch policy
  as single-device code).
* **Overlap** (``overlap=True``, the default): the loop is double-buffered
  — the broadcasts for step ``t+1`` are *issued before* step ``t``'s local
  GEMM, and carry no data dependence on the accumulator, so XLA's async
  collectives run them under the FMACS.  ``overlap=False`` is the
  non-overlapped reference: an :func:`jax.lax.optimization_barrier` ties
  step ``t+1``'s broadcast inputs to step ``t``'s accumulator, forcing the
  serial broadcast→compute→broadcast schedule.  The two paths are
  numerically identical (same panels, same accumulation order) — the
  reference exists for correctness tests and as the bench baseline the
  overlapped path must beat.

Broadcasts are implemented as masked ``psum`` per mesh axis (owner
contributes its panel, everyone else zeros) — one collective per step per
axis, correct for any grid shape including the non-square fake CI meshes.

:func:`summa_comm_stats` is the *shared* cost model: the planner's
comm-costing (:mod:`repro.compiler.lower`), the plan report's ``comm``
section, and the scaling benchmarks all price collective traffic through
this one function, so "predicted comm bytes" always reconciles with the
schedule this module actually runs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.sma import EPILOGUES
from repro.obs import trace as _obs_trace

__all__ = ["sma_gemm_sharded", "summa_grid", "summa_comm_stats",
           "summa_schedule"]


# --------------------------------------------------------------------------
# Grid derivation + the shared comm cost model
# --------------------------------------------------------------------------
def summa_grid(mesh: Mesh, axes: Optional[Sequence[str]] = None
               ) -> Tuple[Optional[str], Optional[str], int, int]:
    """``(row_axis, col_axis, pr, pc)`` for a SUMMA launch on ``mesh``.

    ``axes`` names (row, col) mesh axes; default is the mesh's first two
    axis names.  The row axis shards M (and B's K); the col axis shards N
    (and A's K).  A missing/absent axis contributes grid extent 1, so the
    same call works on 1-D meshes and single-device smoke runs.
    """
    names = tuple(mesh.axis_names)
    if axes is None:
        axes = names[:2]
    axes = tuple(axes)[:2]
    sizes = dict(mesh.shape)
    row = axes[0] if len(axes) >= 1 and axes[0] in names else None
    col = axes[1] if len(axes) >= 2 and axes[1] in names else None
    pr = sizes.get(row, 1) if row else 1
    pc = sizes.get(col, 1) if col else 1
    return row, col, pr, pc


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def summa_schedule(m: int, n: int, k: int, *, pr: int, pc: int,
                   itemsize_a: int = 4, itemsize_b: int = 4
                   ) -> Dict[str, Any]:
    """The step schedule one ``sma_gemm_sharded`` call runs, with per-step
    collective bytes — the ground truth :func:`summa_comm_stats` sums.

    Bytes count *traffic*: a panel broadcast along an axis of extent ``p``
    delivers one copy to each of the ``p - 1`` non-owners, concurrently in
    every row/column of the grid.
    """
    steps = math.lcm(pr, pc)
    mb = _ceil_to(m, pr) // pr
    nb = _ceil_to(n, pc) // pc
    kp = _ceil_to(k, steps) // steps
    per_step = []
    for t in range(steps):
        a_bytes = mb * kp * itemsize_a * (pc - 1) * pr if pc > 1 else 0
        b_bytes = kp * nb * itemsize_b * (pr - 1) * pc if pr > 1 else 0
        per_step.append({"step": t, "bcast_a_bytes": a_bytes,
                         "bcast_b_bytes": b_bytes})
    return {"grid": [pr, pc], "steps": steps,
            "block": [mb, nb, kp], "per_step": per_step}


def summa_comm_stats(m: int, n: int, k: int, *, pr: int, pc: int,
                     itemsize_a: int = 4, itemsize_b: int = 4,
                     overlap: bool = True,
                     row_axis: Optional[str] = None,
                     col_axis: Optional[str] = None) -> Dict[str, Any]:
    """Collective traffic one sharded GEMM moves, and how much of it the
    double-buffered schedule hides.

    ``hidden_bytes`` / ``predicted_overlap_fraction`` come straight from
    the schedule shape: with double buffering, the broadcasts for steps
    ``1..S-1`` are issued while steps ``0..S-2`` compute, so only step 0's
    broadcast is exposed — ``(S-1)/S`` of the traffic is predicted hidden.
    ``overlap=False`` hides nothing by construction.
    """
    sched = summa_schedule(m, n, k, pr=pr, pc=pc,
                           itemsize_a=itemsize_a, itemsize_b=itemsize_b)
    steps = sched["steps"]
    bytes_a = sum(s["bcast_a_bytes"] for s in sched["per_step"])
    bytes_b = sum(s["bcast_b_bytes"] for s in sched["per_step"])
    total = bytes_a + bytes_b
    hidden = total * (steps - 1) / steps if (overlap and steps > 1) else 0.0
    collectives: Dict[str, int] = {}
    if pc > 1:
        collectives[col_axis or "col"] = steps     # A-panel broadcasts
    if pr > 1:
        collectives[row_axis or "row"] = steps     # B-panel broadcasts
    return {
        "grid": sched["grid"],
        "steps": steps,
        "bytes_a": bytes_a,
        "bytes_b": bytes_b,
        "bytes_total": total,
        "hidden_bytes": hidden,
        "predicted_overlap_fraction": (hidden / total) if total else 0.0,
        "collectives_per_axis": collectives,
    }


#: Planner hook: ``comm_coster(m, n, k, itemsize_a, itemsize_b) -> bytes``
#: for one GEMM site on a given grid (used by ``compiler.lower`` so lowered
#: MATMUL ops carry comm bytes alongside their HBM bytes).
def comm_coster_for(mesh: Mesh, axes: Optional[Sequence[str]] = None):
    row, col, pr, pc = summa_grid(mesh, axes)
    if pr * pc <= 1:
        return None

    def coster(m: int, n: int, k: int, itemsize_a: int,
               itemsize_b: int) -> float:
        return float(summa_comm_stats(
            m, n, k, pr=pr, pc=pc, itemsize_a=itemsize_a,
            itemsize_b=itemsize_b)["bytes_total"])

    return coster


# --------------------------------------------------------------------------
# The sharded GEMM
# --------------------------------------------------------------------------
def _bcast_panel(block: jax.Array, *, t: int, panels_local: int, kp: int,
                 axis: Optional[str], extent: int, k_dim: int,
                 tag: str) -> jax.Array:
    """Broadcast global K-panel ``t`` of a block-distributed operand along
    ``axis`` (masked psum from the owner).  ``k_dim`` is the K dimension of
    the local block (1 for A ``(mb, k_local)``, 0 for B ``(k_local, nb)``)."""
    owner, off = divmod(t, panels_local)
    off *= kp
    panel = lax.slice_in_dim(block, off, off + kp, axis=k_dim)
    if extent <= 1 or axis is None:
        return panel
    tr = _obs_trace.current_tracer()
    nbytes = panel.size * panel.dtype.itemsize * (extent - 1)
    ctx = tr.span(f"comm.bcast_{tag}", cat="comm", mode="comm", step=t,
                  axis=axis, bytes=int(nbytes)) if tr is not None else None
    mine = lax.axis_index(axis) == owner
    masked = jnp.where(mine, panel, jnp.zeros_like(panel))
    if ctx is None:
        return lax.psum(masked, axis)
    with ctx:
        return lax.psum(masked, axis)


def sma_gemm_sharded(a: jax.Array, b: jax.Array, *,
                     mesh: Mesh,
                     axes: Optional[Sequence[str]] = None,
                     bias: Optional[jax.Array] = None,
                     epilogue: str = "none",
                     overlap: bool = True,
                     accum_dtype: jnp.dtype = jnp.float32,
                     precision=None,
                     backend: Any = None,
                     interpret: Optional[bool] = None,
                     block_m: Optional[int] = None,
                     block_n: Optional[int] = None,
                     block_k: Optional[int] = None) -> jax.Array:
    """Multi-device SUMMA GEMM: ``epilogue(A @ B + bias)`` sharded on
    ``mesh``, comm/compute-overlapped by default.

    Drop-in for :func:`repro.kernels.ops.sma_gemm` at mesh scale: same
    ``(..., K) @ (K, N)`` contract, same bias/epilogue fusion surface, same
    output dtype (``a.dtype``), with M/N/K padded internally so non-divisible
    edge tiles are handled transparently.  The per-step local tile GEMM goes
    through ``kernels.ops.sma_gemm`` (``mesh=False``), so it dispatches per
    the framework backend contract — systolic Pallas kernels where capable,
    XLA elsewhere — and shows up on the systolic lane of runtime traces,
    while the per-step broadcasts land on the new ``comm`` lane.
    """
    if b.ndim != 2:
        raise ValueError(f"sma_gemm_sharded needs a 2-D stationary operand, "
                         f"got B of shape {b.shape}")
    if a.shape[-1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: A {a.shape} @ B {b.shape}")
    lead = a.shape[:-1]
    m = math.prod(int(d) for d in lead) if lead else 1
    k, n = int(b.shape[0]), int(b.shape[1])
    a2 = a.reshape(m, k)

    row, col, pr, pc = summa_grid(mesh, axes)
    from repro.kernels import ops as kernel_ops
    if pr * pc <= 1:
        out = kernel_ops.sma_gemm(
            a2, b, bias=bias, epilogue=epilogue, mesh=False,
            accum_dtype=accum_dtype, precision=precision, backend=backend,
            interpret=interpret, block_m=block_m, block_n=block_n,
            block_k=block_k)
        return out.reshape(*lead, n)

    steps = math.lcm(pr, pc)
    mp, np_, kp_tot = _ceil_to(m, pr), _ceil_to(n, pc), _ceil_to(k, steps)
    kp = kp_tot // steps
    a_pad = jnp.pad(a2, ((0, mp - m), (0, kp_tot - k)))
    b_pad = jnp.pad(b, ((0, kp_tot - k), (0, np_ - n)))
    bias_pad = jnp.pad(bias, (0, np_ - n)) if bias is not None \
        else jnp.zeros((np_,), a.dtype)
    out_dtype = a.dtype

    local_gemm = partial(kernel_ops.sma_gemm, mesh=False, epilogue="none",
                         accum_dtype=accum_dtype, precision=precision,
                         backend=backend, interpret=interpret,
                         block_m=block_m, block_n=block_n, block_k=block_k)
    fetch_a = partial(_bcast_panel, panels_local=steps // pc, kp=kp,
                      axis=col, extent=pc, k_dim=1, tag="a")
    fetch_b = partial(_bcast_panel, panels_local=steps // pr, kp=kp,
                      axis=row, extent=pr, k_dim=0, tag="b")

    def summa_local(a_loc, b_loc, bias_loc):
        acc = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), accum_dtype)
        a_nxt = fetch_a(a_loc, t=0)
        b_nxt = fetch_b(b_loc, t=0)
        for t in range(steps):
            a_cur, b_cur = a_nxt, b_nxt
            if overlap:
                # Double buffering: issue step t+1's broadcasts BEFORE the
                # local GEMM; they carry no dependence on ``acc``, so async
                # collectives run them under the FMACS.
                if t + 1 < steps:
                    a_nxt = fetch_a(a_loc, t=t + 1)
                    b_nxt = fetch_b(b_loc, t=t + 1)
                acc = acc + local_gemm(a_cur, b_cur).astype(accum_dtype)
            else:
                # Reference schedule: the barrier makes step t+1's
                # broadcasts data-depend on step t's accumulator — strictly
                # serial broadcast -> compute -> broadcast.
                acc = acc + local_gemm(a_cur, b_cur).astype(accum_dtype)
                if t + 1 < steps:
                    a_loc_b, b_loc_b, acc = lax.optimization_barrier(
                        (a_loc, b_loc, acc))
                    a_nxt = fetch_a(a_loc_b, t=t + 1)
                    b_nxt = fetch_b(b_loc_b, t=t + 1)
        acc = acc + bias_loc.astype(accum_dtype)[None, :]
        return EPILOGUES[epilogue](acc).astype(out_dtype)

    fn = jax.shard_map(summa_local, mesh=mesh,
                       in_specs=(P(row, col), P(row, col), P(col)),
                       out_specs=P(row, col), check_vma=False)

    tr = _obs_trace.current_tracer()
    if tr is None:
        out = fn(a_pad, b_pad, bias_pad)
    else:
        with tr.span("distributed.sma_gemm_sharded", cat="distributed",
                     grid=[pr, pc], steps=steps, overlap=overlap,
                     m=m, n=n, k=k) as sp:
            out = sp.block(fn(a_pad, b_pad, bias_pad))
    return out[:m, :n].reshape(*lead, n)
