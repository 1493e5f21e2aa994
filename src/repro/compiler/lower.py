"""Stage 2 — lower: jaxpr equations to the symbolic ``Op`` IR of
:mod:`repro.core.modes`, with FLOP/byte costs inferred from avals.

The mapping implements the paper's taxonomy over JAX primitives:

* ``dot_general`` / ``conv_general_dilated`` → ``MATMUL`` (or
  ``ATTENTION_MATMUL`` when batch dimensions are present — the q@k^T / p@v
  shape) — SYSTOLIC mode;
* ``reduce_*`` / ``argmax`` / ``cum*`` → ``REDUCTION`` (softmax denominators,
  norms) — tile-local only when the reduced axis is the trailing one;
* ``gather`` / ``scatter*`` / ``dynamic_slice`` → ``GATHER_SCATTER``
  (embedding lookup, MoE dispatch/combine) — never tile-local;
* ``top_k`` / ``sort`` → ``TOPK`` (router top-k, sampling) — never tile-local;
* ``scan`` / ``while`` → ``RECURRENCE`` carry markers (plus the loop body,
  unrolled or amortized — see below);
* ``convert_element_type`` → ``CAST``;
* everything value-computing that remains → ``ELEMENTWISE`` (transcendentals
  FLOP-weighted heavier than arithmetic);
* pure layout ops (reshape/broadcast/transpose/slice/pad/concat/iota) are
  *elided* — XLA fuses them for free and counting them would drown the plan
  in zero-FLOP SIMD ops.  Their count is kept in :class:`LowerStats`.

Control flow:

* ``scan`` bodies with length ≤ ``max_scan_unroll`` are unrolled so mode
  switches are counted exactly (the reduced/smoke configs take this path);
* longer scans emit the body ONCE with costs scaled by the trip count (the
  steady-state per-iteration plan — what a 40-group model repeats 40×) plus
  a ``RECURRENCE`` carry marker that truthfully breaks fusion across the
  loop boundary;
* ``while`` emits its body once (trip count unknown) plus a carry marker;
* ``cond``/``switch`` lowers the most expensive branch;
* ``jit`` / ``custom_jvp_call`` / ``custom_vjp_call`` / ``remat`` /
  ``closed_call`` are transparent.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional

from jax.extend import core

from repro.core.modes import Op, OpKind

#: Mesh-aware comm costing hook: ``(m, n, k, itemsize_a, itemsize_b) ->
#: collective bytes`` for one LSMA-eligible GEMM site.  Built from the
#: engine's mesh by :func:`repro.distributed.summa.comm_coster_for` and
#: injected by the dispatch pipeline, so lowering stays jax-only.
CommCoster = Callable[[int, int, int, int, int], float]


def sma_eligible(eqn) -> bool:
    """True for ``(..., K) @ (K, N)`` contractions — the LSMA macro-op shape.

    ``kernels.sma_gemm`` collapses the leading dims of A into the output
    grid's M; batched dots (attention) keep their native lowering.  This is
    both the dispatcher's systolic-routing predicate and (with a mesh set)
    the set of sites the SUMMA comm coster prices — one predicate, so the
    plan's comm ledger covers exactly the sites that shard.
    """
    if eqn.primitive.name != "dot_general":
        return False
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    return (not lhs_b and not rhs_b
            and len(lhs_c) == 1 and len(rhs_c) == 1
            and rhs.ndim == 2 and rhs_c[0] == 0
            and lhs_c[0] == lhs.ndim - 1
            and lhs.ndim >= 2)

# --------------------------------------------------------------------------
# Primitive tables
# --------------------------------------------------------------------------
#: Pure data-layout primitives: zero-cost at plan level (XLA fuses them).
LAYOUT_PRIMS = frozenset({
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "transpose",
    "slice", "pad", "concatenate", "rev", "iota", "copy", "device_put",
    "stop_gradient", "split", "tie_in",
})

#: value → REDUCTION.  params carry the reduced axes.
REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin",
})

#: cumulative reductions: axis in params["axis"].
CUMULATIVE_PRIMS = frozenset({
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})

GATHER_PRIMS = frozenset({
    "gather", "scatter", "scatter-add", "scatter_add", "scatter-mul",
    "scatter-min", "scatter-max", "dynamic_slice", "dynamic_update_slice",
    "take", "take_along_axis",
})

TOPK_PRIMS = frozenset({"top_k", "sort", "approx_top_k", "partial_sort"})

CAST_PRIMS = frozenset({
    "convert_element_type", "bitcast_convert_type", "reduce_precision",
})

#: Transcendental elementwise primitives get a heavier FLOP weight than
#: add/mul — mirrors the hand-written plans' 4-5 FLOPs/element for softmax.
TRANSCENDENTAL_PRIMS = frozenset({
    "exp", "exp2", "expm1", "log", "log2", "log1p", "logistic", "tanh",
    "sin", "cos", "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh",
    "erf", "erfc", "erf_inv", "pow", "rsqrt", "sqrt", "cbrt", "digamma",
    "lgamma", "igamma", "igammac",
})

_TRANSCENDENTAL_FLOPS = 4.0

#: Higher-order primitives the walker recurses through transparently.
_TRANSPARENT = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "xla_call": "call_jaxpr",
    "remat": "jaxpr",
    "checkpoint": "jaxpr",
    "remat_call": "call_jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_jvp_call_jaxpr": "fun_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_vjp_call_jaxpr": "fun_jaxpr",
    "custom_lin": "call_jaxpr",
    # A shard_map region (e.g. a pre-sharded sma_gemm_sharded call baked
    # into the trace) is costed by its body; the defensive any-jaxpr-param
    # lookup below covers param-name drift across jax versions.
    "shard_map": "jaxpr",
}


@dataclasses.dataclass
class LowerStats:
    """Bookkeeping emitted alongside the lowered ops."""

    total_eqns: int = 0
    layout_ops_elided: int = 0
    coarsened_scans: int = 0      # scans amortized rather than unrolled
    unrolled_scans: int = 0
    unknown_prims: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LoweredProgram:
    """The symbolic program handed to :class:`repro.core.sma.SMAPolicy`."""

    ops: List[Op]
    stats: LowerStats

    @property
    def total_flops(self) -> float:
        return sum(op.flops for op in self.ops)

    @property
    def total_bytes(self) -> float:
        return sum(op.bytes_in + op.bytes_out for op in self.ops)

    @property
    def total_comm_bytes(self) -> float:
        return sum(op.comm_bytes for op in self.ops)


# --------------------------------------------------------------------------
# Aval helpers
# --------------------------------------------------------------------------
def _aval_bytes(aval) -> float:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0.0
    return float(size) * dtype.itemsize


def _in_bytes(eqn) -> float:
    return sum(_aval_bytes(v.aval) for v in eqn.invars)


def _out_bytes(eqn) -> float:
    return sum(_aval_bytes(v.aval) for v in eqn.outvars)


def _out_size(eqn) -> float:
    return float(sum(getattr(v.aval, "size", 0) for v in eqn.outvars))


def _prod(xs) -> float:
    out = 1.0
    for x in xs:
        out *= x
    return out


# --------------------------------------------------------------------------
# Per-primitive cost rules
# --------------------------------------------------------------------------
def dot_general_cost(eqn) -> tuple[OpKind, float]:
    """(kind, flops) for a dot_general from its dimension numbers."""
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = _prod(lhs.shape[i] for i in lhs_b)
    k = _prod(lhs.shape[i] for i in lhs_c)
    m = _prod(d for i, d in enumerate(lhs.shape)
              if i not in lhs_b and i not in lhs_c)
    n = _prod(d for i, d in enumerate(rhs.shape)
              if i not in rhs_b and i not in rhs_c)
    kind = OpKind.ATTENTION_MATMUL if lhs_b else OpKind.MATMUL
    return kind, 2.0 * batch * m * n * k


def _conv_cost(eqn) -> float:
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_feature_dim = dn.rhs_spec[0]
    out_features = rhs.shape[out_feature_dim]
    k = rhs.size / max(out_features, 1)  # in_features * prod(window)
    return 2.0 * _out_size(eqn) * k


def _is_trailing_axis_only(axes, ndim: int) -> bool:
    return tuple(axes) == (ndim - 1,)


class _Lowerer:
    def __init__(self, max_scan_unroll: int,
                 comm_coster: Optional[CommCoster] = None) -> None:
        self.max_scan_unroll = max_scan_unroll
        self.comm_coster = comm_coster
        self.ops: List[Op] = []
        self.stats = LowerStats()
        self._seq = 0

    # -------------------------------------------------------------- emit
    def emit(self, name: str, kind: OpKind, *, flops: float,
             bytes_in: float, bytes_out: float, tile_local: bool,
             mult: float, comm_bytes: float = 0.0) -> None:
        self._seq += 1
        self.ops.append(Op(f"{name}#{self._seq}", kind,
                           flops=flops * mult,
                           bytes_in=bytes_in * mult,
                           bytes_out=bytes_out * mult,
                           tile_local=tile_local,
                           comm_bytes=comm_bytes * mult))

    # -------------------------------------------------------------- walk
    def walk(self, jaxpr: core.Jaxpr, path: str = "", mult: float = 1.0
             ) -> None:
        for eqn in jaxpr.eqns:
            self.stats.total_eqns += 1
            self.lower_eqn(eqn, path, mult)

    def lower_eqn(self, eqn, path: str, mult: float) -> None:
        prim = eqn.primitive.name
        name = f"{path}{prim}"

        if prim in LAYOUT_PRIMS:
            self.stats.layout_ops_elided += 1
            return

        if prim in _TRANSPARENT:
            inner = eqn.params.get(_TRANSPARENT[prim])
            if inner is None:  # defensive: unfamiliar call-like primitive
                inner = next(iter(
                    v for v in eqn.params.values()
                    if isinstance(v, (core.Jaxpr, core.ClosedJaxpr))), None)
            if inner is not None:
                sub = inner.jaxpr if isinstance(inner, core.ClosedJaxpr) \
                    else inner
                self.walk(sub, path, mult)
            return

        if prim == "scan":
            self._lower_scan(eqn, path, mult)
            return
        if prim == "while":
            self._lower_while(eqn, path, mult)
            return
        if prim == "cond":
            self._lower_cond(eqn, path, mult)
            return

        bin_, bout = _in_bytes(eqn), _out_bytes(eqn)

        if prim in ("dot_general",):
            kind, flops = dot_general_cost(eqn)
            comm = 0.0
            if self.comm_coster is not None and sma_eligible(eqn):
                lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
                m = int(_prod(lhs.shape[:-1])) if lhs.ndim > 1 else 1
                comm = self.comm_coster(m, int(rhs.shape[1]),
                                        int(rhs.shape[0]),
                                        lhs.dtype.itemsize,
                                        rhs.dtype.itemsize)
            self.emit(name, kind, flops=flops, bytes_in=bin_,
                      bytes_out=bout, tile_local=True, mult=mult,
                      comm_bytes=comm)
        elif prim == "conv_general_dilated":
            self.emit(name, OpKind.MATMUL, flops=_conv_cost(eqn),
                      bytes_in=bin_, bytes_out=bout, tile_local=True,
                      mult=mult)
        elif prim in REDUCE_PRIMS:
            operand = eqn.invars[0].aval
            axes = eqn.params.get("axes", ())
            local = _is_trailing_axis_only(axes, operand.ndim)
            self.emit(name, OpKind.REDUCTION,
                      flops=float(operand.size), bytes_in=bin_,
                      bytes_out=bout, tile_local=local, mult=mult)
        elif prim in CUMULATIVE_PRIMS:
            operand = eqn.invars[0].aval
            local = eqn.params.get("axis", -1) == operand.ndim - 1
            self.emit(name, OpKind.REDUCTION,
                      flops=float(operand.size), bytes_in=bin_,
                      bytes_out=bout, tile_local=local, mult=mult)
        elif prim in GATHER_PRIMS:
            self.emit(name, OpKind.GATHER_SCATTER, flops=0.0,
                      bytes_in=bin_, bytes_out=bout, tile_local=False,
                      mult=mult)
        elif prim in TOPK_PRIMS:
            n = float(max(getattr(eqn.invars[0].aval, "size", 2), 2))
            self.emit(name, OpKind.TOPK, flops=n * math.log2(n),
                      bytes_in=bin_, bytes_out=bout, tile_local=False,
                      mult=mult)
        elif prim in CAST_PRIMS:
            self.emit(name, OpKind.CAST, flops=0.0, bytes_in=bin_,
                      bytes_out=bout, tile_local=True, mult=mult)
        else:
            if prim not in TRANSCENDENTAL_PRIMS and not _is_known_ew(prim):
                self.stats.unknown_prims[prim] = \
                    self.stats.unknown_prims.get(prim, 0) + 1
            weight = _TRANSCENDENTAL_FLOPS \
                if prim in TRANSCENDENTAL_PRIMS else 1.0
            self.emit(name, OpKind.ELEMENTWISE,
                      flops=weight * _out_size(eqn), bytes_in=bin_,
                      bytes_out=bout, tile_local=True, mult=mult)

    # ------------------------------------------------------ control flow
    def _lower_scan(self, eqn, path: str, mult: float) -> None:
        body = eqn.params["jaxpr"].jaxpr
        length = int(eqn.params.get("length", 1))
        num_carry = int(eqn.params.get("num_carry", 0))
        num_consts = int(eqn.params.get("num_consts", 0))
        if length <= self.max_scan_unroll:
            self.stats.unrolled_scans += 1
            for i in range(length):
                self.walk(body, f"{path}scan[{i}]/", mult)
            return
        # Amortized steady state: body once × length, behind a carry marker
        # (the loop-carried dependence is serial — SIMD mode, fusion break).
        self.stats.coarsened_scans += 1
        carry_avals = [v.aval for v in
                       eqn.invars[num_consts:num_consts + num_carry]]
        carry_elems = sum(float(getattr(a, "size", 0)) for a in carry_avals)
        carry_bytes = sum(_aval_bytes(a) for a in carry_avals)
        self.emit(f"{path}scan_carry(len={length})", OpKind.RECURRENCE,
                  flops=carry_elems * length, bytes_in=carry_bytes,
                  bytes_out=carry_bytes, tile_local=False, mult=mult)
        self.walk(body, f"{path}scan(x{length})/", mult * length)

    def _lower_while(self, eqn, path: str, mult: float) -> None:
        body = eqn.params["body_jaxpr"].jaxpr
        n_cc = int(eqn.params.get("cond_nconsts", 0))
        n_bc = int(eqn.params.get("body_nconsts", 0))
        carry_avals = [v.aval for v in eqn.invars[n_cc + n_bc:]]
        carry_bytes = sum(_aval_bytes(a) for a in carry_avals)
        self.emit(f"{path}while_carry", OpKind.RECURRENCE,
                  flops=sum(float(getattr(a, "size", 0))
                            for a in carry_avals),
                  bytes_in=carry_bytes, bytes_out=carry_bytes,
                  tile_local=False, mult=mult)
        self.walk(body, f"{path}while/", mult)

    def _lower_cond(self, eqn, path: str, mult: float) -> None:
        best_ops: List[Op] = []
        best_stats = LowerStats()
        best_flops = -1.0
        for i, branch in enumerate(eqn.params["branches"]):
            probe = _Lowerer(self.max_scan_unroll, self.comm_coster)
            probe.walk(branch.jaxpr, f"{path}cond[{i}]/", mult)
            flops = sum(op.flops for op in probe.ops)
            if flops > best_flops:
                best_flops, best_ops, best_stats = flops, probe.ops, \
                    probe.stats
        self.ops.extend(best_ops)
        self.stats.layout_ops_elided += best_stats.layout_ops_elided
        self.stats.total_eqns += best_stats.total_eqns
        self.stats.coarsened_scans += best_stats.coarsened_scans
        self.stats.unrolled_scans += best_stats.unrolled_scans
        for k, v in best_stats.unknown_prims.items():
            self.stats.unknown_prims[k] = \
                self.stats.unknown_prims.get(k, 0) + v


#: Elementwise primitives we positively recognize (suppresses the
#: unknown-prim stat for the common arithmetic/logic set).
_KNOWN_EW = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs", "sign",
    "floor", "ceil", "round", "clamp", "select_n", "select", "square",
    "integer_pow", "is_finite", "not", "and", "or", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "eq", "ne", "lt",
    "le", "gt", "ge", "nextafter", "real", "imag", "conj", "population_count",
    "clz", "add_any", "random_seed", "random_bits", "random_fold_in",
    "random_wrap", "random_unwrap", "threefry2x32",
})


def _is_known_ew(prim: str) -> bool:
    return prim in _KNOWN_EW


def lower_jaxpr(closed_jaxpr: core.ClosedJaxpr, *,
                max_scan_unroll: int = 8,
                comm_coster: Optional[CommCoster] = None) -> LoweredProgram:
    """Lower a closed jaxpr to the symbolic :class:`Op` program.

    ``comm_coster`` (built from the engine's mesh by
    :func:`repro.distributed.summa.comm_coster_for`) prices collective
    bytes onto every LSMA-eligible GEMM op, so mesh-aware plans carry comm
    traffic alongside HBM bytes.
    """
    lw = _Lowerer(max_scan_unroll, comm_coster)
    lw.walk(closed_jaxpr.jaxpr)
    return LoweredProgram(ops=lw.ops, stats=lw.stats)
