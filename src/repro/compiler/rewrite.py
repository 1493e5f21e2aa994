"""Stage 3.5 — rewrite: realize the planned fusion in the executable program.

:mod:`repro.compiler.fuse` *plans* tile-local SIMD epilogues onto SYSTOLIC
anchors and reports the HBM round-trips that fusion avoids.  This pass makes
the dispatcher actually *execute* those plans: it pattern-matches fusable
chains in the traced jaxpr and replaces each chain with a single
:class:`FusedGemm` pseudo-equation that the dispatcher routes to the fused
kernel entry points (``kernels.ops.sma_gemm(bias=…, epilogue=…)`` /
``kernels.ops.rmsnorm_gemm``).

Matched patterns (all anchored on an LSMA-eligible ``dot_general`` —
see :func:`repro.compiler.dispatch.sma_eligible`):

* **epilogue chains** — ``dot → add(broadcast 1-D bias)`` and/or a named
  activation consumer: ``tanh``, ``relu`` (``max(x, 0)``, also behind
  jax.nn's ``custom_jvp_call``/``jit`` wrappers), ``silu``
  (``x * logistic(x)``, inline or ``jit[silu]``), and the tanh-approximated
  ``gelu`` 8-equation inline chain;
* **prologue chains** — ``rmsnorm(x; scale) → dot`` (the ``square →
  reduce_sum → div → add eps → rsqrt → mul → mul scale`` chain, with
  optional dtype round-trip casts), optionally continued by an activation
  epilogue.

Conservative fallbacks (recorded per reason in :class:`RewriteStats`):

* an intermediate with **multiple consumers** never fuses (the value is
  needed bare, so eliding it would change the program);
* a value that **escapes its jaxpr** (e.g. a scan-body output crossing the
  loop boundary) never fuses across that boundary — matching is strictly
  per-jaxpr, so chains split by ``scan``/``while``/``cond`` fall back by
  construction;
* dtypes outside the kernels' fusable set (f16/bf16/f32) fall back.

``scan`` bodies are rewritten recursively (sites inside a length-L scan
count their avoided bytes L times — same amortization as the lowerer), so
GEMM chains inside layer-group scans fuse per iteration.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple, Union

import jax.numpy as jnp
from jax.extend import core

#: dtypes the fused kernels accept for A/B (the MXU-native set).
FUSABLE_DTYPES = frozenset({"float16", "bfloat16", "float32"})

#: higher-order primitives whose bodies the dispatcher interprets (and this
#: pass therefore rewrites).  Mirrors ``dispatch._Interpreter``.
_BODY_PARAMS: Dict[str, Tuple[str, ...]] = {
    "jit": ("jaxpr",),
    "closed_call": ("call_jaxpr",),
    "core_call": ("call_jaxpr",),
    "xla_call": ("call_jaxpr",),
    "remat": ("jaxpr",),
    "checkpoint": ("jaxpr",),
    "custom_jvp_call": ("call_jaxpr",),
    "custom_vjp_call": ("call_jaxpr",),
    "custom_jvp_call_jaxpr": ("fun_jaxpr",),
    "custom_vjp_call_jaxpr": ("fun_jaxpr",),
    "scan": ("jaxpr",),
    "while": ("cond_jaxpr", "body_jaxpr"),
    "cond": ("branches",),
}


# --------------------------------------------------------------------------
# The rewritten-program artifacts
# --------------------------------------------------------------------------
@dataclasses.dataclass
class FusedGemm:
    """A pseudo-equation standing in for a matched chain of jaxpr equations.

    ``kind == "epilogue"``: ``invars = (a, b[, bias])`` executes
    ``sma_gemm(a, b, bias=…, epilogue=…)``.
    ``kind == "prologue"``: ``invars = (x, scale, w)`` executes
    ``rmsnorm_gemm(x, scale, w, epilogue=…, eps=…)``.
    """

    kind: str
    invars: Tuple[Any, ...]        # jaxpr atoms (Var or Literal)
    outvar: Any                    # the final Var of the replaced chain
    out_aval: Any
    epilogue: str = "none"
    has_bias: bool = False
    eps: float = 1e-6
    precision: Any = None
    preferred_element_type: Any = None
    eqns_elided: int = 0
    hbm_bytes_avoided: float = 0.0
    site: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RewriteStats:
    """Realized-fusion accounting, aggregated over the whole program tree."""

    realized_fused_sites: int = 0
    realized_epilogue_sites: int = 0
    realized_prologue_sites: int = 0
    realized_hbm_bytes_avoided: float = 0.0
    eqns_elided: int = 0
    fallback_reasons: Dict[str, int] = dataclasses.field(default_factory=dict)
    sites: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def fallback(self, reason: str) -> None:
        self.fallback_reasons[reason] = \
            self.fallback_reasons.get(reason, 0) + 1

    def asdict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


RewriteItem = Union[core.JaxprEqn, FusedGemm]


@dataclasses.dataclass
class RewrittenJaxpr:
    """One jaxpr's equation stream with fused chains collapsed."""

    jaxpr: core.Jaxpr
    items: List[RewriteItem]
    fused_sites: int


@dataclasses.dataclass
class RewriteResult:
    """The rewritten program tree: every (nested) jaxpr the dispatcher will
    interpret, keyed by identity."""

    root: RewrittenJaxpr
    programs: Dict[int, RewrittenJaxpr]
    stats: RewriteStats

    def items_for(self, jaxpr: core.Jaxpr) -> Sequence[RewriteItem]:
        prog = self.programs.get(id(jaxpr))
        return prog.items if prog is not None else jaxpr.eqns

    def all_items(self):
        for prog in self.programs.values():
            yield from prog.items


# --------------------------------------------------------------------------
# Matching helpers
# --------------------------------------------------------------------------
def _is_var(atom) -> bool:
    return isinstance(atom, core.Var)


def _literal_value(atom):
    return atom.val if isinstance(atom, core.Literal) else None


def _is_literal_close(atom, value: float, tol: float = 1e-2) -> bool:
    val = _literal_value(atom)
    if val is None or getattr(val, "ndim", 0) != 0:
        return False
    try:
        return abs(float(val) - value) <= tol * max(abs(value), 1.0)
    except (TypeError, ValueError):
        return False


def _aval_bytes(aval) -> float:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0.0
    return float(size) * dtype.itemsize


class _JaxprIndex:
    """Use counts + producer/consumer maps for one jaxpr's equations."""

    def __init__(self, jaxpr: core.Jaxpr) -> None:
        self.jaxpr = jaxpr
        self.uses: Dict[core.Var, int] = {}
        self.consumers: Dict[core.Var, List[int]] = {}
        self.producer: Dict[core.Var, int] = {}
        self.escapes: Set[core.Var] = set()
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if _is_var(v):
                    self.uses[v] = self.uses.get(v, 0) + 1
                    self.consumers.setdefault(v, []).append(i)
            for v in eqn.outvars:
                if _is_var(v):
                    self.producer[v] = i
        for v in jaxpr.outvars:
            if _is_var(v):
                self.uses[v] = self.uses.get(v, 0) + 1
                self.escapes.add(v)

    def sole_consumer(self, v) -> Optional[int]:
        """Equation index of the only consumer, or None if shared/escaping."""
        if self.uses.get(v, 0) != 1 or v in self.escapes:
            return None
        return self.consumers[v][0]

    def eqn(self, i: int) -> core.JaxprEqn:
        return self.jaxpr.eqns[i]


def _resolve_wrapper_body(jaxpr: core.Jaxpr, args: List[Any],
                          depth: int = 0):
    """Flatten call-like wrappers to primitive ops with variables resolved
    through every nesting level.

    Returns ``(ops, outs)`` where ``ops`` is ``[(prim, resolved_invars,
    eqn), …]`` and ``outs`` the resolved output atoms — inner jaxpr
    variables are substituted by the atoms bound at the outermost call, so
    operand *identity* survives the flattening.  Returns None for anything
    unexpectedly deep/structured — matching then just declines.
    """
    if depth > 4:
        return None
    env: Dict[core.Var, Any] = dict(zip(jaxpr.invars, args))

    def resolve(atom):
        return env.get(atom, atom) if isinstance(atom, core.Var) else atom

    ops: List[Tuple[str, List[Any], core.JaxprEqn]] = []
    for eqn in jaxpr.eqns:
        keys = _BODY_PARAMS.get(eqn.primitive.name)
        if keys and eqn.primitive.name not in ("scan", "while", "cond"):
            inner = eqn.params.get(keys[0])
            if inner is None:
                return None
            if isinstance(inner, core.ClosedJaxpr):
                if inner.consts:
                    return None  # closed-over arrays: not a pure f(x)
                sub = inner.jaxpr
            else:
                sub = inner
            got = _resolve_wrapper_body(sub, [resolve(v) for v in eqn.invars],
                                        depth + 1)
            if got is None:
                return None
            inner_ops, inner_outs = got
            ops.extend(inner_ops)
            for ov, val in zip(eqn.outvars, inner_outs):
                env[ov] = val
        else:
            ops.append((eqn.primitive.name,
                        [resolve(v) for v in eqn.invars], eqn))
    return ops, [resolve(v) for v in jaxpr.outvars]


def _wrapper_activation(eqn: core.JaxprEqn) -> Optional[str]:
    """Match a single-input call-like equation that computes a named
    activation *of its input* (jax.nn.relu's custom_jvp, jit[silu], …).

    Operand identity is checked through the wrapper nesting: ``mul(x,
    logistic(x))`` is silu, ``mul(0.5, logistic(x))`` is not.
    """
    keys = _BODY_PARAMS.get(eqn.primitive.name)
    if not keys or eqn.primitive.name in ("scan", "while", "cond"):
        return None
    if len(eqn.invars) != 1 or len(eqn.outvars) != 1:
        return None
    inner = eqn.params.get(keys[0])
    if inner is None:
        return None
    if isinstance(inner, core.ClosedJaxpr) and inner.consts:
        return None
    sub = inner.jaxpr if isinstance(inner, core.ClosedJaxpr) else inner
    if len(sub.invars) != 1:
        return None
    x = object()  # sentinel for "the wrapper's input"
    got = _resolve_wrapper_body(sub, [x])
    if got is None:
        return None
    ops, outs = got
    if len(outs) != 1 or not ops:
        return None
    prims = [p for p, _, _ in ops]
    last_eqn = ops[-1][2]
    if outs[0] is not last_eqn.outvars[0]:
        return None  # wrapper returns something other than the chain result
    if prims == ["max"]:
        ins = ops[0][1]
        if any(v is x for v in ins) \
                and any(_is_literal_close(v, 0.0, tol=0.0) for v in ins):
            return "relu"
        return None
    if prims == ["tanh"]:
        return "tanh" if ops[0][1][0] is x else None
    if prims == ["logistic", "mul"]:
        (_, log_ins, log_eqn), (_, mul_ins, _) = ops
        if (len(log_ins) == 1 and log_ins[0] is x and len(mul_ins) == 2
                and any(v is x for v in mul_ins)
                and any(v is log_eqn.outvars[0] for v in mul_ins)):
            return "silu"
        return None
    return None


def _match_activation(f: core.Var, index: _JaxprIndex
                      ) -> Optional[Tuple[str, core.Var, List[int]]]:
    """Match a named activation applied to ``f``.

    Returns ``(epilogue_name, final_outvar, consumed_eqn_indices)`` or None.
    Handles single-consumer forms (tanh / max(x,0) / wrapped relu/silu) and
    the multi-consumer inline forms of silu (2 eqns) and tanh-gelu (8 eqns).
    """
    uses = index.uses.get(f, 0)
    if f in index.escapes:
        return None

    if uses == 1:
        i = index.consumers[f][0]
        eqn = index.eqn(i)
        prim = eqn.primitive.name
        if prim == "tanh":
            return "tanh", eqn.outvars[0], [i]
        if prim == "max" and any(_is_literal_close(v, 0.0, tol=0.0)
                                 for v in eqn.invars):
            return "relu", eqn.outvars[0], [i]
        wrapped = _wrapper_activation(eqn)
        if wrapped is not None:
            return wrapped, eqn.outvars[0], [i]
        return None

    if uses == 2:
        # inline silu: l = logistic(f); out = mul(f, l)
        idxs = index.consumers[f]
        logi = [i for i in idxs if index.eqn(i).primitive.name == "logistic"]
        muls = [i for i in idxs if index.eqn(i).primitive.name == "mul"]
        if len(logi) == 1 and len(muls) == 1:
            l_out = index.eqn(logi[0]).outvars[0]
            mul_eqn = index.eqn(muls[0])
            mul_ins = [v for v in mul_eqn.invars if _is_var(v)]
            if (index.sole_consumer(l_out) == muls[0]
                    and set(mul_ins) == {f, l_out}):
                return "silu", mul_eqn.outvars[0], [logi[0], muls[0]]
        return None

    if uses == 3:
        return _match_gelu(f, index)
    return None


def _match_gelu(f: core.Var, index: _JaxprIndex
                ) -> Optional[Tuple[str, core.Var, List[int]]]:
    """Match jax.nn.gelu(approximate=True)'s inline chain:

    g = f**3; h = 0.044715*g; i = f+h; j = 0.79788*i; k = tanh(j);
    l = 1+k; m = 0.5*l; out = f*m
    """
    def _sole_chain(v, want_prim):
        i = index.sole_consumer(v)
        if i is None:
            return None
        eqn = index.eqn(i)
        if eqn.primitive.name != want_prim:
            return None
        return i, eqn

    cubes = [i for i in index.consumers[f]
             if index.eqn(i).primitive.name == "integer_pow"
             and index.eqn(i).params.get("y") == 3]
    if len(cubes) != 1:
        return None
    consumed = [cubes[0]]
    g = index.eqn(cubes[0]).outvars[0]

    step = _sole_chain(g, "mul")                        # h = c1 * g
    if step is None or not any(
            _is_literal_close(v, 0.044715) for v in step[1].invars):
        return None
    consumed.append(step[0])
    h = step[1].outvars[0]

    step = _sole_chain(h, "add")                        # i = f + h
    if step is None or f not in step[1].invars:
        return None
    consumed.append(step[0])
    i_var = step[1].outvars[0]

    step = _sole_chain(i_var, "mul")                    # j = c2 * i
    if step is None or not any(
            _is_literal_close(v, math.sqrt(2.0 / math.pi))
            for v in step[1].invars):
        return None
    consumed.append(step[0])
    j = step[1].outvars[0]

    step = _sole_chain(j, "tanh")                       # k = tanh(j)
    if step is None:
        return None
    consumed.append(step[0])
    k = step[1].outvars[0]

    step = _sole_chain(k, "add")                        # l = 1 + k
    if step is None or not any(
            _is_literal_close(v, 1.0, tol=0.0) for v in step[1].invars):
        return None
    consumed.append(step[0])
    l = step[1].outvars[0]

    step = _sole_chain(l, "mul")                        # m = 0.5 * l
    if step is None or not any(
            _is_literal_close(v, 0.5, tol=0.0) for v in step[1].invars):
        return None
    consumed.append(step[0])
    m = step[1].outvars[0]

    step = _sole_chain(m, "mul")                        # out = f * m
    if step is None or f not in step[1].invars:
        return None
    consumed.append(step[0])
    return "gelu", step[1].outvars[0], consumed


def _match_bias_add(y: core.Var, index: _JaxprIndex
                    ) -> Optional[Tuple[Any, core.Var, List[int]]]:
    """Match ``add(y, broadcast_in_dim(bias_1d))`` (either operand order).

    Returns ``(bias_atom, add_outvar, consumed_eqn_indices)``; the broadcast
    equation is consumed only when the add is its sole consumer.
    """
    i = index.sole_consumer(y)
    if i is None:
        return None
    eqn = index.eqn(i)
    if eqn.primitive.name != "add" or len(eqn.invars) != 2:
        return None
    others = [v for v in eqn.invars if v is not y]
    if len(others) != 1 or not _is_var(others[0]):
        return None
    bcast_var = others[0]
    p = index.producer.get(bcast_var)
    if p is None:
        return None
    bcast = index.eqn(p)
    if bcast.primitive.name != "broadcast_in_dim":
        return None
    bias = bcast.invars[0]
    out_ndim = eqn.outvars[0].aval.ndim
    if (getattr(bias.aval, "ndim", None) != 1
            or tuple(bcast.params.get("broadcast_dimensions", ())) !=
            (out_ndim - 1,)):
        return None
    consumed = [i]
    if index.sole_consumer(bcast_var) == i:
        consumed.append(p)
    return bias, eqn.outvars[0], consumed


def _match_rmsnorm_prologue(dot_eqn: core.JaxprEqn, index: _JaxprIndex
                            ) -> Optional[Tuple[Any, Any, float, List[int]]]:
    """Match the rmsnorm chain feeding the dot's LHS.

    Returns ``(x_atom, scale_atom, eps, consumed_eqn_indices)`` or None.
    Chain (with optional convert_element_type round trips)::

        x32 = convert?(x); sq = square(x32); s = reduce_sum(sq, last);
        sb = broadcast(s); mean = sb / K; ve = mean + eps; r = rsqrt(ve);
        xr = x32 * r; normed = xr * broadcast(scale); lhs = convert?(normed)
    """
    lhs = dot_eqn.invars[0]
    if not _is_var(lhs):
        return None
    consumed: List[int] = []

    def _producer_eqn(v, want_prim=None):
        if not _is_var(v):
            return None
        p = index.producer.get(v)
        if p is None:
            return None
        eqn = index.eqn(p)
        if want_prim is not None and eqn.primitive.name != want_prim:
            return None
        # every intermediate must feed this chain alone
        if index.sole_consumer(v) is None:
            return None
        return p, eqn

    step = _producer_eqn(lhs)
    if step is None:
        return None
    if step[1].primitive.name == "convert_element_type":
        consumed.append(step[0])
        normed = step[1].invars[0]
        step = _producer_eqn(normed, "mul")
    elif step[1].primitive.name != "mul":
        return None
    if step is None:
        return None
    consumed.append(step[0])
    mul2 = step[1]                      # normed = xr * broadcast(scale)

    # identify the broadcast(scale) operand by its producer
    scale = None
    xr = None
    for v in mul2.invars:
        p = index.producer.get(v) if _is_var(v) else None
        if p is not None \
                and index.eqn(p).primitive.name == "broadcast_in_dim" \
                and getattr(index.eqn(p).invars[0].aval, "ndim", None) == 1:
            scale_bcast, scale_p = v, p
            scale = index.eqn(p).invars[0]
        else:
            xr = v
    if scale is None or xr is None:
        return None
    if index.sole_consumer(scale_bcast) is not None:
        consumed.append(scale_p)

    step = _producer_eqn(xr, "mul")     # xr = x32 * r
    if step is None:
        return None
    consumed.append(step[0])
    xr_mul_idx = step[0]
    x32 = r = None
    for v in step[1].invars:
        if _is_var(v) and getattr(v.aval, "shape", (0,))[-1:] == (1,):
            r = v
        else:
            x32 = v
    if x32 is None or r is None:
        return None

    step = _producer_eqn(r, "rsqrt")
    if step is None:
        return None
    consumed.append(step[0])
    ve = step[1].invars[0]

    step = _producer_eqn(ve, "add")     # ve = mean + eps
    if step is None:
        return None
    consumed.append(step[0])
    eps_lits = [_literal_value(v) for v in step[1].invars
                if _literal_value(v) is not None]
    mean = next((v for v in step[1].invars if _is_var(v)), None)
    if len(eps_lits) != 1 or mean is None:
        return None
    eps = float(eps_lits[0])

    step = _producer_eqn(mean, "div")   # mean = sb / K
    if step is None:
        return None
    consumed.append(step[0])
    k_dim = x32.aval.shape[-1] if _is_var(x32) else None
    if k_dim is None or not _is_literal_close(step[1].invars[1],
                                              float(k_dim), tol=0.0):
        return None
    sb = step[1].invars[0]

    step = _producer_eqn(sb, "broadcast_in_dim")
    if step is None:
        return None
    consumed.append(step[0])
    s = step[1].invars[0]

    step = _producer_eqn(s, "reduce_sum")
    if step is None:
        return None
    if tuple(step[1].params.get("axes", ())) != (x32.aval.ndim - 1,):
        return None
    consumed.append(step[0])
    sq = step[1].invars[0]

    step = _producer_eqn(sq)
    if step is None:
        return None
    sq_idx, sq_eqn = step
    if sq_eqn.primitive.name == "square":
        pass
    elif (sq_eqn.primitive.name == "integer_pow"
          and sq_eqn.params.get("y") == 2):
        pass
    elif (sq_eqn.primitive.name == "mul"
          and sq_eqn.invars[0] is sq_eqn.invars[1]):
        pass
    else:
        return None
    consumed.append(sq_idx)
    if sq_eqn.invars[0] is not x32:
        return None

    # The chain may open with a single dtype up-cast feeding both the square
    # and the x*r product; the fused kernel re-derives it from the raw input,
    # so elide it when this chain is its only consumer.
    x = x32
    p = index.producer.get(x32) if _is_var(x32) else None
    if (p is not None
            and index.eqn(p).primitive.name == "convert_element_type"
            and x32 not in index.escapes
            and set(index.consumers.get(x32, ())) <= {sq_idx, xr_mul_idx}):
        consumed.append(p)
        x = index.eqn(p).invars[0]
    return x, scale, eps, consumed


# --------------------------------------------------------------------------
# The rewriter
# --------------------------------------------------------------------------
class _Rewriter:
    def __init__(self, stats: RewriteStats) -> None:
        self.stats = stats
        self.programs: Dict[int, RewrittenJaxpr] = {}

    def rewrite(self, jaxpr: core.Jaxpr, mult: float = 1.0) -> RewrittenJaxpr:
        cached = self.programs.get(id(jaxpr))
        if cached is not None:
            return cached
        from repro.compiler.dispatch import sma_eligible

        index = _JaxprIndex(jaxpr)
        consumed: Set[int] = set()
        fused_at: Dict[int, FusedGemm] = {}

        for i, eqn in enumerate(jaxpr.eqns):
            if i in consumed or eqn.primitive.name != "dot_general" \
                    or not sma_eligible(eqn):
                continue
            a, b = eqn.invars
            if (getattr(a.aval.dtype, "name", "") not in FUSABLE_DTYPES
                    or getattr(b.aval.dtype, "name", "") not in
                    FUSABLE_DTYPES):
                self.stats.fallback("unsupported_dtype")
                continue
            site = self._match_site(eqn, i, index, consumed, mult)
            if site is not None:
                # Emit at the LAST covered equation's position: every input
                # (including a bias whose producer sits between the dot and
                # the add) is live there, and the chain's final value was
                # not produced any earlier in the original program either.
                fused_at[max(site.site["consumed_eqns"])] = site
                consumed.update(site.site["consumed_eqns"])

        items: List[RewriteItem] = []
        for i, eqn in enumerate(jaxpr.eqns):
            if i in fused_at:
                items.append(fused_at[i])
                continue
            if i in consumed:
                continue
            items.append(eqn)
            self._recurse(eqn, mult)

        prog = RewrittenJaxpr(jaxpr=jaxpr, items=items,
                              fused_sites=len(fused_at))
        self.programs[id(jaxpr)] = prog
        return prog

    # ---------------------------------------------------------------- site
    def _match_site(self, dot_eqn, dot_idx: int, index: _JaxprIndex,
                    consumed: Set[int], mult: float) -> Optional[FusedGemm]:
        a, b = dot_eqn.invars
        y = dot_eqn.outvars[0]
        chain: List[int] = [dot_idx]
        saved_vars: List[Any] = []

        pet = dot_eqn.params.get("preferred_element_type")
        prologue = _match_rmsnorm_prologue(dot_eqn, index)
        if prologue is not None and pet is not None \
                and jnp.promote_types(pet, jnp.float32) != jnp.float32:
            # rmsnorm_gemm accumulates in f32, which subsumes any narrower
            # preference; honor a *wider* requested accumulator (x64 mode)
            # by leaving the chain bare.
            self.stats.fallback("prologue_accum_dtype")
            prologue = None
        if prologue is not None:
            x, scale, eps, pro_consumed = prologue
            if any(c in consumed for c in pro_consumed):
                prologue = None
            else:
                chain += pro_consumed
                # the normalized matrix never exists in HBM
                saved_vars.append(dot_eqn.invars[0])

        bias = None
        epilogue = "none"
        head = y
        if prologue is None:
            matched_bias = _match_bias_add(y, index)
            if matched_bias is not None:
                bias, head, bias_consumed = matched_bias
                chain += bias_consumed
                saved_vars.append(y)    # the bare GEMM output is elided

        matched_act = _match_activation(head, index)
        if matched_act is not None:
            epilogue, final_out, act_consumed = matched_act
            chain += act_consumed
            saved_vars.append(head)     # the pre-activation value is elided
        else:
            final_out = head

        if prologue is None and bias is None and epilogue == "none":
            # nothing fused — record why and leave the dot to bare dispatch
            if index.uses.get(y, 0) > 1:
                self.stats.fallback("multi_consumer")
            elif y in index.escapes:
                self.stats.fallback("escapes_jaxpr")
            else:
                self.stats.fallback("no_fusable_consumer")
            return None

        if any(c in consumed for c in chain):
            return None

        bytes_avoided = mult * sum(2.0 * _aval_bytes(v.aval)
                                   for v in saved_vars)
        lhs_shape = tuple(a.aval.shape)
        m = 1
        for d in lhs_shape[:-1]:
            m *= d
        site_info = {
            "kind": "prologue" if prologue is not None else "epilogue",
            "epilogue": epilogue,
            "bias": bias is not None,
            "m": m, "k": lhs_shape[-1], "n": b.aval.shape[1],
            "dtype": a.aval.dtype.name,
            "eqns_elided": len(chain) - 1,
            "hbm_bytes_avoided": bytes_avoided,
            "mult": mult,
            "consumed_eqns": sorted(chain),
        }

        if prologue is not None:
            x, scale, eps, _ = prologue
            fg = FusedGemm(kind="prologue", invars=(x, scale, b),
                           outvar=final_out, out_aval=final_out.aval,
                           epilogue=epilogue, eps=eps,
                           precision=dot_eqn.params.get("precision"),
                           preferred_element_type=dot_eqn.params.get(
                               "preferred_element_type"),
                           eqns_elided=len(chain) - 1,
                           hbm_bytes_avoided=bytes_avoided, site=site_info)
            self.stats.realized_prologue_sites += 1
        else:
            invars = (a, b, bias) if bias is not None else (a, b)
            fg = FusedGemm(kind="epilogue", invars=invars,
                           outvar=final_out, out_aval=final_out.aval,
                           epilogue=epilogue, has_bias=bias is not None,
                           precision=dot_eqn.params.get("precision"),
                           preferred_element_type=dot_eqn.params.get(
                               "preferred_element_type"),
                           eqns_elided=len(chain) - 1,
                           hbm_bytes_avoided=bytes_avoided, site=site_info)
            self.stats.realized_epilogue_sites += 1

        self.stats.realized_fused_sites += 1
        self.stats.realized_hbm_bytes_avoided += bytes_avoided
        self.stats.eqns_elided += len(chain) - 1
        self.stats.sites.append(
            {k: v for k, v in site_info.items() if k != "consumed_eqns"})
        return fg

    # ------------------------------------------------------------- recurse
    def _recurse(self, eqn: core.JaxprEqn, mult: float) -> None:
        keys = _BODY_PARAMS.get(eqn.primitive.name)
        if keys is None:
            return
        inner_mult = mult
        if eqn.primitive.name == "scan":
            inner_mult = mult * float(eqn.params.get("length", 1))
        for key in keys:
            val = eqn.params.get(key)
            if val is None:
                continue
            bodies = val if isinstance(val, (tuple, list)) else (val,)
            for body in bodies:
                sub = body.jaxpr if isinstance(body, core.ClosedJaxpr) \
                    else body
                if isinstance(sub, core.Jaxpr):
                    self.rewrite(sub, inner_mult)


def rewrite_program(jaxpr: core.Jaxpr) -> RewriteResult:
    """Rewrite a traced program (and every nested jaxpr the dispatcher will
    interpret) into fused-dispatch form."""
    stats = RewriteStats()
    rw = _Rewriter(stats)
    root = rw.rewrite(jaxpr)
    return RewriteResult(root=root, programs=rw.programs, stats=stats)
