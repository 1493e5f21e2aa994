"""Stage 4 — dispatch: execute the traced program, routing SYSTOLIC-anchored
GEMMs through the fused SMA kernel entry points.

The dispatcher is a plan-driven jaxpr interpreter: it walks the item stream
produced by the fusion-rewrite pass (:mod:`repro.compiler.rewrite`) — jaxpr
equations interleaved with :class:`~repro.compiler.rewrite.FusedGemm`
pseudo-equations.  Most equations re-bind their primitive unchanged; the
exceptions implement the SMA execution contract:

* every matched fusion chain — ``dot → bias-add → activation`` epilogues and
  ``rmsnorm → dot`` prologues — executes as ONE call to the fused entry
  points (:func:`repro.kernels.ops.sma_gemm` with ``bias=``/``epilogue=``,
  :func:`repro.kernels.ops.rmsnorm_gemm`), realizing the planner's
  temporal-mode fusion: the intermediate never round-trips HBM;
* every remaining ``dot_general`` of the LSMA-eligible shape — single
  contracting dimension, no batch dimensions, 2-D stationary operand — is
  executed bare through :func:`repro.kernels.ops.sma_gemm`, which dispatches
  per the framework backend contract (``pallas`` on TPU, ``interpret`` for
  kernel-logic tests on CPU, ``xla`` for dry-runs);
* batched contractions (attention q@k^T / p@v) and everything SIMD-mode
  re-bind natively — on TPU those are exactly the ops XLA places on the VPU;
* higher-order primitives (``scan``/``while``/``cond``/``jit``/custom-vjp
  wrappers) are re-built around recursively interpreted bodies, so GEMM
  chains *inside* layer-group scans fuse and dispatch too.

Because every handler is jax-traceable, the interpreted callable can itself
be ``jax.jit``-ed (``compile_model(..., jit=True)``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.extend import core
from jax.extend import source_info_util

from repro._deprecation import warn_deprecated
from repro.api.options import SMAOptions, options as options_context, \
    resolve_options
from repro.backends import base as _backends_base
from repro.backends import registry as _backends_registry
from repro.compiler.fuse import ModelPlan, plan_program
from repro.compiler.lower import lower_jaxpr, sma_eligible
from repro.compiler.report import backends_section, comm_section, \
    fusion_section, plan_report
from repro.compiler.rewrite import FusedGemm, RewriteResult, rewrite_program
from repro.compiler.trace import TracedModel, subjaxprs, trace_model
from repro.core.sma import SMAPolicy
from repro.obs import trace as _obs_trace


# Eligibility (which dot_generals take the systolic entry point) now lives
# in ``compiler.lower`` — one predicate shared by dispatch routing and the
# planner's mesh comm-costing — and is re-exported here for back-compat.


def count_dispatch_sites(jaxpr: core.Jaxpr) -> Dict[str, int]:
    """Static census of dot_general *code sites*: systolic vs native.

    Counts every site in the program text, including all ``cond`` branches
    (only one executes per call) — unlike the plan, which lowers just the
    most expensive branch.
    """
    counts = {"systolic_dispatch_sites": 0, "native_dot_sites": 0}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            key = ("systolic_dispatch_sites" if sma_eligible(eqn)
                   else "native_dot_sites")
            counts[key] += 1
        for sub in subjaxprs(eqn):
            inner = count_dispatch_sites(sub)
            for k in counts:
                counts[k] += inner[k]
    return counts


def collect_backend_sites(jaxpr: core.Jaxpr,
                          rewritten: Optional[RewriteResult],
                          options: SMAOptions) -> List[Dict[str, Any]]:
    """Static registry resolution for every GEMM site the dispatcher will
    execute — the compile-time mirror of the runtime's per-call
    ``select_backend``.

    Walks exactly the item stream the interpreter walks (FusedGemm
    pseudo-equations where the rewrite realized a fusion, bare
    ``sma_eligible`` dots elsewhere, recursively through every sub-jaxpr)
    and resolves each site from avals alone, so the report's ``backends``
    section records the same choices the runtime will make.
    """
    pref, interpret = options.backend, bool(options.interpret)

    def resolve(op: str, avals, **extras) -> None:
        site = _backends_base.OpSite.from_args(op, tuple(avals), **extras)
        _backends_registry.select_backend(site, pref, interpret)

    def walk(jx: core.Jaxpr) -> None:
        items = rewritten.items_for(jx) if rewritten is not None else jx.eqns
        for eqn in items:
            if isinstance(eqn, FusedGemm):
                if eqn.kind == "prologue":
                    resolve("rmsnorm_gemm", [v.aval for v in eqn.invars])
                else:
                    resolve("sma_gemm", [v.aval for v in eqn.invars[:2]])
                continue
            if eqn.primitive.name == "dot_general" and sma_eligible(eqn):
                resolve("sma_gemm", [v.aval for v in eqn.invars[:2]])
            for sub in subjaxprs(eqn):
                walk(sub)

    with _backends_registry.record_sites() as sites:
        walk(jaxpr)
    for record in sites:
        record["origin"] = "dispatch"
    return sites


def collect_comm_sites(jaxpr: core.Jaxpr,
                       rewritten: Optional[RewriteResult]
                       ) -> List[Dict[str, Any]]:
    """``(m, n, k, itemsizes)`` for every GEMM site that shards on a mesh.

    Walks the same item stream as :func:`collect_backend_sites` — FusedGemm
    pseudo-equations plus bare ``sma_eligible`` dots — which is by design
    the same site set :func:`repro.compiler.lower.sma_eligible` comm-costs
    in the lowered plan, so the report's ``comm`` section and the plan's
    per-op ``comm_bytes`` price identical traffic.  Each site walks once
    (cond branches and scan bodies included once, unmultiplied).
    """
    sites: List[Dict[str, Any]] = []

    def add(a_aval, b_aval) -> None:
        m = 1
        for d in a_aval.shape[:-1]:
            m *= int(d)
        sites.append({"m": m, "n": int(b_aval.shape[1]),
                      "k": int(b_aval.shape[0]),
                      "itemsize_a": a_aval.dtype.itemsize,
                      "itemsize_b": b_aval.dtype.itemsize})

    def walk(jx: core.Jaxpr) -> None:
        items = rewritten.items_for(jx) if rewritten is not None else jx.eqns
        for eqn in items:
            if isinstance(eqn, FusedGemm):
                if eqn.kind == "prologue":
                    # rmsnorm_gemm(x, scale, w): the underlying dot is x @ w.
                    add(eqn.invars[0].aval, eqn.invars[2].aval)
                else:
                    add(eqn.invars[0].aval, eqn.invars[1].aval)
                continue
            if eqn.primitive.name == "dot_general" and sma_eligible(eqn):
                add(eqn.invars[0].aval, eqn.invars[1].aval)
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return sites


# --------------------------------------------------------------------------
# The interpreter
# --------------------------------------------------------------------------
def _scope(source_info):
    """Bind under an equation's recorded name stack, as ``jax.core``'s own
    ``eval_jaxpr`` does: the model's ``jax.named_scope`` paths (and those
    of the kernel sites traced inside it) then survive re-execution and
    name the compiled program's ops."""
    return source_info_util.user_context(
        source_info.traceback,
        name_stack=source_info_util.current_name_stack()
        + source_info.name_stack)


class _Interpreter:
    def __init__(self, options: SMAOptions,
                 rewrite: Optional[RewriteResult] = None) -> None:
        self.options = options
        self.backend = options.backend
        self.interpret = bool(options.interpret)
        self.rewrite = rewrite

    # -------------------------------------------------------------- eval
    def eval_closed(self, closed: core.ClosedJaxpr, args) -> List[Any]:
        return self.eval(closed.jaxpr, closed.consts, args)

    def eval(self, jaxpr: core.Jaxpr, consts, args) -> List[Any]:
        env: Dict[Any, Any] = {}

        def read(v):
            return v.val if isinstance(v, core.Literal) else env[v]

        def write(v, val):
            env[v] = val

        for var, val in zip(jaxpr.constvars, consts):
            write(var, val)
        for var, val in zip(jaxpr.invars, args):
            write(var, val)

        # Mode-region tracking (profiling only): runs of natively-bound
        # equations between systolic dispatch sites are SIMD-mode work —
        # recording them as one span per run makes the runtime timeline
        # alternate exactly like the plan's temporal mode schedule, so the
        # report's measured mode-switch count is comparable to the static
        # ``summary.mode_switches``.  Walls are host/enqueue time (async
        # dispatch); the tracer's sync knob does not block mid-region.
        tracer = _obs_trace.current_tracer()
        region_start: Optional[float] = None
        region_eqns = 0

        def flush_region() -> None:
            nonlocal region_start, region_eqns
            if tracer is not None and region_start is not None:
                end = tracer.now_us()
                if end > region_start:
                    tracer.add_event("dispatch.simd_region", cat="dispatch",
                                     ts=region_start,
                                     dur=end - region_start, mode="simd",
                                     eqns=region_eqns)
            region_start, region_eqns = None, 0

        items = self.rewrite.items_for(jaxpr) if self.rewrite is not None \
            else jaxpr.eqns
        for eqn in items:
            if isinstance(eqn, FusedGemm):
                flush_region()
                with _scope(eqn.source_info):
                    write(eqn.outvar,
                          self._fused(eqn, [read(v) for v in eqn.invars]))
                continue
            invals = [read(v) for v in eqn.invars]
            prim = eqn.primitive.name
            systolic_site = prim == "dot_general" and sma_eligible(eqn)
            if tracer is not None:
                if systolic_site:
                    flush_region()
                elif region_start is None:
                    region_start = tracer.now_us()
            with _scope(eqn.source_info):
                outvals = self._bind(eqn, prim, systolic_site, invals)
            if tracer is not None and not systolic_site:
                region_eqns += 1
            for var, val in zip(eqn.outvars, outvals):
                write(var, val)
        flush_region()
        return [read(v) for v in jaxpr.outvars]

    def _bind(self, eqn, prim: str, systolic_site: bool, invals):
        """Execute one equation: systolic GEMM sites through the kernel
        entry point, higher-order primitives around interpreted bodies,
        everything else re-bound as traced."""
        if systolic_site:
            return [self._dot(eqn, invals)]
        if prim == "jit":
            return self.eval_closed(eqn.params["jaxpr"], invals)
        if prim in ("closed_call", "core_call", "xla_call"):
            return self.eval_closed(eqn.params["call_jaxpr"], invals)
        if prim in ("remat", "checkpoint"):
            return self.eval(eqn.params["jaxpr"], (), invals)
        if prim in ("custom_jvp_call", "custom_vjp_call"):
            return self._closed_or_open(eqn.params["call_jaxpr"], invals)
        if prim in ("custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"):
            return self._closed_or_open(eqn.params["fun_jaxpr"], invals)
        if prim == "scan":
            return self._scan(eqn, invals)
        if prim == "while":
            return self._while(eqn, invals)
        if prim == "cond":
            return self._cond(eqn, invals)
        out = eqn.primitive.bind(*invals, **eqn.params)
        return list(out) if eqn.primitive.multiple_results else [out]

    def _closed_or_open(self, jx, invals):
        if isinstance(jx, core.ClosedJaxpr):
            return self.eval_closed(jx, invals)
        return self.eval(jx, (), invals)

    # ---------------------------------------------------------- handlers
    def _gemm_knobs(self) -> Dict[str, Any]:
        """Kernel-facing knobs from the one options object (the single
        configuration path: options -> dispatch -> kernels).

        ``mesh=False`` (not ``None``) when the options carry no mesh: the
        explicit falsy value pins dispatcher GEMMs to the local path even if
        an ambient ``options(mesh=...)`` context is active at call time —
        the engine's resolved options are the whole truth for its sites.
        """
        o = self.options
        return dict(backend=self.backend, interpret=self.interpret,
                    autotune=bool(o.autotune), block_m=o.block_m,
                    block_n=o.block_n, block_k=o.block_k,
                    check_numerics=o.check_numerics,
                    mesh=o.mesh if o.mesh is not None else False)

    def _dot(self, eqn, invals):
        from repro.kernels import ops as kernel_ops
        a, b = invals
        # No preferred type -> accumulate in at least f32, but never narrow
        # f64 inputs (x64 mode) down to f32.
        accum = eqn.params.get("preferred_element_type") \
            or jnp.promote_types(a.dtype, jnp.float32)
        with _obs_trace.span("dispatch.sma_gemm", cat="dispatch",
                             lhs=list(a.shape), rhs=list(b.shape)):
            out = kernel_ops.sma_gemm(a, b,
                                      accum_dtype=jnp.dtype(accum),
                                      precision=eqn.params.get("precision")
                                      or self.options.precision,
                                      **self._gemm_knobs())
        out_aval = eqn.outvars[0].aval
        if out.dtype != out_aval.dtype:
            out = out.astype(out_aval.dtype)
        return out

    def _fused(self, fg: FusedGemm, invals):
        from repro.kernels import ops as kernel_ops
        knobs = self._gemm_knobs()
        with _obs_trace.span("dispatch.fused_gemm", cat="dispatch",
                             kind=fg.kind, epilogue=fg.epilogue):
            if fg.kind == "prologue":
                x, scale, w = invals
                knobs.pop("autotune")  # rmsnorm_gemm has no measured search
                knobs.pop("mesh")      # prologue fusion runs device-local
                out = kernel_ops.rmsnorm_gemm(x, scale, w,
                                              epilogue=fg.epilogue,
                                              eps=fg.eps,
                                              precision=fg.precision
                                              or self.options.precision,
                                              **knobs)
            else:
                a, b = invals[:2]
                bias = invals[2] if fg.has_bias else None
                accum = fg.preferred_element_type \
                    or jnp.promote_types(a.dtype, jnp.float32)
                out = kernel_ops.sma_gemm(a, b, bias=bias,
                                          epilogue=fg.epilogue,
                                          accum_dtype=jnp.dtype(accum),
                                          precision=fg.precision
                                          or self.options.precision,
                                          **knobs)
        if out.dtype != fg.out_aval.dtype:
            out = out.astype(fg.out_aval.dtype)
        return out

    def _scan(self, eqn, invals):
        p = eqn.params
        body = p["jaxpr"]
        nc, nk = p["num_consts"], p["num_carry"]
        consts = tuple(invals[:nc])
        init = tuple(invals[nc:nc + nk])
        xs = tuple(invals[nc + nk:])

        def body_fn(carry, x):
            outs = self.eval_closed(body, (*consts, *carry, *x))
            return tuple(outs[:nk]), tuple(outs[nk:])

        carry, ys = jax.lax.scan(body_fn, init, xs, length=p["length"],
                                 reverse=p["reverse"], unroll=p["unroll"])
        return [*carry, *ys]

    def _while(self, eqn, invals):
        p = eqn.params
        n_cc, n_bc = p["cond_nconsts"], p["body_nconsts"]
        cond_consts = tuple(invals[:n_cc])
        body_consts = tuple(invals[n_cc:n_cc + n_bc])
        init = tuple(invals[n_cc + n_bc:])

        def cond_fn(carry):
            return self.eval_closed(p["cond_jaxpr"],
                                    (*cond_consts, *carry))[0]

        def body_fn(carry):
            return tuple(self.eval_closed(p["body_jaxpr"],
                                          (*body_consts, *carry)))

        return list(jax.lax.while_loop(cond_fn, body_fn, init))

    def _cond(self, eqn, invals):
        index, *operands = invals
        branches = [functools.partial(
            lambda br, *a: tuple(self.eval_closed(br, a)), br)
            for br in eqn.params["branches"]]
        return list(jax.lax.switch(index, branches, *operands))


# --------------------------------------------------------------------------
# compile_with_options: the canonical pipeline (Engine calls this)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class CompiledModel:
    """Plan + executable for ONE abstract signature.

    Produced by :func:`compile_with_options` (via ``repro.sma_jit`` /
    ``Engine``, which caches one of these per signature).  Calling it with
    the same pytree structure as the example arguments runs the planned
    program with systolic groups dispatched to the SMA kernels.
    """

    traced: TracedModel
    plan: ModelPlan
    report_data: Dict[str, Any]
    _runner: Callable
    rewritten: Optional[RewriteResult] = None
    options: Optional[SMAOptions] = None
    #: The FULL backend-resolution record list (trace-time + static dispatch
    #: walk).  The report's ``backends`` section caps its ``sites`` list for
    #: readability; the static analyzer (:mod:`repro.analysis`) needs every
    #: record to reconcile predicted vs realized fallbacks, so the compiler
    #: stashes the uncapped list here.
    backend_records: Optional[List[Dict[str, Any]]] = None
    #: Installed by the owning :class:`repro.api.engine.Engine`: re-stamps
    #: the live report sections (``engine`` hit counters, measured
    #: ``runtime`` timeline) on every access, so a report read after N
    #: cache hits shows N, not the numbers frozen at compile time.
    report_refresh: Optional[Callable[[Dict[str, Any]], None]] = \
        dataclasses.field(default=None, repr=False, compare=False)
    #: Under ``jit``: the XLA executable for this signature, compiled with
    #: the rest of the pipeline (its ``memory_analysis()`` is the device
    #: footprint of one call).  ``None`` on the interpreted path.
    executable: Any = dataclasses.field(default=None, repr=False,
                                        compare=False)

    @property
    def report(self) -> Dict[str, Any]:
        """The plan report, with live sections refreshed on access — the
        one shared stamping path for ``Engine.compile()``, report reads,
        and obs snapshots."""
        if self.report_refresh is not None:
            self.report_refresh(self.report_data)
        return self.report_data

    @property
    def name(self) -> str:
        return self.traced.name

    @property
    def summary(self):
        return self.plan.summary

    @property
    def fused_sites(self) -> List[FusedGemm]:
        """Every realized fusion site across the program tree."""
        if self.rewritten is None:
            return []
        return [it for it in self.rewritten.all_items()
                if isinstance(it, FusedGemm)]

    def __call__(self, *args, **kwargs):
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        if in_tree != self.traced.in_tree:
            raise TypeError(
                f"compiled model '{self.name}' called with argument "
                f"structure {in_tree}; compiled for {self.traced.in_tree}")
        outs = self._runner(*flat)
        return jax.tree_util.tree_unflatten(self.traced.out_tree, outs)


def _flat_donate_indices(args, kwargs, donate_argnums) -> tuple:
    """Map user-level donated positional argnums to flattened leaf indices
    (the runner's calling convention).  Keyword arguments flatten after the
    positionals and are never donated."""
    donate = set(donate_argnums)
    idx, out = 0, []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate:
            out.extend(range(idx, idx + n))
        idx += n
    return tuple(out)


def compile_with_options(fn: Callable, *args, name: Optional[str] = None,
                         options: Optional[SMAOptions] = None,
                         **kwargs) -> CompiledModel:
    """Trace → lower → plan → rewrite → wrap a dispatching executable.

    The canonical compile pipeline: every configuration knob comes from ONE
    :class:`repro.api.options.SMAOptions` (explicit ``options`` overlaid on
    the ambient ``repro.options(...)`` context).  ``args``/``kwargs`` may be
    real arrays or ``jax.ShapeDtypeStruct`` placeholders; execution of the
    returned callable of course needs real arrays.

    Callers normally do not use this directly — ``repro.sma_jit`` wraps it
    with the shape-polymorphic compile cache.
    """
    o = resolve_options(options)
    # Mesh-aware compile: install the sharding-rule context for the trace
    # (so ``distributed.shard(x, ...)`` constraints in model code resolve
    # against the engine's mesh) and build the SUMMA comm coster that
    # prices collective bytes onto the lowered plan's GEMM ops.
    comm_coster = None
    rules_ctx = contextlib.nullcontext()
    if o.mesh is not None:
        from repro.distributed.sharding import MeshRules, use_rules
        from repro.distributed.summa import comm_coster_for
        comm_coster = comm_coster_for(o.mesh)
        rules_ctx = use_rules(o.mesh_rules or MeshRules(),
                              tuple(o.mesh.axis_names))
    # Record backend resolution for direct kernels.ops calls in model code
    # (flash/decode attention, rglru, mlstm, hand-written sma_gemm): their
    # ladders resolve while the model traces, and those choices are baked
    # into the trace.  The *resolved* options are pushed as the ambient
    # context for the trace, so engine/per-compile options govern those
    # trace-time calls exactly like the dispatcher's own GEMM sites — one
    # dispatch policy everywhere (explicit per-call kwargs win at trace
    # time; note that a GEMM entry point resolving to a jnp path lowers to
    # a bare dot_general, which the dispatcher — per its long-standing
    # contract — re-claims and re-resolves under the engine options at
    # runtime).
    with _backends_registry.record_sites() as traced_sites, \
            options_context(o), rules_ctx, \
            _obs_trace.span("compile.trace", cat="compile"):
        traced = trace_model(fn, *args, name=name, **kwargs)
    for record in traced_sites:
        record["origin"] = "traced"
    with _obs_trace.span("compile.lower", cat="compile"):
        program = lower_jaxpr(traced.closed_jaxpr,
                              max_scan_unroll=o.max_scan_unroll,
                              comm_coster=comm_coster)
    policy = o.policy if o.policy is not None else SMAPolicy(
        fuse_epilogues=bool(o.fuse_epilogues),
        max_epilogue_ops=o.max_epilogue_ops)
    with _obs_trace.span("compile.plan", cat="compile"):
        plan = plan_program(program, name=traced.name, policy=policy)
    with _obs_trace.span("compile.rewrite", cat="compile"):
        rewritten = rewrite_program(traced.jaxpr) if o.fuse_runtime \
            else None

    interp = _Interpreter(o, rewritten)

    def runner(*flat):
        return interp.eval_closed(traced.closed_jaxpr, flat)

    # jax.jit names the XLA module after the function: jit_<engine name>.
    runner.__name__ = re.sub(r"\W", "_", traced.name)

    executable = None
    if o.jit:
        donate = _flat_donate_indices(args, kwargs, o.donate_argnums) \
            if o.donate_argnums else ()
        runner = jax.jit(runner, donate_argnums=donate)
        # The XLA compile belongs to this signature's compile bill: done
        # here, the first call finds it in jax.jit's own cache.
        with _obs_trace.span("compile.xla", cat="compile"):
            executable = runner.lower(
                *jax.tree_util.tree_leaves((args, kwargs))).compile()

    report = plan_report(plan)
    report["options"] = o.asdict()
    report["dispatch"] = {
        "backend": list(o.backend) if isinstance(o.backend, tuple)
        else (o.backend or "auto"),
        "interpret": bool(o.interpret),
        **count_dispatch_sites(traced.jaxpr),
    }
    report["fusion"] = fusion_section(plan, rewritten)
    backend_records = traced_sites + collect_backend_sites(
        traced.jaxpr, rewritten, o)
    report["backends"] = backends_section(backend_records, o)
    report["comm"] = comm_section(
        o.mesh, collect_comm_sites(traced.jaxpr, rewritten),
        plan_comm_bytes=program.total_comm_bytes)
    from repro.resilience import guard as _resilience_guard
    report["resilience"] = _resilience_guard.resilience_section()
    compiled = CompiledModel(traced=traced, plan=plan, report_data=report,
                             _runner=runner, rewritten=rewritten, options=o,
                             backend_records=backend_records,
                             executable=executable)
    # Every compile runs the static analyzer and stamps the ``diagnostics``
    # report section (cheap: a few O(eqns) walks over structures already in
    # hand).  The ``verify`` policy only decides what error-severity
    # verifier findings do; raising happens *before* the engine caches the
    # artifact, so a broken plan never serves.
    from repro.analysis import PlanVerificationError, attach_diagnostics
    with _obs_trace.span("compile.analyze", cat="compile"):
        diags = attach_diagnostics(compiled)
    if (o.verify or "off") != "off":
        errors = [d for d in diags if d.severity == "error"]
        if errors:
            if o.verify == "error":
                raise PlanVerificationError(errors)
            warnings.warn(
                f"plan verification for '{compiled.name}' found "
                f"{len(errors)} error(s): "
                + "; ".join(d.render() for d in errors[:3]),
                stacklevel=2)
    return compiled


#: Sentinel distinguishing "kwarg omitted" (inherit from ambient options)
#: from an explicitly-passed falsy value (which must win over the context).
_UNSET: Any = object()


def compile_model(fn: Callable, *args, name: Optional[str] = None,
                  policy: Optional[SMAPolicy] = None,
                  backend: Optional[str] = None, interpret: Any = _UNSET,
                  max_scan_unroll: Any = _UNSET, jit: Any = _UNSET,
                  fuse_runtime: Any = _UNSET,
                  **kwargs) -> CompiledModel:
    """DEPRECATED single-signature front door (one release of back-compat).

    Use ``repro.sma_jit(fn, options=SMAOptions(...))`` instead — it compiles
    the same pipeline but caches executables per abstract signature, so
    repeated calls (serving!) skip trace/plan/rewrite.  This wrapper builds
    a one-shot :class:`repro.api.engine.Engine`, compiles the given example
    signature through it, and returns the cached :class:`CompiledModel`.
    """
    warn_deprecated(
        "compiler.compile_model is deprecated; use repro.sma_jit(fn, "
        "options=repro.SMAOptions(...)) — the engine caches compiled "
        "executables per abstract signature instead of re-tracing per call")
    from repro.api.engine import Engine
    legacy = SMAOptions(
        backend=backend,
        interpret=None if interpret is _UNSET else interpret,
        max_scan_unroll=None if max_scan_unroll is _UNSET
        else max_scan_unroll,
        jit=None if jit is _UNSET else jit,
        fuse_runtime=None if fuse_runtime is _UNSET else fuse_runtime,
        policy=policy,
    )
    engine = Engine(fn, options=legacy, name=name)
    return engine.compile(*args, **kwargs)
