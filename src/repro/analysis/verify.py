"""Static plan verifier: structural invariants of compiled SMA artifacts.

Every check here is an *internal consistency* proof over one
:class:`repro.compiler.dispatch.CompiledModel` — the traced jaxpr, the
rewrite pass's fused item stream, the symbolic plan, and the report the
compiler stamped from them.  A firing means the pipeline (or a hand-edited
report) is inconsistent with itself; correct compiles produce zero errors
on every config family, and CI's golden baseline pins that at zero.

Checks (one stable code each — see :mod:`repro.analysis.diagnostics`):

* ``SMAV01`` — dataflow: walking exactly the item stream the dispatcher
  interprets (``FusedGemm`` pseudo-equations included), every variable is
  defined before use, and every fused site's operand shapes/dtypes agree
  (``A@B`` contraction, bias width, fusable dtype set, output aval).
* ``SMAV02`` — execution modes: every planned op's kind maps to a legal
  :class:`~repro.core.modes.ExecMode`, the fusion groups partition the op
  sequence exactly, systolic groups anchor on a systolic op with only
  fusable tile-local SIMD epilogues attached, SIMD groups contain no
  systolic work.
* ``SMAV03`` — fused-site liveness: each ``FusedGemm`` stands in for
  equations of its own jaxpr, produces its chain's final variable, and
  consumes no variable produced inside the chain it elides.
* ``SMAV04`` — ledgers: the report's FLOP/byte/comm totals and every
  summary field reconcile exactly (float-tolerant) with the op-level sums
  and a recomputation through the plan's own policy.
* ``SMAV05`` — scan multipliers: every coarsened scan body op carries a
  matching ``scan_carry(len=L)`` recurrence marker, and the marker count
  equals ``stats.coarsened_scans``.
* ``SMAV06`` — fallback reconciliation: replaying ``Backend.supports``
  statically over every recorded op site predicts exactly the fallback the
  runtime realized (quarantine-induced fallbacks excluded — they are
  runtime state no static pass can see).
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Set

from jax.extend import core

from repro.analysis.diagnostics import Diagnostic, make
from repro.analysis.lints import predict_fallback
from repro.compiler.rewrite import FUSABLE_DTYPES, FusedGemm
from repro.compiler.trace import subjaxprs
from repro.core.modes import (
    FUSABLE_INTO_SYSTOLIC,
    MODE_OF,
    ExecMode,
)

__all__ = ["PlanVerificationError", "verify_compiled", "check_dataflow",
           "check_modes", "check_fused_liveness", "check_ledgers",
           "check_scan_multipliers", "check_fallback_reconciliation"]


class PlanVerificationError(Exception):
    """Raised at compile time under ``SMAOptions(verify="error")``."""

    def __init__(self, diagnostics: List[Diagnostic]) -> None:
        self.diagnostics = list(diagnostics)
        preview = "; ".join(d.render() for d in self.diagnostics[:3])
        more = len(self.diagnostics) - 3
        if more > 0:
            preview += f" (+{more} more)"
        super().__init__(
            f"plan verification failed with "
            f"{len(self.diagnostics)} error(s): {preview}")


def _isclose(a: float, b: float) -> bool:
    return math.isclose(float(a), float(b), rel_tol=1e-6, abs_tol=1e-6)


# --------------------------------------------------------------------------
# SMAV01 — dataflow over the dispatched item stream
# --------------------------------------------------------------------------
def _fused_shape_check(fg: FusedGemm, out: List[Diagnostic]) -> None:
    avals = [getattr(v, "aval", None) for v in fg.invars]
    if any(a is None for a in avals):
        out.append(make("SMAV01", f"fused {fg.kind} site has an operand "
                                  f"with no aval", {"kind": fg.kind}))
        return
    shapes = [tuple(a.shape) for a in avals]
    dtypes = [a.dtype.name for a in avals]
    site = {"kind": fg.kind, "shapes": [list(s) for s in shapes],
            "dtypes": dtypes}
    if fg.kind == "prologue":
        x, scale, w = shapes
        if scale != (x[-1],):
            out.append(make("SMAV01", f"rmsnorm scale shape {scale} != "
                                      f"({x[-1]},)", site))
        if x[-1] != w[0]:
            out.append(make("SMAV01", f"prologue contraction mismatch: "
                                      f"x {x} @ w {w}", site))
        expect = (*x[:-1], w[1])
    else:
        a, b = shapes[0], shapes[1]
        if a[-1] != b[0]:
            out.append(make("SMAV01", f"fused GEMM contraction mismatch: "
                                      f"{a} @ {b}", site))
        if fg.has_bias and shapes[2] != (b[1],):
            out.append(make("SMAV01", f"fused bias shape {shapes[2]} != "
                                      f"({b[1]},)", site))
        expect = (*a[:-1], b[1])
    got = tuple(fg.out_aval.shape)
    if got != expect:
        out.append(make("SMAV01", f"fused {fg.kind} output shape {got} != "
                                  f"expected {expect}", site))
    for dt in dtypes[:2]:
        if dt not in FUSABLE_DTYPES:
            out.append(make("SMAV01", f"fused {fg.kind} operand dtype "
                                      f"{dt} outside fusable set "
                                      f"{sorted(FUSABLE_DTYPES)}", site))


def check_dataflow(jaxpr: core.Jaxpr, rewritten: Any) -> List[Diagnostic]:
    """Def-before-use + fused-site shape/dtype agreement, over exactly the
    item stream the dispatcher interprets (recursively)."""
    out: List[Diagnostic] = []
    seen: Set[int] = set()

    def walk(jx: core.Jaxpr) -> None:
        if id(jx) in seen:
            return
        seen.add(id(jx))
        defined: Set[Any] = set(jx.constvars) | set(jx.invars)
        items = rewritten.items_for(jx) if rewritten is not None else jx.eqns

        def require(v: Any, what: str) -> None:
            if isinstance(v, core.Var) and v not in defined:
                out.append(make(
                    "SMAV01",
                    f"{what} reads undefined variable {v} "
                    f"(aval {getattr(v, 'aval', None)})"))

        for item in items:
            if isinstance(item, FusedGemm):
                for v in item.invars:
                    require(v, f"fused {item.kind} site")
                _fused_shape_check(item, out)
                defined.add(item.outvar)
                continue
            for v in item.invars:
                require(v, f"equation {item.primitive.name}")
            defined.update(item.outvars)
            for sub in subjaxprs(item):
                walk(sub)

        for v in jx.outvars:
            require(v, "jaxpr output")

    walk(jaxpr)
    return out


# --------------------------------------------------------------------------
# SMAV02 — legal execution modes + exact group partition
# --------------------------------------------------------------------------
def check_modes(plan: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for op in plan.ops:
        if op.kind not in MODE_OF:
            out.append(make("SMAV02", f"op {op.name} has kind {op.kind!r} "
                                      f"with no legal ExecMode",
                            {"op": op.name}))
    flat = [op for g in plan.groups for op in g.ops]
    if len(flat) != len(plan.ops) or any(
            a is not b for a, b in zip(flat, plan.ops)):
        out.append(make(
            "SMAV02",
            f"fusion groups do not partition the op sequence: "
            f"{len(flat)} grouped ops vs {len(plan.ops)} planned"))
    budget = getattr(plan.policy, "max_epilogue_ops", None)
    for i, g in enumerate(plan.groups):
        if not g.ops:
            out.append(make("SMAV02", f"group {i} is empty", {"group": i}))
            continue
        site = {"group": i, "anchor": g.ops[0].name}
        if g.mode == ExecMode.SYSTOLIC:
            if g.ops[0].mode != ExecMode.SYSTOLIC:
                out.append(make("SMAV02", f"systolic group {i} does not "
                                          f"open with its anchor "
                                          f"({g.ops[0].name})", site))
            for op in g.ops[1:]:
                if op.mode == ExecMode.SYSTOLIC:
                    out.append(make("SMAV02",
                                    f"group {i} holds a second systolic "
                                    f"op {op.name}", site))
                elif op.kind not in FUSABLE_INTO_SYSTOLIC \
                        or not op.tile_local:
                    out.append(make("SMAV02",
                                    f"group {i} fuses non-fusable SIMD op "
                                    f"{op.name} (kind {op.kind.value}, "
                                    f"tile_local={op.tile_local})", site))
            if budget is not None and g.fused_simd_ops > budget:
                out.append(make("SMAV02",
                                f"group {i} fuses {g.fused_simd_ops} SIMD "
                                f"ops, over the policy budget {budget}",
                                site))
        else:
            for op in g.ops:
                if op.mode == ExecMode.SYSTOLIC:
                    out.append(make("SMAV02",
                                    f"SIMD group {i} holds systolic op "
                                    f"{op.name}", site))
    return out


# --------------------------------------------------------------------------
# SMAV03 — fused sites reference live ops of their own jaxpr
# --------------------------------------------------------------------------
def check_fused_liveness(rewritten: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    if rewritten is None:
        return out
    for prog in rewritten.programs.values():
        eqns = prog.jaxpr.eqns
        for item in prog.items:
            if not isinstance(item, FusedGemm):
                continue
            consumed = item.site.get("consumed_eqns", [])
            site = {"kind": item.kind, "consumed_eqns": list(consumed)}
            if not consumed or any(not 0 <= c < len(eqns)
                                   for c in consumed):
                out.append(make("SMAV03",
                                f"fused {item.kind} site consumes "
                                f"equation indices {consumed} outside its "
                                f"jaxpr (0..{len(eqns) - 1})", site))
                continue
            produced = {v for c in consumed for v in eqns[c].outvars}
            if item.outvar not in produced:
                out.append(make("SMAV03",
                                f"fused {item.kind} site output "
                                f"{item.outvar} is not produced by its "
                                f"consumed chain", site))
            for v in item.invars:
                if isinstance(v, core.Var) and v in produced:
                    out.append(make("SMAV03",
                                    f"fused {item.kind} site reads {v}, "
                                    f"which its own chain elides", site))
    return out


# --------------------------------------------------------------------------
# SMAV04 — ledger reconciliation
# --------------------------------------------------------------------------
def check_ledgers(plan: Any, report: Dict[str, Any],
                  rewritten: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []

    def expect(field: str, got: Any, want: Any, *, close: bool = True
               ) -> None:
        ok = _isclose(got, want) if close else got == want
        if not ok:
            out.append(make("SMAV04",
                            f"{field} = {got} does not reconcile with "
                            f"recomputed {want}", {"field": field}))

    ops = plan.ops
    expect("num_ops", report.get("num_ops"), len(ops), close=False)
    expect("total_flops", report.get("total_flops", 0.0),
           sum(op.flops for op in ops))
    if "total_bytes" in report:
        expect("total_bytes", report["total_bytes"],
               sum(op.bytes_in + op.bytes_out for op in ops))
    expect("hbm_bytes_avoided", report.get("hbm_bytes_avoided", 0.0),
           sum(g.bytes_kept_in_vmem for g in plan.groups))

    recomputed = plan.policy.summarize(ops)
    expect("groups", report.get("groups"), recomputed.groups, close=False)
    expect("mode_switches", report.get("mode_switches"),
           recomputed.mode_switches, close=False)
    expect("fused_simd_ops", report.get("fused_simd_ops"),
           recomputed.fused_simd_ops, close=False)
    expect("systolic_flop_share", report.get("systolic_flop_share", 0.0),
           recomputed.systolic_flop_share)

    comm = report.get("comm")
    if comm is not None:
        expect("comm.plan_comm_bytes", comm.get("plan_comm_bytes", 0.0),
               sum(op.comm_bytes for op in ops))

    fusion = report.get("fusion")
    if fusion is not None and rewritten is not None:
        fused = [it for prog in rewritten.programs.values()
                 for it in prog.items if isinstance(it, FusedGemm)]
        expect("fusion.realized_fused_sites",
               fusion.get("realized_fused_sites"), len(fused), close=False)
        expect("fusion.realized_hbm_bytes_avoided",
               fusion.get("realized_hbm_bytes_avoided", 0.0),
               sum(fg.hbm_bytes_avoided for fg in fused))
    return out


# --------------------------------------------------------------------------
# SMAV05 — scan multiplier consistency
# --------------------------------------------------------------------------
_SCAN_BODY = re.compile(r"scan\(x(\d+)\)/")
_SCAN_CARRY = re.compile(r"scan_carry\(len=(\d+)\)")


def check_scan_multipliers(plan: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    carries: Set[Any] = set()
    carry_count = 0
    for op in plan.ops:
        m = _SCAN_CARRY.search(op.name)
        if m is not None:
            carry_count += 1
            carries.add((op.name[:m.start()], int(m.group(1))))
    for op in plan.ops:
        for m in _SCAN_BODY.finditer(op.name):
            key = (op.name[:m.start()], int(m.group(1)))
            if key not in carries:
                out.append(make("SMAV05",
                                f"op {op.name} is multiplied by a "
                                f"coarsened scan (x{m.group(1)}) with no "
                                f"matching scan_carry(len={m.group(1)}) "
                                f"marker at path {key[0]!r}",
                                {"op": op.name}))
    coarsened = getattr(plan.stats, "coarsened_scans", None)
    if coarsened is not None and carry_count != coarsened:
        out.append(make("SMAV05",
                        f"{carry_count} scan_carry markers vs "
                        f"stats.coarsened_scans={coarsened}"))
    return out


# --------------------------------------------------------------------------
# SMAV06 — predicted vs realized backend fallbacks
# --------------------------------------------------------------------------
def check_fallback_reconciliation(records: List[Dict[str, Any]]
                                  ) -> List[Diagnostic]:
    """Replay ``Backend.supports`` statically per recorded site and demand
    the prediction match what the runtime recorded.  Quarantine fallbacks
    are excluded: the denylist is runtime state, invisible statically."""
    out: List[Diagnostic] = []
    for r in records:
        realized: Optional[str] = r.get("fallback_reason")
        if realized is not None and realized.split(":", 1)[0] in (
                "quarantine", "runtime"):
            continue
        predicted = predict_fallback(r)
        if predicted != realized:
            out.append(make(
                "SMAV06",
                f"site {r.get('op')}{r.get('shapes')}: statically "
                f"predicted fallback {predicted!r} but runtime recorded "
                f"{realized!r}",
                {"op": r.get("op"), "shapes": r.get("shapes"),
                 "predicted": predicted, "realized": realized}))
    return out


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------
def verify_compiled(compiled: Any) -> List[Diagnostic]:
    """All verifier checks over one ``CompiledModel``; ``error`` diagnostics
    only (empty list == the artifact is internally consistent)."""
    report = compiled.report_data
    records = getattr(compiled, "backend_records", None)
    if records is None:
        records = report.get("backends", {}).get("sites", [])
    diags: List[Diagnostic] = []
    diags += check_dataflow(compiled.traced.jaxpr, compiled.rewritten)
    diags += check_modes(compiled.plan)
    diags += check_fused_liveness(compiled.rewritten)
    diags += check_ledgers(compiled.plan, report, compiled.rewritten)
    diags += check_scan_multipliers(compiled.plan)
    diags += check_fallback_reconciliation(records)
    return diags
