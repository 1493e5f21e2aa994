"""SMA lint pass: advisory diagnostics with stable codes (SMA001..SMA006).

Unlike the verifier (:mod:`repro.analysis.verify`), nothing here means the
compile is *wrong* — each lint flags a plan that is correct but leaves SMA
efficiency on the table, or carries a numeric hazard worth a look:

* ``SMA001`` — mode ping-pong: a tiny SIMD island wedged between two
  systolic groups forces two temporal mode switches for negligible work.
* ``SMA002`` — missed fusion: a fusable GEMM chain stayed unrewritten,
  citing the rewrite pass's recorded fallback reason.
* ``SMA003`` — predicted runtime backend fallback: replaying
  ``Backend.supports`` over the recorded op sites says the preferred rung
  will decline at runtime (the static half of the reconciliation the
  verifier's SMAV06 pins to the runtime-realized records).
* ``SMA004`` — MXU/block misalignment: the kernel will pad tiles (GEMMs via
  :func:`repro.kernels.sma_gemm.mxu_alignment`; other ops via the pallas
  backend's kernel-constraint hooks).
* ``SMA005`` — dtype-downcast hazard: a value is cast to a narrower float
  and then fed into a contraction.
* ``SMA006`` — dead ops: equations whose outputs are never consumed.

Repeated findings aggregate (per op/reason, per dtype pair, per primitive)
so large models produce stable, readable counts — this keeps the committed
golden baseline insensitive to layer count.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from jax.core import DropVar
from jax.extend import core

from repro.analysis.diagnostics import Diagnostic, make
from repro.backends.base import FallbackReason, OpSite
from repro.backends.registry import get_backend
from repro.compiler.trace import subjaxprs
from repro.core.modes import ExecMode

__all__ = [
    "lint_compiled",
    "lint_dead_ops",
    "lint_dtype_downcast",
    "lint_missed_fusion",
    "lint_mode_ping_pong",
    "lint_mxu_alignment",
    "lint_predicted_fallbacks",
    "predict_fallback",
    "predicted_fallbacks",
    "site_from_record",
]

#: SMA001: a SIMD island below this FLOP fraction of its smaller systolic
#: neighbor is "tiny" — the two mode switches around it cost more than the
#: island computes.
PING_PONG_FLOP_FRACTION = 0.01

#: Rewrite fallback reasons that indicate genuinely *missed* fusion (a
#: chain existed but could not be taken).  ``no_fusable_consumer`` is
#: excluded: a bare GEMM with nothing to fuse is the normal case, not a
#: missed opportunity.
_MISSED_FUSION_REASONS = (
    "multi_consumer",
    "escapes_jaxpr",
    "unsupported_dtype",
    "prologue_accum_dtype",
)

#: Fallback categories that only exist at runtime — no static pass can see
#: the quarantine denylist, so both SMA003 and SMAV06 exclude them.
RUNTIME_ONLY_CATEGORIES = ("quarantine", "runtime")


# --------------------------------------------------------------------------
# Static replay of ``Backend.supports`` over recorded sites
# --------------------------------------------------------------------------
def site_from_record(record: Dict[str, Any]) -> OpSite:
    """Rebuild the :class:`OpSite` a backend record was resolved from.

    The registry's recorder serializes every field ``Backend.supports``
    consults (shapes, dtypes, platform, extras), so the rebuilt site
    resolves identically — that round-trip is what SMAV06 verifies.
    """
    return OpSite(
        op=record["op"],
        shapes=tuple(tuple(int(d) for d in s) for s in record["shapes"]),
        dtypes=tuple(record["dtypes"]),
        platform=record["platform"],
        extras=tuple((k, v) for k, v in record.get("extras", [])),
    )


def predict_fallback(record: Dict[str, Any]) -> Optional[str]:
    """Statically predict the fallback reason the preferred ladder rung
    would record for this site — ``None`` when the first rung takes it.

    Mirrors :func:`repro.backends.registry.select_backend` exactly, minus
    the quarantine consult (runtime state, invisible statically).
    """
    ladder = tuple(record.get("requested") or ("xla",))
    site = site_from_record(record)
    verdict = get_backend(ladder[0]).supports(site)
    if verdict is True:
        return None
    if isinstance(verdict, FallbackReason):
        return str(verdict)
    return f"unsupported:declined by '{ladder[0]}'"


def predicted_fallbacks(records: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
    """Aggregate static fallback predictions per ``(op, reason)``.

    Returns sorted entries ``{"op", "reason", "count", "example_shapes"}``
    — the SMA003 payload, and the "predicted" half tests compare against
    the runtime-realized ``fallback_reason`` fields of the same records.
    """
    agg: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for r in records:
        reason = predict_fallback(r)
        if reason is None:
            continue
        key = (r["op"], reason)
        entry = agg.get(key)
        if entry is None:
            agg[key] = {"op": r["op"], "reason": reason, "count": 1,
                        "example_shapes": list(r["shapes"])}
        else:
            entry["count"] += 1
    return [agg[k] for k in sorted(agg)]


# --------------------------------------------------------------------------
# SMA001 — mode ping-pong
# --------------------------------------------------------------------------
def lint_mode_ping_pong(plan: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    groups = plan.groups
    for i in range(1, len(groups) - 1):
        prev_g, island, next_g = groups[i - 1], groups[i], groups[i + 1]
        if island.mode != ExecMode.SIMD \
                or prev_g.mode != ExecMode.SYSTOLIC \
                or next_g.mode != ExecMode.SYSTOLIC:
            continue
        island_flops = sum(op.flops for op in island.ops)
        neighbor = min(sum(op.flops for op in prev_g.ops),
                       sum(op.flops for op in next_g.ops))
        if neighbor > 0 and \
                island_flops < PING_PONG_FLOP_FRACTION * neighbor:
            head = island.ops[0].name if island.ops else "?"
            out.append(make(
                "SMA001",
                f"SIMD island at group {i} ({head}, "
                f"{island_flops:.3g} FLOPs) forces two mode switches "
                f"between systolic neighbors "
                f"({neighbor:.3g} FLOPs min)",
                {"group": i, "op": head,
                 "island_flops": island_flops,
                 "neighbor_flops": neighbor}))
    return out


# --------------------------------------------------------------------------
# SMA002 — missed fusion
# --------------------------------------------------------------------------
def lint_missed_fusion(report: Dict[str, Any],
                       rewritten: Any) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    fus = report.get("fusion")
    if not fus:
        return out
    if rewritten is None and fus.get("planned_fused_sites", 0) > 0:
        out.append(make(
            "SMA002",
            f"runtime fusion is disabled (fuse_runtime=False) but the "
            f"plan promised {fus['planned_fused_sites']} fused sites",
            {"planned_fused_sites": fus["planned_fused_sites"]}))
        return out
    for reason in _MISSED_FUSION_REASONS:
        count = fus.get("fallback_reasons", {}).get(reason, 0)
        if count:
            out.append(make(
                "SMA002",
                f"{count} fusable GEMM chain(s) left unrewritten: "
                f"{reason}",
                {"reason": reason, "count": count}))
    return out


# --------------------------------------------------------------------------
# SMA003 — predicted runtime backend fallbacks
# --------------------------------------------------------------------------
def lint_predicted_fallbacks(records: List[Dict[str, Any]]
                             ) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    for entry in predicted_fallbacks(records):
        out.append(make(
            "SMA003",
            f"{entry['op']} predicted to fall off its preferred backend "
            f"at {entry['count']} site(s): {entry['reason']}",
            dict(entry)))
    return out


# --------------------------------------------------------------------------
# SMA004 — MXU/block misalignment
# --------------------------------------------------------------------------
def _gemm_mnk(record: Dict[str, Any]
              ) -> Optional[Tuple[int, int, int, str]]:
    shapes = record["shapes"]
    if record["op"] == "sma_gemm":
        a, b = shapes[0], shapes[1]
    elif record["op"] == "rmsnorm_gemm":
        a, b = shapes[0], shapes[2]
    else:
        return None
    if len(a) < 1 or len(b) != 2:
        return None
    m = 1
    for d in a[:-1]:
        m *= int(d)
    return m, int(b[1]), int(a[-1]), record["dtypes"][0]


def lint_mxu_alignment(records: List[Dict[str, Any]]) -> List[Diagnostic]:
    from repro.kernels.sma_gemm import mxu_alignment

    out: List[Diagnostic] = []
    pallas = get_backend("pallas")
    seen = set()
    for r in records:
        key = (r["op"], tuple(tuple(s) for s in r["shapes"]),
               tuple(r["dtypes"]))
        if key in seen:
            continue
        seen.add(key)
        site_info = {"op": r["op"], "shapes": list(r["shapes"]),
                     "dtypes": list(r["dtypes"])}
        mnk = _gemm_mnk(r)
        if mnk is not None:
            m, n, k, dtype = mnk
            why = mxu_alignment(m, n, k, dtype)
            if why is not None:
                out.append(make(
                    "SMA004",
                    f"{r['op']} site M={m} N={n} K={k} is MXU-misaligned "
                    f"({why})", site_info))
            continue
        check = pallas.constraints.get(r["op"])
        if check is None:
            continue
        why = check(site_from_record(r))
        if why is not None and why.split(":", 1)[0] == "shape":
            out.append(make(
                "SMA004",
                f"{r['op']} site shape gates the hardware kernel: {why}",
                site_info))
    return out


# --------------------------------------------------------------------------
# SMA005 — dtype-downcast feeding a contraction
# --------------------------------------------------------------------------
_CONTRACTIONS = ("dot_general", "conv_general_dilated")


def lint_dtype_downcast(jaxpr: core.Jaxpr) -> List[Diagnostic]:
    import jax.numpy as jnp

    agg: Dict[Tuple[str, str, str], int] = {}
    seen = set()

    def walk(jx: core.Jaxpr) -> None:
        if id(jx) in seen:
            return
        seen.add(id(jx))
        downcast: Dict[Any, Tuple[str, str]] = {}
        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type":
                src = eqn.invars[0].aval.dtype
                dst = eqn.outvars[0].aval.dtype
                if (jnp.issubdtype(src, jnp.floating)
                        and jnp.issubdtype(dst, jnp.floating)
                        and jnp.dtype(dst).itemsize
                        < jnp.dtype(src).itemsize):
                    downcast[eqn.outvars[0]] = (jnp.dtype(src).name,
                                                jnp.dtype(dst).name)
            elif eqn.primitive.name in _CONTRACTIONS:
                for v in eqn.invars:
                    pair = downcast.get(v)
                    if pair is not None:
                        key = (pair[0], pair[1], eqn.primitive.name)
                        agg[key] = agg.get(key, 0) + 1
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return [
        make("SMA005",
             f"{count} contraction operand(s) downcast {src} -> {dst} "
             f"immediately before {prim} (accumulation precision hazard)",
             {"from": src, "to": dst, "primitive": prim, "count": count})
        for (src, dst, prim), count in sorted(agg.items())
    ]


# --------------------------------------------------------------------------
# SMA006 — dead ops
# --------------------------------------------------------------------------
def lint_dead_ops(jaxpr: core.Jaxpr) -> List[Diagnostic]:
    agg: Dict[str, int] = {}
    seen = set()

    def walk(jx: core.Jaxpr) -> None:
        if id(jx) in seen:
            return
        seen.add(id(jx))
        used = set()
        for eqn in jx.eqns:
            for v in eqn.invars:
                if isinstance(v, core.Var):
                    used.add(v)
            for sub in subjaxprs(eqn):
                walk(sub)
        for v in jx.outvars:
            if isinstance(v, core.Var):
                used.add(v)
        for eqn in jx.eqns:
            if getattr(eqn, "effects", None):
                continue
            outs = [v for v in eqn.outvars
                    if not isinstance(v, DropVar)]
            if outs and all(v not in used for v in outs):
                agg[eqn.primitive.name] = \
                    agg.get(eqn.primitive.name, 0) + 1

    walk(jaxpr)
    return [
        make("SMA006",
             f"{count} {prim} equation(s) produce values never consumed",
             {"primitive": prim, "count": count})
        for prim, count in sorted(agg.items())
    ]


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------
def lint_compiled(compiled: Any) -> List[Diagnostic]:
    """The full lint set over one ``CompiledModel``."""
    report = compiled.report_data
    records = getattr(compiled, "backend_records", None)
    if records is None:
        records = report.get("backends", {}).get("sites", [])
    static_records = [
        r for r in records
        if not (r.get("fallback_reason")
                and r["fallback_reason"].split(":", 1)[0]
                in RUNTIME_ONLY_CATEGORIES)
    ]
    diags: List[Diagnostic] = []
    diags += lint_mode_ping_pong(compiled.plan)
    diags += lint_missed_fusion(report, compiled.rewritten)
    diags += lint_predicted_fallbacks(static_records)
    diags += lint_mxu_alignment(static_records)
    diags += lint_dtype_downcast(compiled.traced.jaxpr)
    diags += lint_dead_ops(compiled.traced.jaxpr)
    return diags
