"""Static-analysis tests: the plan verifier (SMAV01..SMAV06), the SMA lint
pass (SMA001..SMA006), the ``verify`` compile-time policy, the predicted ==
realized fallback reconciliation, and the CLI golden-check round trip."""

import json
import types
import warnings

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.analysis import (
    PlanVerificationError,
    analyze_compiled,
    attach_diagnostics,
    diagnostics_section,
    predicted_fallbacks,
    verify_compiled,
)
from repro.analysis.diagnostics import CODES, Diagnostic, make
from repro.analysis import lints as L
from repro.analysis import verify as V
from repro.api import SMAOptions, sma_jit
from repro.core.modes import Op, OpKind
from repro.core.sma import SMAPolicy
from repro.launch.families import compile_family

AUTO = SMAOptions(backend="auto")


def _tiny_compiled(**overlay):
    """A small two-GEMM model through the full pipeline."""
    w1 = jnp.ones((64, 128), jnp.float32)
    w2 = jnp.ones((128, 32), jnp.float32)
    fn = lambda x: jax.nn.gelu(x @ w1) @ w2
    eng = sma_jit(fn, options=AUTO.replace(**overlay) if overlay else AUTO)
    return eng.compile(jax.ShapeDtypeStruct((16, 64), jnp.float32))


# ===========================================================================
# Verifier: zero errors on every correct compile
# ===========================================================================
class TestVerifierOnFamilies:
    @pytest.mark.parametrize("arch", C.ARCH_IDS)
    def test_zero_errors_every_family(self, arch):
        """The structural invariants hold on all ten config families."""
        compiled = compile_family(arch, seq_len=128, reduced=True,
                                  options=AUTO)
        errors = [d for d in verify_compiled(compiled)
                  if d.severity == "error"]
        assert errors == [], [d.render() for d in errors]

    def test_diagnostics_section_stamped_on_compile(self):
        compiled = _tiny_compiled()
        diag = compiled.report_data["diagnostics"]
        assert diag["errors"] == 0
        assert diag["num"] == diag["errors"] + diag["warnings"] \
            + diag["infos"]
        assert sum(diag["by_code"].values()) == diag["num"]


# ===========================================================================
# SMAV06 / SMA003: statically predicted fallbacks == runtime-realized
# ===========================================================================
class TestFallbackReconciliation:
    # Families with known fallbacks on CPU under the auto ladder:
    # recurrentgemma (rglru + flash sites) and xlstm (mlstm sites).
    @pytest.mark.parametrize("arch", ["recurrentgemma-2b", "xlstm-1.3b"])
    def test_predicted_equals_realized(self, arch):
        compiled = compile_family(arch, seq_len=128, reduced=True,
                                  options=AUTO)
        records = compiled.backend_records
        assert records, "expected recorded backend sites"

        predicted = {(e["op"], e["reason"]): e["count"]
                     for e in predicted_fallbacks(records)}
        realized = {}
        for r in records:
            reason = r["fallback_reason"]
            if reason is None or reason.split(":", 1)[0] \
                    in L.RUNTIME_ONLY_CATEGORIES:
                continue
            key = (r["op"], reason)
            realized[key] = realized.get(key, 0) + 1

        assert predicted == realized
        assert realized, f"{arch} should have fallbacks on CPU"
        # The report's backends section is a view over the same records.
        bks = compiled.report_data["backends"]
        assert bks["fallback_sites"] == sum(realized.values())

    def test_verifier_catches_tampered_record(self):
        compiled = _tiny_compiled()
        records = [r for r in compiled.backend_records
                   if r["fallback_reason"]]
        assert records
        records[0]["fallback_reason"] = "dtype:fabricated mismatch"
        codes = {d.code for d in verify_compiled(compiled)}
        assert "SMAV06" in codes

    def test_quarantine_reasons_excluded(self):
        record = {"op": "sma_gemm", "shapes": [[8, 8], [8, 8]],
                  "dtypes": ["float32", "float32"], "platform": "cpu",
                  "extras": [], "requested": ["pallas", "xla"],
                  "backend": "xla", "mode": "systolic",
                  "fallback_reason":
                      "quarantine:'pallas' quarantined for sma_gemm (x)"}
        assert V.check_fallback_reconciliation([record]) == []


# ===========================================================================
# Verifier: each invariant trips on a tampered artifact
# ===========================================================================
class TestVerifierInvariants:
    def test_ledger_tamper_trips_smav04(self):
        compiled = _tiny_compiled()
        compiled.report_data["total_flops"] += 1e6
        codes = {d.code for d in verify_compiled(compiled)}
        assert "SMAV04" in codes

    def test_group_partition_tamper_trips_smav02(self):
        compiled = _tiny_compiled()
        for g in compiled.plan.groups:
            if g.ops:
                g.ops.pop()
                break
        codes = {d.code for d in verify_compiled(compiled)}
        assert "SMAV02" in codes

    def test_scan_multiplier_tamper_trips_smav05(self):
        compiled = _tiny_compiled()
        plan = types.SimpleNamespace(
            ops=[Op("layer/scan(x8)/dot#1", OpKind.MATMUL, flops=1.0)],
            stats=types.SimpleNamespace(coarsened_scans=0))
        diags = V.check_scan_multipliers(plan)
        assert {d.code for d in diags} == {"SMAV05"}
        del compiled

    def test_scan_multiplier_consistent_on_coarsened_model(self):
        """A real coarsened scan (length > max_scan_unroll) verifies."""
        w = jnp.ones((32, 32), jnp.float32)

        def fn(x):
            def body(c, _):
                return jax.nn.relu(c @ w), ()
            y, _ = jax.lax.scan(body, x, None, length=16)
            return y

        eng = sma_jit(fn, options=AUTO)
        compiled = eng.compile(jax.ShapeDtypeStruct((8, 32), jnp.float32))
        assert compiled.plan.stats.coarsened_scans >= 1
        assert [d for d in verify_compiled(compiled)
                if d.code == "SMAV05"] == []

    def test_fused_liveness_tamper_trips_smav03(self):
        compiled = _tiny_compiled()
        sites = compiled.fused_sites
        assert sites, "tiny model should realize a fused epilogue"
        sites[0].site["consumed_eqns"] = [10 ** 6]
        codes = {d.code for d in verify_compiled(compiled)}
        assert "SMAV03" in codes


# ===========================================================================
# Lints
# ===========================================================================
class TestLints:
    def test_sma001_mode_ping_pong(self):
        ops = [
            Op("gemm_a", OpKind.MATMUL, flops=1e9),
            Op("route", OpKind.TOPK, flops=10.0),  # not fusable: own group
            Op("gemm_b", OpKind.MATMUL, flops=1e9),
        ]
        plan = types.SimpleNamespace(groups=SMAPolicy().plan(ops))
        diags = L.lint_mode_ping_pong(plan)
        assert [d.code for d in diags] == ["SMA001"]
        assert "route" in diags[0].message

    def test_sma001_silent_when_island_is_substantial(self):
        ops = [
            Op("gemm_a", OpKind.MATMUL, flops=1e9),
            Op("route", OpKind.TOPK, flops=5e8),
            Op("gemm_b", OpKind.MATMUL, flops=1e9),
        ]
        plan = types.SimpleNamespace(groups=SMAPolicy().plan(ops))
        assert L.lint_mode_ping_pong(plan) == []

    def test_sma002_missed_fusion_cites_reason(self):
        report = {"fusion": {"planned_fused_sites": 3,
                             "fallback_reasons": {"multi_consumer": 2,
                                                  "no_fusable_consumer": 5}}}
        diags = L.lint_missed_fusion(report, rewritten=object())
        assert [d.code for d in diags] == ["SMA002"]
        assert "multi_consumer" in diags[0].message
        # the benign no-consumer case is not a missed fusion
        assert all("no_fusable_consumer" not in d.message for d in diags)

    def test_sma002_fusion_disabled(self):
        report = {"fusion": {"planned_fused_sites": 3,
                             "fallback_reasons": {}}}
        diags = L.lint_missed_fusion(report, rewritten=None)
        assert len(diags) == 1 and "fuse_runtime" in diags[0].message

    def test_sma004_misaligned_gemm(self):
        record = {"op": "sma_gemm", "shapes": [[8, 60], [60, 100]],
                  "dtypes": ["float32", "float32"], "platform": "cpu",
                  "extras": [], "requested": ["pallas", "xla"]}
        diags = L.lint_mxu_alignment([record, dict(record)])
        assert [d.code for d in diags] == ["SMA004"]  # deduped

    def test_sma004_aligned_gemm_is_silent(self):
        record = {"op": "sma_gemm", "shapes": [[128, 128], [128, 128]],
                  "dtypes": ["float32", "float32"], "platform": "cpu",
                  "extras": [], "requested": ["pallas", "xla"]}
        assert L.lint_mxu_alignment([record]) == []

    def test_sma005_downcast_into_contraction(self):
        w = jnp.ones((16, 16), jnp.bfloat16)

        def fn(x):
            return x.astype(jnp.bfloat16) @ w

        jaxpr = jax.make_jaxpr(fn)(jnp.ones((4, 16), jnp.float32)).jaxpr
        diags = L.lint_dtype_downcast(jaxpr)
        assert [d.code for d in diags] == ["SMA005"]
        assert diags[0].site["from"] == "float32"
        assert diags[0].site["to"] == "bfloat16"

    def test_sma005_upcast_is_silent(self):
        w = jnp.ones((16, 16), jnp.float32)

        def fn(x):
            return x.astype(jnp.float32) @ w

        jaxpr = jax.make_jaxpr(fn)(jnp.ones((4, 16), jnp.bfloat16)).jaxpr
        assert L.lint_dtype_downcast(jaxpr) == []

    def test_sma006_dead_op(self):
        # Tracing turns dead outputs into DropVars; SMA006 exists for
        # *rewritten* programs where a named result loses its last
        # consumer.  Model that by truncating a jaxpr's outvars.
        from jax.extend import core as jcore

        jx = jax.make_jaxpr(lambda x: (jnp.sin(x), x + 1.0))(
            jnp.ones((4,), jnp.float32)).jaxpr
        dead = jcore.Jaxpr(jx.constvars, jx.invars, jx.outvars[1:],
                           jx.eqns)
        diags = L.lint_dead_ops(dead)
        assert [d.code for d in diags] == ["SMA006"]
        assert diags[0].site["primitive"] == "sin"

    def test_sma006_live_program_is_silent(self):
        jaxpr = jax.make_jaxpr(lambda x: jnp.sin(x) + x)(
            jnp.ones((4,), jnp.float32)).jaxpr
        assert L.lint_dead_ops(jaxpr) == []


# ===========================================================================
# Diagnostic objects + report section
# ===========================================================================
class TestDiagnostics:
    def test_unknown_code_rejected(self):
        with pytest.raises(ValueError):
            Diagnostic(code="SMA999", severity="warning", message="x")

    def test_make_uses_registered_severity(self):
        assert make("SMAV01", "x").severity == "error"
        assert make("SMA004", "x").severity == "info"

    def test_section_counts_and_cap(self):
        diags = [make("SMA004", f"i{i}") for i in range(60)] \
            + [make("SMAV01", "boom")]
        sec = diagnostics_section(diags, max_items=10)
        assert sec["num"] == 61 and sec["errors"] == 1
        assert sec["by_code"] == {"SMA004": 60, "SMAV01": 1}
        assert len(sec["items"]) == 10
        assert sec["items"][0]["code"] == "SMAV01"  # most severe first

    def test_render_text_includes_diagnostics(self):
        from repro.compiler.report import render_text
        compiled = _tiny_compiled()
        text = render_text(compiled.report)
        assert "static analysis" in text

    def test_every_code_documented_in_readme(self):
        import pathlib
        readme = pathlib.Path(__file__).resolve().parents[1] / "README.md"
        text = readme.read_text()
        for code in CODES:
            assert code in text, f"{code} missing from README"


# ===========================================================================
# The verify= compile-time policy
# ===========================================================================
class TestVerifyPolicy:
    def _broken_attach(self, monkeypatch):
        import repro.analysis as A
        boom = [make("SMAV04", "fabricated ledger break")]
        monkeypatch.setattr(A, "attach_diagnostics", lambda c: boom)

    def test_default_off_stamps_but_never_raises(self):
        compiled = _tiny_compiled()
        assert "diagnostics" in compiled.report_data

    def test_warn_policy_warns(self, monkeypatch):
        self._broken_attach(monkeypatch)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            _tiny_compiled(verify="warn")
        assert any("plan verification" in str(w.message) for w in caught)

    def test_error_policy_raises_and_never_caches(self, monkeypatch):
        self._broken_attach(monkeypatch)
        w = jnp.ones((8, 8), jnp.float32)
        eng = sma_jit(lambda x: x @ w, options=AUTO.replace(verify="error"))
        with pytest.raises(PlanVerificationError) as ei:
            eng.compile(jax.ShapeDtypeStruct((4, 8), jnp.float32))
        assert ei.value.diagnostics[0].code == "SMAV04"
        assert eng.cache_size == 0

    def test_invalid_verify_value_rejected(self):
        with pytest.raises(ValueError):
            SMAOptions(verify="sometimes")

    def test_analyze_compiled_is_verify_plus_lints(self):
        compiled = _tiny_compiled()
        assert len(analyze_compiled(compiled)) == \
            len(verify_compiled(compiled)) \
            + len(L.lint_compiled(compiled))

    def test_attach_overwrites_section(self):
        compiled = _tiny_compiled()
        compiled.report_data["diagnostics"] = {"num": -1}
        attach_diagnostics(compiled)
        assert compiled.report_data["diagnostics"]["num"] >= 0


# ===========================================================================
# CLI round trip
# ===========================================================================
class TestCLI:
    def test_golden_roundtrip(self, tmp_path, capsys):
        from repro.analysis.cli import main

        golden = tmp_path / "golden.json"
        out = tmp_path / "diag.json"
        base = ["stablelm-1.6b", "--reduced", "--seq", "64",
                "--golden", str(golden)]
        assert main(base + ["--update-golden", "--json", str(out)]) == 0
        payload = json.loads(out.read_text())
        assert "stablelm-1.6b" in payload["families"]

        assert main(base + ["--check"]) == 0

        # force a count down in the golden -> drift failure (exit 2)
        g = json.loads(golden.read_text())
        by_code = g["families"]["stablelm-1.6b"]["by_code"]
        code = next(iter(by_code))
        by_code[code] -= 1
        golden.write_text(json.dumps(g))
        assert main(base + ["--check"]) == 2
        capsys.readouterr()

    def test_missing_golden_fails_check(self, tmp_path):
        from repro.analysis.cli import main
        rc = main(["stablelm-1.6b", "--reduced", "--seq", "64", "--check",
                   "--golden", str(tmp_path / "absent.json")])
        assert rc == 2

    def test_unknown_arch_errors(self):
        from repro.analysis.cli import main
        with pytest.raises(SystemExit):
            main(["not-a-model"])
