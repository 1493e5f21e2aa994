"""repro.backends: registry, capability fallback, custom backends, shims.

Covers the pluggable-executor contract:

* the three built-in registrants (pallas / interpret / xla) and the
  register/get/available/unregister surface,
* capability-checked resolution — unsupported dtype and non-MXU-aligned
  shapes on ``decode_attention`` / ``rglru_scan`` fall back to the ``xla``
  backend with the reason recorded (unit level and in the plan report's
  ``backends`` section),
* a toy backend registered in-test is selectable end-to-end through
  ``sma_jit`` with zero per-op edits,
* ordered preference ladders via ``SMAOptions.backend`` tuples,
* the deprecated ``Runtime(backend=...)`` shim warns exactly once per
  process.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import SMAOptions, sma_jit
from repro.backends import (Backend, FallbackReason, OpSite,
                            available_backends, get_backend,
                            normalize_preference, record_sites,
                            register_backend, select_backend,
                            unregister_backend)
from repro.core.modes import ExecMode
from repro.kernels import ops, ref


def _gemm_site(m=8, k=16, n=8, dtype=jnp.float32):
    a = jnp.ones((m, k), dtype)
    b = jnp.ones((k, n), dtype)
    return OpSite.from_args("sma_gemm", (a, b)), a, b


# ---------------------------------------------------------------------------
# Registry surface
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_builtins_registered(self):
        names = available_backends()
        for name in ("pallas", "interpret", "xla"):
            assert name in names

    def test_builtin_modes(self):
        assert get_backend("pallas").mode is ExecMode.SYSTOLIC
        assert get_backend("interpret").mode is ExecMode.SYSTOLIC
        assert get_backend("xla").mode is ExecMode.SIMD

    def test_every_kernel_op_covered_by_builtins(self):
        from repro.backends.base import KERNEL_OPS
        for name in ("pallas", "interpret", "xla"):
            assert set(get_backend(name).ops_covered()) == set(KERNEL_OPS)

    def test_unknown_backend_raises_with_available_list(self):
        with pytest.raises(KeyError, match="xla"):
            get_backend("no-such-backend")

    def test_duplicate_registration_requires_overwrite(self):
        be = Backend("dup-test", ExecMode.SIMD, ops={})
        register_backend(be)
        try:
            with pytest.raises(ValueError, match="already registered"):
                register_backend(Backend("dup-test", ExecMode.SIMD, ops={}))
            replacement = Backend("dup-test", ExecMode.SYSTOLIC, ops={})
            register_backend(replacement, overwrite=True)
            assert get_backend("dup-test") is replacement
        finally:
            unregister_backend("dup-test")
        assert "dup-test" not in available_backends()

    def test_normalize_preference(self):
        assert normalize_preference(None) == ("pallas", "xla")
        assert normalize_preference("auto") == ("pallas", "xla")
        assert normalize_preference("pallas") == ("pallas", "xla")
        assert normalize_preference("xla") == ("xla",)
        assert normalize_preference(("interpret", "xla")) == \
            ("interpret", "xla")
        # the legacy interpret boolean wins over any preference
        assert normalize_preference("pallas", interpret=True) == \
            ("interpret", "xla")

    def test_fallback_reason_is_falsy_and_categorized(self):
        why = FallbackReason("shape:head_dim 40 not MXU-aligned")
        assert not why
        assert why.category == "shape"
        assert "head_dim" in str(why)

    def test_opsite_from_shape_dtype_structs(self):
        site = OpSite.from_args(
            "sma_gemm",
            (jax.ShapeDtypeStruct((4, 8), jnp.bfloat16),
             jax.ShapeDtypeStruct((8, 16), jnp.bfloat16)))
        assert site.shapes == ((4, 8), (8, 16))
        assert site.dtypes == ("bfloat16", "bfloat16")


# ---------------------------------------------------------------------------
# Capability-checked resolution + fallback recording
# ---------------------------------------------------------------------------
class TestCapabilityFallback:
    def test_auto_on_cpu_resolves_to_xla_with_platform_reason(self):
        site, _, _ = _gemm_site()
        assert jax.default_backend() != "tpu"
        backend, why = select_backend(site)
        assert backend.name == "xla"
        assert why is not None and why.category == "platform"

    def test_explicit_interpret_sticks(self):
        site, _, _ = _gemm_site()
        backend, why = select_backend(site, interpret=True)
        assert backend.name == "interpret" and why is None

    def test_decode_attention_misaligned_shape_falls_back_to_xla(self):
        """Non-MXU-aligned head_dim: the hardware decode kernel declines
        with a shape reason (checked before the platform gate) and the
        ladder lands on xla.  Numerics must match the oracle."""
        b, hq, hkv, smax, d = 2, 4, 2, 32, 40  # d % 64 != 0
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, hq, d), jnp.float32)
        kc = jax.random.normal(key, (b, hkv, smax, d), jnp.float32)
        vc = jax.random.normal(key, (b, hkv, smax, d), jnp.float32)
        cl = jnp.array([5, 17], jnp.int32)
        with record_sites() as sites:
            got = ops.decode_attention(q, kc, vc, cl, backend="pallas")
        (site,) = sites
        assert site["backend"] == "xla"
        assert site["fallback_reason"].startswith("shape:")
        assert "head_dim 40" in site["fallback_reason"]
        want = ref.decode_attention_ref(q, kc, vc, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_rglru_misaligned_channels_fall_back_to_xla(self):
        b, s, d = 2, 16, 37  # d % 8 != 0
        key = jax.random.PRNGKey(1)
        a = jax.nn.sigmoid(jax.random.normal(key, (b, s, d)))
        u = jax.random.normal(key, (b, s, d)) * 0.1
        with record_sites() as sites:
            h_seq, h_last = ops.rglru_scan(a, u, backend="pallas")
        (site,) = sites
        assert site["backend"] == "xla"
        assert site["fallback_reason"].startswith("shape:")
        ws, wl = ref.rglru_ref(a, u)
        np.testing.assert_allclose(np.asarray(h_seq), np.asarray(ws),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(wl),
                                   rtol=1e-4, atol=1e-4)

    def test_unsupported_dtype_falls_back_with_dtype_reason(self):
        with jax.enable_x64(True):
            q = jnp.ones((1, 2, 64), jnp.float64)
            kc = jnp.ones((1, 2, 8, 64), jnp.float64)
            vc = jnp.ones((1, 2, 8, 64), jnp.float64)
            site = OpSite.from_args("decode_attention", (q, kc, vc))
            backend, why = select_backend(site, "interpret")
            assert backend.name == "xla"
            assert why is not None and why.category == "dtype"
            assert "float64" in str(why)

    def test_mlstm_return_state_rides_xla_with_param_reason(self):
        q = jnp.ones((1, 2, 16, 8), jnp.float32)
        site = OpSite.from_args("mlstm_chunkwise", (q, q, q),
                                return_state=True)
        backend, why = select_backend(site, "interpret")
        assert backend.name == "xla"
        assert why is not None and why.category == "param"

    def test_fallback_recorded_in_plan_report(self):
        """The plan report's ``backends`` section carries the per-site
        chosen backend + fallback reason for a traced model that calls
        decode_attention on a non-MXU-aligned shape."""
        b, hq, hkv, smax, d = 2, 4, 2, 32, 40
        q = jax.ShapeDtypeStruct((b, hq, d), jnp.float32)
        kc = jax.ShapeDtypeStruct((b, hkv, smax, d), jnp.float32)
        vc = jax.ShapeDtypeStruct((b, hkv, smax, d), jnp.float32)
        cl = jax.ShapeDtypeStruct((b,), jnp.int32)

        def model(q, kc, vc, cl):
            return ops.decode_attention(q, kc, vc, cl, backend="pallas")

        engine = sma_jit(model, name="decode_fallback")
        compiled = engine.compile(q, kc, vc, cl)
        section = compiled.report["backends"]
        decode_sites = [s for s in section["sites"]
                        if s["op"] == "decode_attention"]
        assert len(decode_sites) == 1
        assert decode_sites[0]["backend"] == "xla"
        assert decode_sites[0]["origin"] == "traced"
        assert "head_dim 40" in decode_sites[0]["fallback_reason"]
        assert section["fallback_reasons"].get("shape", 0) >= 1
        assert section["chosen"].get("xla", 0) >= 1
        assert section["backend_modes"]["xla"] == "simd"
        assert section["backend_modes"]["pallas"] == "systolic"

    def test_dispatch_gemm_sites_in_backends_section(self):
        """Every dispatcher GEMM site appears in the section with
        origin="dispatch" and a mode consistent with the chosen backend."""
        w = jnp.ones((16, 8), jnp.float32)
        engine = sma_jit(lambda x: jax.nn.relu(x @ w + 0.5) @ jnp.ones((8, 4)),
                         options=SMAOptions(backend="xla"))
        compiled = engine.compile(jnp.ones((4, 16), jnp.float32))
        section = compiled.report["backends"]
        dispatch = [s for s in section["sites"] if s["origin"] == "dispatch"]
        assert len(dispatch) >= 2           # fused gemm + bare gemm
        assert all(s["backend"] == "xla" and s["mode"] == "simd"
                   for s in dispatch)
        assert section["requested"] == "xla"


# ---------------------------------------------------------------------------
# Custom backends, end to end
# ---------------------------------------------------------------------------
class TestCustomBackend:
    def _toy(self, calls):
        def toy_gemm(a, b, *, bias=None, epilogue="none",
                     accum_dtype=jnp.float32, precision=None,
                     block_m=None, block_n=None, block_k=None,
                     autotune=False):
            calls.append((tuple(a.shape), tuple(b.shape)))
            return ref.gemm_ref(a, b, bias=bias, epilogue=epilogue,
                                accum_dtype=accum_dtype, precision=precision)

        return Backend("toy-test", ExecMode.SYSTOLIC,
                       ops={"sma_gemm": toy_gemm},
                       description="in-test toy executor")

    def test_toy_backend_end_to_end_through_sma_jit(self):
        calls = []
        register_backend(self._toy(calls))
        try:
            w1 = jnp.full((16, 32), 0.5, jnp.float32)
            w2 = jnp.full((32, 8), 0.25, jnp.float32)
            x = jnp.ones((4, 16), jnp.float32)
            engine = sma_jit(lambda x: (x @ w1) @ w2, name="toy_mlp")
            with repro.options(backend="toy-test"):
                y = engine(x)
                report = engine.compile(x).report
            # both GEMMs ran through the registered toy backend...
            assert len(calls) >= 2
            assert ((4, 16), (16, 32)) in calls
            # ...the report says so...
            assert report["backends"]["chosen"]["toy-test"] >= 2
            assert all(s["backend"] == "toy-test"
                       for s in report["backends"]["sites"])
            # ...and the math is right.
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray((x @ w1) @ w2),
                                       rtol=1e-5, atol=1e-5)
        finally:
            unregister_backend("toy-test")

    def test_preference_ladder_mixes_toy_and_xla(self):
        """A backend covering only sma_gemm: GEMMs go to it, every other op
        falls through the explicit ladder to xla (reason op:...)."""
        calls = []
        register_backend(self._toy(calls))
        try:
            a = jnp.ones((2, 8, 16), jnp.float32)
            with repro.options(backend=("toy-test", "xla")):
                with record_sites() as sites:
                    ops.sma_gemm(jnp.ones((4, 8)), jnp.ones((8, 4)))
                    ops.rglru_scan(a * 0.5, a)
            by_op = {s["op"]: s for s in sites}
            assert by_op["sma_gemm"]["backend"] == "toy-test"
            assert by_op["rglru_scan"]["backend"] == "xla"
            assert by_op["rglru_scan"]["fallback_reason"].startswith("op:")
        finally:
            unregister_backend("toy-test")

    def test_options_normalize_list_preference(self):
        o = SMAOptions(backend=["interpret", "xla"])
        assert o.backend == ("interpret", "xla")
        hash(o.cache_key())  # stays hashable (engine cache key)
        assert o.asdict()["backend"] == ["interpret", "xla"]

    def test_bare_false_supports_gets_categorized_reason(self):
        """A custom supports() returning plain False (allowed by the
        protocol) must record a categorized reason, not 'False'."""
        class Grumpy(Backend):
            def supports(self, site):
                return False

        register_backend(Grumpy("grumpy", ExecMode.SIMD,
                                ops={"sma_gemm": lambda *a, **k: None}))
        try:
            site, _, _ = _gemm_site()
            backend, why = select_backend(site, "grumpy")
            assert backend.name == "xla"
            assert why.category == "unsupported"
            assert "grumpy" in str(why)
        finally:
            unregister_backend("grumpy")

    def test_unknown_backend_name_raises_at_resolution(self):
        with pytest.raises(KeyError, match="no-such"):
            ops.sma_gemm(jnp.ones((4, 8)), jnp.ones((8, 4)),
                         backend="no-such")


# ---------------------------------------------------------------------------
# Ambient-xla equivalence + legacy shims
# ---------------------------------------------------------------------------
class TestShimsAndAmbient:
    def test_ambient_xla_matches_default_on_cpu(self):
        w = jnp.full((16, 8), 0.5, jnp.float32)
        engine = sma_jit(lambda x: jax.nn.gelu(x @ w, approximate=True))
        x = jnp.ones((4, 16), jnp.float32)
        y_default = engine(x)
        with repro.options(backend="xla"):
            y_xla = engine(x)
        np.testing.assert_allclose(np.asarray(y_default), np.asarray(y_xla),
                                   rtol=1e-6, atol=1e-6)

    def test_explicit_falsy_interpret_beats_ambient(self, monkeypatch):
        """interpret=False passed explicitly must win over an ambient
        repro.options(interpret=True) — the single-resolver dedup keeps the
        explicit-beats-ambient contract, falsy values included."""
        import importlib
        kernel_mod = importlib.import_module("repro.kernels.sma_gemm")
        calls = []
        orig = kernel_mod.sma_gemm
        monkeypatch.setattr(kernel_mod, "sma_gemm",
                            lambda *a, **kw: (calls.append(kw),
                                              orig(*a, **kw))[1])
        a, b = jnp.ones((8, 16), jnp.float32), jnp.ones((16, 8), jnp.float32)
        with repro.options(interpret=True):
            ops.sma_gemm(a, b)                    # ambient -> interpreter
            assert len(calls) == 1
            ops.sma_gemm(a, b, interpret=False)   # explicit False wins
        assert len(calls) == 1                    # no second kernel call

    def test_runtime_backend_shim_warns_exactly_once_per_process(
            self, monkeypatch):
        from repro.models import layers
        monkeypatch.setattr(layers, "_RUNTIME_BACKEND_WARNED", False)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            layers.Runtime(backend="xla")     # warns
            layers.Runtime(backend="xla")     # silent (once per process)
            layers.Runtime(interpret=True)    # silent
            layers.Runtime()                  # defaults: never warns
        dep = [w for w in caught
               if issubclass(w.category, DeprecationWarning)
               and "Runtime(backend" in str(w.message)]
        assert len(dep) == 1

    def test_runtime_default_construction_does_not_warn(self):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            from repro.models.layers import Runtime
            Runtime(remat=False)
        assert not [w for w in caught
                    if issubclass(w.category, DeprecationWarning)]

    def test_server_and_train_accept_options(self):
        """The launch drivers take SMAOptions directly (Runtime.backend
        retired); the engine bakes them in."""
        from repro.launch.train import make_step
        from repro.models.layers import Runtime
        from repro.optim import adamw
        import repro.configs as C
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        step = make_step(cfg, Runtime(remat=False), adamw.AdamWConfig(),
                         None, (), grad_compression=False,
                         options=SMAOptions(backend="xla"))
        assert step.options.backend == "xla"
        assert step.options.jit is True
