"""Public API tests: the ``sma_jit`` engine's shape-polymorphic compile
cache, the ``SMAOptions`` single configuration path, and the deprecated
back-compat shims (``compile_model``, ``sma_matmul``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import Engine, SMAOptions, sma_jit
from repro.api.options import DEFAULTS, current_options, resolve_options

KEY = jax.random.PRNGKey(0)


def _mlp_weights(k=32, h=64, out=16):
    w1 = jax.random.normal(KEY, (k, h), jnp.float32) * k ** -0.5
    w2 = jax.random.normal(jax.random.PRNGKey(1), (h, out),
                           jnp.float32) * h ** -0.5
    return w1, w2


# ===========================================================================
# Shape-polymorphic cache keying
# ===========================================================================
class TestCacheKeying:
    def test_second_call_is_cache_hit_with_zero_retrace(self, monkeypatch):
        """Identical abstract signature -> zero re-trace/re-plan work."""
        from repro.compiler import dispatch as D
        traces = []
        orig = D.trace_model
        monkeypatch.setattr(D, "trace_model",
                            lambda *a, **kw: (traces.append(1),
                                              orig(*a, **kw))[1])
        w1, w2 = _mlp_weights()
        engine = sma_jit(lambda x: jnp.tanh(x @ w1) @ w2,
                         options=SMAOptions(backend="xla"))
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 32))
        want = jnp.tanh(x @ w1) @ w2
        np.testing.assert_allclose(np.float32(engine(x)), np.float32(want),
                                   rtol=1e-5, atol=1e-5)
        assert len(traces) == 1
        for _ in range(3):
            engine(x)
        assert len(traces) == 1, "cache hit must not re-trace"
        assert engine.stats.misses == 1
        assert engine.stats.hits == 3
        assert engine.cache_size == 1

    def test_new_shape_compiles_once(self):
        w1, w2 = _mlp_weights()
        engine = sma_jit(lambda x: jnp.tanh(x @ w1) @ w2,
                         options=SMAOptions(backend="xla"))
        engine(jnp.zeros((4, 32)))
        engine(jnp.zeros((16, 32)))   # new batch -> miss
        engine(jnp.zeros((16, 32)))   # -> hit
        engine(jnp.zeros((4, 32)))    # first entry still cached
        assert engine.stats.misses == 2
        assert engine.stats.hits == 2
        assert engine.cache_size == 2

    def test_dtype_is_part_of_the_key(self):
        engine = sma_jit(lambda x: x * 2.0, options=SMAOptions(backend="xla"))
        engine(jnp.zeros((4,), jnp.float32))
        engine(jnp.zeros((4,), jnp.bfloat16))
        assert engine.stats.misses == 2

    def test_weak_type_is_part_of_the_key(self):
        engine = sma_jit(lambda x, c: x + c,
                         options=SMAOptions(backend="xla"))
        x = jnp.zeros((4,), jnp.float32)
        engine(x, 2.0)                          # python scalar: weak f32
        engine(x, jnp.float32(2.0))             # committed f32 -> new entry
        engine(x, 3.0)                          # weak f32 again -> hit
        assert engine.stats.misses == 2
        assert engine.stats.hits == 1

    def test_pytree_structure_is_part_of_the_key(self):
        engine = sma_jit(lambda d: d["a"] + d.get("b", 0.0),
                         options=SMAOptions(backend="xla"))
        engine({"a": jnp.ones((2,))})
        engine({"a": jnp.ones((2,)), "b": jnp.ones((2,))})
        assert engine.stats.misses == 2

    def test_static_kwargs_key_and_control_flow(self):
        w1, w2 = _mlp_weights()

        @sma_jit(static_argnames=("act",), options=SMAOptions(backend="xla"))
        def mlp(x, *, act):
            h = x @ w1
            h = jnp.tanh(h) if act == "tanh" else jax.nn.relu(h)
            return h @ w2

        x = jax.random.normal(jax.random.PRNGKey(3), (4, 32))
        got_t = mlp(x, act="tanh")
        got_r = mlp(x, act="relu")
        assert mlp.stats.misses == 2
        mlp(x, act="tanh")
        assert mlp.stats.hits == 1
        np.testing.assert_allclose(np.float32(got_t),
                                   np.float32(jnp.tanh(x @ w1) @ w2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.float32(got_r),
                                   np.float32(jax.nn.relu(x @ w1) @ w2),
                                   rtol=1e-5, atol=1e-5)

    def test_non_jax_leaf_without_static_marker_raises(self):
        engine = sma_jit(lambda x, mode: x)
        with pytest.raises(TypeError, match="static_argnames"):
            engine(jnp.zeros((2,)), "greedy")

    def test_resolved_options_are_part_of_the_key(self):
        w1, w2 = _mlp_weights()
        engine = sma_jit(lambda x: jax.nn.relu(x @ w1) @ w2)
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 32))
        with repro.options(backend="xla"):
            engine(x)
        with repro.options(backend="interpret"):
            got = engine(x)
        assert engine.stats.misses == 2
        with repro.options(backend="xla"):
            engine(x)
        assert engine.stats.hits == 1
        np.testing.assert_allclose(np.float32(got),
                                   np.float32(jax.nn.relu(x @ w1) @ w2),
                                   rtol=2e-4, atol=2e-4)

    def test_compile_accepts_shape_structs(self):
        w1, w2 = _mlp_weights()
        engine = sma_jit(lambda x: jnp.tanh(x @ w1) @ w2,
                         options=SMAOptions(backend="xla"))
        compiled = engine.compile(jax.ShapeDtypeStruct((8, 32), jnp.float32))
        assert compiled.report["dispatch"]["systolic_dispatch_sites"] == 2
        # the real call with the same signature reuses the entry
        engine(jnp.zeros((8, 32), jnp.float32))
        assert engine.stats.misses == 1 and engine.stats.hits == 1
        assert compiled.executable is None   # interpreted: nothing to show

    def test_jit_compile_builds_the_executable(self):
        """Under jit the XLA compile belongs to the entry's compile: a
        compile on shapes yields the executable and its memory analysis."""
        w1, w2 = _mlp_weights()
        engine = sma_jit(lambda x: jnp.tanh(x @ w1) @ w2,
                         options=SMAOptions(backend="xla", jit=True))
        compiled = engine.compile(jax.ShapeDtypeStruct((8, 32), jnp.float32))
        mem = compiled.executable.memory_analysis()
        assert mem.output_size_in_bytes == 8 * w2.shape[1] * 4
        x = jnp.ones((8, 32), jnp.float32)
        np.testing.assert_allclose(np.asarray(engine(x)),
                                   np.asarray(jnp.tanh(x @ w1) @ w2),
                                   rtol=2e-4, atol=2e-4)
        assert engine.stats.misses == 1 and engine.stats.hits == 1

    def test_engine_report_and_plan_report_carry_cache_stats(self):
        w1, w2 = _mlp_weights()
        engine = sma_jit(lambda x: jnp.tanh(x @ w1) @ w2,
                         options=SMAOptions(backend="xla"), name="mlp")
        x = jnp.zeros((4, 32))
        engine(x)
        engine(x)
        rep = engine.report
        assert rep["engine"] == "mlp"
        assert rep["cache"]["hits"] == 1 and rep["cache"]["misses"] == 1
        assert rep["cache"]["compile_time_s"] > 0
        (entry,) = rep["entries"]
        assert entry["cache_hits"] == 1
        per_sig = engine.compile(x).report["engine"]
        assert per_sig["cache_hits"] == 2  # compile() itself was a hit
        assert per_sig["amortized_compile_s"] <= per_sig["compile_time_s"]
        import json
        json.dumps(rep)


# ===========================================================================
# SMAOptions: the single configuration path
# ===========================================================================
class TestOptionsPropagation:
    def test_engine_options_reach_the_kernel_call(self, monkeypatch):
        """SMAOptions(backend='interpret', autotune=False) must arrive at
        kernels.ops.sma_gemm — end-to-end through trace->dispatch."""
        from repro.kernels import ops as kernel_ops
        seen = []
        orig = kernel_ops.sma_gemm

        def spy(a, b, **kw):
            seen.append(kw)
            return orig(a, b, **kw)

        monkeypatch.setattr(kernel_ops, "sma_gemm", spy)
        w1, _ = _mlp_weights()
        engine = sma_jit(lambda x: jax.nn.relu(x @ w1),
                         options=SMAOptions(backend="interpret",
                                            autotune=False))
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 32))
        got = engine(x)
        assert seen, "dispatch must route the GEMM through kernels.ops"
        assert all(kw["backend"] == "interpret" for kw in seen)
        assert all(kw["autotune"] is False for kw in seen)
        np.testing.assert_allclose(np.float32(got),
                                   np.float32(jax.nn.relu(x @ w1)),
                                   rtol=2e-4, atol=2e-4)

    def test_block_overrides_reach_the_kernel_call(self, monkeypatch):
        from repro.kernels import ops as kernel_ops
        seen = []
        orig = kernel_ops.sma_gemm

        def spy(a, b, **kw):
            seen.append(kw)
            return orig(a, b, **kw)

        monkeypatch.setattr(kernel_ops, "sma_gemm", spy)
        w1, _ = _mlp_weights(k=32, h=64)
        engine = sma_jit(lambda x: x @ w1,
                         options=SMAOptions(backend="interpret",
                                            block_m=8, block_n=64,
                                            block_k=32))
        engine(jnp.ones((8, 32), jnp.float32))
        assert seen and seen[0]["block_m"] == 8
        assert seen[0]["block_n"] == 64 and seen[0]["block_k"] == 32

    def test_ambient_context_reaches_bare_kernel_calls(self, monkeypatch):
        """Even a hand-written ops.sma_gemm call obeys repro.options(...)."""
        from repro.kernels import ops as kernel_ops
        from repro.kernels import sma_gemm as kernel_mod
        calls = []
        orig = kernel_mod.sma_gemm
        monkeypatch.setattr(kernel_mod, "sma_gemm",
                            lambda *a, **kw: (calls.append(kw),
                                              orig(*a, **kw))[1])
        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        kernel_ops.sma_gemm(a, b)              # default: xla ref on CPU
        assert not calls
        with repro.options(backend="interpret"):
            kernel_ops.sma_gemm(a, b)          # ambient -> Pallas interpret
        assert len(calls) == 1 and calls[0]["interpret"] is True

    def test_context_nesting_inner_wins_outer_survives(self):
        assert current_options().backend is DEFAULTS.backend
        with repro.options(autotune=True, backend="xla"):
            assert current_options().autotune is True
            assert current_options().backend == "xla"
            with repro.options(backend="interpret"):
                o = current_options()
                assert o.backend == "interpret"
                assert o.autotune is True      # inherited from outer
            assert current_options().backend == "xla"
        assert current_options().autotune is DEFAULTS.autotune

    def test_explicit_options_beat_ambient_context(self):
        with repro.options(backend="interpret", autotune=True):
            o = resolve_options(SMAOptions(backend="xla"))
            assert o.backend == "xla"          # explicit wins
            assert o.autotune is True          # unset field inherits

    def test_options_object_context_form(self):
        with repro.options(SMAOptions(max_epilogue_ops=2)):
            assert current_options().max_epilogue_ops == 2
        with pytest.raises(TypeError):
            with repro.options(SMAOptions(), backend="xla"):
                pass

    def test_policy_objects_never_alias_in_the_cache_key(self):
        """Keys hold the policy object itself (identity hash + strong ref),
        so a GC'd policy's recycled id can never collide two entries."""
        from repro.core.sma import SMAPolicy
        p0 = SMAPolicy(max_epilogue_ops=0)
        k0 = SMAOptions(policy=p0).cache_key()
        assert p0 in k0  # the key keeps the policy alive
        del p0
        k1 = SMAOptions(policy=SMAPolicy(max_epilogue_ops=4)).cache_key()
        assert k0 != k1

    def test_donate_argnums_map_to_flat_leaf_indices(self):
        from repro.compiler.dispatch import _flat_donate_indices
        args = ({"a": jnp.zeros(2), "b": jnp.zeros(3)},   # 2 leaves
                jnp.zeros(4),                              # 1 leaf
                [jnp.zeros(1), jnp.zeros(1)])              # 2 leaves
        assert _flat_donate_indices(args, {}, (0,)) == (0, 1)
        assert _flat_donate_indices(args, {}, (1,)) == (2,)
        assert _flat_donate_indices(args, {}, (0, 2)) == (0, 1, 3, 4)
        assert _flat_donate_indices(args, {}, ()) == ()

    def test_donation_through_the_engine(self):
        """A donated train-style step still computes correctly and reuses
        the cache entry (donation is baked into the jitted runner)."""
        engine = sma_jit(lambda p, g: jax.tree.map(lambda w, d: w - d, p, g),
                        options=SMAOptions(backend="xla", jit=True,
                                           donate_argnums=(0,)))
        p = {"w": jnp.arange(4.0)}
        for step in range(3):
            p = engine(p, {"w": jnp.ones(4)})
        np.testing.assert_allclose(np.asarray(p["w"]),
                                   np.arange(4.0) - 3.0)
        assert engine.stats.misses == 1 and engine.stats.hits == 2

    def test_fuse_runtime_off_via_options(self):
        w1, _ = _mlp_weights()
        engine = sma_jit(lambda x: jax.nn.relu(x @ w1 + 0.5),
                         options=SMAOptions(backend="xla",
                                            fuse_runtime=False))
        compiled = engine.compile(jnp.zeros((4, 32)))
        assert compiled.report["fusion"]["realized_fused_sites"] == 0
        assert compiled.rewritten is None


# ===========================================================================
# Deprecated shims (one release of back-compat)
# ===========================================================================
class TestDeprecatedShims:
    def test_compile_model_warns_and_matches_engine(self):
        from repro import compiler
        w1, w2 = _mlp_weights()

        def mlp(x):
            return jnp.tanh(x @ w1) @ w2

        x = jax.random.normal(jax.random.PRNGKey(6), (8, 32))
        with pytest.warns(DeprecationWarning, match="sma_jit"):
            compiled = compiler.compile_model(mlp, x, backend="xla")
        np.testing.assert_allclose(np.float32(compiled(x)),
                                   np.float32(mlp(x)),
                                   rtol=1e-5, atol=1e-5)
        assert "engine" in compiled.report

    def test_compile_model_legacy_knobs_map_to_options(self):
        from repro import compiler
        w1, _ = _mlp_weights()
        with pytest.warns(DeprecationWarning):
            compiled = compiler.compile_model(
                lambda x: jax.nn.relu(x @ w1 + 0.5), jnp.zeros((4, 32)),
                backend="xla", fuse_runtime=False)
        assert compiled.options.fuse_runtime is False
        assert compiled.report["fusion"]["realized_fused_sites"] == 0

    def test_compile_model_explicit_falsy_kwargs_beat_ambient(self):
        """An explicit interpret=False must win over an ambient
        repro.options(interpret=True) — omitted kwargs inherit, explicit
        ones never do."""
        from repro import compiler
        w1, _ = _mlp_weights()
        with repro.options(interpret=True, fuse_runtime=False):
            with pytest.warns(DeprecationWarning):
                explicit = compiler.compile_model(
                    lambda x: x @ w1, jnp.zeros((4, 32)),
                    backend="xla", interpret=False, fuse_runtime=True)
            with pytest.warns(DeprecationWarning):
                inherited = compiler.compile_model(
                    lambda x: x @ w1, jnp.zeros((4, 32)), backend="xla")
        assert explicit.options.interpret is False
        assert explicit.options.fuse_runtime is True
        assert inherited.options.interpret is True
        assert inherited.options.fuse_runtime is False

    def test_sma_matmul_warns_and_matches_oracle(self):
        from repro.core.sma import sma_matmul
        from repro.kernels import ref
        a = jax.random.normal(KEY, (16, 32), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 8), jnp.float32)
        bias = jnp.ones((8,), jnp.float32) * 0.1
        with pytest.warns(DeprecationWarning, match="sma_gemm"):
            got = sma_matmul(a, b, epilogue="gelu", bias=bias, backend="xla")
        np.testing.assert_allclose(
            np.float32(got),
            np.float32(ref.gemm_ref(a, b, bias=bias, epilogue="gelu")),
            rtol=1e-5, atol=1e-5)

    def test_top_level_reexports(self):
        assert repro.sma_jit is sma_jit
        assert repro.SMAOptions is SMAOptions
        assert isinstance(repro.sma_jit(lambda x: x), Engine)
        import repro.compiler as comp
        assert repro.compiler is comp
