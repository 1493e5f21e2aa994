"""Compiler pipeline tests: SMAPolicy edge cases, jaxpr lowering of each
OpKind, ten-family compile coverage, and dispatch correctness.

The ten-family cases trace with ``jax.eval_shape`` parameter placeholders —
compile-only, no parameter memory — and assert the plan summaries are
non-trivial (mode switches, fused epilogues, HBM bytes avoided).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro import compiler
from repro.core.modes import ExecMode, Op, OpKind
from repro.core.sma import SMAPolicy
from repro.models import lm
from repro.models.layers import Runtime

KEY = jax.random.PRNGKey(0)
RT = Runtime(remat=False)


def kinds_of(fn, *args, **lower_kw):
    traced = compiler.trace_model(fn, *args)
    program = compiler.lower_jaxpr(traced.closed_jaxpr, **lower_kw)
    return program, {op.kind for op in program.ops}


# ===========================================================================
# SMAPolicy edge cases
# ===========================================================================
class TestPolicyEdges:
    def test_epilogue_budget_exhaustion(self):
        """A 5th tile-local SIMD op overflows max_epilogue_ops=4 and must
        open a SIMD group instead of fusing."""
        ops = [Op("gemm", OpKind.MATMUL, flops=1e9)] + [
            Op(f"ew{i}", OpKind.ELEMENTWISE, flops=1e3, bytes_in=1e3)
            for i in range(6)]
        policy = SMAPolicy(max_epilogue_ops=4)
        groups = policy.plan(ops)
        assert len(groups) == 2
        assert groups[0].mode == ExecMode.SYSTOLIC
        assert groups[0].fused_simd_ops == 4
        assert groups[1].mode == ExecMode.SIMD
        assert len(groups[1].ops) == 2

    def test_tile_local_false_breaks_fusion(self):
        """A fusable-kind op with tile_local=False (cross-tile softmax) must
        not attach to the open systolic group."""
        ops = [Op("gemm", OpKind.MATMUL, flops=1e9),
               Op("softmax_full", OpKind.REDUCTION, flops=1e4,
                  bytes_in=1e4, tile_local=False),
               Op("scale", OpKind.ELEMENTWISE, flops=1e3)]
        groups = SMAPolicy().plan(ops)
        assert groups[0].fused_simd_ops == 0
        assert groups[1].mode == ExecMode.SIMD
        # the trailing elementwise coalesces into the SIMD group, it does
        # NOT rejoin the closed systolic group
        assert len(groups) == 2 and len(groups[1].ops) == 2

    def test_leading_simd_program(self):
        """Programs that open in SIMD mode (embedding gather first) plan a
        leading anchorless group and count the switch into systolic."""
        ops = [Op("embed", OpKind.GATHER_SCATTER, tile_local=False),
               Op("scale", OpKind.ELEMENTWISE, flops=1e3),
               Op("gemm", OpKind.MATMUL, flops=1e9)]
        policy = SMAPolicy()
        groups = policy.plan(ops)
        assert groups[0].anchor is None and len(groups[0].ops) == 2
        assert groups[1].mode == ExecMode.SYSTOLIC
        assert policy.summarize(ops).mode_switches == 1

    def test_fuse_epilogues_off(self):
        ops = [Op("gemm", OpKind.MATMUL, flops=1e9),
               Op("relu", OpKind.ELEMENTWISE, flops=1e3, bytes_in=1e3)]
        summary = SMAPolicy(fuse_epilogues=False).summarize(ops)
        assert summary.fused_simd_ops == 0
        assert summary.hbm_bytes_avoided == 0.0

    def test_consecutive_systolic_anchors_each_open_groups(self):
        ops = [Op("a", OpKind.MATMUL, flops=1e9),
               Op("b", OpKind.MATMUL, flops=1e9),
               Op("c", OpKind.ATTENTION_MATMUL, flops=1e9)]
        groups = SMAPolicy().plan(ops)
        assert len(groups) == 3
        assert all(g.mode == ExecMode.SYSTOLIC for g in groups)


# ===========================================================================
# jaxpr lowering: one case per OpKind mapping
# ===========================================================================
class TestLowering:
    def test_dot_general_matmul_kind_and_flops(self):
        a = jnp.zeros((8, 32))
        b = jnp.zeros((32, 16))
        program, kinds = kinds_of(lambda x, y: x @ y, a, b)
        assert kinds == {OpKind.MATMUL}
        (op,) = program.ops
        assert op.flops == 2 * 8 * 16 * 32
        assert op.bytes_in == (8 * 32 + 32 * 16) * 4
        assert op.bytes_out == 8 * 16 * 4

    def test_batched_dot_is_attention_matmul(self):
        q = jnp.zeros((2, 4, 16, 8))
        k = jnp.zeros((2, 4, 16, 8))
        fn = lambda q, k: jnp.einsum("bhqd,bhkd->bhqk", q, k)
        program, kinds = kinds_of(fn, q, k)
        assert OpKind.ATTENTION_MATMUL in kinds
        (op,) = [o for o in program.ops if o.kind == OpKind.ATTENTION_MATMUL]
        assert op.flops == 2 * (2 * 4) * 16 * 16 * 8

    def test_softmax_lowers_to_reduction_and_elementwise(self):
        x = jnp.zeros((4, 64))
        program, kinds = kinds_of(lambda x: jax.nn.softmax(x, -1), x)
        assert OpKind.REDUCTION in kinds
        assert OpKind.ELEMENTWISE in kinds
        # last-axis reductions stay tile-local (fusable epilogues)
        assert all(op.tile_local for op in program.ops
                   if op.kind == OpKind.REDUCTION)

    def test_non_trailing_reduction_not_tile_local(self):
        x = jnp.zeros((4, 64))
        program, _ = kinds_of(lambda x: jnp.sum(x, axis=0), x)
        (op,) = [o for o in program.ops if o.kind == OpKind.REDUCTION]
        assert not op.tile_local

    def test_gather_scatter(self):
        table = jnp.zeros((100, 16))
        idx = jnp.zeros((4,), jnp.int32)
        _, kinds = kinds_of(lambda t, i: t[i], table, idx)
        assert OpKind.GATHER_SCATTER in kinds

    def test_topk(self):
        x = jnp.zeros((4, 64))
        program, kinds = kinds_of(lambda x: jax.lax.top_k(x, 4), x)
        assert OpKind.TOPK in kinds
        assert all(not op.tile_local for op in program.ops
                   if op.kind == OpKind.TOPK)

    def test_long_scan_is_recurrence_marker_plus_amortized_body(self):
        def fn(x):
            return jax.lax.scan(lambda c, _: (c * 0.5 + 1.0, c),
                                x, None, length=100)

        x = jnp.zeros((16,))
        program, kinds = kinds_of(fn, x, max_scan_unroll=8)
        assert OpKind.RECURRENCE in kinds
        rec = [o for o in program.ops if o.kind == OpKind.RECURRENCE]
        assert rec[0].tile_local is False
        # body ops amortized: flops scaled by the trip count
        body_ew = [o for o in program.ops if o.kind == OpKind.ELEMENTWISE]
        assert body_ew and all(o.flops >= 100 * 16 for o in body_ew)
        assert program.stats.coarsened_scans == 1

    def test_short_scan_unrolls_exactly(self):
        def fn(x):
            return jax.lax.scan(lambda c, _: (c + 1.0, c), x, None, length=3)

        program, kinds = kinds_of(fn, jnp.zeros((4,)), max_scan_unroll=8)
        assert OpKind.RECURRENCE not in kinds
        assert program.stats.unrolled_scans == 1
        assert len([o for o in program.ops
                    if o.kind == OpKind.ELEMENTWISE]) == 3

    def test_cast(self):
        _, kinds = kinds_of(lambda x: x.astype(jnp.bfloat16),
                            jnp.zeros((8, 8)))
        assert kinds == {OpKind.CAST}

    def test_elementwise_and_layout_elision(self):
        def fn(x):
            return jnp.tanh(x).reshape(-1)[None, :]

        program, kinds = kinds_of(fn, jnp.zeros((4, 4)))
        assert kinds == {OpKind.ELEMENTWISE}
        assert program.stats.layout_ops_elided >= 1
        (op,) = program.ops  # transcendental weighting
        assert op.flops == 4.0 * 16

    def test_pjit_is_transparent(self):
        f = jax.jit(lambda x: jnp.sin(x) @ jnp.zeros((4, 4)))
        program, kinds = kinds_of(f, jnp.zeros((2, 4)))
        assert OpKind.MATMUL in kinds and OpKind.ELEMENTWISE in kinds


# ===========================================================================
# compile_model over every assigned model family (compile-only, eval_shape)
# ===========================================================================
def _abstract_batch(cfg, b=2, s=16):
    if cfg.input_mode == "tokens":
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.input_mode == "embeds":
        return {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.float32)}
    nv = cfg.num_vision_tokens
    return {"tokens": jax.ShapeDtypeStruct((b, s - nv), jnp.int32),
            "vision_embeds": jax.ShapeDtypeStruct((b, nv, cfg.d_model),
                                                  jnp.float32)}


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_compile_model_all_families_nontrivial(arch):
    cfg = C.reduced(C.get_config(arch))
    p_shapes = jax.eval_shape(lambda k: lm.init(k, cfg)[0], KEY)
    batch = _abstract_batch(cfg)
    compiled = compiler.compile_model(
        lambda p, b: lm.forward(p, cfg, RT, b), p_shapes, batch, name=arch)
    s = compiled.summary
    assert s.groups > 3, arch
    assert s.mode_switches >= 1, arch
    assert s.fused_simd_ops > 0, arch
    assert s.hbm_bytes_avoided > 0, arch
    assert 0.3 < s.systolic_flop_share <= 1.0, arch
    disp = compiled.report["dispatch"]
    assert disp["systolic_dispatch_sites"] > 0, arch
    # report is JSON-serializable
    import json
    json.dumps(compiled.report)


def test_compile_full_scale_config_is_shape_only():
    """Full (132B-class) configs trace abstractly: big scans amortize into
    RECURRENCE-marked steady state, systolic share stays dominant."""
    cfg = C.get_config("dbrx-132b")
    p_shapes = jax.eval_shape(lambda k: lm.init(k, cfg)[0], KEY)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    compiled = compiler.compile_model(
        lambda p, b: lm.forward(p, cfg, RT, b), p_shapes, batch,
        name="dbrx-132b-full")
    assert compiled.plan.stats.coarsened_scans >= 1
    assert compiled.summary.systolic_flop_share > 0.9


# ===========================================================================
# dispatch correctness
# ===========================================================================
class TestDispatch:
    def test_mlp_xla_matches_native(self):
        w1 = jax.random.normal(KEY, (32, 64))
        w2 = jax.random.normal(jax.random.PRNGKey(1), (64, 16))

        def mlp(x):
            return jnp.tanh(x @ w1) @ w2

        x = jax.random.normal(jax.random.PRNGKey(2), (8, 32))
        compiled = compiler.compile_model(mlp, x, backend="xla")
        np.testing.assert_allclose(np.float32(compiled(x)),
                                   np.float32(mlp(x)),
                                   rtol=1e-5, atol=1e-5)
        assert compiled.report["dispatch"]["systolic_dispatch_sites"] == 2

    def test_mlp_interpret_backend_matches_native(self):
        """The Pallas-interpreter backend runs the real kernel logic."""
        w = jax.random.normal(KEY, (32, 48))

        def f(x):
            return jax.nn.relu(x @ w)

        x = jax.random.normal(jax.random.PRNGKey(3), (4, 32))
        compiled = compiler.compile_model(f, x, interpret=True)
        np.testing.assert_allclose(np.float32(compiled(x)),
                                   np.float32(f(x)),
                                   rtol=2e-4, atol=2e-4)

    def test_kernel_body_is_no_dispatch_site(self):
        """A Pallas kernel called from model code is bound as it is: the
        dot inside its body is neither counted nor resolved as a site."""
        from repro.kernels.sma_gemm import sma_gemm
        w = jax.random.normal(KEY, (32, 128))

        def f(x):
            return sma_gemm(x, w, interpret=True)

        x = jax.random.normal(jax.random.PRNGKey(4), (8, 32))
        compiled = compiler.compile_model(f, x, backend="xla")
        np.testing.assert_allclose(np.float32(compiled(x)),
                                   np.float32(x @ w), rtol=2e-4, atol=2e-4)
        assert compiled.report["dispatch"]["systolic_dispatch_sites"] == 0
        assert compiled.report["backends"]["num_sites"] == 0

    def test_model_forward_dispatch_matches_native(self):
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        params, _ = lm.init(KEY, cfg)
        batch = {"tokens": jax.random.randint(KEY, (2, 16), 0,
                                              cfg.vocab_size)}
        fn = functools.partial(lm.forward, cfg=cfg, rt=RT)
        compiled = compiler.compile_model(lambda p, b: fn(p, batch=b),
                                          params, batch, backend="xla")
        got, _ = compiled(params, batch)
        want, _ = lm.forward(params, cfg, RT, batch)
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=1e-4, atol=1e-4)

    def test_recurrent_model_with_scan_dispatch(self):
        """GEMMs inside lax.scan bodies (layer groups + recurrences) route
        through the interpreter's rebuilt scan."""
        cfg = C.reduced(C.get_config("recurrentgemma-2b"))
        params, _ = lm.init(KEY, cfg)
        batch = {"tokens": jax.random.randint(KEY, (2, 16), 0,
                                              cfg.vocab_size)}
        compiled = compiler.compile_model(
            lambda p, b: lm.forward(p, cfg, RT, b), params, batch,
            backend="xla")
        got, _ = compiled(params, batch)
        want, _ = lm.forward(params, cfg, RT, batch)
        np.testing.assert_allclose(np.float32(got), np.float32(want),
                                   rtol=1e-4, atol=1e-4)

    def test_hybrid_workload_with_topk_gather_loop(self):
        """The paper's hybrid shape: GEMM backbone + top-k + gather + an
        iterative refinement loop, compiled and dispatched end to end."""
        w1 = jax.random.normal(KEY, (32, 32)) / 32 ** 0.5
        w2 = jax.random.normal(jax.random.PRNGKey(1), (32, 8)) / 32 ** 0.5

        def hybrid(feats):
            h = jax.nn.relu(feats @ w1)
            logits = h @ w2
            scores = jax.nn.softmax(logits, -1).max(-1)
            top_scores, top_idx = jax.lax.top_k(scores, 4)
            pooled = jnp.take_along_axis(h, top_idx[..., None], axis=1)

            def body(i, q):
                return jax.nn.softmax(q @ (w2.T @ w2) * 0.1 + q, -1)

            q = jax.lax.fori_loop(0, 3, body, jax.nn.softmax(logits, -1))
            return q.argmax(-1), pooled, top_scores

        feats = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 32))
        compiled = compiler.compile_model(hybrid, feats, backend="xla")
        got = compiled(feats)
        want = hybrid(feats)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.float32(g), np.float32(w),
                                       rtol=1e-4, atol=1e-4)
        kinds = {op.kind for op in compiled.plan.ops}
        assert OpKind.TOPK in kinds
        assert OpKind.GATHER_SCATTER in kinds

    def test_wrong_arg_structure_raises(self):
        w = jnp.zeros((4, 4))
        compiled = compiler.compile_model(lambda x: x @ w, jnp.zeros((2, 4)))
        with pytest.raises(TypeError):
            compiled(jnp.zeros((2, 4)), jnp.zeros((2, 4)))

    def test_jit_wrapped_runner(self):
        w = jax.random.normal(KEY, (16, 16))
        compiled = compiler.compile_model(lambda x: x @ w,
                                          jnp.zeros((4, 16)),
                                          backend="xla", jit=True)
        x = jax.random.normal(KEY, (4, 16))
        np.testing.assert_allclose(np.float32(compiled(x)),
                                   np.float32(x @ w), rtol=1e-5, atol=1e-5)
