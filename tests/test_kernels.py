"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Every kernel is swept over shapes/dtypes and asserted allclose against its
``ref.py`` oracle; hypothesis drives property-style shape generation for the
GEMM kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is optional: property-based cases skip without it
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    given = settings = st = None

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm import mlstm_chunkwise
from repro.kernels.rglru import rglru_scan
from repro.kernels.sma_gemm import sma_gemm

KEY = jax.random.PRNGKey(0)


def tol_for(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-4


def assert_close(got, want, dtype):
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=tol_for(dtype), atol=tol_for(dtype))


# ---------------------------------------------------------------- sma_gemm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,ep,bias", [
    (256, 512, 256, "none", False),
    (128, 384, 320, "gelu", True),
    (100, 70, 50, "relu", True),     # non-multiple shapes -> padding
    (8, 1024, 256, "silu", False),   # skinny M
])
def test_sma_gemm_allclose(m, k, n, ep, bias, dtype):
    ks = jax.random.split(KEY, 3)
    a = jax.random.normal(ks[0], (m, k), dtype)
    b = jax.random.normal(ks[1], (k, n), dtype)
    bias_v = jax.random.normal(ks[2], (n,), dtype) if bias else None
    got = sma_gemm(a, b, bias=bias_v, epilogue=ep, interpret=True,
                   block_m=64, block_n=128, block_k=128)
    want = ref.gemm_ref(a, b, bias=bias_v, epilogue=ep)
    assert_close(got, want, dtype)


def test_sma_gemm_narrow_accum_matches_ref():
    """A bf16 ``accum_dtype`` (a bf16 einsum's preferred type) names only
    the output rounding: kernel and reference both accumulate, add the
    bias and apply the epilogue in f32, so they differ by at most one bf16
    rounding."""
    ks = jax.random.split(KEY, 3)
    a = jax.random.normal(ks[0], (64, 512), jnp.bfloat16)
    b = jax.random.normal(ks[1], (512, 256), jnp.bfloat16)
    bias = jax.random.normal(ks[2], (256,), jnp.bfloat16)
    kw = dict(bias=bias, epilogue="gelu", accum_dtype=jnp.bfloat16)
    got = np.float32(sma_gemm(a, b, interpret=True, block_m=64,
                              block_n=128, block_k=128, **kw))
    want = np.float32(ref.gemm_ref(a, b, **kw))
    # One bf16 ulp, plus f32 summation-order noise near zero.
    bound = (2.0 ** -7 * np.maximum(np.abs(got), np.abs(want))
             + 1e-5 * np.abs(want).max())
    assert np.all(np.abs(got - want) <= bound)


def test_sma_gemm_batched_leading_dims():
    a = jax.random.normal(KEY, (2, 3, 64, 128), jnp.float32)
    b = jax.random.normal(KEY, (128, 96), jnp.float32)
    got = sma_gemm(a, b, interpret=True, block_m=64, block_n=64, block_k=64)
    assert got.shape == (2, 3, 64, 96)
    assert_close(got, ref.gemm_ref(a, b), jnp.float32)


if st is not None:
    @settings(max_examples=12, deadline=None)
    @given(m=st.integers(1, 96), k=st.integers(1, 96), n=st.integers(1, 96),
           ep=st.sampled_from(["none", "relu", "gelu", "silu", "tanh"]))
    def test_sma_gemm_property(m, k, n, ep):
        """Property: kernel == oracle for arbitrary small shapes+epilogues."""
        a = jax.random.normal(jax.random.PRNGKey(m * 997 + k), (m, k))
        b = jax.random.normal(jax.random.PRNGKey(n), (k, n))
        got = sma_gemm(a, b, epilogue=ep, interpret=True,
                       block_m=32, block_n=32, block_k=32)
        assert_close(got, ref.gemm_ref(a, b, epilogue=ep), jnp.float32)
else:
    def test_sma_gemm_property():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------- flash_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window", [
    (2, 4, 4, 256, 256, 64, True, None),
    (1, 8, 2, 256, 256, 64, True, None),      # GQA
    (1, 4, 1, 192, 192, 32, True, 64),        # MQA + sliding window
    (2, 2, 2, 100, 100, 64, True, None),      # padding
    (1, 2, 2, 128, 128, 64, False, None),     # non-causal
    (1, 4, 4, 64, 256, 64, True, None),       # sq < skv, end-aligned
])
def test_flash_attention_allclose(b, hq, hkv, sq, skv, d, causal, window,
                                  dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal, window=window)
    assert_close(got, want, dtype)


def test_flash_attention_xla_path_matches_oracle():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 8, 200, 64))
    k = jax.random.normal(ks[1], (2, 2, 200, 64))
    v = jax.random.normal(ks[2], (2, 2, 200, 64))
    for w in (None, 64):
        got = ops._chunked_mha_xla(q, k, v, causal=True, window=w,
                                   scale=None, chunk=64)
        assert_close(got, ref.mha_ref(q, k, v, causal=True, window=w),
                     jnp.float32)


# ---------------------------------------------------- decode_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,smax,d,bs,lens", [
    (2, 8, 2, 512, 64, 128, [512, 100]),
    (1, 4, 4, 256, 64, 64, [1]),
    (3, 4, 1, 300, 128, 128, [300, 37, 250]),  # padding + MQA
])
def test_decode_attention_allclose(b, hq, hkv, smax, d, bs, lens, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kc = jax.random.normal(ks[1], (b, hkv, smax, d), dtype)
    vc = jax.random.normal(ks[2], (b, hkv, smax, d), dtype)
    cl = jnp.array(lens, jnp.int32)
    got = decode_attention(q, kc, vc, cl, block_s=bs, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, cl)
    assert_close(got, want, dtype)


# ------------------------------------------------------------- rglru
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,d,bs,bd,h0", [
    (2, 128, 256, 64, 128, True),
    (1, 100, 96, 32, 64, False),    # padding both dims
    (1, 257, 130, 64, 128, True),   # awkward pads
])
def test_rglru_allclose(b, s, d, bs, bd, h0, dtype):
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, d), dtype))
    u = (jax.random.normal(ks[1], (b, s, d), dtype) * 0.1).astype(dtype)
    h = jax.random.normal(ks[2], (b, d), dtype) if h0 else None
    gs, gl = rglru_scan(a, u, h, block_s=bs, block_d=bd, interpret=True)
    ws, wl = ref.rglru_ref(a, u, h)
    assert_close(gs, ws, dtype)
    assert_close(gl, wl, dtype)


def test_rglru_xla_associative_scan_matches_sequential():
    a = jax.nn.sigmoid(jax.random.normal(KEY, (2, 100, 32)))
    u = jax.random.normal(KEY, (2, 100, 32)) * 0.1
    h0 = jax.random.normal(KEY, (2, 32))
    gs, gl = ops.rglru_scan(a, u, h0, backend="xla")
    ws, wl = ref.rglru_ref(a, u, h0)
    assert_close(gs, ws, jnp.float32)
    assert_close(gl, wl, jnp.float32)


# ------------------------------------------------------------- mlstm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,s,d,chunk", [
    (1, 2, 128, 32, 32),
    (2, 1, 96, 64, 32),
    (1, 1, 100, 32, 64),   # padding
])
def test_mlstm_kernel_allclose(b, h, s, d, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, h, s), dtype) + 2.0)
    li = (jax.random.normal(ks[4], (b, h, s)) * 0.5).astype(dtype)
    got = mlstm_chunkwise(q, k, v, lf, li, chunk=chunk, interpret=True)
    want = ref.mlstm_ref(q, k, v, lf, li)
    assert_close(got, want, dtype)


def test_mlstm_xla_chunkwise_matches_sequential():
    ks = jax.random.split(KEY, 5)
    b, h, s, d = 2, 2, 100, 32
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, h, s)) + 2.0)
    li = jax.random.normal(ks[4], (b, h, s)) * 0.5
    got = ops._mlstm_chunkwise_xla(q, k, v, lf, li, chunk=32)
    assert_close(got, ref.mlstm_ref(q, k, v, lf, li), jnp.float32)


if st is not None:
    @settings(max_examples=8, deadline=None)
    @given(s=st.integers(2, 80), chunk=st.sampled_from([8, 16, 32]))
    def test_mlstm_chunk_invariance(s, chunk):
        """Property: output independent of chunk size (exact handoff)."""
        ks = jax.random.split(jax.random.PRNGKey(s), 5)
        q = jax.random.normal(ks[0], (1, 1, s, 16))
        k = jax.random.normal(ks[1], (1, 1, s, 16))
        v = jax.random.normal(ks[2], (1, 1, s, 16))
        lf = jax.nn.log_sigmoid(jax.random.normal(ks[3], (1, 1, s)) + 1.0)
        li = jax.random.normal(ks[4], (1, 1, s)) * 0.5
        a = ops._mlstm_chunkwise_xla(q, k, v, lf, li, chunk=chunk)
        b = ref.mlstm_ref(q, k, v, lf, li)
        assert_close(a, b, jnp.float32)
else:
    def test_mlstm_chunk_invariance():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------- rmsnorm_gemm (prologue)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,ep", [
    (256, 512, 256, "none"),
    (100, 130, 70, "gelu"),   # padding on every dim
    (32, 1024, 64, "silu"),
])
def test_rmsnorm_gemm_allclose(m, k, n, ep, dtype):
    from repro.kernels.norm_gemm import rmsnorm_gemm
    x = jax.random.normal(KEY, (m, k), dtype)
    g = (jax.random.normal(jax.random.PRNGKey(1), (k,), dtype) * 0.1
         + 1.0).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n), dtype)
    got = rmsnorm_gemm(x, g, w, epilogue=ep, interpret=True,
                       block_m=64, block_n=64, block_k=64)
    want = ref.rmsnorm_gemm_ref(x, g, w, epilogue=ep)
    assert_close(got, want, dtype)


def test_rmsnorm_gemm_closes_mode_loop():
    """Prologue fusion + epilogue fusion: SIMD->systolic->SIMD in-kernel;
    result == unfused three-op reference."""
    from repro.kernels.norm_gemm import rmsnorm_gemm
    x = jax.random.normal(KEY, (128, 256))
    g = jnp.ones((256,))
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 128))
    fused = rmsnorm_gemm(x, g, w, epilogue="relu", interpret=True,
                         block_m=64, block_n=64, block_k=128)
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), -1, keepdims=True) + 1e-6)
    unfused = jax.nn.relu(normed @ w)
    assert_close(fused, unfused, jnp.float32)


# ---------------------------------------------------- block autotuner
class TestAutotune:
    def test_heuristic_clips_to_problem(self):
        from repro.kernels import autotune
        bm, bn, bk = autotune.heuristic_blocks(32, 300, 96, jnp.float32)
        assert bm == 32          # decode-shaped M: no 256-row padding waste
        assert bn == 256 and bn % 128 == 0
        assert bk == 128         # K=96 rounds up to one MXU tile

    def test_heuristic_respects_vmem_budget(self):
        from repro.kernels import autotune
        for dtype in (jnp.float32, jnp.bfloat16):
            bm, bn, bk = autotune.heuristic_blocks(4096, 8192, 8192, dtype)
            assert autotune.block_footprint_bytes(bm, bn, bk, dtype) \
                <= autotune.VMEM_BUDGET

    def test_heuristic_bf16_streams_deeper_k(self):
        from repro.kernels import autotune
        _, _, bk32 = autotune.heuristic_blocks(512, 512, 4096, jnp.float32)
        _, _, bk16 = autotune.heuristic_blocks(512, 512, 4096, jnp.bfloat16)
        assert bk16 >= bk32

    def test_explicit_blocks_always_win(self):
        from repro.kernels import autotune
        assert autotune.resolve_blocks(64, 64, 64, jnp.float32,
                                       16, 32, 64) == (16, 32, 64)
        bm, bn, bk = autotune.resolve_blocks(64, 64, 64, jnp.float32,
                                             block_m=16)
        assert bm == 16  # explicit M kept, N/K filled from the heuristic

    def test_kernel_resolves_none_blocks(self):
        a = jax.random.normal(KEY, (24, 48))
        b = jax.random.normal(jax.random.PRNGKey(1), (48, 40))
        got = sma_gemm(a, b, interpret=True)  # block_* default to None
        assert_close(got, ref.gemm_ref(a, b), jnp.float32)

    def test_measured_search_picks_candidate_and_caches(self):
        from repro.kernels import autotune
        autotune.clear_measured_cache()
        cands = [(16, 64, 64), (32, 64, 64)]
        best = autotune.measured_blocks(32, 64, 64, jnp.float32,
                                        interpret=True, iters=1,
                                        candidates=cands)
        assert best in cands
        # second call must hit the cache even with different candidates
        again = autotune.measured_blocks(32, 64, 64, jnp.float32,
                                         interpret=True, iters=1,
                                         candidates=[(8, 64, 64)])
        assert again == best
        autotune.clear_measured_cache()

    def test_ops_entry_point_autotune_flag(self):
        from repro.kernels import autotune, ops
        autotune.clear_measured_cache()
        a = jax.random.normal(KEY, (16, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
        got = ops.sma_gemm(a, b, interpret=True, autotune=True)
        assert_close(got, ref.gemm_ref(a, b), jnp.float32)
        assert autotune._MEASURED_CACHE  # search ran and cached
        autotune.clear_measured_cache()


def test_sma_gemm_precision_plumbs_through():
    a = jax.random.normal(KEY, (16, 32))
    b = jax.random.normal(jax.random.PRNGKey(1), (32, 24))
    hi = sma_gemm(a, b, interpret=True, block_m=16, block_n=24, block_k=32,
                  precision=jax.lax.Precision.HIGHEST)
    assert_close(hi, ref.gemm_ref(a, b, precision=jax.lax.Precision.HIGHEST),
                 jnp.float32)
