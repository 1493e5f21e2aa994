"""Gradient compression (int8 + error feedback): quantization error
bounds, the error-feedback invariant, multi-step convergence of the
residual, the compression-ratio accounting, and the compressed psum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.compress import (
    compress_grads,
    compressed_psum,
    compression_ratio,
    decompress,
    init_error,
    roundtrip,
)

KEY = jax.random.PRNGKey(0)


def _grads(key=KEY, scale=1.0):
    k1, k2 = jax.random.split(key)
    return {
        "w": scale * jax.random.normal(k1, (32, 16), jnp.float32),
        "b": scale * jax.random.normal(k2, (16,), jnp.float32),
    }


class TestQuantization:
    def test_error_bounded_by_half_step(self):
        """Per-tensor int8: |deq - x| <= scale/2 = max|x| / 254."""
        g = _grads()
        err = init_error(g)
        q, new_err = compress_grads(g, err)
        deq = decompress(q)
        for key in g:
            bound = np.abs(np.asarray(g[key])).max() / 127.0 / 2.0
            np.testing.assert_array_less(
                np.abs(np.asarray(deq[key]) - np.asarray(g[key])),
                bound + 1e-7)
            # the residual IS that quantization error, negated into the
            # next step's feedback
            np.testing.assert_allclose(np.asarray(new_err[key]),
                                       np.asarray(g[key])
                                       - np.asarray(deq[key]),
                                       atol=1e-7)

    def test_int8_payload(self):
        q, _ = compress_grads(_grads(), init_error(_grads()))
        for leaf in jax.tree_util.tree_leaves(q):
            if leaf.ndim:  # quantized payloads; scales are scalars
                assert leaf.dtype in (jnp.int8, jnp.float32)

    def test_error_feedback_invariant(self):
        """deq + new_err == g + old_err exactly (up to float assoc.):
        nothing is lost, only delayed."""
        g = _grads()
        old_err = jax.tree.map(
            lambda x: 0.01 * jnp.ones_like(x), g)
        q, new_err = compress_grads(g, old_err)
        deq = decompress(q)
        for key in g:
            np.testing.assert_allclose(
                np.asarray(deq[key]) + np.asarray(new_err[key]),
                np.asarray(g[key]) + 0.01,
                rtol=1e-5, atol=1e-6)


class TestRoundtrip:
    def test_matches_compress_then_decompress(self):
        g = _grads()
        err = init_error(g)
        deq_rt, err_rt = roundtrip(g, err)
        q, err2 = compress_grads(g, err)
        deq = decompress(q)
        for a, b in zip(jax.tree_util.tree_leaves(deq_rt),
                        jax.tree_util.tree_leaves(deq)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(err_rt),
                        jax.tree_util.tree_leaves(err2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_residual_stays_bounded_over_steps(self):
        """Error feedback must not accumulate unboundedly on a constant
        gradient stream."""
        g = _grads()
        err = init_error(g)
        bound = {k: np.abs(np.asarray(v)).max() / 127.0 for k, v in
                 g.items()}
        for _ in range(16):
            _, err = roundtrip(g, err)
            for k in g:
                assert np.abs(np.asarray(err[k])).max() <= \
                    2.0 * bound[k] + 1e-6

    def test_mean_gradient_preserved_over_steps(self):
        """Sum over steps of dequantized grads approaches sum of true
        grads: the EF residual is the exact difference at every step."""
        g = _grads(scale=0.05)
        err = init_error(g)
        acc = jax.tree.map(jnp.zeros_like, g)
        steps = 8
        for _ in range(steps):
            deq, err = roundtrip(g, err)
            acc = jax.tree.map(jnp.add, acc, deq)
        for k in g:
            total_err = np.abs(np.asarray(acc[k])
                               - steps * np.asarray(g[k])).max()
            one_step_bound = np.abs(np.asarray(g[k])).max() / 127.0
            assert total_err <= one_step_bound + 1e-6


class TestAccounting:
    def test_compression_ratio_formula(self):
        g = _grads()
        n = sum(x.size for x in jax.tree_util.tree_leaves(g))
        t = len(jax.tree_util.tree_leaves(g))
        expected = (4.0 * n) / (n + 4.0 * t)
        assert compression_ratio(g) == pytest.approx(expected)
        # int8 + one f32 scale per tensor -> close to 4x for real tensors
        assert 3.5 < compression_ratio(g) < 4.0


class TestCompressedPsum:
    def test_matches_uncompressed_mean_single_device(self):
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()[:1])
        mesh = Mesh(devs, ("dp",))
        x = jax.random.normal(KEY, (len(devs), 64), jnp.float32)

        out = jax.jit(jax.shard_map(
            lambda v: compressed_psum(v, "dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
        mean = np.asarray(x).mean(axis=0)
        # one int8 quantization of the shard-local value
        tol = np.abs(np.asarray(x)).max() / 127.0
        np.testing.assert_allclose(np.asarray(out).reshape(-1, 64)[0],
                                   mean, atol=tol + 1e-6)
