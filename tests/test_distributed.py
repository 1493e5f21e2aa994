"""Distribution tests: sharding rules, pipeline parallelism, compressed
collectives, and a small-mesh dry-run integration.

Multi-device cases run in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the main pytest
process keeps its single CPU device, as smoke tests should see 1 device).
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

import repro.configs as C
from repro.distributed.sharding import rules_for


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------ sharding rules
class TestRules:
    def _mesh(self, multi=False):
        # rules_for only reads axis names/sizes — safe on one device via
        # an abstract mesh.
        shape = (2, 16, 16) if multi else (16, 16)
        names = ("pod", "data", "model") if multi else ("data", "model")
        try:
            return jax.sharding.AbstractMesh(shape, names)
        except TypeError:  # jax<=0.4.x: takes ((name, size), ...) pairs
            return jax.sharding.AbstractMesh(tuple(zip(names, shape)))

    def test_divisible_heads_get_tp(self):
        cfg = C.get_config("stablelm-1.6b")  # 32 heads
        r = rules_for(cfg, self._mesh(), batch_size=256, kind="train")
        assert r.heads == "model" and r.kv_heads == "model"

    def test_indivisible_heads_fall_back(self):
        cfg = C.get_config("deepseek-coder-33b")  # 56 heads, kv 8
        r = rules_for(cfg, self._mesh(), batch_size=256, kind="train")
        assert r.heads is None and r.kv_heads is None
        assert r.head_dim == "model"  # hd=128 picks up the TP axis instead

    def test_decode_context_parallel(self):
        cfg = C.get_config("mistral-nemo-12b")  # kv 8 < 16
        r = rules_for(cfg, self._mesh(), batch_size=128, kind="decode")
        assert r.kv_seq == "model" and r.head_dim is None

    def test_batch_1_drops_dp(self):
        cfg = C.get_config("xlstm-1.3b")
        r = rules_for(cfg, self._mesh(True), batch_size=1, kind="decode")
        assert r.batch is None

    def test_batch_hierarchical(self):
        cfg = C.get_config("stablelm-1.6b")
        r = rules_for(cfg, self._mesh(True), batch_size=256, kind="train")
        assert r.batch == ("pod", "data")

    def test_spec_never_reuses_axis(self):
        """A PartitionSpec may not name one mesh axis twice."""
        for arch in C.ARCH_IDS:
            cfg = C.get_config(arch)
            for kind, bs in (("train", 256), ("decode", 128)):
                r = rules_for(cfg, self._mesh(), batch_size=bs, kind=kind)
                spec = r.spec("batch", "kv_heads", "kv_seq", "head_dim",
                              mesh_axes=("data", "model"))
                flat = []
                for part in spec:
                    if isinstance(part, tuple):
                        flat.extend(part)
                    elif part is not None:
                        flat.append(part)
                assert len(flat) == len(set(flat)), (arch, kind, spec)


# ------------------------------------------------------------- fake meshes
class TestFakeMesh:
    def test_single_device_mesh(self):
        from repro.launch.mesh import fake_mesh
        mesh = fake_mesh(1)
        assert mesh.axis_names == ("data", "model")
        assert dict(mesh.shape) == {"data": 1, "model": 1}

    def test_too_many_devices_raises_with_flag_hint(self):
        from repro.launch.mesh import fake_mesh
        n = len(jax.devices()) + 1
        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            fake_mesh(n)

    def test_production_mesh_raises_clear_error(self):
        from repro.launch.mesh import make_production_mesh
        with pytest.raises(ValueError, match="256 devices"):
            make_production_mesh()
        with pytest.raises(ValueError, match="512 devices"):
            make_production_mesh(multi_pod=True)

    def test_balanced_grids(self):
        from repro.launch.mesh import _balanced_grid
        assert _balanced_grid(1) == (1, 1)
        assert _balanced_grid(2) == (1, 2)
        assert _balanced_grid(4) == (2, 2)
        assert _balanced_grid(8) == (2, 4)
        assert _balanced_grid(6) == (2, 3)

    def test_four_fake_devices(self):
        out = run_subprocess("""
            from repro.launch.mesh import fake_mesh
            mesh = fake_mesh(4)
            assert dict(mesh.shape) == {'data': 2, 'model': 2}, mesh
            mesh2 = fake_mesh(2, axes=('x', 'y'))
            assert dict(mesh2.shape) == {'x': 1, 'y': 2}, mesh2
            print('FAKE_MESH_OK')
        """, devices=4)
        assert "FAKE_MESH_OK" in out


# ----------------------------------------------------- pipeline parallelism
def test_bubble_fraction():
    from repro.distributed.pipeline import bubble_fraction
    assert bubble_fraction(1, 8) == 0.0           # one stage: no bubble
    assert bubble_fraction(4, 1) == pytest.approx(3 / 4)
    assert bubble_fraction(4, 8) == pytest.approx(3 / 11)
    # more microbatches amortize the fill/drain bubble monotonically
    fracs = [bubble_fraction(4, m) for m in (1, 2, 4, 8, 16)]
    assert fracs == sorted(fracs, reverse=True)


def test_pipeline_parallel_2_stages_roundtrip():
    """2-stage round-trip on the fake mesh: per-stage affine funcs compose
    in stage order, and the (P*M)-tiled gather returns the last stage's
    microbatches in order."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_apply
        mesh = Mesh(np.array(jax.devices()).reshape(2), ('pipe',))
        sp = {'w': jnp.array([3., 0.5]).reshape(2, 1),
              'b': jnp.array([-1., 2.]).reshape(2, 1)}
        x = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
        y = pipeline_apply(lambda p, t: t * p['w'] + p['b'],
                           mesh, 'pipe', sp, x)
        want = (x * 3. - 1.) * 0.5 + 2.
        assert y.shape == x.shape, y.shape
        np.testing.assert_allclose(np.array(y), np.array(want), rtol=1e-6)
        print('PIPELINE2_OK')
    """, devices=2)
    assert "PIPELINE2_OK" in out


def test_pipeline_parallel_4_stages():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_apply
        mesh = Mesh(np.array(jax.devices()).reshape(4), ('pipe',))
        sp = {'w': jnp.array([2.,3.,.5,4.]).reshape(4,1),
              'b': jnp.array([1.,0.,2.,-1.]).reshape(4,1)}
        x = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)
        y = pipeline_apply(lambda p, t: t * p['w'] + p['b'],
                           mesh, 'pipe', sp, x)
        want = ((x*2+1)*3*0.5+2)*4-1
        np.testing.assert_allclose(np.array(y), np.array(want), rtol=1e-6)
        print('PIPELINE_OK')
    """, devices=4)
    assert "PIPELINE_OK" in out


def test_compressed_psum_accuracy():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compress import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(4), ('dp',))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        f = jax.shard_map(lambda t: compressed_psum(t, 'dp'), mesh=mesh,
                          in_specs=P('dp'), out_specs=P('dp'), check_vma=False)
        got = f(g)
        want = jnp.broadcast_to(jnp.mean(g, 0, keepdims=True), g.shape)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 0.02, err
        print('PSUM_OK', err)
    """, devices=4)
    assert "PSUM_OK" in out


# ----------------------------------------------- small-mesh dry-run (8 dev)
@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    """The full build_cell -> lower -> compile -> roofline path on a 2x4
    mesh with a reduced arch: proves the machinery end-to-end in-tests."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        import repro.configs as C
        from repro.core import roofline as rl
        from repro.launch.common import build_cell
        from repro.configs.base import ShapeConfig
        import dataclasses
        cfg = dataclasses.replace(C.reduced(C.get_config('stablelm-1.6b')),
                                  num_groups=2)
        from repro.launch.mesh import fake_mesh
        mesh = fake_mesh(8)
        shape = ShapeConfig('tiny_train', seq_len=64, global_batch=8,
                            kind='train')
        fn, args = build_cell(cfg, shape, mesh)
        with mesh:
            compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # jax<=0.4.x returns [dict]
            cost = cost[0]
        coll = rl.collective_bytes_from_hlo(compiled.as_text())
        assert cost.get('flops', 0) > 0
        assert coll['total'] > 0, coll
        print('DRYRUN_OK flops=%.2e coll=%.2e' % (cost['flops'],
                                                  coll['total']))
    """, devices=8)
    assert "DRYRUN_OK" in out


@pytest.mark.slow
def test_dryrun_decode_small_mesh():
    out = run_subprocess("""
        import jax, dataclasses
        import repro.configs as C
        from repro.launch.common import build_cell
        from repro.configs.base import ShapeConfig
        cfg = dataclasses.replace(C.reduced(C.get_config('mistral-nemo-12b')),
                                  num_groups=2)
        from repro.launch.mesh import fake_mesh
        mesh = fake_mesh(8)
        shape = ShapeConfig('tiny_decode', seq_len=128, global_batch=8,
                            kind='decode')
        fn, args = build_cell(cfg, shape, mesh)
        with mesh:
            compiled = fn.lower(*args).compile()
        print('DECODE_OK')
    """, devices=8)
    assert "DECODE_OK" in out
