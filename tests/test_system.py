"""End-to-end system tests: training learns, fault tolerance, serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, DataPipeline, make_batch
from repro.launch.serve import Request, Server
from repro.launch.train import TrainLoopConfig, train
from repro.models import lm
from repro.optim import adamw
from repro.optim import compress as gcomp

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------- training
class TestTraining:
    def test_loss_decreases(self):
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        out = train(cfg, TrainLoopConfig(steps=40, seq_len=64, global_batch=8,
                                         log_every=40))
        hist = out["history"]
        assert hist[-1]["loss"] < 6.0 - 1.0  # well below ln(256)=5.55 start

    def test_resume_is_bit_exact(self, tmp_path):
        """Crash-restart: 20 straight steps == crash@10 + restore + 10.

        Both runs use the *same* 20-step config (schedules key off the
        global step); the first is interrupted by fault injection.
        """
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        base = dict(steps=20, seq_len=32, global_batch=4, log_every=1000,
                    checkpoint_every=100)
        d1 = str(tmp_path / "a")
        out_a = train(cfg, TrainLoopConfig(checkpoint_dir=d1, **base))
        d2 = str(tmp_path / "b")
        train(cfg, TrainLoopConfig(checkpoint_dir=d2, halt_at_step=10,
                                   **base))
        out_b = train(cfg, TrainLoopConfig(checkpoint_dir=d2, **base))
        pa = jax.tree.leaves(out_a["params"])
        pb = jax.tree.leaves(out_b["params"])
        for a, b in zip(pa, pb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_grad_compression_still_learns(self):
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        out = train(cfg, TrainLoopConfig(steps=40, seq_len=64, global_batch=8,
                                         log_every=40, grad_compression=True))
        assert out["history"][-1]["loss"] < 5.0


# ------------------------------------------------------------- checkpointing
class TestCheckpoint:
    def test_atomic_and_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        tree = {"w": jnp.arange(8.0), "n": {"b": jnp.ones((2, 3))}}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree)
        assert mgr.all_steps() == [3, 4]
        step, restored = mgr.restore(tree)
        assert step == 4
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(8.0))

    def test_corrupt_tmp_does_not_break_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        tree = {"w": jnp.ones(4)}
        mgr.save(7, tree)
        os.makedirs(tmp_path / "tmp.8")  # simulated crash mid-save
        (tmp_path / "tmp.8" / "garbage").write_text("x")
        assert mgr.latest_step() == 7
        step, _ = mgr.restore(tree)
        assert step == 7

    def test_elastic_reshard_on_load(self, tmp_path):
        """Save unsharded, restore onto an explicit (1-device) sharding."""
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        mgr.save(1, tree)
        from repro.launch.mesh import smoke_mesh
        mesh = smoke_mesh()
        sh = jax.sharding.NamedSharding(mesh,
                                        jax.sharding.PartitionSpec("data"))
        step, restored = mgr.restore(tree, shardings={"w": sh})
        assert restored["w"].sharding == sh
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(16.0).reshape(4, 4))

    def test_missing_leaf_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(1, {"w": jnp.ones(2)})
        with pytest.raises(KeyError):
            mgr.restore({"w": jnp.ones(2), "extra": jnp.ones(3)})


# ------------------------------------------------------------------ data
class TestData:
    def test_deterministic_addressing(self):
        cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=4)
        b1 = make_batch(cfg, 7)
        b2 = make_batch(cfg, 7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        b3 = make_batch(cfg, 8)
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_learnable_structure(self):
        """Labels follow the bigram map ~ (1 - noise) of the time."""
        cfg = DataConfig(vocab_size=128, seq_len=64, global_batch=8,
                         noise=0.1)
        b = make_batch(cfg, 0)
        from repro.data.pipeline import _bigram_params
        a, c = _bigram_params(cfg.seed, cfg.vocab_size)
        pred = (a * b["tokens"] + c) % cfg.vocab_size
        match = (pred == b["labels"]).mean()
        assert match > 0.8

    def test_cursor_checkpoint(self):
        from repro.data.pipeline import PipelineState
        cfg = DataConfig(vocab_size=64, seq_len=8, global_batch=2)
        p = DataPipeline(cfg)
        next(p)
        next(p)
        state = p.state.to_dict()
        p2 = DataPipeline(cfg)
        p2.state = PipelineState.from_dict(state)
        np.testing.assert_array_equal(np.asarray(next(p)["tokens"]),
                                      np.asarray(next(p2)["tokens"]))

    def test_modality_batches(self):
        for mode, arch in (("embeds", "musicgen-large"),
                           ("tokens+vision", "internvl2-2b")):
            mcfg = C.reduced(C.get_config(arch))
            cfg = DataConfig(vocab_size=mcfg.vocab_size, seq_len=32,
                             global_batch=2, input_mode=mode,
                             d_model=mcfg.d_model,
                             num_vision_tokens=mcfg.num_vision_tokens)
            b = make_batch(cfg, 0)
            if mode == "embeds":
                assert b["embeds"].shape == (2, 32, mcfg.d_model)
            else:
                assert (b["labels"][:, :mcfg.num_vision_tokens] == -1).all()


# ------------------------------------------------------------------ optim
class TestOptim:
    def test_adamw_descends_quadratic(self):
        params = {"w": jnp.full((4,), 5.0)}
        state = adamw.init(params)
        cfg = adamw.AdamWConfig(peak_lr=0.1, warmup_steps=0, total_steps=200,
                                weight_decay=0.0, clip_norm=None)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, _ = adamw.update(grads, state, params, cfg)
        assert float(jnp.abs(params["w"]).max()) < 0.5

    def test_clip_norm_reported_preclip(self):
        params = {"w": jnp.zeros(3)}
        state = adamw.init(params)
        cfg = adamw.AdamWConfig(clip_norm=1.0)
        _, _, m = adamw.update({"w": jnp.full((3,), 100.0)}, state, params,
                               cfg)
        assert m["grad_norm"] > 100

    def test_lr_schedule_shapes(self):
        cfg = adamw.AdamWConfig(peak_lr=1.0, warmup_steps=10,
                                total_steps=100, end_lr_ratio=0.1)
        assert float(adamw.lr_at(cfg, jnp.asarray(5))) == pytest.approx(0.5)
        assert float(adamw.lr_at(cfg, jnp.asarray(10))) == pytest.approx(1.0)
        assert float(adamw.lr_at(cfg, jnp.asarray(100))) == pytest.approx(
            0.1, abs=1e-6)

    def test_error_feedback_invariant(self):
        """EF accumulates exactly the quantization residual."""
        g = {"w": jax.random.normal(KEY, (64,))}
        e0 = gcomp.init_error(g)
        (q, s), e1 = gcomp.compress_grads(g, e0)
        deq = gcomp.decompress((q, s))
        np.testing.assert_allclose(np.asarray(deq["w"] + e1["w"]),
                                   np.asarray(g["w"]), rtol=1e-5, atol=1e-6)

    def test_compression_ratio(self):
        g = {"w": jnp.zeros((1024,)), "b": jnp.zeros((8,))}
        assert gcomp.compression_ratio(g) > 3.9


# ------------------------------------------------------------------ serving
class TestServing:
    def test_server_generates_and_reuses_slots(self):
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        params, _ = lm.init(KEY, cfg)
        server = Server(cfg, params, slots=2, cache_size=64)
        rng = np.random.RandomState(0)
        reqs = [Request(rid=i, prompt=rng.randint(
            0, cfg.vocab_size, size=(4,)).astype(np.int32),
            max_new_tokens=4) for i in range(3)]
        done = 0
        pending = list(reqs)
        for _ in range(40):
            while pending and server.admit(pending[0]):
                pending.pop(0)
            before = len(server.active)
            server.tick()
            done += before - len(server.active)
            if done == 3:
                break
        assert done == 3
        for r in reqs:
            assert len(r.out_tokens) == 4
            assert all(0 <= t < lm.padded_vocab(cfg) for t in r.out_tokens)

    def test_engine_zero_retrace_after_warmup(self):
        """Each serving phase compiles once per padded-batch bucket: after
        the first tick touches a (phase, bucket) signature, every later
        tick with that signature is a pure cache hit (the bug used to be
        per-slot re-derivation)."""
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        params, _ = lm.init(KEY, cfg)
        server = Server(cfg, params, slots=2, cache_size=64)
        server.admit(Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                             max_new_tokens=3))
        prefill = server.core.engines["prefill"]
        decode = server.core.engines["decode"]
        # the whole prompt prefilled through ONE chunked-prefill compile
        assert prefill.stats.misses == 1
        assert decode.stats.misses == 0
        server.admit(Request(rid=1, prompt=np.array([4, 5], np.int32),
                             max_new_tokens=3))
        assert prefill.stats.misses == 1  # second slot reused the entry
        assert prefill.stats.hits >= 1
        while server.active:
            server.tick()
        # decode saw two buckets (2 rows, then 1 after rid=1 finished);
        # each compiled exactly once, every other tick was a hit
        assert decode.stats.misses == decode.cache_size <= 2
        assert decode.stats.hits >= 2
        assert prefill.stats.misses == 1, \
            "decode ticks must not touch the prefill cache"

    def test_greedy_decode_deterministic(self):
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        params, _ = lm.init(KEY, cfg)
        outs = []
        for _ in range(2):
            server = Server(cfg, params, slots=1, cache_size=64)
            req = Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                          max_new_tokens=5)
            server.admit(req)
            while server.active:
                server.tick()
            outs.append(tuple(req.out_tokens))
        assert outs[0] == outs[1]


class TestServingEdgeCases:
    @pytest.fixture(scope="class")
    def served(self):
        cfg = C.reduced(C.get_config("stablelm-1.6b"))
        params, _ = lm.init(KEY, cfg)
        return cfg, params

    def test_empty_prompt_rejected_not_crashed(self, served):
        cfg, params = served
        server = Server(cfg, params, slots=1, cache_size=64)
        req = Request(rid=0, prompt=np.zeros((0,), np.int32),
                      max_new_tokens=4)
        assert server.admit(req) is True  # consumed, not admitted
        assert req.status == "failed"
        assert "empty prompt" in req.error
        assert not server.active and 0 in server.failed

    def test_kv_cache_overflow_rejected_at_admit(self, served):
        """The old behavior silently wrapped/stopped attending past the
        cache bound; now the request is rejected at the door with the
        budget spelled out."""
        cfg, params = served
        server = Server(cfg, params, slots=1, cache_size=16)
        req = Request(rid=0, prompt=np.arange(12, dtype=np.int32),
                      max_new_tokens=8)  # 12 + 8 > 16
        assert server.admit(req) is True
        assert req.status == "failed"
        assert "cache_size is 16" in req.error
        assert "20 KV-cache positions" in req.error
        # an in-budget request on the same server still decodes fine
        ok = Request(rid=1, prompt=np.array([1, 2, 3], np.int32),
                     max_new_tokens=4)
        assert server.admit(ok)
        while server.active:
            server.tick()
        assert ok.status == "done" and len(ok.out_tokens) == 4

    def test_zero_max_new_tokens_trivially_done(self, served):
        cfg, params = served
        server = Server(cfg, params, slots=1, cache_size=64)
        req = Request(rid=0, prompt=np.array([1, 2], np.int32),
                      max_new_tokens=0)
        assert server.admit(req) is True
        assert req.status == "done"
        assert req.out_tokens == []
        assert not server.active and 0 in server.done

    def test_admission_waits_for_freed_slot(self, served):
        cfg, params = served
        server = Server(cfg, params, slots=1, cache_size=64)
        first = Request(rid=0, prompt=np.array([1, 2], np.int32),
                        max_new_tokens=2)
        second = Request(rid=1, prompt=np.array([3, 4], np.int32),
                         max_new_tokens=2)
        assert server.admit(first)
        assert server.admit(second) is False  # slot busy: NOT consumed
        while server.active:
            server.tick()
        assert first.status == "done"
        assert server.admit(second) is True   # freed slot admits it
        while server.active:
            server.tick()
        assert second.status == "done" and len(second.out_tokens) == 2

    def test_temperature_sampling_deterministic_under_seed(self, served):
        cfg, params = served
        outs = []
        for _ in range(2):
            server = Server(cfg, params, slots=1, cache_size=64,
                            temperature=0.7, seed=123)
            req = Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                          max_new_tokens=5)
            server.admit(req)
            while server.active:
                server.tick()
            outs.append(tuple(req.out_tokens))
        assert outs[0] == outs[1]
        # a different seed draws a different trajectory (overwhelmingly)
        server = Server(cfg, params, slots=1, cache_size=64,
                        temperature=0.7, seed=7)
        req = Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                      max_new_tokens=5)
        server.admit(req)
        while server.active:
            server.tick()
        assert all(0 <= t < lm.padded_vocab(cfg) for t in req.out_tokens)


# ------------------------------------------------------------ compile cache
class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch.common import use_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_dir_in_the_checkout(self, monkeypatch):
        from repro.launch.common import use_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = use_compile_cache()
        assert use_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(root, ".jax_cache")
