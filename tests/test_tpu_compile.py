"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers one kernel at stablelm-1.6b widths (d_model
2048, 32 heads of 64, d_ff 5632, vocab 100352, bf16) for a described
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode accepts (unaligned slices, too much VMEM, an accumulator
Mosaic cannot hold).  The topology is described inside a fixture, never at
import: only one process may load the TPU library at a time, so a test
worker must not touch it while merely collecting this file.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D_MODEL, HEADS, HEAD_DIM, D_FF, VOCAB = 2048, 32, 64, 5632, 100352
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_text(one_chip, no_compile_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


@pytest.mark.parametrize("m,k,n,epilogue,accum", [
    (8, D_MODEL, D_FF, "silu", BF16),       # decode MLP in, as dispatched
    (8, D_FF, D_MODEL, "none", BF16),       # decode MLP out
    (1, D_MODEL, D_FF, "silu", BF16),       # batch-1 decode bucket
    (256, D_MODEL, D_FF, "silu", BF16),     # prefill chunk 32 x bucket 8
    (8, D_MODEL, VOCAB, "none", jnp.float32),  # LM head
], ids=["mlp_in", "mlp_out", "batch1", "prefill", "head"])
def test_sma_gemm(compile_text, m, k, n, epilogue, accum):
    from repro.kernels.sma_gemm import sma_gemm
    fn = functools.partial(sma_gemm, epilogue=epilogue, accum_dtype=accum)
    text = compile_text(fn, ((m, k), BF16), ((k, n), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(8, 3 * D_MODEL), (256, D_MODEL)],
                         ids=["decode_qkv", "prefill_q"])
def test_rmsnorm_gemm(compile_text, m, n):
    from repro.kernels.norm_gemm import rmsnorm_gemm
    text = compile_text(rmsnorm_gemm, ((m, D_MODEL), BF16),
                        ((D_MODEL,), jnp.float32), ((D_MODEL, n), BF16))
    assert "tpu_custom_call" in text


def test_paged_decode_attention(compile_text):
    """The kernel backend's paged path: page gather + decode kernel, at
    the serving smoke's pool (576 blocks of 16) and batch 8, reading one
    layer of the stacked token-major pools."""
    from repro.backends.registry import get_backend
    b, layers, blocks, bs, table = 8, 2, 576, 16, 72
    pool = ((layers, blocks, bs, HEADS * HEAD_DIM), BF16)
    paged = get_backend("pallas").op("paged_decode_attention")
    text = compile_text(
        paged,
        ((b, 1, HEADS, HEAD_DIM), BF16), pool, pool, ((), jnp.int32),
        ((b, table), jnp.int32), ((b, 1), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text


#: Instructions that would move a pool: XLA's copies (re-layouts) and the
#: slicing of one layer out of a stack or into one.
POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
#: An HLO instruction's result shape and opcode.
RESULT = re.compile(r"=\s+\w+\[([\d,]*)\]\S*\s+([\w-]+)\(")


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_step_programs_move_no_pool(one_chip, no_compile_cache, phase):
    """The serving layer scan carries the token-major KV pools and writes
    and reads them at the layer index, so a step program compiled with
    its state donated holds no copy or slice of the stacked pools or of
    one layer's pool, and no temporary as large as one layer's K pool.
    stablelm-1.6b widths with bf16 weights (as served), 2 layer groups,
    the smoke's pool (576 blocks of 16, tables of 72), 4 rows, prefill
    chunks of 32."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.layers import Runtime
    from repro.serving import model as smodel
    from repro.serving.kv_cache import CacheConfig
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), num_groups=2,
                              param_dtype="bfloat16")
    cache = CacheConfig(block_size=16, num_blocks=576, max_seq_len=1152)
    b, chunk, table = 4, 32, 72
    rt = Runtime(remat=False)

    def specs(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = specs(jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), cfg)[0]))
    state = specs(jax.eval_shape(
        lambda: smodel.init_state(cfg, b, cache)))
    if phase == "decode":
        def step(p, s, bt, cl, toks):
            return smodel.paged_decode_step(p, s, bt, cl, cfg, rt,
                                            {"tokens": toks})
        args = (params, state, ints(b, table), ints(b), ints(b, 1))
    else:
        def step(p, s, bt, cl, n, toks):
            return smodel.paged_prefill_step(p, s, bt, cl, n, cfg, rt,
                                             {"tokens": toks})
        args = (params, state, ints(b, table), ints(b), ints(b),
                ints(b, chunk))
    compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()

    (k_pool, *_) = jax.tree.leaves(state[0])
    stacked = math.prod(k_pool.shape)
    one_layer = stacked // cfg.num_groups
    moves = []
    for line in compiled.as_text().splitlines():
        m = RESULT.search(line)
        if m and m.group(2) in POOL_MOVES:
            n = math.prod(int(d) for d in m.group(1).split(",") if d)
            if n in (stacked, one_layer):
                moves.append(line.strip())
    assert not moves, moves
    layer_k_bytes = one_layer * k_pool.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_k_bytes, (temp, layer_k_bytes)


def test_flash_attention(compile_text):
    from repro.kernels.flash_attention import flash_attention
    shape = (1, HEADS, 2048, HEAD_DIM)
    text = compile_text(flash_attention, (shape, BF16), (shape, BF16),
                        (shape, BF16))
    assert "tpu_custom_call" in text
