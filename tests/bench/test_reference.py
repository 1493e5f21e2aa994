"""The plain reference against the program at CPU size, for every
configuration of BENCHMARK.json, each through its kind's modules: the same
weights, then the logits every served token was chosen from."""
import jax
import numpy as np
import pytest

from bench import check, serve, spec
from bench import weights as W
from conftest import CONFIGS, tiny_config

SEED = 2 ** 33 + 3


@pytest.mark.parametrize("name", CONFIGS)
def test_stacked_weights_are_the_per_layer_weights(name):
    conf = tiny_config(name)
    kind = spec.model_kind(conf)
    params = kind.program_params(SEED, conf)
    key = W.root_key(SEED)
    for i in range(conf["num_hidden_layers"]):
        one = kind.layer(key, conf, i)
        stacked = jax.tree.map(lambda a: a[i], params["blocks"][0])
        same = jax.tree.map(lambda a, b: bool((a == b).all()), one, stacked)
        assert all(jax.tree.leaves(same))
    assert params["embed"]["table"].dtype == W.DTYPE


def test_seed_uses_all_64_bits():
    a, b = W.root_key(5), W.root_key(5 + 2 ** 32)
    assert not bool((a == b).all())


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_serve_engine(name):
    from repro.serving import Request
    conf = tiny_config(name)
    engine = serve.build(conf, SEED)
    rows = []
    sample, emit = engine._sample, engine._emit
    engine._sample = lambda r: (rows.append(r.copy()), sample(r))[1]
    seen = {}

    def record(req, tok):
        seen.setdefault(req.rid, []).append(rows[-1])
        emit(req, tok)

    engine._emit = record
    rng = np.random.default_rng(0)
    # Prompts of several 32-token prefill chunks, served together.
    for rid, n in enumerate((40, 70, 101)):
        engine.submit(Request(rid=rid, prompt=rng.integers(
            0, conf["vocab_size"], n, dtype=np.int32), max_new_tokens=12))
    engine.run()
    done = [{"rid": r.rid, "prompt": np.asarray(r.prompt),
             "tokens": list(r.out_tokens)} for r in engine.done.values()]
    assert len(done) == 3 and not engine.failed
    seqs, pos = check.sequences(done)
    ref = spec.reference(conf).Reference(conf, SEED,
                                         conf["serving"]["max_seq_len"])
    logits = ref.logits(seqs, pos)[None]
    for r, want in zip(done, logits):
        got = np.stack(seen[r["rid"]]).astype(np.float32)
        # bf16 activations and KV against float32: a few bf16 ulps of the
        # largest logit after two layers; a wrong layer is off by order 1.
        dev = np.abs(got - want).max() / np.abs(want).max()
        assert dev < 2e-2, (r["rid"], dev)
    assert check.served_gaps(logits, done).max() < 0.05
