"""A whole run past the look for a chip, at CPU size: sound, it is
``correct``; with the timed path broken underneath, it is not.

The faults are those a one-chip serving cell can have: a step that
returns its state unchanged (the KV pools never written), half of a
decode batch left out (its rows given the other half's logits), and a
token altered where it is produced.  The exchange between chips does not
exist on one chip.
"""
import time

import jax
import pytest

from bench import faults, run, serve, spec
from conftest import PEAKS, tiny_cell

#: The first cell of BENCHMARK.json, cut to CPU size by its kind.
CELL = spec.benchmark()["workloads"][0]["name"]
SEED = 2 ** 31 + 21


def _run(monkeypatch, fault=None):
    warm = serve.warm_up

    def warm_then_break(engine):
        n = warm(engine)
        if fault is not None:
            fault(engine)
        return n

    monkeypatch.setattr(serve, "warm_up", warm_then_break)
    return run.run_cell(tiny_cell(CELL), SEED, 3.0, False,
                        jax.devices("cpu")[:1], PEAKS, time.perf_counter())


def test_sound_run_is_correct(monkeypatch):
    out = _run(monkeypatch)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["tokens_compared"]["value"] >= 20


@pytest.mark.parametrize("fault", list(faults.FAULTS.values()),
                         ids=list(faults.FAULTS))
def test_broken_path_is_not_correct(monkeypatch, fault):
    out = _run(monkeypatch, fault)
    assert out["correct"] is False, out["checks"]
