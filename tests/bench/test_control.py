"""The control comes out not correct: the reference computed in int8, the
precision below the configured bf16, put in the program's place.

On the chip this is read at each cell's own size (``bench/control.py``,
readings in PERF.md).  Here it runs at a size a test holds: each
configuration at its kind's control cut (``tiny(conf, control=True)``;
the dense kind's is 8 layers of width 512 with the configuration's head
layout and a 32k vocabulary), over four sequences of 1024 tokens, held
to each cell's own limit."""
import numpy as np
import pytest

from bench import check, spec
from conftest import CONFIGS, tiny_config

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def readings():
    out = {}
    for name in CONFIGS:
        conf = tiny_config(name, control=True)
        rng = np.random.default_rng(0)
        seqs = [rng.integers(0, conf["vocab_size"], 1024, dtype=np.int32)
                for _ in range(4)]
        rows = [np.arange(24, 1024)] * 4
        ref = spec.reference(conf).Reference(conf, 7, 1024)
        lg = ref.logits(seqs, rows, quants=(None, "int8"))
        out[name] = (check.control_gaps(lg[None], lg["int8"]),
                     check.served_gaps(lg[None], [
                         {"tokens": g.argmax(-1)} for g in lg[None]]))
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(readings, cell):
    c = spec.cell(cell)
    control, best = readings[c.config["name"]]
    limits = c.checks["limits"]
    assert not check.passed(check.numbers(control, 0, limits))
    # The reference's own choices pass: the gap is 0 at every position.
    assert best.max() == 0.0
    assert check.passed(check.numbers(best, 0, limits))
