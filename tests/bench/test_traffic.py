"""The traffic generator: seeded, within its clips, the same work per
seed, and each arrival kind found by name and keeping its promise."""
import numpy as np
import pytest

from bench import spec
from bench.generator import Stream, arrivals, lognormal_quantiles

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))

#: A code-completion mix at a fixed rate (the knee sweep's kind of mix).
CODE = {"arrival": "poisson", "rate_per_s": 3.0,
        "prompt": {"median": 512, "sigma": 1.0, "min": 64, "max": 2048},
        "output": {"median": 24, "sigma": 0.8, "min": 4, "max": 128},
        "block": 44}


def _mix(name):
    return CODE if name == "code" else spec.traffic(name)


def _draws(mix, seed, n, vocab=1000):
    s = Stream(mix, seed, vocab)
    return [s.next() for _ in range(n)]


def _released(mix, seed, until_s, vocab=1000, max_batch=4):
    """What an arrival kind releases by ``until_s``, asked every 0.1 s with
    nothing admitted."""
    arr = arrivals(mix, seed, vocab, max_batch)
    out = []
    for now in np.arange(0.0, until_s, 0.1):
        out += arr.release(float(now), len(out))
    return out


@pytest.mark.parametrize("name", MIXES + ["code"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = _released(mix, 2 ** 31 + 9, 30.0)
    b = _released(mix, 2 ** 31 + 9, 30.0)
    c = _released(mix, 2 ** 31 + 10, 30.0)
    assert len(a) == len(b) > 5
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and da == db for (da, x), (db, y) in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for (_, x), (_, y) in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips_and_same_per_block(name):
    mix = _mix(name)
    block = mix["block"]
    for seed in (1, 2, 3):
        draws = _draws(mix, seed, 2 * block, vocab=50)
        plen = [len(d.prompt) for d in draws]
        outs = [d.max_new for d in draws]
        assert mix["prompt"]["min"] <= min(plen)
        assert max(plen) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= min(outs)
        assert max(outs) <= mix["output"]["max"]
        assert all(0 <= d.prompt.min() and d.prompt.max() < 50
                   for d in draws)
        # Every seed offers the same sizes in each block, in its own order.
        want = sorted(lognormal_quantiles(mix["prompt"], block))
        assert sorted(plen[:block]) == want == sorted(plen[block:])


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_meets_the_same_sizes_in_each_run(name):
    mix = _mix(name)
    g = mix.get("shuffle", mix["block"])

    def sizes(seed):
        return [(len(d.prompt), d.max_new)
                for d in _draws(mix, seed, 2 * mix["block"], vocab=50)]

    a, b = sizes(2 ** 31 + 1), sizes(5)
    # The same (prompt, output) pairs in every run of ``shuffle``
    # requests, so a window meets the same work; the seed only reorders
    # within a run, and a run of 1 keeps one order for every seed.
    assert [sorted(a[i:i + g]) for i in range(0, len(a), g)] == \
        [sorted(b[i:i + g]) for i in range(0, len(b), g)]
    assert (a != b) == (g > 1)


def test_seed_reorders_within_runs_only():
    mix, n = dict(CODE, shuffle=4), CODE["block"]
    a, b = ([(len(d.prompt), d.max_new) for d in _draws(mix, seed, n)]
            for seed in (1, 2))
    assert a != b
    assert [sorted(a[i:i + 4]) for i in range(0, n, 4)] == \
        [sorted(b[i:i + 4]) for i in range(0, n, 4)]


def test_shuffle_must_divide_the_block():
    with pytest.raises(ValueError, match="shuffle"):
        Stream(dict(CODE, shuffle=5), 0, 10)


def test_poisson_mean_rate():
    arr = _released(CODE, 5, 10 * CODE["block"] / 3.0)
    due = [d for d, _ in arr]
    rate = len(due) / due[-1]
    # Stratified gaps: the block mean is the rate's to well under 3%.
    assert rate == pytest.approx(3.0, rel=0.03)
    assert (np.diff([0.0] + due) > 0).all()


def test_poisson_bursts_follow_the_phases():
    mix = dict(CODE, phases=[[2.0, 2.5], [3.0, 0.0]])
    due = np.array([d for d, _ in _released(mix, 7, 50.0)])
    phase = np.mod(due, 5.0)
    # Nothing in the off phases; the on phases carry the whole mean rate.
    assert (phase <= 2.0 + 1e-9).all()
    assert len(due) / 50.0 == pytest.approx(3.0 * 2.0 * 2.5 / 5.0, rel=0.1)


def test_backlog_has_no_due_times():
    arr = arrivals(spec.traffic("azure-conv-backlog"), 1, 100, 4)
    assert arr.next_due() is None
    first = arr.release(0.5, 0)
    assert len(first) == 2 * 4 and {d for d, _ in first} == {0.5}
    assert arr.release(0.6, 8) == []
    assert len(arr.release(0.7, 5)) == 3


def test_classes_share_each_block():
    short = {"median": 16, "sigma": 0.1, "min": 8, "max": 32}
    long = {"median": 900, "sigma": 0.1, "min": 512, "max": 1024}
    mix = {"arrival": "backlog", "pending_per_row": 1, "block": 8,
           "classes": [{"share": 0.75, "prompt": short, "output": short},
                       {"share": 0.25, "prompt": long, "output": short}]}
    for seed in (3, 4):
        plen = [len(d.prompt) for d in _draws(mix, seed, 16)]
        assert sum(p >= 512 for p in plen[:8]) == 2
        assert sum(p >= 512 for p in plen[8:]) == 2


def test_prefix_shared_by_its_asks():
    mix = {"arrival": "backlog", "pending_per_row": 1, "block": 6,
           "prompt": {"median": 10, "sigma": 0.0, "min": 10, "max": 10},
           "output": {"median": 4, "sigma": 0.0, "min": 4, "max": 4},
           "prefix": {"asks": 3, "length": {"median": 100, "sigma": 0.5,
                                            "min": 50, "max": 200}}}
    d = _draws(mix, 9, 6)
    heads = [x.prompt[:-10] for x in d]
    assert all(np.array_equal(heads[0], h) for h in heads[:3])
    assert all(np.array_equal(heads[3], h) for h in heads[3:])
    assert not np.array_equal(heads[0][:50], heads[3][:50])


def test_unknown_arrival_refused():
    with pytest.raises(ValueError, match="unknown arrival"):
        arrivals(dict(CODE, arrival="bursty"), 0, 10, 4)
