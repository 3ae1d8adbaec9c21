"""The traffic generator: the same seed gives the same requests, and every
seed gives the same work on the same schedule, with other token ids."""
import numpy as np

from conftest import MIX
from harness import traffic


def _gen(seed, rate=2.0, seconds=30.0, mix=MIX):
    return traffic.generate(mix, rate_rps=rate, seconds=seconds, seed=seed,
                            vocab_size=1000)


def test_same_seed_same_requests():
    a, b = _gen(2**31 + 11), _gen(2**31 + 11)
    assert [(r.rid, r.due_s, r.max_new) for r in a] == \
        [(r.rid, r.due_s, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_differ_in_tokens_not_in_work():
    a, b = _gen(3), _gen(2**33 + 4)
    assert [(r.due_s, r.max_new, len(r.prompt)) for r in a] == \
        [(r.due_s, r.max_new, len(r.prompt)) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_schedule_seed_sets_the_order():
    a = _gen(3)
    b = _gen(3, mix=dict(MIX, schedule_seed=1))
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)


def test_window_holds_rate_times_seconds_requests():
    reqs = _gen(5, rate=2.0, seconds=30.0)
    assert len(reqs) == 60
    assert reqs[0].due_s == 0.0
    assert all(x.due_s <= y.due_s for x, y in zip(reqs, reqs[1:]))
    assert reqs[-1].due_s < 30.0


def test_lengths_follow_the_mix():
    reqs = _gen(6, rate=10.0, seconds=40.0)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= 8 and p.max() <= 64 and o.min() >= 4 and o.max() <= 16
    assert abs(np.median(p) - 24) <= 1 and abs(np.median(o) - 8) <= 1
    assert all(((r.prompt >= 1) & (r.prompt < 1000)).all() for r in reqs)


def test_gaps_are_poisson_at_shape_one():
    g = traffic.gaps({"dist": "gamma", "shape": 1.0}, 400, rate_rps=4.0)
    assert abs(g.mean() - 0.25) < 1e-12
    # exponential: the coefficient of variation is about 1
    assert 0.9 < g.std() / g.mean() < 1.1
