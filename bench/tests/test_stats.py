"""Percentile and interval arithmetic against numpy and by hand."""
import numpy as np
import pytest

from harness.stats import gaps_between, percentile, union_length


@pytest.mark.parametrize("q", [0, 10, 50, 90, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(1).lognormal(size=37)
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 90)


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7), (9, 12)]
    assert union_length(iv) == pytest.approx(3 + 1 + 3)
    assert gaps_between(iv, 0, 10) == [(3, 5), (6, 9)]
    assert gaps_between(iv, -1, 4) == [(-1, 0), (3, 4)]
    assert gaps_between([], 0, 1) == [(0, 1)]


def test_throughput_reader():
    from harness import spec
    rec = {"delivered_tokens": 1200, "seconds": 50.0, "chips": 1}
    read = spec.load_module(spec.BENCH_DIR / "metrics" / "delivered_tok_s.py",
                            "delivered_tok_s").read
    assert read(rec) == pytest.approx(24.0)
