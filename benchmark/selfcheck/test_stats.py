import pytest

import stats


def test_percentile_interpolates_between_ranks():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([1, 2], 50) == 1.5
    assert stats.percentile([7], 95) == 7


def test_percentile_agrees_with_numpy():
    import numpy as np
    rng = np.random.default_rng(0)
    xs = rng.random(317).tolist()
    for q in (5, 50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_stat_names():
    xs = [3.0, 1.0, 2.0]
    assert stats.stat(xs, "median") == 2.0
    assert stats.stat(xs, "mean") == 2.0
    assert stats.stat(xs, "sum") == 6.0
    assert stats.stat(xs, "max") == 3.0
    assert stats.stat(xs, "p50") == 2.0
    with pytest.raises(ValueError):
        stats.stat(xs, "mode")
    with pytest.raises(ValueError):
        stats.median([])


def test_union_and_gaps():
    spans = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (5.2, 5.4)]
    assert stats.union_seconds(spans) == pytest.approx(3.0)
    assert stats.union_seconds([]) == 0.0
    assert stats.gaps(spans, 0.0, 7.0) == [(0.0, 1.0), (3.0, 5.0), (6.0, 7.0)]
    assert stats.gaps([], 2.0, 4.0) == [(2.0, 4.0)]
    assert stats.gaps([(0.0, 10.0)], 2.0, 4.0) == []
