"""Percentile and rate arithmetic on fixed inputs."""

import pytest

from benchmark import stats


@pytest.mark.parametrize("q,want", [(0.5, 50), (0.95, 95), (0.99, 99),
                                    (1.0, 100), (0.001, 1)])
def test_percentile_nearest_rank(q, want):
    assert stats.percentile(list(range(100, 0, -1)), q) == want


def test_percentile_small_and_empty():
    assert stats.percentile([7.0], 0.95) == 7.0
    assert stats.percentile([1, 2, 3, 4], 0.95) == 4
    with pytest.raises(ValueError):
        stats.percentile([], 0.95)
    with pytest.raises(ValueError):
        stats.percentile([1], 0.0)


def test_rates():
    assert stats.per_second(500, 20.0) == 25.0
    # three engines made 600 step calls in a 10 s window: 20 steps/s each
    assert stats.steps_per_second(1000, 1600, 3, 10.0) == 20.0
    assert stats.ns_to_ms(2_500_000) == 2.5
    with pytest.raises(ValueError):
        stats.per_second(1, 0.0)
    with pytest.raises(ValueError):
        stats.steps_per_second(0, 1, 0, 1.0)
