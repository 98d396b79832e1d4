"""Percentile arithmetic of the latency metrics."""
import pytest

from bench import stats


@pytest.mark.parametrize("q,want", [(50, 50.0), (99, 99.0), (100, 100.0),
                                    (0.5, 1.0), (1, 1.0)])
def test_percentile_nearest_rank(q, want):
    values = list(range(100, 0, -1))       # 1..100, unsorted
    assert stats.percentile(values, q) == want


def test_percentile_small_and_empty():
    assert stats.percentile([], 99) is None
    assert stats.percentile([3.0], 99) == 3.0
    # 99th of 10 values: rank ceil(9.9) = 10, the largest
    assert stats.percentile([float(v) for v in range(10)], 99) == 9.0
