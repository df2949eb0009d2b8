import statistics

import pytest

from benchmarks import stats


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 102.5)


def test_spread_without_farthest_only_ever_narrows():
    steady = [100.0, 100.2, 100.4, 100.6, 100.8, 101.0]
    one_off = steady[:-1] + [120.0]
    assert stats.spread_without_farthest(one_off) < stats.spread(one_off)
    assert stats.spread_without_farthest(steady) <= stats.spread(steady)
