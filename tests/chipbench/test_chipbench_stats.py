"""Percentiles, failures counted as the worst, the peaks table."""

import pytest

from chipbench import peaks, stats


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (90, 3.7), (100, 4.0), (25, 1.75)])
def test_percentile_interpolates_between_closest_ranks(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_counts_as_worst():
    got = stats.with_failed_as_worst([10.0, None, 30.0, None], worst=20.0)
    assert got == [10.0, 30.0, 30.0, 30.0]  # never better than a latency that was measured
    assert stats.with_failed_as_worst([10.0, None], worst=500.0) == [10.0, 500.0]
    ten = [float(i) for i in range(1, 10)] + [None]
    assert stats.percentile(stats.with_failed_as_worst(ten, 1000.0), 95) > 500  # one failure in ten owns the tail


def test_quartile_spread_is_the_drivers():
    assert stats.quartile_spread([100.0, 101.0, 102.0, 103.0, 104.0, 105.0]) == pytest.approx(3.5 / 102.5)


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
