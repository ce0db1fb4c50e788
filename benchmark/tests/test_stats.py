import numpy as np
import pytest

from harness import stats


def test_percentile_on_known_inputs():
    v = [10, 20, 30, 40, 50]
    assert stats.percentile(v, 0) == 10
    assert stats.percentile(v, 50) == 30
    assert stats.percentile(v, 100) == 50
    assert stats.percentile(v, 95) == pytest.approx(48.0)     # 40 + 0.8 * 10
    assert stats.percentile([1, 2], 50) == pytest.approx(1.5)
    assert stats.percentile([7], 99) == 7
    # order of the readings does not matter
    assert stats.percentile([50, 10, 40, 20, 30], 25) == 20


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1, 2], 101)


def test_spread_is_quartile_distance_over_median():
    # quartiles of 1..5 are 2 and 4, the median is 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    assert stats.spread([5, 5, 5]) == 0
    with pytest.raises(ValueError):
        stats.spread([0, 0, 0])


def test_window_counts_half_open():
    stamps = np.array([0, 10, 20, 30, 40])
    assert stats.count_in_window(stamps, 10, 40) == 3       # 10, 20, 30
    assert stats.count_in_window([5, 15], 0, 5) == 0
    assert stats.rate_per_s(30, 0, 2_000_000_000) == 15.0
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 5, 5)


def test_interval_union_and_clip():
    iv = [(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)]
    assert stats.merge_intervals(iv) == [(0, 15), (20, 31)]
    assert stats.union_length(iv) == 26
    assert stats.clip_intervals(iv, 8, 25) == [(8, 10), (8, 15), (20, 25)]


def test_block_rates_ignore_where_the_clock_would_cut_a_step():
    # seven completions one second apart, blocks of two: three blocks of 2 s
    stamps = [0, 1e9, 2e9, 3e9, 4e9, 5e9, 6e9]
    assert stats.block_rates_per_s(stamps, 2) == [1.0, 1.0, 1.0]
    # one stalled block moves its own rate and not the median
    stalled = [0, 1e9, 2e9, 3e9, 14e9, 15e9, 16e9]
    rates = stats.block_rates_per_s(stalled, 2)
    assert rates == pytest.approx([1.0, 2 / 12, 1.0])
    assert stats.median(rates) == 1.0
    assert stats.block_rates_per_s([0, 1e9], 2) == []
    with pytest.raises(ValueError):
        stats.block_rates_per_s(stamps, 0)
    with pytest.raises(ValueError):
        stats.block_rates_per_s([5, 5, 5], 1)


def test_slice_percentiles_by_each_reading_s_time():
    times = [0, 1, 2, 10, 11, 12, 25, 31]
    values = [1, 2, 3, 4, 5, 6, 7, 99]
    # whole slices of [0, 30): three; the reading at 31 is outside
    assert stats.slice_percentiles(times, values, 0, 30, 10, 50) == [2, 5, 7]
    assert stats.slice_percentiles(times, values, 0, 30, 10, 50,
                                   min_readings=2) == [2, 5]
    assert stats.slice_percentiles(times, values, 0, 35, 10, 100) == [3, 6, 7]
    with pytest.raises(ValueError):
        stats.slice_percentiles([1], [1, 2], 0, 10, 5, 50)


def test_quantiles_ms_names_its_quantiles():
    q = stats.quantiles_ms([1_000_000, 2_000_000, 3_000_000])
    assert q["p50"] == 2.0 and q["p100"] == 3.0 and set(q) == {
        "p5", "p25", "p50", "p75", "p95", "p100"}
