"""Metric arithmetic and the table of peaks."""

import statistics

import pytest

from lib import peaks, roofline, stats


def test_p95_is_over_every_op_not_over_rounds():
    # 19 fast rounds of 100 ops and one slow round of 1,900 ops: half of
    # all ops waited 9 s, so the p95 over ops is 9 s, while the p95 over
    # rounds would be a fast round's time
    lat = [1.0] * 19 + [9.0]
    lanes = [100] * 19 + [1900]
    assert stats.percentile(lat, lanes, 95) == 9.0
    assert stats.percentile(lat, [1] * 20, 95) == 1.0


def test_percentile_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, [1] * 100, 95) == 95
    assert stats.percentile(values, [1] * 100, 100) == 100
    assert stats.percentile([5.0], [3], 95) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], [], 95)


def test_spread_uses_statistics_quartiles():
    v = [10.0, 11.0, 12.0, 13.0, 30.0, 10.5]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)


def test_peaks_of_v5e():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks(kind)


def test_get_bytes_counted_from_the_algorithm():
    assert sum(roofline.GET_LANE_BYTES.values()) == 50
    assert roofline.get_bytes(8192) == 8192 * 50
    # 819 bytes in 1 ns at 819 GB/s is the whole roofline
    assert roofline.roofline_share(819.0, 1e-9, 819e9) == pytest.approx(100)
    assert roofline.roofline_share(819.0, 0.0, 819e9) is None
