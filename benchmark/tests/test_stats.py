"""Overlap-weighted throughput and medians on hand-made request lists."""

import pytest

from lib import stats
from lib.stats import Req


def test_request_inside_the_window_counts_whole():
    reqs = [Req(1.0, 2.0, 100, True)]
    assert stats.overlap_bytes_per_s(reqs, 0.0, 10.0) == pytest.approx(10.0)


def test_request_across_an_edge_counts_by_its_overlap():
    # 4 s long, 1 s of it inside: a quarter of its bytes
    reqs = [Req(-3.0, 1.0, 400, True), Req(9.0, 13.0, 400, True)]
    assert stats.overlap_bytes_per_s(reqs, 0.0, 10.0) == pytest.approx(20.0)


def test_request_longer_than_the_window():
    reqs = [Req(-5.0, 15.0, 2000, True)]  # half of it inside
    assert stats.overlap_bytes_per_s(reqs, 0.0, 10.0) == pytest.approx(100.0)


def test_failed_and_outside_requests_contribute_nothing():
    reqs = [Req(1.0, 2.0, 100, False), Req(11.0, 12.0, 100, True),
            Req(-2.0, -1.0, 100, True)]
    assert stats.overlap_bytes_per_s(reqs, 0.0, 10.0) == 0.0


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        stats.overlap_bytes_per_s([], 1.0, 1.0)


def test_median_over_requests_that_ended_inside():
    reqs = [Req(0.0, 1.0, 1, True), Req(0.0, 3.0, 1, True),
            Req(5.0, 7.0, 1, True), Req(8.0, 12.0, 1, True),  # ends outside
            Req(1.0, 2.0, 1, False)]                          # failed
    inside = stats.ended_inside(reqs, 0.0, 10.0)
    assert len(inside) == 3
    assert stats.median(r.t_end - r.t_start for r in inside) == 2.0


def test_median_even_count_and_empty():
    assert stats.median([4.0, 1.0, 3.0, 2.0]) == 2.5
    assert stats.median([]) is None  # no sample: left out, never 0


def test_spread_is_interquartile_range_over_median():
    assert stats.spread([10.0, 10.0, 10.0]) == 0.0
    # quartiles of 1..5 by linear interpolation: 2 and 4; median 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    assert stats.spread([1.0]) is None
