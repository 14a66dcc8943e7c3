"""The arithmetic of the end-to-end numbers, on hand-made records."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import stats  # noqa: E402


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 50, 1.0),
    ([1.0, 3.0], 50, 2.0),
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),
    ([10.0, 20.0], 0, 10.0),
    ([10.0, 20.0], 100, 20.0),
    ([], 50, None),
])
def test_percentile_interpolates_between_ranks(values, q, want):
    got = stats.percentile(values, q)
    assert got == want if want is None else got == pytest.approx(want)


def test_percentile_agrees_with_numpy():
    import numpy as np

    xs = list(np.random.default_rng(0).normal(size=37))
    for q in (5, 50, 90, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_mean_gap_shows_a_stall_that_the_median_gap_passes_over():
    # completions every 2 s, one of them 1 s late: one gap long, the next short
    times = [0.0, 2.0, 4.0, 7.0, 8.0, 10.0, 12.0]
    assert stats.gaps(times) == [2.0, 2.0, 3.0, 1.0, 2.0, 2.0]
    assert stats.rate_from_median_gap(times, 1024) == pytest.approx(512.0)
    assert stats.rate_from_mean_gap(times, 1024) == pytest.approx(1024 * 6 / 12.0)
    # a stall that is never made up moves the mean and not the median
    late = [0.0, 2.0, 4.0, 7.0, 9.0, 11.0, 13.0]
    assert stats.rate_from_median_gap(late, 1024) == pytest.approx(512.0)
    assert stats.rate_from_mean_gap(late, 1024) == pytest.approx(1024 * 6 / 13.0)


@pytest.mark.parametrize("times", [[], [5.0]])
def test_rates_need_two_completions(times):
    assert stats.rate_from_median_gap(times, 1024) is None
    assert stats.rate_from_mean_gap(times, 1024) is None


def test_gaps_sort_first():
    assert stats.gaps([3.0, 1.0, 2.0]) == [1.0, 1.0]


def test_rate_over_span_is_all_work_over_all_time():
    assert stats.rate_over_span(8 * 1024 * 10, 100.0, 120.0) == pytest.approx(4096.0)
    assert stats.rate_over_span(1.0, 5.0, 5.0) is None


def test_in_window_keeps_the_closed_interval():
    recs = [{"t": t} for t in (0.9, 1.0, 1.5, 2.0, 2.1)]
    assert [r["t"] for r in stats.in_window(recs, "t", 1.0, 2.0)] == [1.0, 1.5, 2.0]
