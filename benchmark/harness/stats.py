"""The arithmetic every end-to-end number goes through, in plain Python so a
test can check it on hand-made records.  (The percentile is the one
`tools/loadgen.py` takes from numpy: linear interpolation between the two
closest ranks.)"""
from __future__ import annotations

from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q in [0, 100]; None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def gaps(times: Sequence[float]) -> List[float]:
    """Differences between successive completion times (sorted first)."""
    ts = sorted(times)
    return [b - a for a, b in zip(ts, ts[1:])]


def rate_from_mean_gap(times: Sequence[float], units_per_completion: float) -> Optional[float]:
    """Units delivered per second, all of them over all the time: units * (k -
    1) / (t_k - t_1) over k completions.  Needs two completions.  Every gap
    between two completions holds the same work, so no partial unit enters,
    and a stall anywhere between the first and the last one shows."""
    ts = sorted(times)
    if len(ts) < 2 or ts[-1] <= ts[0]:
        return None
    return units_per_completion * (len(ts) - 1) / (ts[-1] - ts[0])


def rate_from_median_gap(times: Sequence[float], units_per_completion: float) -> Optional[float]:
    """The same over the MEDIAN gap: the steady-state rate, which one host
    hiccup does not move.  Beside the mean it says whether a loss is the
    step's or a stall's."""
    g = gaps(times)
    return units_per_completion / median(g) if g else None


def rate_over_span(units: float, t_start: float, t_end: float) -> Optional[float]:
    """All the work over all the time: units / (t_end - t_start)."""
    return units / (t_end - t_start) if t_end > t_start else None


def in_window(records: Sequence[dict], key: str, t_open: float, t_close: float) -> List[dict]:
    return [r for r in records if t_open <= r[key] <= t_close]
