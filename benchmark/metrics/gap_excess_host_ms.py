"""Time the host itself took beyond a normal gap's: per completion gap all of
its wall time that is not `dispatch_s + block_s` (admission, the eviction's
bookkeeping, what no span of a poll covers, and the time BETWEEN polls, the
caller's loop), and the mean over the window's gaps of what each holds beyond
the median gap's.  The host's side of a stall, the harness included
(`benchmark/harness/poll_series.py`)."""
from benchmark.harness import poll_series


def read(ctx):
    gaps = poll_series.of(ctx)
    return None if gaps is None else poll_series.gap_excess_host_ms(gaps)
