"""Time the host waited on the runtime beyond a normal gap's: per completion
gap the engine's `dispatch_s + block_s` summed over its polls (a dispatch
blocks once the device's queue is full, an eviction drains it), and the mean
over the window's gaps of what each holds beyond the median gap's.  A stall on
the device's or the runtime's side lands here and not in `gap_excess_host_ms`
(`benchmark/harness/poll_series.py`)."""
from benchmark.harness import poll_series


def read(ctx):
    gaps = poll_series.of(ctx)
    return None if gaps is None else poll_series.gap_excess_blocked_ms(gaps)
