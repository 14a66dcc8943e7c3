"""The share of the window's throughput that stalls took: 100 x the sum over
the window's completion gaps of what each holds beyond the median gap, over the
sum of the gaps.  A gap runs from the end of one poll that returned a
completion to the end of the next, on the clock of the engine's own rows
(`serving/polls`, one a poll; `benchmark/harness/poll_series.py`), so the gaps
tile the interval `gen_img_tok_per_s` is taken over.  0.0 in a window whose
gaps are equal; a run 7 % short of a clean one reads ~7."""
from benchmark.harness import poll_series


def read(ctx):
    gaps = poll_series.of(ctx)
    return None if gaps is None else poll_series.completion_gap_excess_pct(gaps)
