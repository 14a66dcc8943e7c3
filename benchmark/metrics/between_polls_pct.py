"""The share of the window's completion gaps spent BETWEEN polls, outside
`engine.poll()`: from one row's `t0_s + dur_s` to the next row's `t0_s` in the
engine's series `serving/polls` (`benchmark/harness/poll_series.py`).  In the
benchmark that is the harness's client loop, in `cli/serve.py` the server's."""
from benchmark.harness import poll_series


def read(ctx):
    gaps = poll_series.of(ctx)
    return None if gaps is None else poll_series.between_polls_pct(gaps)
