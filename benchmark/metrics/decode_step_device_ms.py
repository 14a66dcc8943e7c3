"""Median device time of one fused decode program.  The jits inside serving/
carry no stable names yet, so the program is found by identity: the one with
the most device time inside the traced window (it runs once a poll; a dozen
microsecond-long eager programs an admission can outnumber it, none outweighs it)."""
from benchmark.harness import stats


def read(ctx):
    if ctx.trace is None:
        return None
    m = stats.median(ctx.trace.durations_of(ctx.trace.heaviest_module()))
    return None if m is None else m * 1e-6
