"""Median host time of one `step_fn(...)` call (enqueue only: the step is not
waited for inside the span), from the harness's own clock around the call."""
from benchmark.harness import stats


def read(ctx):
    return stats.median(ctx.records.get("host_dispatch_ms") or [])
