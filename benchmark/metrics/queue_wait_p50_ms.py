"""Median `Request.phases["queue_wait"]` (submit -> popped for admission) of the
requests sent and completed inside the window: the engine's own span."""
from benchmark.harness import stats


def read(ctx):
    v = [c["queue_wait_s"] for c in ctx.records.get("sent_inside", []) if c["queue_wait_s"] is not None]
    m = stats.median(v)
    return None if m is None else 1e3 * m
