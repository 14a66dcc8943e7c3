"""Median host time of one admission: the engine's `serve/admit` span (table
allocation, the admit jit's dispatch, the eager lane scatters and the TTFT
sync) in the traced stretch."""
from benchmark.harness import program_trace, stats


def read(ctx):
    t = program_trace.of(ctx)
    if t is None:
        return None
    m = stats.median([s.dur for s in t.spans_named("serve/admit")])
    return None if m is None else m * 1e-6
