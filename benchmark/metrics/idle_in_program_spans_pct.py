"""Share of the traced stretch's device idle time that falls inside a leaf
`serve/` span of the engine: how much of the idle time the program's own
spans explain.  100 where the device never idles outside a named phase."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    if t is None or not t.spans or not t.ops:
        return None
    by, total = t.idle_by_leaf_span()
    return 100.0 * sum(by.values()) / total if total else None
