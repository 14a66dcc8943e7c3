"""Median host time of one eviction less its drain: the engine's `serve/evict`
span minus its `serve/evict.flag_sync` child.  The flag sync waits for the
decode steps the host has queued ahead, which is device-busy time and no
cost of the eviction; the rest (codes pull, lane reset, VAE decode, pixel
pull) is what the device idles under."""
from benchmark.harness import program_trace, stats


def read(ctx):
    t = program_trace.of(ctx)
    if t is None:
        return None
    own = []
    for s in t.spans_named("serve/evict"):
        drain = s.child("serve/evict.flag_sync")
        own.append(s.dur - (drain.dur if drain is not None else 0.0))
    m = stats.median(own)
    return None if m is None else m * 1e-6
