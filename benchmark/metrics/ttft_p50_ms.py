"""Median `Request.ttft_s` (submit -> first image token exists on the device)
of the requests sent and completed inside the window.  Not delivered to anyone
today (the engine hands over whole images) and under 1 % of an image's
latency: a per-layer metric until a later benchmark issue promotes it."""
from benchmark.harness import stats


def read(ctx):
    v = [c["ttft_s"] for c in ctx.records.get("sent_inside", []) if c["ttft_s"] is not None]
    m = stats.median(v)
    return None if m is None else 1e3 * m
