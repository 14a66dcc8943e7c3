"""Median `Request.latency_s` of ALL the requests that completed inside the
window, those sent during the pre-roll included (ISSUE 23's population; the
end-to-end `image_latency_p50_s` takes only the requests also sent inside).
The difference between the two is what the pre-roll's one-off costs add."""
from benchmark.harness import stats


def read(ctx):
    return stats.median([c["latency_s"] for c in ctx.records.get("completions", [])])
