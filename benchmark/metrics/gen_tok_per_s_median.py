"""The median beside the mean of `gen_img_tok_per_s`: image tokens / the
MEDIAN gap between successive completions of the window.  One stall moves one
gap and not this, so the two together say whether a loss is the decode step's
(both fall) or a stall's (only the end-to-end mean falls)."""
from benchmark.harness import stats


def read(ctx):
    times = [c["t"] for c in ctx.records.get("completions", [])]
    return stats.rate_from_median_gap(times, ctx.records.get("image_tokens", 0))
