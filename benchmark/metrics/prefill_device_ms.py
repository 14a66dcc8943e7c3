"""Median device time of one admission (prefill of the prompt plus the ingest
of its K/V into the paged pool, one program).  Found by identity: in each
poll the harness marked `poll.admit` (it had just submitted a request), the
longest program execution that is not the decode program."""
from benchmark.harness import stats


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    decode = t.heaviest_module()
    longest = []
    for _, s, d in t.spans_named("poll.admit"):
        inside = [m[2] for m in t.modules_inside(s, s + d) if m[0] != decode]
        if inside:
            longest.append(max(inside))
    m = stats.median(longest)
    return None if m is None else m * 1e-6
