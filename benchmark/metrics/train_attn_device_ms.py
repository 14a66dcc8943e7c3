"""Device time per optimizer step of attention: scope `attn` of a `train_step`
execution (projections, the flash kernels inside it, forward, backward and
any recomputation), median over the traced stretch's whole steps."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("train_step", ("attn",))
