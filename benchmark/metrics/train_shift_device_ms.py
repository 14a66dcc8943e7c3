"""Device time per optimizer step of the token shift: scope `token_shift` of a
`train_step` execution, forward and backward."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("train_step", ("token_shift",))
