"""Device time per optimizer step of stacking the per-layer weights for the
layer scan, and of unstacking their gradients: scope `stack_layers` of a
`train_step` execution.  0 where the layers are unrolled."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("train_step", ("stack_layers",))
