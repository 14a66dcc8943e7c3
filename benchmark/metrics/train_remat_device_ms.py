"""Device time per optimizer step of what the backward recomputes: the
operations of a `train_step` execution whose scope path holds
`rematted_computation` (jax.checkpoint's own name).  0 where nothing is
rematerialised."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.remat_ms("train_step")
