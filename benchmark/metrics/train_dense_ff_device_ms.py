"""Device time per optimizer step of the leading dense layers' SwiGLU: scope
`dense_ff` of a `train_step` execution, median over whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("dense_ff",))
