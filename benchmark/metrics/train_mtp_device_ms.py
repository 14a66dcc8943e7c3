"""Device time per optimizer step of the prediction module: everything under
the scope `mtp` of a `train_step` execution (its merge, its block's attention
and experts, its head and loss), median over whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("mtp",))
