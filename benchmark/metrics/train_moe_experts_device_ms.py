"""Device time per optimizer step of the held experts' grouped products alone:
scope `moe_experts` of a `train_step` execution, median over whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("moe_experts",))
