"""Device time per optimizer step of latent attention's core alone, the flash
kernels over the materialised heads: scope `mla_core` of a `train_step`
execution, median over whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("mla_core",))
