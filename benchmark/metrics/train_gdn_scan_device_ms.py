"""Device time per optimizer step of the chunked delta rule alone: scope
`gdn_scan` of a `train_step` execution, median over whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("gdn_scan",))
