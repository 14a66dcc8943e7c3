"""Device time per optimizer step of the window layers' whole mixer: scope
`swa` of a `train_step` execution (projections, rotary, the band's flash
kernels, the per-head gate; forward, backward and any recomputation), median
over the traced stretch's whole steps.  None on a program that has no window
layer."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("swa",))
