"""Device time per optimizer step of the flash-attention kernels: the events
of the trace whose HLO text names a `flash_*` Pallas call (the seven kernels
of kernels/flash_attention.py carry such names), summed over the executions
of the step program that lie wholly inside the traced window (the step
program is the one with the most device time there), divided by their count."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    step = t.heaviest_module()
    runs = [m for m in t.modules_inside() if m[0] == step]
    if not runs:
        return None
    total = sum(t.op_seconds_matching(r"flash_[a-z_]+", s, s + d) for _, s, d in runs)
    return 1e3 * total / len(runs)
