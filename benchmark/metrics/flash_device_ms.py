"""Device time per optimizer step of the flash-attention kernels: the self
time of the operations named `flash_*` (the seven Pallas calls of
kernels/flash_attention.py are named after their kernels) inside each WHOLE
execution of the program named `train_step`, median over the traced stretch's
whole executions (`ProgramTrace.executions`).  An execution that the stretch
cuts, or that the profiler's start or stop clipped, counts for nothing, above
or below the line; a scanned model's kernels run inside its `while`, whose
own time self time leaves out."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.op_ms("train_step", "flash_")
