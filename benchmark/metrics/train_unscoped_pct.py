"""Share of the train step's device time that falls under none of the
program's scopes (`program_trace.SCOPES`)."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.unscoped_pct("train_step")
