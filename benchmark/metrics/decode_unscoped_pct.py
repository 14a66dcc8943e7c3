"""Share of the decode program's device time that falls under none of the
program's scopes (`program_trace.SCOPES`): what the by-scope split of the
decode step cannot place."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.unscoped_pct("serve_decode_step")
