"""Device time per decode step of the paged K/V gather: the operations of a
`serve_decode_step` execution whose scope is `kv_gather` (the `take`,
transpose and reshape of `_paged_attention_step`), median over the traced
stretch's executions.  Nothing to read where the program names no such
program or scope."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("serve_decode_step", ("kv_gather",))
