"""Device time per decode step of the model's own work: the scopes `attn`,
`ff`, `norm`, `token_shift`, `embed`, `kv_write` and `codes_write` of a
`serve_decode_step` execution, median over the traced stretch."""
from benchmark.harness import program_trace

SCOPES = ("attn", "ff", "norm", "token_shift", "embed", "kv_write", "codes_write")


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("serve_decode_step", SCOPES)
