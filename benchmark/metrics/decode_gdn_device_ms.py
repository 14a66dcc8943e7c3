"""Device time per decode step of the Gated DeltaNet layers' own work: the
scopes `gdn_proj`, `gdn_conv_step`, `gdn_step` and `gdn_gate_norm` of a
`serve_decode_step` execution (their output projection stays under plain
`attn`), self time, median over the traced stretch's whole executions.
Nothing to read where the program names no such scope (a parent from before
PR 33, or a trunk without `gated_delta` layers)."""
from benchmark.harness import work_q3n

SCOPES = ("gdn_proj", "gdn_conv_step", "gdn_step", "gdn_gate_norm")


def read(ctx):
    return work_q3n.scope_device_ms(ctx, SCOPES, program="serve_decode_step")
