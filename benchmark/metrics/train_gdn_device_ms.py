"""Device time per optimizer step of the Gated DeltaNet layers: the scopes
`gdn_proj`, `gdn_conv`, `gdn_scan` and `gdn_gate_norm` of a `train_step`
execution (their output projection stays under plain `attn`), median over the
traced stretch's whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(ctx, ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_gate_norm"))
