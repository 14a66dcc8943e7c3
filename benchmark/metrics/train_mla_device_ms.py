"""Device time per optimizer step of latent attention: the scopes `mla_q_proj`,
`mla_kv_proj`, `mla_rope`, `mla_core` and `mla_out` of a `train_step` execution
(forward, backward and what the backward recomputes; the prediction module's
block included), median over the traced stretch's whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(
        ctx, ("mla_q_proj", "mla_kv_proj", "mla_rope", "mla_core", "mla_out"))
