"""Device time per optimizer step of the routed feed-forward: the scopes
`moe_router`, `moe_dispatch`, `moe_experts`, `moe_combine` and `shared_expert`
of a `train_step` execution (forward, backward and what the backward
recomputes), median over the traced stretch's whole steps."""
from benchmark.harness import work_q3n


def read(ctx):
    return work_q3n.scope_device_ms(
        ctx, ("moe_router", "moe_dispatch", "moe_experts", "moe_combine", "shared_expert"))
