"""Device time per optimizer step of the output head: scope `logits_loss` of a
`train_step` execution (logits matmul, mask, cross-entropy, and their
backward)."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("train_step", ("logits_loss",))
