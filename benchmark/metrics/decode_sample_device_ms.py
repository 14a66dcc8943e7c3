"""Device time per decode step of the sampler (`lane_sample_pipeline`: logits
matmul, guidance, top-k, draw): scope `sample` of a `serve_decode_step`
execution, median over the traced stretch."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.scope_ms("serve_decode_step", ("sample",))
