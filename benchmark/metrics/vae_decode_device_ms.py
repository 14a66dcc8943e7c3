"""Median device time of one execution of `serve_vae_decode`, the program
that turns a finished request's codes into pixels inside the eviction."""
from benchmark.harness import program_trace


def read(ctx):
    t = program_trace.of(ctx)
    return None if t is None else t.program_ms("serve_vae_decode")
