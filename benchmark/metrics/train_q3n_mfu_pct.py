"""Model FLOP/s utilisation of the window for a hybrid trunk: the operations
forward and backward REQUIRE (benchmark/harness/work_q3n.train_step_flops: the
held experts at the expected pairs a token, the causal half of the score
matrix, the delta rule as its recurrence defines it; recomputation not
counted) times steps per second, over the chip's published bf16 peak.  Cannot
pass 100."""
from benchmark.harness import work_q3n


def read(ctx):
    r = ctx.records
    if ctx.peaks is None or not r.get("steps") or not r.get("elapsed_s") \
            or "gated_delta" not in ctx.sizes.get("attn_types", ()):
        return None
    flops_per_s = work_q3n.train_step_flops(ctx.sizes, r["batch"]) * r["steps"] / r["elapsed_s"]
    return 100.0 * flops_per_s / ctx.peaks["bf16_flops_per_s"]
