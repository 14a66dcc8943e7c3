"""Model FLOP/s utilisation of the window for a latent-attention trunk: the
operations forward and backward REQUIRE (benchmark/harness/work_glm.train_step_flops:
the held experts at the expected pairs a token, the causal half of every score
matrix, both heads, the prediction module; recomputation not counted) times
steps per second, over the chip's published bf16 peak.  Cannot pass 100."""
from benchmark.harness import work_glm


def read(ctx):
    r = ctx.records
    if ctx.peaks is None or not r.get("steps") or not r.get("elapsed_s") \
            or "mla" not in ctx.sizes.get("attn_types", ()):
        return None
    flops_per_s = work_glm.train_step_flops(ctx.sizes, r["batch"]) * r["steps"] / r["elapsed_s"]
    return 100.0 * flops_per_s / ctx.peaks["bf16_flops_per_s"]
