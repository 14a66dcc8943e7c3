"""Model FLOP/s utilisation of the window for a trunk of window and full
attention layers: the operations forward and backward REQUIRE
(benchmark/harness/work_laguna.train_step_flops: the band's pairs for the
window kind, the causal half for the full kind, the held experts at the
expected pairs a token; recomputation not counted) times steps per second,
over the chip's published bf16 peak.  Cannot pass 100."""
from benchmark.harness import work_laguna


def read(ctx):
    r = ctx.records
    if ctx.peaks is None or not r.get("steps") or not r.get("elapsed_s") \
            or "gated_window" not in ctx.sizes.get("attn_types", ()):
        return None
    flops_per_s = work_laguna.train_step_flops(ctx.sizes, r["batch"]) * r["steps"] / r["elapsed_s"]
    return 100.0 * flops_per_s / ctx.peaks["bf16_flops_per_s"]
