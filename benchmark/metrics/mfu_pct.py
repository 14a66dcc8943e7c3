"""Model FLOP/s utilisation of the window: the operations forward and
backward REQUIRE (benchmark/harness/work.train_step_flops: element-granular
attention density, recomputation not counted) times steps per second, over
the chip's published bf16 peak.  Cannot pass 100."""
from benchmark.harness import work


def read(ctx):
    r = ctx.records
    if ctx.peaks is None or not r.get("steps") or not r.get("elapsed_s"):
        return None
    flops_per_s = work.train_step_flops(ctx.sizes, r["batch"]) * r["steps"] / r["elapsed_s"]
    return 100.0 * flops_per_s / ctx.peaks["bf16_flops_per_s"]
