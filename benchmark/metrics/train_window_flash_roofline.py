"""The window layers' flash kernels against the chip's bf16 peak: the
operations their band REQUIRES (benchmark/harness/work_laguna.window_flash_flops:
the (query, key) pairs inside the window only, forward and backward, every
window layer and sequence of a step) over the device time of the `flash_*`
operations under scope `swa_core` of a `train_step` execution (median over
whole steps), over 197e12.  The operations bound it: at train_laguna_ep8's sizes a step's
window calls require 2.40 TFLOP (12.2 ms at the peak) and read and write
about 5.6 GB of q, k, v, out and their gradients at the key/value heads the
layer holds (6.9 ms at 819 GB/s).  Cannot pass 100: the tiles compute more
pairs than the band holds."""
from benchmark.harness import work_laguna


def read(ctx):
    r = ctx.records
    if ctx.peaks is None or not r.get("batch") or "gated_window" not in ctx.sizes.get("attn_types", ()):
        return None
    ms = work_laguna.flash_ms_under(ctx, "swa_core")
    if not ms:
        return None
    return 100.0 * work_laguna.window_flash_flops(ctx.sizes, r["batch"]) / (ms * 1e-3) \
        / ctx.peaks["bf16_flops_per_s"]
