"""The one-token gated delta rule's share of its roofline, which is
BANDWIDTH-bound: per step every slot's float32 state is read once and written
once (`work_olmoh.gdn_step_bytes`, with the q, k, v and output vectors beside
it), and the least time that takes is those bytes over the chip's HBM
bandwidth (819e9 bytes/s on a v5e).  Divided by the device time under the
scope `gdn_step` of a `serve_decode_step` execution (all `gated_delta` layers
of the step together, median over the traced stretch's whole executions),
whatever implements the rule there."""
from benchmark.harness import work_olmoh, work_q3n


def read(ctx):
    if ctx.peaks is None or "slots" not in ctx.traffic:
        return None
    ms = work_q3n.scope_device_ms(ctx, ("gdn_step",), program="serve_decode_step")
    if not ms:
        return None
    least_s = work_olmoh.gdn_step_bytes(ctx.sizes, int(ctx.traffic["slots"])) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
