"""The whole decode step's share of its roofline for an Olmo-Hybrid trunk,
BANDWIDTH-bound like every decode step: the bytes of
`work_olmoh.decode_step_bytes` (the layers' weights and the untied head's
image rows once; every slot's recurrent state and taps read and written; the
keys and values each active lane sees at its traced position) over the chip's
HBM bandwidth, divided by the median device time of a whole
`serve_decode_step` execution.  (The accepted `decode_step_roofline` counts
the DALL-E block's weights and patterns and is not this trunk's.)"""
from benchmark.harness import program_trace, stats, work_olmoh


def read(ctx):
    r = ctx.records
    if ctx.peaks is None or not r.get("trace_positions") or "gdn_value_heads" not in ctx.sizes:
        return None
    t = program_trace.of(ctx)
    step_ms = None if t is None else t.program_ms("serve_decode_step")
    if not step_ms:
        return None
    byts = [work_olmoh.decode_step_bytes(ctx.sizes, pos, int(ctx.traffic["slots"]),
                                         r["weight_itemsize"], r["kv_itemsize"])
            for pos in r["trace_positions"] if pos]
    return 100.0 * stats.median(byts) / ctx.peaks["hbm_bytes_per_s"] / (step_ms * 1e-3)
