"""The decode step's share of its roofline, which is BANDWIDTH-bound: one
token a lane against every weight, so the least time a step can take is the
bytes it must read over the chip's HBM bandwidth (819e9 bytes/s on a v5e).
Bytes (benchmark/harness/work.decode_step_bytes): every layer's matmul weights
once, in the type they are stored in; of the shared table the rows a decode
step can emit (`num_image_tokens`, which its lookup and its head both read),
once; plus, for each active lane at its position in each traced poll, the keys
and values its layers' patterns let it see, in the pool's type.  Divided by
the median device time of the decode program."""
from benchmark.harness import stats, work


def read(ctx):
    r = ctx.records
    if ctx.trace is None or ctx.peaks is None or not r.get("trace_positions"):
        return None
    step_ns = stats.median(ctx.trace.durations_of(ctx.trace.heaviest_module()))
    if not step_ns:
        return None
    byts = [work.decode_step_bytes(ctx.sizes, pos, r["weight_itemsize"], r["kv_itemsize"])
            for pos in r["trace_positions"] if pos]
    least_s = stats.median(byts) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (step_ns * 1e-9)
