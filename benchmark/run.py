"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration file, its traffic file
(benchmark/traffic/<traffic>.json), the generator of the traffic's kind
(benchmark/kinds/<kind>.py) and, with `--trace 1`, the reader of each per-layer
metric (benchmark/metrics/<metric>.py), runs the generator against the program,
and prints one JSON object as the last line of
its output.  `--rehearse` (tests) lets it run on the CPU, where the line
names the CPU as its device and no device metric is computed.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

T_PROCESS_START = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None, help="default: BENCHMARK.json at the repo root")
    ap.add_argument("--rehearse", action="store_true", help="allow the CPU (tests only)")
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import device, manifest, tracer as tracer_mod

    # the program's own switch: JAX_COMPILATION_CACHE_DIR if set, else the fixed
    # <checkout>/.jax_cache.  The thresholds are this process's: without them
    # the small eager programs of admission and eviction (under a second to
    # compile) are never cached and every run compiles them again.
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    if not args.rehearse:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    man = manifest.load(args.manifest)
    cell = manifest.cell(man, args.workload)
    try:
        devices = device.require_devices(int(cell["chips"]), args.rehearse)
        peaks = device.peaks(devices[0])
    except device.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sizes = manifest.config_sizes(man, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    compiles = device.CompileCounter()
    tracer = tracer_mod.Tracer(bool(args.trace), ROOT / ".bench_trace" / args.workload)

    try:  # the generator of the traffic's kind: benchmark/kinds/<kind>.py
        kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    except ModuleNotFoundError:
        print(f"benchmark: traffic kind {traffic['kind']!r} has no generator", file=sys.stderr)
        return 2
    result = kind.run(sizes, traffic, args.seed, args.seconds, tracer, compiles)
    setup_s = result["t_open"] - T_PROCESS_START  # process start -> window open

    values = dict(result["end_to_end"], setup_s=setup_s)
    dev = device.describe(devices, result["records"]["memory_at_close"])
    on_cpu = dev["platform"] == "cpu"  # a rehearsal: counts only, no time or rate is reported

    def reported(metric: dict, value):
        if on_cpu and metric["source"] != "program_counter":
            return None
        return None if value is None else float(value)

    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        view = tracer.view()
        ctx = manifest.Context(sizes=sizes, traffic=traffic, records=result["records"],
                               trace=view, peaks=peaks, end_to_end=values)
        line["metrics"] = {
            m["name"]: {"value": reported(m, v), "unit": m["unit"]}
            for m, v in manifest.read_per_layer(man, args.workload, ctx)}
        if view is not None and not on_cpu:
            dev["busy_s"], dev["window_s"] = view.busy_s(), view.window_s
            line["breakdown"] = view.breakdown()
    else:
        line["metrics"] = {}
        for m in manifest.metrics_for(man, "end_to_end", args.workload):
            if values.get(m["name"]) is None:
                print(f"benchmark: no value for {m['name']} (too few completions in the "
                      f"window?)", file=sys.stderr)
                return 4
            line["metrics"][m["name"]] = {"value": reported(m, values[m["name"]]),
                                          "unit": m["unit"]}
    line["device"] = dev
    line["detail"] = {"correct": result["records"].get("correct_detail"),
                      "window_compiles": result["records"].get("window_compiles"),
                      "rehearsal": bool(args.rehearse),
                      "window": None if on_cpu else result["records"].get("window_detail")}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
