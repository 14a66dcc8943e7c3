"""Run cells one after another, each run a process of its own as the driver
runs them (this parent never touches JAX, so the chip is free for each child),
and keep every result line with the command that made it.

    python3 benchmark/tools/run_cells.py [--note TEXT] <tag> <cell>:<seed>:<trace> ...
    python3 benchmark/tools/run_cells.py [--note TEXT] --sets <n> [--traced] <cell> ...

The second form is the contract's measurement of a bound: for each cell two
sets (tags <cell>_set_a, <cell>_set_b) of n runs with the same n seeds in
both, then with --traced one traced run (tag <cell>_traced).  Every run lasts
BENCHMARK.json's `run_seconds` (the first form takes --seconds for a trial); a
set stops at its first run that fails or is not `correct`.  Appends to chiprun_out/<tag>.jsonl one line a
run (tag, cell, seed, trace, command, exit code, wall seconds, note, the
result) and prints a short table.  The lines of this PR's chip runs are kept,
as written, in benchmark/runs/ (tools/spread.py reads them).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SEEDS = [1000003, 2147483659, 3000000017, 41, 4000000001, 77777]
TRACED_SEED = 5000000029


def run_one(tag: str, cell: str, seed: int, trace: int, note: str, seconds=None) -> int:
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    command = man["command"] + ["--workload", cell, "--seed", str(seed),
                                "--seconds", str(seconds or man["run_seconds"]), "--trace", str(trace)]
    t0 = time.monotonic()
    proc = subprocess.run(command, cwd=ROOT, capture_output=True, text=True)
    wall = time.monotonic() - t0
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    rec = {"tag": tag, "cell": cell, "seed": seed, "trace": trace, "command": " ".join(command),
           "rc": proc.returncode, "wall_s": round(wall, 1), "harness": note, "result": result}
    failed = proc.returncode != 0 or result is None or not result.get("correct")
    if proc.returncode != 0 or result is None:
        rec["stderr_tail"] = proc.stderr[-3000:]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with open(out_dir / f"{tag}.jsonl", "a") as log:
        log.write(json.dumps(rec) + "\n")
    short = {k: v["value"] for k, v in (result or {}).get("metrics", {}).items()}
    print(tag, cell, seed, f"trace={trace} rc={proc.returncode} wall={wall:.0f}s",
          "correct=", (result or {}).get("correct"),
          "attempted=", (result or {}).get("attempted"), json.dumps(short), flush=True)
    if result is None:
        print(proc.stderr[-3000:], flush=True)
    else:
        print("   device", json.dumps(result.get("device")),
              "detail", json.dumps(result.get("detail")), flush=True)
        if "breakdown" in result:
            print("   breakdown", json.dumps(result["breakdown"]), flush=True)
    return int(failed)


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--note", default="", help="the state of the harness, kept on every line")
    ap.add_argument("--sets", type=int, default=0, help="runs a set; the rest are cells")
    ap.add_argument("--traced", action="store_true")
    ap.add_argument("--seconds", default=None, help="a trial at another length than run_seconds")
    ap.add_argument("rest", nargs="+")
    args = ap.parse_args(argv)
    bad = 0
    if args.sets:
        for cell in args.rest:
            for which in "ab":
                for seed in SEEDS[:args.sets]:
                    if run_one(f"{cell}_set_{which}", cell, seed, 0, args.note):
                        return 1  # a set with a failed or incorrect run proves nothing
            if args.traced:
                bad += run_one(f"{cell}_traced", cell, TRACED_SEED, 1, args.note)
    else:
        tag, specs = args.rest[0], args.rest[1:]
        for spec in specs:
            cell, seed, trace = spec.split(":")
            bad += run_one(tag, cell, int(seed), int(trace), args.note, args.seconds)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
