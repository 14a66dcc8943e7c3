"""Quartile spread of each metric over the runs of a set, as the contract
measures it: (Q3 - Q1) / median with `statistics.quantiles(values, n=4)`.

    python3 benchmark/tools/spread.py benchmark/runs/*.jsonl
"""
from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    sets = defaultdict(lambda: defaultdict(list))
    for path in paths:
        for line in open(path):
            rec = json.loads(line)
            if rec.get("result") and not rec["trace"]:
                for name, m in rec["result"]["metrics"].items():
                    sets[(rec["cell"], rec["tag"])][name].append(m["value"])
    for (cell, tag), metrics in sorted(sets.items()):
        for name, values in metrics.items():
            s = spread(values)
            print(f"{cell:14s} {tag:22s} {name:22s} n={len(values)} "
                  f"median={statistics.median(values):.6g} "
                  f"spread={'-' if s is None else format(100 * s, '.3f') + '%'} "
                  f"values={[round(v, 4) for v in values]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
