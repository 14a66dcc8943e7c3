"""One traced run by the program's own names: device time by scope for every
named program, host time of every `serve/` span, and the device's idle time by
the innermost program span it fell under.

    python3 benchmark/tools/program_report.py [<dir-or-.xplane.pb-or-events.json>]
    python3 benchmark/tools/program_report.py <trace> --cut OUT.json PART [BEFORE_MS AFTER_MS]

Without an argument it reads the newest trace under `.bench_trace/` (what the
last `--trace 1` run of `benchmark/run.py` left).  The first table is SELF
device time: per program its executions in the traced stretch, the median
device ms of one, and that median split by scope (`program_trace.SCOPES`, `-`
for operations under none, `remat` for what the backward recomputes, counted
inside its scope too).  The second is the engine's spans: count, median and
total host ms.  The third is idle device time by leaf span.  The last lists
what the per-layer readers built on `program_trace` give on this trace.
`--cut` writes part PART (`serve`, `train`) of the JSON fixture of
tests/benchmark/test_bench_program_trace.py, other parts of OUT.json kept:
the events, stats kept, from BEFORE_MS (default 35) before the end of the
first eviction's drain (`serve/evict.flag_sync`) to AFTER_MS (default 30)
after the `serve/admit` that follows it, host spans clipped to that (the
whole trace where there is no eviction), and the tables they reduce to.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import manifest, stats  # noqa: E402
from benchmark.harness import program_trace as pt  # noqa: E402


def reader_values(trace: pt.ProgramTrace) -> dict:
    """{metric: value} of BENCHMARK.json's per-layer readers that read a
    ProgramTrace, where they find something to read in this one."""
    ctx = manifest.Context(sizes={}, traffic={}, records={}, trace=trace, peaks=None,
                           end_to_end={})
    ctx.program_trace = trace
    out = {}
    for m in manifest.load()["per_layer"]:
        read = manifest.reader(m["name"])
        if "program_trace" in read.__globals__:  # the readers built on this module
            value = read(ctx)
            if value is not None:
                out[m["name"]] = value
    return out


def tables(trace: pt.ProgramTrace) -> dict:
    """The report as data: what the printed tables and the fixture's
    `expected` are made from."""
    programs = {}
    for program in trace.programs():
        runs = trace.executions(program)
        if not runs:
            continue
        by_scope = trace.time_by(program, pt.scope_of)
        scopes = sorted({s for by in by_scope for s in by})
        programs[program] = {
            "executions": len(runs),
            "median_ms": trace.program_ms(program),
            "scope_ms": {s or "-": stats.median([by.get(s, 0.0) for by in by_scope]) * 1e-6
                         for s in scopes},
            "remat_ms": trace.remat_ms(program),
            "unscoped_pct": trace.unscoped_pct(program),
        }
    spans = {}
    for s in trace.spans:
        if s.start >= trace.lo and s.end <= trace.hi:
            spans.setdefault(s.name, []).append(s.dur * 1e-6)
    idle, idle_total = trace.idle_by_leaf_span()
    return {
        "stretch_ms": (trace.hi - trace.lo) * 1e-6,
        "programs": programs,
        "spans": {n: {"n": len(v), "median_ms": stats.median(v), "total_ms": sum(v)}
                  for n, v in sorted(spans.items())},
        "idle_ms": {"total": idle_total * 1e3,
                    "by_leaf_span": {k: v * 1e3 for k, v in sorted(idle.items())}},
        "metrics": reader_values(trace),
    }


def render(t: dict, out=sys.stdout) -> None:
    print(f"traced stretch {t['stretch_ms']:.1f} ms", file=out)
    print("\ndevice time by program and scope (self time, median ms of one execution)", file=out)
    heavy = sorted(t["programs"].items(),
                   key=lambda kv: -(kv[1]["median_ms"] or 0.0) * kv[1]["executions"])
    for name, p in heavy:
        if (p["median_ms"] or 0.0) < 0.05 and len(heavy) > 12:
            continue  # the eager one-microsecond programs of admission and eviction
        print(f"  {name}: {p['executions']} executions, median {p['median_ms']:.3f} ms, "
              f"unscoped {p['unscoped_pct'] or 0.0:.2f} %, remat {p['remat_ms'] or 0.0:.3f} ms",
              file=out)
        for scope, ms in sorted(p["scope_ms"].items(), key=lambda kv: -kv[1]):
            print(f"      {scope:<18}{ms:10.3f}", file=out)
    print("\nhost spans of the engine (ms)", file=out)
    for name, s in t["spans"].items():
        print(f"  {name:<28} n={s['n']:<5} median {s['median_ms']:9.3f}  total {s['total_ms']:10.3f}",
              file=out)
    idle = t["idle_ms"]
    print(f"\ndevice idle {idle['total']:.3f} ms, by innermost program span", file=out)
    named = sum(idle["by_leaf_span"].values())
    for name, ms in sorted(idle["by_leaf_span"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:<28}{ms:10.3f}", file=out)
    print(f"  {'(outside any leaf span)':<28}{idle['total'] - named:10.3f}", file=out)
    print("\nper-layer metrics read from this trace", file=out)
    for name, value in t["metrics"].items():
        print(f"  {name:<32}{value:12.4f}", file=out)


def main(argv) -> int:
    path = argv[0] if argv and not argv[0].startswith("--") else pt.newest_xplane()
    if path is None:
        print("program_report: no trace given and none under .bench_trace/", file=sys.stderr)
        return 2
    events = json.loads(Path(path).read_text()) if str(path).endswith(".json") \
        else pt.load_xplane(path)
    if "--cut" in argv:
        i = argv.index("--cut")
        out, part = Path(argv[i + 1]), argv[i + 2]
        before, after = (float(x) * 1e6 for x in argv[i + 3:i + 5]) if len(argv) > i + 4 \
            else (35e6, 30e6)
        whole = pt.ProgramTrace(events)
        lo, hi = whole.lo, whole.hi
        drains = whole.spans_named("serve/evict.flag_sync")
        if drains:
            admit = next(s for s in whole.spans_named("serve/admit") if s.start > drains[0].end)
            lo, hi = drains[0].end - before, admit.end + after
        events = pt.cut(events, lo, hi)
        fixture = json.loads(out.read_text()) if out.exists() else {}
        fixture[part] = {"events": events,
                         "expected": tables(pt.ProgramTrace(json.loads(json.dumps(events))))}
        out.write_text(json.dumps(fixture, separators=(",", ":")))
    render(tables(pt.ProgramTrace(events)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
