"""Where a serving run's completion gaps went: one line a gap, by the six parts
the engine's poll series splits it into, and for every stalled gap its three
longest polls.

    python3 benchmark/tools/stall_report.py <run.json[.gz]> [<trace>]
    python3 benchmark/tools/stall_report.py --run <out.json[.gz]> <benchmark/run.py's arguments>

The second form runs one cell in THIS process as `benchmark/run.py` runs it
(the line it prints is the cell's), then keeps what the engine wrote into
`serving/polls` beside that line in <out> and reports on it; with `--trace 1`
among the arguments also against the trace the run left under `.bench_trace/`.
The first form reports on a kept run, and <trace> is a directory, an
`.xplane.pb` or a JSON of events (`program_trace.load_xplane`'s dictionary).

A gap runs from the end of one poll that evicted a request to the end of the
next (`benchmark/harness/poll_series.py`), over ALL such polls the series
holds, the traced stretch after the window included; gaps of the window (the
line's `detail.window`) carry a `w`.  Columns, ms: the gap's wall time, then
`admit`, `dispatch`, `block`, `evict` as the engine booked them over the gap's
polls, `other` (the rest of the polls' `dur_s`) and `between` (outside
`poll()`: the caller's loop).  A gap over the median by more than 1 % is
marked `*`, with what it holds beyond the median gap in all and by part, and
its three longest polls: `iter`, `dur_s` and the largest part inside.  With a
trace: the offset between each `serve/poll` span's start and the `t0_s` of the
row of the same `iter` (one constant if the row and the span are one interval
on two clocks: median and range) and the same for the two durations, and for
every listed poll the trace holds, the device's busy and idle time inside it:
idle, the runtime or the device retired nothing; busy, a step ran long.
"""
from __future__ import annotations

import contextlib
import gzip
import io
import json
import sys
from pathlib import Path
from typing import Dict, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import poll_series as ps  # noqa: E402
from benchmark.harness import program_trace as pt  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402

POLL_SPAN = "serve/poll"


# ---- a kept run --------------------------------------------------------------
def _open(path, mode):
    return gzip.open(path, mode + "t") if str(path).endswith(".gz") else open(path, mode)


def keep(path, rows: Dict[str, np.ndarray], line: Optional[dict]) -> None:
    rows = dict(rows)
    doc = {"line": line, "dropped": int(rows.pop("dropped", 0)),
           "columns": {c: v.tolist() for c, v in rows.items()}}
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with _open(path, "w") as f:
        json.dump(doc, f)


def load(path) -> dict:
    """{"line": the result line or None, "rows": {column: array, "dropped": n}}"""
    with _open(path, "r") as f:
        doc = json.load(f)
    rows = {c: np.asarray(v, np.float64) for c, v in doc["columns"].items()}
    return {"line": doc.get("line"), "rows": dict(rows, dropped=doc.get("dropped", 0))}


def window_polls(line: Optional[dict], polls: list) -> set:
    """Which of the run's completion polls closed a gap of the window: the
    line names the poll the window opened at and lists its completions."""
    window = ((line or {}).get("detail") or {}).get("window") or {}
    if window.get("open_poll") is None:
        return set()
    inside = [p for p in polls if p > window["open_poll"]][:len(window.get("completions", []))]
    return set(inside[1:])  # the first completion opens the first gap


# ---- the report ----------------------------------------------------------------
def report(rows: Dict[str, np.ndarray], line: Optional[dict] = None, trace=None,
           out=sys.stdout) -> Optional[dict]:
    """Prints the table; returns it as data ({"gaps": [...], "marked": [...],
    "clock": {...}}), None where the rows hold fewer than three gaps."""
    polls = ps.completion_polls(rows)
    gaps = ps.window_gaps(rows, polls, say=lambda text: print(f"stall_report: {text}", file=out))
    if gaps is None:
        return None
    in_window = window_polls(line, polls)
    spans = _poll_spans(trace)
    device_ops = [op[:3] for op in trace.ops] if trace is not None else []
    median = float(np.median(gaps.wall_s))
    typical = {p: float(np.median(gaps.parts[p])) for p in ps.PARTS}
    marked = set(gaps.marked())
    shown = {"median_gap_s": median, "gaps": [], "marked": [], "clock": _clock(rows, spans, out)}
    if rows.get("dropped"):
        print(f"the series dropped {int(rows['dropped'])} rows: the run's first polls are gone", file=out)
    print(f"{len(gaps)} completion gaps, median {1e3 * median:.3f} ms; "
          f"completion_gap_excess_pct {ps.completion_gap_excess_pct(gaps):.4f}, "
          f"gap_excess_blocked_ms {ps.gap_excess_blocked_ms(gaps):.4f}, "
          f"gap_excess_host_ms {ps.gap_excess_host_ms(gaps):.4f}, "
          f"between_polls_pct {ps.between_polls_pct(gaps):.4f} (over all of them)", file=out)
    print("    gap  to_iter  polls      wall" + "".join(f"{p[:-2]:>10}" for p in ps.PARTS), file=out)
    for k in range(len(gaps)):
        parts = {p: float(gaps.parts[p][k]) for p in ps.PARTS}
        entry = {"gap": k, "to_iter": gaps.polls[k + 1], "polls": int(gaps.n_polls[k]),
                 "wall_s": float(gaps.wall_s[k]), "parts_s": parts,
                 "window": gaps.polls[k + 1] in in_window}
        shown["gaps"].append(entry)
        flags = ("*" if k in marked else " ") + ("w" if entry["window"] else " ")
        print(f"{flags} {k:4d} {entry['to_iter']:8d} {entry['polls']:6d} {1e3 * entry['wall_s']:9.3f}"
              + "".join(f"{1e3 * parts[p]:10.3f}" for p in ps.PARTS), file=out)
        if k not in marked:
            continue
        over = entry["wall_s"] - median
        by_part = {p: parts[p] - typical[p] for p in ps.PARTS}
        grew = max(by_part, key=by_part.get)
        entry.update(excess_s=over, excess_by_part_s=by_part, grew=grew, longest=[])
        print(f"       +{1e3 * over:.3f} ms (+{100 * over / median:.2f} %) over the median gap; "
              + ", ".join(f"{p[:-2]} {1e3 * v:+.3f}" for p, v in by_part.items())
              + f": {grew[:-2]} grew most", file=out)
        for it, dur, part, secs in gaps.longest_polls(k):
            poll = {"iter": it, "dur_s": dur, "part": part, "part_s": secs}
            text = f"       poll iter {it}: {1e3 * dur:.3f} ms, {part[:-2]} {1e3 * secs:.3f}"
            if it in spans:
                start, span_ns = spans[it]
                busy = sum(b - a for a, b in tr.busy_intervals(device_ops, start, start + span_ns)) * 1e-9
                poll.update(device_busy_s=busy, device_idle_s=span_ns * 1e-9 - busy)
                text += (f"; device busy {1e3 * busy:.3f} ms, idle "
                         f"{1e3 * poll['device_idle_s']:.3f} ms inside its span")
            entry["longest"].append(poll)
            print(text, file=out)
        shown["marked"].append(entry)
    if not marked:
        print(f"no gap is over the median by more than {100 * ps.MARK:g} %", file=out)
    return shown


def _poll_spans(trace) -> Dict[int, tuple]:
    """{iter: (start ns, duration ns)} of the trace's `serve/poll` spans."""
    if trace is None:
        return {}
    return {int(s.stats["iter"]): (s.start, s.dur) for s in trace.spans
            if s.name == POLL_SPAN and "iter" in s.stats}


def _clock(rows, spans: Dict[int, tuple], out) -> Optional[dict]:
    """The row and the span of one `iter` are one interval on two clocks: the
    offset between their starts is one constant, and their durations agree."""
    at = {int(it): i for i, it in enumerate(rows["iter"])}
    both = sorted(it for it in spans if it in at)
    if not both:
        return None
    offset = np.array([spans[it][0] * 1e-9 - rows["t0_s"][at[it]] for it in both])
    longer = np.array([spans[it][1] * 1e-9 - rows["dur_s"][at[it]] for it in both])
    clock = {"polls": len(both), "from_iter": both[0], "to_iter": both[-1],
             "offset_median_s": float(np.median(offset)),
             "offset_range_s": float(offset.max() - offset.min()),
             "dur_diff_median_s": float(np.median(longer)),
             "dur_diff_range_s": float(longer.max() - longer.min())}
    print(f"trace: {len(both)} serve/poll spans (iter {both[0]}-{both[-1]}) beside their rows: "
          f"span.start - t0_s median {clock['offset_median_s']:.6f} s, range "
          f"{1e6 * clock['offset_range_s']:.1f} us; span.dur - dur_s median "
          f"{1e6 * clock['dur_diff_median_s']:.1f} us, range {1e6 * clock['dur_diff_range_s']:.1f} us",
          file=out)
    return clock


def load_trace(path) -> pt.ProgramTrace:
    events = json.loads(Path(path).read_text()) if str(path).endswith(".json") else pt.load_xplane(str(path))
    return pt.ProgramTrace(events)


# ---- running a cell ------------------------------------------------------------
def run_and_keep(out_path: str, argv: list) -> int:
    from benchmark import run

    printed = io.StringIO()  # the cell prints its line last: held, then passed on
    try:
        with contextlib.redirect_stdout(printed):
            rc = run.main(argv)
    finally:
        sys.stdout.write(printed.getvalue())
    rows = ps.engine_rows()
    if rows is None:
        print("stall_report: this program keeps no series serving/polls", file=sys.stderr)
        return rc or 2
    try:
        line = json.loads(printed.getvalue().strip().splitlines()[-1])
    except (IndexError, json.JSONDecodeError):
        line = None
    keep(out_path, rows, line)
    trace = None
    if "--trace" in argv and argv[argv.index("--trace") + 1] == "1":
        newest = pt.newest_xplane()
        trace = load_trace(newest) if newest else None
    print(f"stall_report: {len(rows['iter'])} rows kept in {out_path}")
    report(rows, line, trace)
    return rc


def main(argv) -> int:
    if argv and argv[0] == "--run":
        return run_and_keep(argv[1], argv[2:])
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    run = load(argv[0])
    trace = load_trace(argv[1]) if len(argv) > 1 else None
    return 0 if report(run["rows"], run["line"], trace) is not None else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
