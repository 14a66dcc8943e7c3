"""Cut the small test fixture from a trace a `--trace 1` serve run left behind.

    python3 benchmark/tools/cut_fixture.py <.bench_trace/<cell> | events.json> <out.json> [before_ms after_ms]

Keeps the stretch from `before_ms` before the end of the first `poll.evict`
span (the tail of the drain, the eviction, the VAE decode) to `after_ms` after
the end of the `poll.admit` span that follows it: harness spans and device
events clipped to that stretch, operation names shortened to the instruction
name (all the reducer reads), times in whole ns from the start of the stretch.
The numbers the reducer gave when the fixture was cut are stored beside the
events; the test holds the reducer to them."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import trace_reduce as tr  # noqa: E402


def main(argv) -> int:
    src, out = argv[0], argv[1]
    before, after = (float(argv[2]), float(argv[3])) if len(argv) > 3 else (60.0, 30.0)
    ev = json.loads(Path(src).read_text())["events"] if src.endswith(".json") else tr.load_xplane(src)
    spans = sorted(ev["spans"], key=lambda s: s[1])
    evict = next(s for s in spans if s[0] == "bench/poll.evict")
    admit = next(s for s in spans if s[0] == "bench/poll.admit" and s[1] >= evict[1])
    lo, hi = evict[1] + evict[2] - before * 1e6, admit[1] + admit[2] + after * 1e6

    def clipped(events, name_of=lambda n: n):
        return [[name_of(n), round(a - lo), round(b - a)] for n, a, b in tr.clip(events, lo, hi)]

    cut = {"devices": {p: {"ops": clipped(d["ops"], lambda n: tr._OP_NAME.match(n).group(0)),
                           "modules": clipped(d["modules"])}
                       for p, d in ev["devices"].items()},
           "spans": clipped(spans)}
    view = tr.TraceView(json.loads(json.dumps(cut)))
    expected = dict(view.breakdown(), window_s=view.window_s, busy_s=view.busy_s(),
                    decode_program=view.heaviest_module())
    Path(out).write_text(json.dumps({
        "what": "serve_batch on a TPU v5e (PR 23): the end of one eviction's drain, the "
                "eviction, the submit and the admission that follow it, and the next polls",
        "events": cut, "expected": expected}, separators=(",", ":")))
    print(json.dumps(expected)[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
