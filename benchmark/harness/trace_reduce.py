"""From a profiler trace to numbers: device busy and idle time, device time by
operation name, idle gaps by what the host was doing, and the per-program
views the per-layer readers use.  Works on a plain dictionary of events
(`load_xplane` makes one from the profiler's `.xplane.pb`; the test fixture is
one, cut from a chip run), so the arithmetic is checked without a chip.

What a TPU trace looks like (jax 0.9, v5e; `benchmark/tools/dump_trace.py`
prints one): a plane `/device:TPU:<n>` per chip with the lines `XLA Modules`
(one event per executed program, named `jit_<function>(<fingerprint>)`) and
`XLA Ops` (one event per HLO instruction on the core, named by its whole HLO
text, `%fusion.12 = f32[...] fusion(...)`); a plane `/host:CPU` whose thread
lines hold the `jax.profiler.TraceAnnotation` spans.  All on one clock, in
nanoseconds.  The harness names its spans `bench/<what>`; the traced window is
the stretch those spans cover.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)
SPAN_PREFIX = "bench/"
_OP_NAME = re.compile(r"^%?([^\s=(]+)")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def load_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [Event], "modules": [Event]}}, "spans": [Event]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = {"devices": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                                for ev in line.events]
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                                 for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    out["spans"].sort(key=lambda e: e[1])
    return out


def op_group(name: str) -> str:
    """`%copy_select_fusion.3 = f32[..] fusion(..)` -> `copy_select_fusion`."""
    m = _OP_NAME.match(name)
    short = m.group(1) if m else name
    return re.sub(r"[.\d]+$", "", short) or short


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[str, float, float]]:
    """(name, start, end) of the parts of `events` inside [lo, hi]."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def busy_intervals(ops: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    return merge([(a, b) for _, a, b in clip(ops, lo, hi)])


def time_by_name(ops: Sequence[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds of device time per operation group inside the window, as SELF
    time: a `while` or `conditional` event spans the events of its body on
    the same line, and only what its children do not cover is its own."""
    events = sorted(clip(ops, lo, hi), key=lambda e: (e[1], -e[2]))
    own = [b - a for _, a, b in events]
    stack: List[int] = []  # indices of the events that enclose the current one
    for i, (_, a, b) in enumerate(events):
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    out: Dict[str, float] = {}
    for (name, _, _), t in zip(events, own):
        g = op_group(name)
        out[g] = out.get(g, 0.0) + max(t, 0.0) * 1e-9
    return out


def idle_gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def attribute_gaps(gaps: Sequence[Tuple[float, float]], spans: Sequence[Event]) -> Dict[str, float]:
    """Seconds of idle time by the harness span the host was in.  Spans may
    nest (a `poll.admit` inside nothing, a `wait_loss` beside a `dispatch`);
    a stretch of a gap goes to the span that STARTED LAST among those
    covering it, and to `unattributed` where none does."""
    spans = sorted(spans, key=lambda e: e[1])
    starts = [s[1] for s in spans]
    out: Dict[str, float] = {}
    for ga, gb in gaps:
        cuts = {ga, gb}
        live = [s for s in spans[:bisect.bisect_left(starts, gb)] if s[1] + s[2] > ga]
        for _, s, d in live:
            cuts.update(x for x in (s, s + d) if ga < x < gb)
        edges = sorted(cuts)
        for a, b in zip(edges, edges[1:]):
            mid = 0.5 * (a + b)
            owner = None
            for name, s, d in live:
                if s <= mid < s + d and (owner is None or s >= owner[1]):
                    owner = (name, s)
            key = owner[0][len(SPAN_PREFIX):] if owner else "unattributed"
            out[key] = out.get(key, 0.0) + (b - a) * 1e-9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


class TraceView:
    """One traced window, reduced on demand by the readers in benchmark/metrics/."""

    def __init__(self, events: dict):
        self.devices: Dict[str, dict] = events["devices"]
        self.spans: List[Event] = events["spans"]
        if not self.spans:
            raise ValueError("the trace holds no bench/ span: no window to reduce")
        self.lo = min(s for _, s, _ in self.spans)
        self.hi = max(s + d for _, s, d in self.spans)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips traced."""
        per = [sum(b - a for a, b in busy_intervals(d["ops"], self.lo, self.hi)) * 1e-9
               for d in self.devices.values()]
        return sum(per) / len(per) if per else 0.0

    def first_device(self) -> dict:
        return self.devices[sorted(self.devices)[0]] if self.devices else {"ops": [], "modules": []}

    def breakdown(self) -> dict:
        dev = self.first_device()
        busy = busy_intervals(dev["ops"], self.lo, self.hi)
        return {
            "device_ops": top(time_by_name(dev["ops"], self.lo, self.hi)),
            "idle_gaps": top(attribute_gaps(idle_gaps(busy, self.lo, self.hi), self.spans)),
        }

    # ---- program views ------------------------------------------------
    def modules_inside(self, lo: Optional[float] = None, hi: Optional[float] = None) -> List[Event]:
        """Program executions that lie wholly inside [lo, hi] (default: the window)."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        return [m for m in self.first_device()["modules"] if m[1] >= lo and m[1] + m[2] <= hi]

    def heaviest_module(self) -> Optional[str]:
        total: Dict[str, float] = {}
        for name, _, dur in self.modules_inside():
            total[name] = total.get(name, 0.0) + dur
        return max(total, key=total.get) if total else None

    def durations_of(self, program: str) -> List[float]:
        """Device ns of each whole execution of `program` inside the window."""
        return [d for name, _, d in self.modules_inside() if name == program]

    def spans_named(self, what: str) -> List[Event]:
        return [s for s in self.spans if s[0] == SPAN_PREFIX + what]
