"""What the PROGRAM names in a traced run, read from the profiler's `.xplane.pb`.

`trace_reduce` sees a trace from outside: the harness's own `bench/` spans and
device time by XLA's operation names.  Since PR 24 the program names itself:

* host spans `serve/...` (the engine's phases, `dalle_pytorch_tpu/serving/
  engine.py` has the tree), each with its stats (`iter`, `req`, `lanes`) and,
  by containment on its thread, its parent;
* programs by name: the `XLA Modules` event `jit_serve_decode_step(<id>)` is an
  execution of `serve_decode_step`;
* scopes inside the programs (`jax.named_scope`): an operation's scope is the
  innermost of `SCOPES` found as a component of its scope path, where
  `jvp(attn)` and `transpose(jvp(attn))` count as `attn`; a fusion belongs to
  the scope XLA's metadata gives it.  The path is the `tf_op` stat of the
  `XLA Ops` event's METADATA (`<op_name>:`), beside `hlo_category`, `flops`
  and `bytes_accessed`.  `jax.profiler.ProfileData` hands out an event's own
  stats only, so the device planes are read from the file's protobuf wire
  format here (`_fields`; the schema is tsl's `xplane.proto`).  Device time
  by scope is SELF time per whole execution of a named program: a `while` of
  a scanned model encloses its body's events.

Everything works on a plain dictionary of events (`load_xplane` makes one; the
test fixture is one, cut from chip traces by `cut`), and every accessor
returns nothing, and does not raise, where the program names nothing (a parent
commit from before PR 24).  `of(ctx)` is what a per-layer reader calls: the
newest `.xplane.pb` under `.bench_trace/`, whose cell directory the harness
empties before each traced run, loaded once and kept on the run's Context.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import stats as stats_mod
from benchmark.harness import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / ".bench_trace"
SPAN_PREFIX = "serve/"
# the names `jax.named_scope` gives inside the programs (models/, serving/,
# parallel/train_step.py), innermost wins
SCOPES = ("embed", "norm", "token_shift", "kv_gather", "attn", "kv_write", "ff", "sample",
          "codes_write", "logits_loss", "stack_layers", "fwd_bwd", "grad_norm",
          "optimizer_update")
UNSCOPED = ""
REMAT = "rematted_computation"  # jax.checkpoint's name for what the backward recomputes
PATH_STAT = "tf_op"  # on the event's metadata: the HLO metadata's op_name and a colon
_PROGRAM = re.compile(r"^jit_(.+?)(\(\d+\))?$")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")


# ---- loading -------------------------------------------------------------
def newest_xplane(root: Path = TRACE_DIR) -> Optional[str]:
    hits = sorted(Path(root).glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return str(hits[-1]) if hits else None


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _device_planes(path: str) -> Dict[str, dict]:
    """{plane: {"ops": [[name, start_ns, dur_ns, scope path]], "modules":
    [[name, start_ns, dur_ns]]}} of the `/device:` planes of an `.xplane.pb`.
    xplane.proto: XSpace.planes = 1; XPlane name 2, lines 3, event_metadata 4
    and stat_metadata 5 (maps: key 1, value 2); XLine name 2, timestamp_ns 3,
    events 4; XEvent metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata
    id 1, name 2, stats 5; XStat metadata_id 1, str_value 5, ref_value 7;
    XStatMetadata id 1, name 2."""
    out = {}
    data = memoryview(Path(path).read_bytes())
    for number, plane in _fields(data):
        if number != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in parts if f == 2), "")
        if not name.startswith("/device:") or "CUSTOM" in name:
            continue
        stat_names = {}
        for f, entry in parts:
            if f == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        event_meta = {}  # id -> (name, scope path)
        for f, entry in parts:
            if f != 4:
                continue
            ident, text, op_path = 0, "", ""
            for mf, mv in _fields(dict(_fields(entry))[2]):
                if mf == 1:
                    ident = mv
                elif mf == 2:
                    text = bytes(mv).decode()
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if stat_names.get(stat.get(1)) == PATH_STAT:
                        op_path = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), "")).rstrip(":")
            event_meta[ident] = (text, op_path)
        dev = {"ops": [], "modules": []}
        for f, line in parts:
            if f != 3:
                continue
            fields = list(_fields(line))
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                next((bytes(v).decode() for lf, v in fields if lf == 2), ""))
            if key is None:
                continue
            t0 = next((v for lf, v in fields if lf == 3), 0)
            for lf, event in fields:
                if lf != 4:
                    continue
                e = dict(_fields(event))
                text, op_path = event_meta.get(e.get(1, 0), ("", ""))
                start, dur = t0 + e.get(2, 0) * 1e-3, e.get(3, 0) * 1e-3
                dev[key].append([tr._OP_NAME.match(text).group(0), start, dur, op_path]
                                if key == "ops" else [text, start, dur])
        if dev["ops"] or dev["modules"]:
            out[name] = dev
    return out


def load_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [[name, start, dur, path]], "modules":
    [[name, start, dur]]}}, "recorded": {plane: [first start, last end]},
    "host": [[name, start, dur, thread, stats]]} with the host events named
    `serve/...` or `bench/...` only.  `recorded` is what the profiler's session
    caught of each device: an execution in flight when it started or stopped is
    there clipped, beginning with the first or ending with the last event."""
    from jax.profiler import ProfileData

    path = tr.find_xplane(path)
    out = {"devices": _device_planes(path), "host": []}
    for plane, dev in out["devices"].items():
        every = dev["ops"] + dev["modules"]
        out.setdefault("recorded", {})[plane] = [min(e[1] for e in every), max(e[1] + e[2] for e in every)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                out["host"] += [[ev.name, float(ev.start_ns), float(ev.duration_ns), thread,
                                 {k: str(v) for k, v in ev.stats}]
                                for ev in line.events
                                if ev.name.startswith((SPAN_PREFIX, tr.SPAN_PREFIX))]
    out["host"].sort(key=lambda e: (e[1], -e[2]))
    return out


def cut(events: dict, lo: float, hi: float) -> dict:
    """What a test fixture holds of [lo, hi]: the device events that lie wholly
    inside (so every execution kept is whole, and the piece carries no
    `recorded` extent to clip by), the host spans clipped to it, times from
    `lo` in whole ns, and the scope paths once, in a table (`paths`; an
    operation's fourth field is its index there)."""
    paths: Dict[str, int] = {}
    table = events.get("paths")  # a piece cut before: an operation holds an index

    def moved(e, a=None, b=None):
        a, b = e[1] if a is None else a, e[1] + e[2] if b is None else b
        return [e[0], round(a - lo), round(b - a)]

    def inside(evs):
        return [e for e in evs if e[1] >= lo and e[1] + e[2] <= hi]

    devices = {}
    for plane, dev in events["devices"].items():
        devices[plane] = {
            "ops": [moved(e) + [paths.setdefault(e[3] if table is None else table[e[3]], len(paths))]
                    for e in inside(dev["ops"])],
            "modules": [moved(e) for e in inside(dev["modules"])]}
    host = [moved(e, max(e[1], lo), min(e[1] + e[2], hi)) + [e[3], e[4]]
            for e in events["host"] if e[1] < hi and e[1] + e[2] > lo]
    return {"devices": devices, "paths": list(paths), "host": host}


# ---- scopes ----------------------------------------------------------------
def scope_of(path: str) -> str:
    """The innermost of SCOPES among the components of a scope path such as
    `jit(train_step)/fwd_bwd/transpose(jvp(attn))/flash_attn_bwd/mul`."""
    for part in reversed(path.split("/")):
        for word in reversed(_WORD.findall(part)):
            if word in SCOPES:
                return word
    return UNSCOPED


def is_remat(path: str) -> bool:
    return REMAT in path


def program_of(module_name: str) -> str:
    m = _PROGRAM.match(module_name)
    return m.group(1) if m else module_name


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float
    stats: Dict[str, str]
    parent: Optional["Span"] = None
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def end(self) -> float:
        return self.start + self.dur

    def child(self, name: str) -> Optional["Span"]:
        return next((c for c in self.children if c.name == name), None)


def _nest(host: Sequence[Sequence]) -> List[Span]:
    """Spans with parents, by containment among the events of one thread."""
    out: List[Span] = []
    stacks: Dict[int, List[Span]] = {}
    for name, start, dur, thread, stats in sorted(host, key=lambda e: (e[1], -e[2])):
        span = Span(name, start, dur, dict(stats))
        stack = stacks.setdefault(thread, [])
        while stack and stack[-1].end < span.end:
            stack.pop()
        if stack:
            span.parent = stack[-1]
            stack[-1].children.append(span)
        stack.append(span)
        out.append(span)
    return out


class ProgramTrace:
    """One traced stretch, by the program's own names."""

    def __init__(self, events: dict):
        paths = events.get("paths")
        self.devices = events["devices"]
        first = sorted(self.devices)[0] if self.devices else None
        dev = self.devices[first] if self.devices else {"ops": [], "modules": []}
        # (first start, last end) of what the profiler recorded of the device, where the loader says
        self.recorded: Optional[Sequence[float]] = events.get("recorded", {}).get(first)
        self.modules: List[Tuple[str, float, float]] = sorted(
            ((program_of(n), s, d) for n, s, d in dev["modules"]), key=lambda e: e[1])
        self.ops: List[Tuple[str, float, float, str]] = sorted(
            ((n, s, d, paths[p] if paths is not None else p) for n, s, d, p in dev["ops"]),
            key=lambda e: (e[1], -e[2]))
        self._op_starts = [o[1] for o in self.ops]
        self._by_path: Dict[str, List[Dict[str, float]]] = {}  # program -> self ns by path, per execution
        every = _nest(events["host"])
        self.spans: List[Span] = [s for s in every if s.name.startswith(SPAN_PREFIX)]
        # the traced stretch: what the harness's own spans cover, else everything
        bench = [s for s in every if s.name.startswith(tr.SPAN_PREFIX)] or every
        ends = [(s.start, s.end) for s in bench] or [(m[1], m[1] + m[2]) for m in self.modules]
        self.lo = min((a for a, _ in ends), default=0.0)
        self.hi = max((b for _, b in ends), default=0.0)

    # ---- host spans -------------------------------------------------------
    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name and s.start >= self.lo and s.end <= self.hi]

    def leaf_spans(self) -> List[Span]:
        return [s for s in self.spans
                if not any(c.name.startswith(SPAN_PREFIX) for c in s.children)]

    # ---- programs ---------------------------------------------------------
    def programs(self) -> List[str]:
        return sorted({m[0] for m in self.modules})

    def executions(self, program: str) -> List[Tuple[float, float]]:
        """(start, duration) of each WHOLE execution of `program` inside the
        stretch.  The host runs ahead of the device, so an execution is in
        flight when the profiler starts and another when it stops; each is
        recorded clipped, from the session's first event or up to its last,
        and may still lie inside the harness's spans (the one cut 2 ms after
        its start that `flash_device_ms` used to divide by).  Neither is whole."""
        rec_lo, rec_hi = self.recorded or (float("-inf"), float("inf"))
        return [(s, d) for n, s, d in self.modules
                if n == program and s >= self.lo and s + d <= self.hi and s > rec_lo and s + d < rec_hi]

    def op_self_times(self, start: float, dur: float) -> List[Tuple[str, str, float]]:
        """(operation, scope path, self ns) of the operations of one execution."""
        i = bisect.bisect_left(self._op_starts, start)
        j = bisect.bisect_right(self._op_starts, start + dur)
        events = self.ops[i:j]
        own = [e[2] for e in events]
        stack: List[int] = []
        for k, (_, a, d, _) in enumerate(events):
            while stack and events[stack[-1]][1] + events[stack[-1]][2] <= a:
                stack.pop()
            if stack:
                own[stack[-1]] -= d
            stack.append(k)
        return [(e[0], e[3], max(t, 0.0)) for e, t in zip(events, own)]

    def time_by(self, program: str, key: Callable[[str], str]) -> List[Dict[str, float]]:
        """For each whole execution of `program`, device self ns by `key(scope path)`."""
        if program not in self._by_path:  # several readers ask for the same program
            per = []
            for start, dur in self.executions(program):
                by_path: Dict[str, float] = {}
                for _, path, t in self.op_self_times(start, dur):
                    by_path[path] = by_path.get(path, 0.0) + t
                per.append(by_path)
            self._by_path[program] = per
        out = []
        for by_path in self._by_path[program]:
            by: Dict[str, float] = {}
            for path, t in by_path.items():
                k = key(path)
                by[k] = by.get(k, 0.0) + t
            out.append(by)
        return out

    def scope_ms(self, program: str, scopes: Sequence[str]) -> Optional[float]:
        """Median over the executions of `program` of the device ms under `scopes`."""
        per = [sum(by.get(s, 0.0) for s in scopes) for by in self.time_by(program, scope_of)]
        m = stats_mod.median(per)
        return None if m is None else m * 1e-6

    def unscoped_pct(self, program: str) -> Optional[float]:
        """Share of the program's device time under none of SCOPES, all executions together."""
        per = self.time_by(program, scope_of)
        total = sum(sum(by.values()) for by in per)
        return 100.0 * sum(by.get(UNSCOPED, 0.0) for by in per) / total if total else None

    def op_ms(self, program: str, name_pattern: str) -> Optional[float]:
        """Median over the whole executions of `program` of the device ms (self
        time) of its operations whose NAME begins with a match of `name_pattern`
        (`flash_` finds `%flash_fwd.3`, a Pallas call named after its kernel).
        None where no whole execution holds such an operation."""
        named = re.compile(name_pattern)
        per = [sum(t for name, _, t in self.op_self_times(start, dur) if named.match(name.lstrip("%")))
               for start, dur in self.executions(program)]
        if not any(per):
            return None
        return stats_mod.median(per) * 1e-6

    def remat_ms(self, program: str) -> Optional[float]:
        per = [by.get("remat", 0.0)
               for by in self.time_by(program, lambda p: "remat" if is_remat(p) else "")]
        m = stats_mod.median(per)
        return None if m is None else m * 1e-6

    def program_ms(self, program: str) -> Optional[float]:
        m = stats_mod.median([d for _, d in self.executions(program)])
        return None if m is None else m * 1e-6

    # ---- idle time --------------------------------------------------------
    def idle_by_leaf_span(self) -> Tuple[Dict[str, float], float]:
        """(seconds of device idle time inside each kind of leaf `serve/` span,
        seconds of idle time in the stretch).  Leaf spans do not overlap."""
        busy = tr.merge([(a, a + d) for _, a, d, _ in self.ops
                         if a + d > self.lo and a < self.hi])
        gaps = tr.idle_gaps([(max(a, self.lo), min(b, self.hi)) for a, b in busy], self.lo, self.hi)
        total = sum(b - a for a, b in gaps) * 1e-9
        leaves = sorted(self.leaf_spans(), key=lambda s: s.start)
        starts = [s.start for s in leaves]
        by: Dict[str, float] = {}
        for ga, gb in gaps:
            k = max(bisect.bisect_right(starts, ga) - 1, 0)
            while k < len(leaves) and leaves[k].start < gb:
                a, b = max(ga, leaves[k].start), min(gb, leaves[k].end)
                if b > a:
                    by[leaves[k].name] = by.get(leaves[k].name, 0.0) + (b - a) * 1e-9
                k += 1
        return by, total


def of(ctx) -> Optional[ProgramTrace]:
    """The ProgramTrace of the run a reader is called for: None where no trace
    was taken.  Loaded once a run and kept on its Context."""
    if ctx.trace is None:
        return None
    held = getattr(ctx, "program_trace", None)
    if held is None:
        path = newest_xplane()
        if path is None:
            return None
        held = ctx.program_trace = ProgramTrace(load_xplane(path))
    return held
