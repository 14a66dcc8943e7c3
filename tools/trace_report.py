#!/usr/bin/env python
"""Reconstruct request journeys from serving telemetry and attribute p99.

A *journey* is one logical request — identified by its content uid (sha1 of
key words + text ids + sampler knobs, the same id the request journal uses) —
across every hop it took through the fleet: the original placement, requeue
hops after a replica loss, hedged duplicates, poison retries, and post-crash
replays.  Every hop leaves one terminal `kind:"request"` record plus causally
linked `kind:"trace"` spans (admit / handoff / requeue / hedge / replay /
poison_retry / journal_accept / journal_ack), all carrying the journey uid.
This tool stitches those records — from ONE OR MANY per-process
`*.spans.jsonl` files — back into journeys and answers:

  * what was each journey's critical path (which phases, on which hops, plus
    the named gaps between hops: requeue_wait / hedge_wait / replay_wait)?
  * which phases and hop kinds dominate the p99 of journey TTFT and TTLB?
  * do the invariants hold — exactly one ack-terminal hop per journey, no
    orphan spans, critical-path durations summing to end-to-end latency?

and exports Chrome-trace / Perfetto JSON: one process track per replica, one
thread track per hop, flow arrows following the journey across replicas.

Hops are keyed by (replica, engine-local request id, arrival wall-ts): engine
ids restart at 0 per process, so the arrival timestamp — rounded identically
on the admit span and the terminal record — is what makes the join exact.

Honest caveat (also in the README): timestamps are per-process wall-clock
anchors over monotonic time.  Within one host they are consistent to well
under a millisecond; across hosts they inherit NTP skew, so cross-process
gap durations (requeue_wait between two real machines) carry that error.

Stdlib-only on purpose: reads the same JSONL `telemetry_report` reads, runs
anywhere, tolerates torn final lines from crashed writers.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

# terminal outcomes that acknowledge the journey (first ack wins); "deferred"
# is terminal for the HOP (the engine closed under it) but not the journey —
# a router requeue or a journal replay continues it on another hop
ACK_OUTCOMES = ("completed", "shed", "poisoned", "requeue_exhausted")

# canonical phase layout inside one hop (extras sort after these)
PHASE_ORDER = ("queue_wait", "admission", "prefill", "decode", "evict_sync",
               "codes_pull", "vae_decode", "evict")

_TOL = 2e-6  # join/ordering tolerance: both sides round timestamps to 6dp


# --------------------------------------------------------------------- load
def load_records(paths) -> List[Dict[str, Any]]:
    """Records from files and/or directories (every *.spans.jsonl inside a
    directory — one file per process is the multi-process case).  Torn lines
    (a writer crashed mid-append) are skipped, matching the journal's rule:
    a record that was not durable never happened."""
    files: List[Path] = []
    for p in paths:
        pth = Path(p)
        if pth.is_dir():
            files.extend(sorted(pth.glob("*.spans.jsonl")))
        else:
            files.append(pth)
    records: List[Dict[str, Any]] = []
    for f in files:
        with open(f, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    return records


# -------------------------------------------------------------------- build
def _new_hop(replica, hop_id, arrival) -> Dict[str, Any]:
    return {
        "replica": replica, "id": hop_id, "arrival": arrival,
        "outcome": None, "phases": {}, "latency_s": None, "ttft_s": None,
        "duplicate": False, "hedged": False, "replayed": False,
        "admit": None, "record_ts": None,
    }


def build_journeys(records: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Group records by journey uid.  Returns {uid: journey} where a journey
    holds `hops` (list, arrival order), `edges` (non-admit trace events), and
    `events` (total span count, for orphan accounting).  Engine-wide
    spec_round events (no journey) are attached to every journey whose hop
    ids they advanced, under `spec`."""
    journeys: Dict[str, Dict[str, Any]] = {}
    spec_rounds: List[Dict[str, Any]] = []

    def jny(uid: str) -> Dict[str, Any]:
        return journeys.setdefault(
            uid, {"uid": uid, "hops": {}, "edges": [], "events": 0,
                  "spec": {"rounds": 0, "draft_s": 0.0, "verify_s": 0.0}})

    for r in records:
        kind = r.get("kind")
        if kind == "request" and r.get("journey"):
            jj = jny(r["journey"])
            jj["events"] += 1
            arrival = r.get("arrival_ts", r.get("ts"))
            key = (r.get("replica"), r.get("request_id"), arrival)
            hop = jj["hops"].setdefault(key, _new_hop(*key))
            hop.update(
                outcome=r.get("outcome"), phases=dict(r.get("phases") or {}),
                latency_s=r.get("latency_s"), ttft_s=r.get("ttft_s"),
                duplicate=bool(r.get("duplicate")),
                hedged=bool(r.get("hedged")),
                replayed=bool(r.get("replayed")),
                record_ts=r.get("ts"),
            )
        elif kind == "trace":
            ev = r.get("ev")
            if ev == "spec_round":
                spec_rounds.append(r)
                continue
            uid = r.get("journey")
            if not uid:
                continue
            jj = jny(uid)
            jj["events"] += 1
            if ev == "admit":
                key = (r.get("replica"), r.get("hop"), r.get("arrival_ts"))
                hop = jj["hops"].setdefault(key, _new_hop(*key))
                hop["admit"] = {k: r.get(k) for k in
                                ("queue_wait_s", "admission_s", "prefill_s",
                                 "ttft_s", "lanes", "mode", "prefix_hash",
                                 "prefix_repeat")}
            else:
                jj["edges"].append(r)

    # spec rounds advance engine-local hop ids on one replica; credit every
    # journey owning such a hop (rounds are shared across the batch, so this
    # is attribution of *participation*, not exclusive time)
    if spec_rounds:
        by_key: Dict[Tuple[Any, Any], Dict[str, Any]] = {}
        for jj in journeys.values():
            for (replica, hop_id, _), hop in jj["hops"].items():
                by_key[(replica, hop_id)] = jj
        for r in spec_rounds:
            hit = set()
            for hop_id in (r.get("hops") or {}):
                jj = by_key.get((r.get("replica"), int(hop_id)))
                if jj is not None and id(jj) not in hit:
                    hit.add(id(jj))
                    jj["spec"]["rounds"] += 1
                    jj["spec"]["draft_s"] += r.get("draft_s", 0.0)
                    jj["spec"]["verify_s"] += r.get("verify_s", 0.0)

    for jj in journeys.values():
        jj["hops"] = sorted(
            jj["hops"].values(),
            key=lambda h: h["arrival"] if h["arrival"] is not None else 0.0)
    return journeys


# ---------------------------------------------------------------- summarize
def _hop_phase_entries(hop) -> List[Tuple[str, float]]:
    """(name, seconds) phase slices for one hop, canonical order.  A partial
    hop (admit span but no terminal record — the process died under it)
    reports the admit-measured phases; that is all we durably know."""
    phases = hop["phases"]
    if not phases and hop["admit"]:
        a = hop["admit"]
        phases = {"queue_wait": a.get("queue_wait_s") or 0.0,
                  "admission": a.get("admission_s") or 0.0,
                  "prefill": a.get("prefill_s") or 0.0}
    out = [(k, float(phases[k])) for k in PHASE_ORDER
           if phases.get(k) is not None]
    out.extend((k, float(v)) for k, v in sorted(phases.items())
               if k not in PHASE_ORDER)
    return [(k, v) for k, v in out if v > 0.0]


def _hop_duration(hop) -> float:
    if hop.get("latency_s") is not None:
        return float(hop["latency_s"])
    return sum(v for _, v in _hop_phase_entries(hop))


def _hop_end(hop) -> float:
    return hop["arrival"] + _hop_duration(hop)


def _edge_name(jj, hop) -> str:
    """Name the gap that *precedes* `hop` from the journey's edge events."""
    if hop.get("replayed"):
        return "replay_wait"
    for e in jj["edges"]:
        if e.get("ev") == "requeue" and e.get("to_replica") == hop["replica"]:
            return "requeue_wait"
    for e in jj["edges"]:
        if e.get("ev") == "hedge" and e.get("to_replica") == hop["replica"]:
            return "hedge_wait"
    return "gap"


def _hop_kind(jj, hop, is_first: bool) -> str:
    if hop.get("replayed"):
        return "replay"
    if not is_first:
        name = _edge_name(jj, hop)
        if name != "gap":
            return name.replace("_wait", "")
    if hop.get("hedged"):
        return "hedge"
    return "origin"


def summarize_journey(jj: Dict[str, Any]) -> Dict[str, Any]:
    """One journey's reconstruction: winner hop, critical-path chain,
    (name, seconds) path entries whose sum should equal end-to-end latency,
    journey TTFT (first token anywhere minus first arrival) and TTLB."""
    hops = [h for h in jj["hops"] if h["arrival"] is not None]
    acks = [h for h in hops
            if h["outcome"] in ACK_OUTCOMES and not h["duplicate"]]
    summary: Dict[str, Any] = {
        "uid": jj["uid"], "hops": len(jj["hops"]),
        "replicas": sorted({h["replica"] for h in jj["hops"]
                            if h["replica"] is not None}),
        "ack_hops": len(acks),
        "spec": dict(jj["spec"]) if jj["spec"]["rounds"] else None,
    }
    if not hops:
        summary.update(outcome="open", start=None, e2e_s=None, ttft_s=None,
                       path=[], path_err_s=None)
        return summary
    start = min(h["arrival"] for h in hops)
    summary["start"] = start
    if not acks:
        outcome = ("deferred" if any(h["outcome"] == "deferred"
                                     for h in hops) else "open")
        summary.update(outcome=outcome, e2e_s=None, ttft_s=None, path=[],
                       path_err_s=None)
        return summary
    winner = min(acks, key=_hop_end)
    summary["outcome"] = winner["outcome"]

    # chain: walk back from the winner through non-overlapping earlier hops
    # (a hedge loser overlaps the winner and is correctly excluded — its
    # time was parallel, not on the critical path)
    chain = [winner]
    pool = [h for h in hops if h is not winner and not h["duplicate"]]
    while True:
        preds = [h for h in pool if _hop_end(h) <= chain[0]["arrival"] + _TOL]
        if not preds:
            break
        prev = max(preds, key=_hop_end)
        chain.insert(0, prev)
        pool.remove(prev)

    path: List[Tuple[str, float]] = []
    t = start
    for hop in chain:
        gap = hop["arrival"] - t
        if gap > _TOL:
            path.append((_edge_name(jj, hop), gap))
        path.extend(_hop_phase_entries(hop))
        t = _hop_end(hop)
    e2e = _hop_end(winner) - start
    path_sum = sum(v for _, v in path)
    firsts = [h["arrival"] + h["ttft_s"] for h in hops
              if h.get("ttft_s") is not None]
    if not firsts:
        firsts = [h["arrival"] + h["admit"]["ttft_s"] for h in hops
                  if h.get("admit") and h["admit"].get("ttft_s") is not None]
    summary.update(
        e2e_s=e2e, ttft_s=(min(firsts) - start if firsts else None),
        path=path, path_sum_s=path_sum, path_err_s=abs(path_sum - e2e),
        hop_kind_s={},
    )
    t = start
    for hop in chain:
        gap = hop["arrival"] - t
        kind = _hop_kind(jj, hop, hop is chain[0])
        dur = _hop_duration(hop) + max(gap, 0.0)
        summary["hop_kind_s"][kind] = summary["hop_kind_s"].get(kind, 0.0) + dur
        t = _hop_end(hop)
    return summary


def summarize_journeys(journeys) -> List[Dict[str, Any]]:
    return [summarize_journey(jj) for jj in journeys.values()]


# ----------------------------------------------------------------- validate
def validate_journeys(journeys, tol: float = 1e-3) -> Dict[str, Any]:
    """The trace invariants the chaos drills assert:

      * orphan_spans — spans in journeys with NO terminal record at all
        (every span must belong to a request some engine accounted for)
      * multi_ack_journeys — more than one non-duplicate ack-outcome hop
      * max_phase_sum_err_s — worst |critical-path sum − end-to-end| over
        journeys with a winner (phases must explain the latency)
    """
    orphans = 0
    multi_ack = 0
    checked = 0
    max_err = 0.0
    terminal = 0
    for jj in journeys.values():
        if not any(h["outcome"] is not None for h in jj["hops"]):
            orphans += jj["events"]
            continue
        terminal += 1
        s = summarize_journey(jj)
        if s["ack_hops"] > 1:
            multi_ack += 1
        if s.get("path_err_s") is not None:
            checked += 1
            max_err = max(max_err, s["path_err_s"])
    return {
        "journeys": len(journeys), "journeys_with_terminal": terminal,
        "orphan_spans": orphans, "multi_ack_journeys": multi_ack,
        "paths_checked": checked, "max_phase_sum_err_s": round(max_err, 6),
        "ok": orphans == 0 and multi_ack == 0 and max_err <= tol,
    }


# -------------------------------------------------------------- attribution
def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    s = sorted(vals)
    if len(s) == 1:
        return s[0]
    k = (len(s) - 1) * q / 100.0
    f = int(k)
    c = min(f + 1, len(s) - 1)
    return s[f] + (s[c] - s[f]) * (k - f)


def _clip_path(path: List[Tuple[str, float]], budget: float):
    """Path prefix summing to `budget` seconds (TTFT attribution: only the
    slice of the critical path that ran before the first token counts)."""
    out: List[Tuple[str, float]] = []
    acc = 0.0
    for name, sec in path:
        take = min(sec, budget - acc)
        if take <= 0.0:
            break
        out.append((name, take))
        acc += take
        if acc >= budget - 1e-9:
            break
    return out


def p99_attribution(summaries: List[Dict[str, Any]],
                    metric: str = "e2e_s") -> Optional[Dict[str, Any]]:
    """Where does the p99 of journey TTLB (`e2e_s`) / TTFT (`ttft_s`) go?
    Aggregates critical-path seconds over the journeys at/above the p99,
    by phase-or-gap name and by hop kind (origin/requeue/hedge/replay)."""
    band_all = [s for s in summaries if s.get(metric) is not None]
    if not band_all:
        return None
    p99 = _pct([s[metric] for s in band_all], 99)
    band = [s for s in band_all if s[metric] >= p99 - 1e-12]
    by_phase: Dict[str, float] = {}
    by_kind: Dict[str, float] = {}
    for s in band:
        path = (s["path"] if metric == "e2e_s"
                else _clip_path(s["path"], s[metric]))
        for name, sec in path:
            by_phase[name] = by_phase.get(name, 0.0) + sec
        for kind, sec in (s.get("hop_kind_s") or {}).items():
            by_kind[kind] = by_kind.get(kind, 0.0) + sec
    total = sum(by_phase.values()) or 1.0
    ktotal = sum(by_kind.values()) or 1.0
    rank = lambda d, tot: sorted(  # noqa: E731
        ((k, round(v, 6), round(v / tot, 4)) for k, v in d.items()),
        key=lambda kv: -kv[1])
    return {"metric": metric, "p99_s": round(p99, 6), "count": len(band),
            "by_phase": rank(by_phase, total),
            "by_hop_kind": rank(by_kind, ktotal)}


# ----------------------------------------------------------------- perfetto
def to_chrome_trace(journeys) -> Dict[str, Any]:
    """Chrome-trace / Perfetto JSON: pid = replica (process track), tid =
    engine-local hop id, "X" complete slices per phase, "s"/"f" flow arrows
    between consecutive hops of one journey (binding-point "e": the arrow
    lands at the next hop's enqueue).  Timestamps are rebased to the first
    arrival so the trace opens at t=0 instead of the epoch."""
    arrivals = [h["arrival"] for jj in journeys.values()
                for h in jj["hops"] if h["arrival"] is not None]
    t0 = min(arrivals) if arrivals else 0.0
    us = lambda t: round((t - t0) * 1e6, 3)  # noqa: E731

    events: List[Dict[str, Any]] = []
    seen_pids = set()

    def pid_of(replica) -> int:
        pid = 0 if replica is None else int(replica)
        if pid not in seen_pids:
            seen_pids.add(pid)
            events.append({"ph": "M", "name": "process_name", "pid": pid,
                           "args": {"name": f"replica {pid}"}})
        return pid

    for jj in sorted(journeys.values(), key=lambda j: j["uid"]):
        hops = [h for h in jj["hops"] if h["arrival"] is not None]
        try:
            flow = int(jj["uid"][:8], 16)
        except ValueError:
            flow = abs(hash(jj["uid"])) & 0xFFFFFFFF
        prev = None
        for hop in hops:
            pid = pid_of(hop["replica"])
            tid = int(hop["id"]) if hop["id"] is not None else 0
            t = hop["arrival"]
            for name, sec in _hop_phase_entries(hop):
                events.append({
                    "ph": "X", "name": name, "cat": "phase",
                    "pid": pid, "tid": tid, "ts": us(t),
                    "dur": max(round(sec * 1e6, 3), 1.0),
                    "args": {"journey": jj["uid"],
                             "outcome": hop["outcome"] or "open"},
                })
                t += sec
            if prev is not None:
                prev_hop, prev_pid, prev_tid, i = prev
                fid = flow * 16 + i  # one arrow per hop pair, shared prefix
                events.append({
                    "ph": "s", "id": fid, "name": "journey", "cat": "journey",
                    "pid": prev_pid, "tid": prev_tid,
                    "ts": us(min(_hop_end(prev_hop), hop["arrival"]))})
                events.append({
                    "ph": "f", "bp": "e", "id": fid, "name": "journey",
                    "cat": "journey", "pid": pid, "tid": tid,
                    "ts": us(hop["arrival"])})
                prev = (hop, pid, tid, i + 1)
            else:
                prev = (hop, pid, tid, 0)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# --------------------------------------------------------------------- CLI
def build_payload(records: List[Dict[str, Any]],
                  max_rows: int = 20) -> Dict[str, Any]:
    """Everything the CLI renders, as one JSON-ready dict (also the bench /
    test entry point: validation + percentiles + attribution + journeys)."""
    journeys = build_journeys(records)
    summaries = summarize_journeys(journeys)
    validation = validate_journeys(journeys)
    e2e = [s["e2e_s"] for s in summaries if s.get("e2e_s") is not None]
    ttft = [s["ttft_s"] for s in summaries if s.get("ttft_s") is not None]
    outcomes: Dict[str, int] = {}
    for s in summaries:
        outcomes[s["outcome"]] = outcomes.get(s["outcome"], 0) + 1
    rows = sorted((s for s in summaries if s.get("e2e_s") is not None),
                  key=lambda s: -s["e2e_s"])[:max_rows]
    return {
        "validation": validation,
        "outcomes": outcomes,
        "percentiles": {
            "ttft_p50_s": _pct(ttft, 50), "ttft_p99_s": _pct(ttft, 99),
            "ttlb_p50_s": _pct(e2e, 50), "ttlb_p99_s": _pct(e2e, 99),
        },
        "ttlb_attribution": p99_attribution(summaries, "e2e_s"),
        "ttft_attribution": p99_attribution(summaries, "ttft_s"),
        "journeys": rows,
    }


def _ms(v: Optional[float]) -> str:
    return "--" if v is None else f"{v * 1e3:8.1f}ms"


def _render(payload: Dict[str, Any]) -> str:
    lines: List[str] = []
    v = payload["validation"]
    lines.append(
        f"journeys: {v['journeys']}  (terminal {v['journeys_with_terminal']})"
        f"   orphan spans: {v['orphan_spans']}"
        f"   multi-ack: {v['multi_ack_journeys']}"
        f"   max phase-sum err: {v['max_phase_sum_err_s'] * 1e3:.3f}ms")
    lines.append("outcomes: " + "  ".join(
        f"{k}={n}" for k, n in sorted(payload["outcomes"].items())))
    p = payload["percentiles"]
    lines.append(f"journey TTFT p50/p99: {_ms(p['ttft_p50_s'])} /"
                 f" {_ms(p['ttft_p99_s'])}"
                 f"   TTLB p50/p99: {_ms(p['ttlb_p50_s'])} /"
                 f" {_ms(p['ttlb_p99_s'])}")
    for key, title in (("ttlb_attribution", "p99 TTLB"),
                       ("ttft_attribution", "p99 TTFT")):
        att = payload[key]
        if att is None:
            continue
        lines.append(f"\n{title} attribution"
                     f" (n={att['count']}, p99={_ms(att['p99_s']).strip()}):")
        for name, sec, share in att["by_phase"][:8]:
            lines.append(f"  {name:<14} {sec * 1e3:9.1f}ms  {share * 100:5.1f}%")
        kinds = "  ".join(f"{k}={share * 100:.0f}%"
                          for k, _, share in att["by_hop_kind"])
        lines.append(f"  by hop kind: {kinds}")
    if payload["journeys"]:
        lines.append("\nslowest journeys:")
        lines.append(f"  {'uid':<18} {'hops':>4} {'outcome':<18}"
                     f" {'e2e':>10} {'ttft':>10}  top phase")
        for s in payload["journeys"]:
            top = max(s["path"], key=lambda kv: kv[1])[0] if s["path"] else "--"
            lines.append(
                f"  {s['uid']:<18} {s['hops']:>4} {s['outcome']:<18}"
                f" {_ms(s['e2e_s'])} {_ms(s['ttft_s'])}  {top}")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("path", nargs="+",
                        help="*.spans.jsonl file(s) and/or telemetry dir(s) "
                             "(a dir contributes every *.spans.jsonl in it)")
    parser.add_argument("--perfetto", metavar="OUT",
                        help="write Chrome-trace/Perfetto JSON here")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable payload on stdout")
    parser.add_argument("--max-rows", type=int, default=20)
    args = parser.parse_args(argv)
    records = load_records(args.path)
    if not records:
        print("no records found", file=sys.stderr)
        return 1
    payload = build_payload(records, max_rows=args.max_rows)
    if args.perfetto:
        trace = to_chrome_trace(build_journeys(records))
        with open(args.perfetto, "w", encoding="utf-8") as f:
            json.dump(trace, f)
        print(f"wrote {len(trace['traceEvents'])} trace events"
              f" -> {args.perfetto}", file=sys.stderr)
    if args.json:
        print(json.dumps(payload, indent=2, default=float))
    else:
        print(_render(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
