#!/usr/bin/env python
"""Render a serving run's SLO story from its telemetry spans JSONL.

    python tools/serving_report.py /tmp/tele/serve.spans.jsonl
    python tools/serving_report.py /tmp/tele           # picks *.spans.jsonl
    python tools/serving_report.py /tmp/r0 /tmp/r1     # merge fleet streams

Sections, all from the stream serving/engine.py writes:

* **requests** (`kind:"request"`; legacy `serving_request` accepted) —
  outcome counts (completed/shed/deferred), exact p50/p99 time-to-first-
  token and request latency, guided/synthetic split, throughput;
* **phase attribution** — mean/p50/p99 wall-seconds per lifecycle phase
  (queue_wait, admission, prefill, decode, evict, vae_decode) and each
  phase's share of total latency (the serving analogue of
  telemetry_report.py's step table);
* **waterfall** — one scaled bar per request showing where its latency
  went;
* **engine windows** (`kind:"serving_window"`) — queue depth, lanes, pool
  occupancy, goodput, and the poll-loop admit/dispatch/block/evict split;
* **quantization** — when windows carry the engine's quantization state
  (`--quantize_weights` / `--quantize_kv` runs), the active weight/KV
  storage dtypes plus the analytic dequant overhead: extra flops per decode
  step and their fraction of the step's matmul work — per-request overhead
  is that fraction times the decode share from the phase table;
* **speculation** — when speculative decoding ran (`--spec_k`), the
  per-request acceptance rate (from request records) and the draft/verify
  wall-clock split (from the windows' `spec_draft_time_frac`);
* **SLO windows** (`kind:"slo_window"`) + burn-rate / backpressure alarms
  and the refusal/deferral counters from metric snapshots;
* **fleet** — when request records carry a `replica` tag (serving/fleet.py
  runs), a per-replica outcome/latency breakdown plus the `replica_lost`
  drain/requeue story.  Multiple paths merge into one report (per-replica
  telemetry dirs, or one combined stream);
* **pool** (`kind:"pool"`) — when the KV-pool flight recorder ran, the
  block-lifecycle story per replica: high-water occupancy, block-lifetime
  p50/p99, reserved-but-never-written waste, per-request footprint
  percentiles, the overcommit forecast (expected-blocks + prefix-sharing
  admissible slots vs worst-case), and whether the capacity simulator's
  self-validation reproduced the recorded run exactly (tools/
  pool_report.py has the full what-if grid);
* **durability** — the PR 14 story: terminal `poisoned` /
  `requeue_exhausted` outcomes, `replica_circuit_open` breaker episodes,
  hedged requests and suppressed duplicate completions, journal-replayed
  requests, and the degrade ladder's rung transitions plus how many
  requests were admitted under each rung (`degrade_rung` request tags).

`--json` emits the same content machine-readably: one dict whose keys
mirror the rendered sections (requests / phases / fleet / durability /
quantization / speculation / counters), for dashboards and the bench
harness — no screen-scraping the tables.

Pure stdlib; works on a partially-written file from a live run."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List

sys.path.insert(0, str(Path(__file__).resolve().parent))

from telemetry_report import load_records  # noqa: E402 — same torn-line tolerance

import pool_report  # noqa: E402 — kind:"pool" lifecycle + capacity forecast


def _pct(vals: List[float], q: float):
    if not vals:
        return None
    vals = sorted(vals)
    idx = min(int(round(q * (len(vals) - 1))), len(vals) - 1)
    return vals[idx]


def _ms(v) -> str:
    return f"{v * 1e3:.1f}ms" if v is not None else "-"


# lifecycle phase order + the glyph each gets in the waterfall bars
PHASES = (("queue_wait", "."), ("admission", "a"), ("prefill", "p"),
          ("decode", "#"), ("evict_sync", "s"), ("codes_pull", "c"),
          ("evict", "e"), ("vae_decode", "v"))


def _phase_table(done: List[Dict[str, Any]]) -> List[str]:
    """Mean/p50/p99 per phase + share of summed latency."""
    out = ["", "phase attribution (completed requests):",
           "  phase        mean      p50      p99   share"]
    total = sum(r.get("latency_s") or 0.0 for r in done) or 1e-12
    for name, _ in PHASES:
        vals = [r["phases"][name] for r in done
                if (r.get("phases") or {}).get(name) is not None]
        if not vals:
            continue
        out.append(
            f"  {name:<10} {_ms(sum(vals) / len(vals)):>8} "
            f"{_ms(_pct(vals, 0.50)):>8} {_ms(_pct(vals, 0.99)):>8} "
            f"{sum(vals) / total * 100:>6.1f}%")
    return out


def _waterfall(done: List[Dict[str, Any]], max_rows: int,
               width: int = 40) -> List[str]:
    """One bar per request, each phase's glyph run scaled to its share."""
    out = ["", f"waterfall (last {min(len(done), max_rows)} of {len(done)}; "
               f"legend: {' '.join(f'{g}={n}' for n, g in PHASES)}):"]
    for r in done[-max_rows:]:
        lat = r.get("latency_s")
        phases = r.get("phases") or {}
        if not lat or not phases:
            continue
        bar = ""
        for name, glyph in PHASES:
            n = int(round((phases.get(name) or 0.0) / lat * width))
            bar += glyph * n
        out.append(f"  req {r.get('request_id', '?'):>4} "
                   f"{_ms(lat):>10}  |{bar[:width]:<{width}}|")
    return out


def _fleet_table(reqs: List[Dict[str, Any]],
                 lost: List[Dict[str, Any]]) -> List[str]:
    """Per-replica breakdown (only when records carry a `replica` tag) plus
    the preemption story: which replica died, how much was requeued."""
    by_rep: Dict[Any, List[Dict[str, Any]]] = {}
    for r in reqs:
        if "replica" in r:
            by_rep.setdefault(r["replica"], []).append(r)
    if not by_rep and not lost:
        return []
    out = ["", f"fleet ({len(by_rep)} replicas seen in request records):"]
    if by_rep:
        out.append("  replica  completed  shed  deferred  lat_p50    lat_p99")
        for rep in sorted(by_rep):
            rs = by_rep[rep]
            done = [r for r in rs if r.get("outcome", "completed") == "completed"]
            shed = sum(1 for r in rs if r.get("outcome") == "shed")
            defer = sum(1 for r in rs if r.get("outcome") == "deferred")
            lats = [r["latency_s"] for r in done
                    if r.get("latency_s") is not None]
            out.append(f"  {rep!s:>7} {len(done):>10} {shed:>5} {defer:>9} "
                       f"{_ms(_pct(lats, 0.50)):>8} {_ms(_pct(lats, 0.99)):>10}")
    for a in lost:
        out.append(f"  replica_lost: replica {a.get('replica')} "
                   f"({a.get('reason', '?')}) — {a.get('requeued', 0)} "
                   f"requests requeued onto {a.get('survivors', '?')} "
                   f"survivor(s)")
    return out


def _quant_section(windows: List[Dict[str, Any]],
                   done: List[Dict[str, Any]]) -> List[str]:
    """Active storage dtypes + dequant overhead, from the quantization state
    the engine spreads into every serving_window event."""
    qw = [w for w in windows
          if w.get("weight_dtype") or w.get("kv_dtype")]
    if not qw:
        return []
    last = qw[-1]
    out = ["", "quantization:"]
    out.append(f"  weight storage dtype  {last.get('weight_dtype') or '-'}")
    out.append(f"  kv storage dtype      {last.get('kv_dtype') or '-'}")
    frac = last.get("dequant_frac_of_step")
    flops = last.get("dequant_flops_per_step")
    if flops is not None:
        out.append(f"  dequant flops/step    {flops:.3g}")
    if frac is not None:
        out.append(f"  dequant frac of step  {frac * 100:.1f}% of matmul work")
        decode_s = [(r.get("phases") or {}).get("decode") for r in done]
        decode_s = [v for v in decode_s if v is not None]
        if decode_s:
            mean_dec = sum(decode_s) / len(decode_s)
            out.append(f"  per-request overhead  ~{_ms(mean_dec * frac)} "
                       f"(dequant frac x mean decode {_ms(mean_dec)})")
        if frac >= 0.25:
            out.append("  note: dequant overhead is a large share of the "
                       "step — at this scale quantization buys capacity "
                       "(slots/lanes), not wall-clock")
    return out


def _spec_section(windows: List[Dict[str, Any]],
                  done: List[Dict[str, Any]]) -> List[str]:
    """Speculative decoding: per-request acceptance rate (from the request
    records' `accepted_tokens_per_step` field) and the draft/verify phase
    attribution (from the serving_window spec fields)."""
    accepts = [r["accepted_tokens_per_step"] for r in done
               if r.get("accepted_tokens_per_step") is not None]
    sw = [w for w in windows
          if w.get("spec_accepted_tokens_per_step") is not None]
    if not accepts and not sw:
        return []
    out = ["", "speculation:"]
    if accepts:
        out.append(f"  per-request accepted tokens/step: "
                   f"mean {sum(accepts) / len(accepts):.2f}  "
                   f"p50 {_pct(accepts, 0.50):.2f}  "
                   f"min {min(accepts):.2f}  "
                   f"({len(accepts)} speculative request(s))")
        if sum(accepts) / len(accepts) <= 1.0:
            out.append("  note: mean acceptance <= 1 token/step — the draft "
                       "passes are pure overhead at this acceptance rate; "
                       "lower --spec_k or raise --spec_draft_layers")
    if sw:
        wacc = [w["spec_accepted_tokens_per_step"] for w in sw]
        out.append(f"  window accepted tokens/step:      "
                   f"mean {sum(wacc) / len(wacc):.2f} over {len(sw)} window(s)")
        fracs = [w["spec_draft_time_frac"] for w in sw
                 if w.get("spec_draft_time_frac") is not None]
        if fracs:
            mean_frac = sum(fracs) / len(fracs)
            out.append(f"  draft/verify attribution:         "
                       f"{mean_frac * 100:.0f}% of round wall in the draft "
                       f"pass, {(1 - mean_frac) * 100:.0f}% in verify")
    return out


RUNG_NAMES = ("normal", "no_cfg", "cap_candidates", "short_prompts", "shed")


def _durability_section(records: List[Dict[str, Any]],
                        reqs: List[Dict[str, Any]]) -> List[str]:
    """Breaker episodes, hedging, journal replay, and the degrade ladder —
    everything the durable-serving layer did to keep the run alive."""
    breaker = [r for r in records if r.get("kind") == "alarm"
               and r.get("type") == "replica_circuit_open"]
    rq_alarms = [r for r in records if r.get("kind") == "alarm"
                 and r.get("type") == "requeue_exhausted"]
    rungs = [r for r in records if r.get("kind") == "degrade_rung"]
    hedged = [r for r in reqs if r.get("hedged")]
    dups = [r for r in reqs if r.get("duplicate")]
    replayed = [r for r in reqs if r.get("replayed")]
    by_rung: Dict[int, int] = {}
    for r in reqs:
        rung = r.get("degrade_rung")
        if rung:
            by_rung[rung] = by_rung.get(rung, 0) + 1
    if not (breaker or rq_alarms or rungs or hedged or replayed):
        return []
    out = ["", "durability:"]
    for a in breaker:
        out.append(f"  circuit open: replica {a.get('replica')} stalled "
                   f"{a.get('stalled_s', '?')}s with "
                   f"{a.get('inflight', 0)} in flight + "
                   f"{a.get('queued', 0)} queued")
    if hedged or dups:
        out.append(f"  hedging: {len(hedged)} request record(s) hedged, "
                   f"{len(dups)} duplicate completion(s) suppressed "
                   f"(first-completion-wins)")
    if replayed:
        out.append(f"  journal: {len(replayed)} request(s) replayed from a "
                   f"previous process generation")
    for a in rq_alarms:
        out.append(f"  requeue exhausted: replica {a.get('replica')} — "
                   f"{a.get('shed', 0)} shed after the "
                   f"{a.get('budget_s', '?')}s requeue budget "
                   f"({a.get('requeued', 0)} made it to survivors)")
    if rungs:
        peak = max(r.get("rung", 0) for r in rungs)
        last = rungs[-1]
        out.append(f"  degrade ladder: {len(rungs)} transition(s), peak "
                   f"rung {peak} ({RUNG_NAMES[min(peak, 4)]}), final rung "
                   f"{last.get('rung')} ({last.get('name')})")
        for rung in sorted(by_rung):
            out.append(f"    rung {rung} ({RUNG_NAMES[min(rung, 4)]}): "
                       f"{by_rung[rung]} request(s) admitted under it")
    return out


def _pool_lines(records: List[Dict[str, Any]]) -> List[str]:
    """KV-pool flight-recorder section (empty when no recorder ran)."""
    pool = pool_report.pool_section(records)
    if pool is None:
        return []
    out = ["", "kv pool (flight recorder):"]
    for rep, s in pool["pools"].items():
        cfg = s["config"]
        out.append(
            f"  replica {rep}: {s['requests']} request(s), high water "
            f"{s['high_water']}/{cfg['num_blocks']} blocks "
            f"(block_size {cfg['block_size']})")
        out.append(
            f"    block lifetime p50/p99: "
            f"{_ms(s['block_lifetime_p50_s'])} / "
            f"{_ms(s['block_lifetime_p99_s'])}   reserved-unused: "
            f"{s['reserved_unused_blocks']} blocks "
            f"(frac {s['reserved_unused_frac']})")
        out.append(
            f"    footprint blocks p50/p99: {s['footprint_blocks_p50']} / "
            f"{s['footprint_blocks_p99']}   overcommit-safe extra slots: "
            f"{s['overcommit_safe_slots']}")
        if s["dropped"]:
            out.append(f"    !! recorder dropped {s['dropped']} event(s)")
    out.append(
        f"  simulator self-validation: "
        f"{'PASS' if pool['validation_ok'] else 'FAIL'}   "
        f"expected+sharing vs worst-case admissible slots: "
        f"{pool['overcommit_slots_ratio']}x "
        f"(tools/pool_report.py for the what-if grid)")
    return out


_COUNTER_NAMES = (
    "serving/submitted", "serving/admitted", "serving/refused",
    "serving/refused_queue_overflow", "serving/refused_never_fits",
    "serving/admission_deferrals", "serving/completed",
    "serving/flood_injected", "serving/drained",
    "serving/handoff_requests", "serving/handoff_bytes",
    "router/requeued", "router/shed", "router/replicas_lost",
    "serving/quarantined", "serving/poison_retries",
    "serving/spec_rounds", "serving/spec_accepted_tokens",
    "serving/spec_rejected_tokens",
    "serving/degrade_climbs", "serving/degrade_cfg_disabled",
    "router/breaker_open", "router/breaker_closed",
    "router/hedged", "router/hedge_duplicates",
    "router/requeue_exhausted",
    "journal/accepted", "journal/duplicate_acks",
)


def _counters(records: List[Dict[str, Any]]) -> Dict[str, float]:
    counters: Dict[str, float] = {}
    for r in records:
        if r.get("kind") != "metrics":
            continue
        for name in _COUNTER_NAMES:
            rec = (r.get("metrics") or {}).get(name)
            if rec and rec.get("total") is not None:
                counters[name] = rec["total"]
    return counters


def build_summary(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The report as one JSON-ready dict — the same numbers the rendered
    sections print, keyed by section.  This is the `--json` payload and the
    programmatic entry point (dashboards, bench assertions)."""
    reqs = [r for r in records
            if r.get("kind") in ("request", "serving_request")]
    windows = [r for r in records if r.get("kind") == "serving_window"]
    done = [r for r in reqs if r.get("outcome", "completed") == "completed"]
    ttfts = [r["ttft_s"] for r in done if r.get("ttft_s") is not None]
    lats = [r["latency_s"] for r in done if r.get("latency_s") is not None]
    ts = [r.get("ts") for r in done if r.get("ts") is not None]
    span_s = (max(ts) - min(ts)) if len(ts) >= 2 else None

    outcomes: Dict[str, int] = {}
    for r in reqs:
        o = r.get("outcome", "completed")
        outcomes[o] = outcomes.get(o, 0) + 1

    total_lat = sum(r.get("latency_s") or 0.0 for r in done) or 1e-12
    phases: Dict[str, Dict[str, Any]] = {}
    for name, _ in PHASES:
        vals = [r["phases"][name] for r in done
                if (r.get("phases") or {}).get(name) is not None]
        if vals:
            phases[name] = {
                "mean_s": sum(vals) / len(vals),
                "p50_s": _pct(vals, 0.50), "p99_s": _pct(vals, 0.99),
                "share": sum(vals) / total_lat,
            }

    by_rep: Dict[str, Dict[str, Any]] = {}
    for r in reqs:
        if "replica" in r:
            rep = by_rep.setdefault(str(r["replica"]),
                                    {"completed": 0, "shed": 0, "deferred": 0,
                                     "latencies": []})
            o = r.get("outcome", "completed")
            if o in rep:
                rep[o] += 1
            if o == "completed" and r.get("latency_s") is not None:
                rep["latencies"].append(r["latency_s"])
    for rep in by_rep.values():
        lat = rep.pop("latencies")
        rep["latency_p50_s"] = _pct(lat, 0.50)
        rep["latency_p99_s"] = _pct(lat, 0.99)

    breaker = [r for r in records if r.get("kind") == "alarm"
               and r.get("type") == "replica_circuit_open"]
    rungs = [r for r in records if r.get("kind") == "degrade_rung"]
    accepts = [r["accepted_tokens_per_step"] for r in done
               if r.get("accepted_tokens_per_step") is not None]
    qw = [w for w in windows if w.get("weight_dtype") or w.get("kv_dtype")]

    summary: Dict[str, Any] = {
        "requests": {
            "outcomes": outcomes,
            "completed": len(done),
            "guided": sum(1 for r in done if r.get("guided")),
            "synthetic": sum(1 for r in done if r.get("synthetic")),
            "ttft_p50_s": _pct(ttfts, 0.50), "ttft_p99_s": _pct(ttfts, 0.99),
            "latency_p50_s": _pct(lats, 0.50),
            "latency_p99_s": _pct(lats, 0.99),
            "images_per_sec_per_chip": (len(done) / span_s
                                        if span_s else None),
        },
        "phases": phases,
        "fleet": by_rep,
        "durability": {
            "hedged": sum(1 for r in reqs if r.get("hedged")),
            "duplicates_suppressed": sum(1 for r in reqs
                                         if r.get("duplicate")),
            "replayed": sum(1 for r in reqs if r.get("replayed")),
            "breaker_opens": len(breaker),
            "degrade_transitions": len(rungs),
            "degrade_peak_rung": (max(r.get("rung", 0) for r in rungs)
                                  if rungs else 0),
        },
        "counters": _counters(records),
    }
    pool = pool_report.pool_section(records)
    if pool is not None:
        summary["pool"] = pool
    if qw:
        summary["quantization"] = {
            k: qw[-1].get(k) for k in
            ("weight_dtype", "kv_dtype", "dequant_flops_per_step",
             "dequant_frac_of_step")}
    if accepts:
        summary["speculation"] = {
            "accepted_tokens_per_step_mean": sum(accepts) / len(accepts),
            "accepted_tokens_per_step_p50": _pct(accepts, 0.50),
            "accepted_tokens_per_step_min": min(accepts),
            "requests": len(accepts),
        }
    return summary


def build_report(records: List[Dict[str, Any]], max_rows: int = 20) -> str:
    reqs = [r for r in records
            if r.get("kind") in ("request", "serving_request")]
    windows = [r for r in records if r.get("kind") == "serving_window"]
    slo_windows = [r for r in records if r.get("kind") == "slo_window"]
    alarms = [r for r in records if r.get("kind") == "alarm"
              and r.get("type") == "serving_backpressure"]
    slo_alarms = [r for r in records if r.get("kind") == "alarm"
                  and r.get("type") == "slo_burn_rate"]
    lost_alarms = [r for r in records if r.get("kind") == "alarm"
                   and r.get("type") == "replica_lost"]

    out: List[str] = []
    # legacy serving_request records carry no outcome: they were only ever
    # written at completion
    done = [r for r in reqs if r.get("outcome", "completed") == "completed"]
    shed = [r for r in reqs if r.get("outcome") == "shed"]
    deferred = [r for r in reqs if r.get("outcome") == "deferred"]
    poisoned = [r for r in reqs if r.get("outcome") == "poisoned"]
    exhausted = [r for r in reqs if r.get("outcome") == "requeue_exhausted"]
    if reqs:
        ttfts = [r["ttft_s"] for r in done if r.get("ttft_s") is not None]
        lats = [r["latency_s"] for r in done if r.get("latency_s") is not None]
        guided = sum(1 for r in done if r.get("guided"))
        synth = sum(1 for r in done if r.get("synthetic"))
        span_s = None
        ts = [r.get("ts") for r in done if r.get("ts") is not None]
        if len(ts) >= 2:
            span_s = max(ts) - min(ts)
        out.append(f"requests: {len(done)} completed "
                   f"({guided} guided, {synth} synthetic)"
                   + (f", {len(shed)} shed" if shed else "")
                   + (f", {len(deferred)} deferred" if deferred else "")
                   + (f", {len(poisoned)} poisoned" if poisoned else "")
                   + (f", {len(exhausted)} requeue-exhausted"
                      if exhausted else ""))
        out.append(f"  TTFT     p50 {_ms(_pct(ttfts, 0.50))}   "
                   f"p99 {_ms(_pct(ttfts, 0.99))}")
        out.append(f"  latency  p50 {_ms(_pct(lats, 0.50))}   "
                   f"p99 {_ms(_pct(lats, 0.99))}")
        if span_s and span_s > 0:
            out.append(f"  throughput over record span: "
                       f"{len(done) / span_s:.3f} images/sec/chip")
        traced = [r for r in done if r.get("phases")]
        if traced:
            out.extend(_phase_table(traced))
            out.extend(_waterfall(traced, max_rows))
    else:
        out.append("no request records — did the run route through "
                   "the engine with telemetry active?")

    out.extend(_fleet_table(reqs, lost_alarms))
    out.extend(_durability_section(records, reqs))
    out.extend(_pool_lines(records))

    if windows:
        out.append("")
        out.append(f"engine windows ({len(windows)}; last {max_rows}):")
        out.append("  iter     queue  lanes  pool_occ  free_blocks  goodput"
                   "  admit/dispatch/block/evict")
        for w in windows[-max_rows:]:
            g = w.get("goodput_frac")
            ph = w.get("phase_s") or {}
            split = "/".join(
                _ms(ph.get(k)) if ph.get(k) is not None else "-"
                for k in ("admit", "dispatch", "block", "evict")) if ph else "-"
            out.append(
                f"  {w.get('iter', '-'):>6} {w.get('queue_depth', 0):>6} "
                f"{w.get('active_lanes', 0):>6} "
                f"{(w.get('pool_occupancy_frac') or 0) * 100:>7.1f}% "
                f"{w.get('pool_free_blocks', '-'):>10} "
                f"{f'{g * 100:.0f}%' if g is not None else '-':>8}  {split}")

    out.extend(_quant_section(windows, done))
    out.extend(_spec_section(windows, done))

    if slo_windows:
        out.append("")
        out.append(f"SLO windows ({len(slo_windows)}; last {max_rows}):")
        out.append("  iter   completed  refused  burns")
        for w in slo_windows[-max_rows:]:
            burns = w.get("burns") or {}
            brief = " ".join(
                f"{k}={v.get('burn'):.2f}" for k, v in sorted(burns.items())
                if isinstance(v, dict) and v.get("burn") is not None)
            fired = w.get("fired") or []
            out.append(f"  {w.get('iter', '-'):>6} {w.get('completed', 0):>9} "
                       f"{w.get('refused', 0):>8}  {brief}"
                       + (f"  ALARM:{','.join(fired)}" if fired else ""))

    out.append("")
    if slo_alarms:
        out.append(f"SLO burn-rate alarms: {len(slo_alarms)}")
        for a in slo_alarms[-5:]:
            out.append(f"  {a.get('slo', '?')}: measured {a.get('measured')} "
                       f"vs target {a.get('target')} "
                       f"(burn short {a.get('burn_short'):.2f} / "
                       f"long {a.get('burn_long'):.2f})")
    if alarms:
        out.append(f"backpressure alarms: {len(alarms)}")
        for a in alarms[-5:]:
            out.append(f"  {a.get('reason', '')}")
    elif not slo_alarms:
        out.append("backpressure alarms: none")

    counters = _counters(records)
    if counters:
        out.append("")
        out.append("counters (final snapshot):")
        for name, v in counters.items():
            out.append(f"  {name:<30} {v:>10.0f}")
    return "\n".join(out)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("paths", nargs="+", metavar="path",
                        help="spans JSONL files or telemetry dirs; several "
                             "merge into one report (fleet replicas)")
    parser.add_argument("--max_rows", type=int, default=20)
    parser.add_argument("--json", action="store_true",
                        help="machine-readable summary (same numbers as the "
                             "rendered sections) on stdout")
    args = parser.parse_args(argv)

    records: List[Dict[str, Any]] = []
    for path in args.paths:
        p = Path(path)
        if p.is_dir():
            candidates = sorted(p.glob("*.spans.jsonl"))
            if not candidates:
                print(f"no *.spans.jsonl under {p}")
                return 1
            p = candidates[-1]
        records.extend(load_records(p))
    # one merged timeline: fleet replicas each stamp ts at write time
    records.sort(key=lambda r: r.get("ts") or 0.0)
    if args.json:
        print(json.dumps(build_summary(records), indent=2, default=float))
    else:
        print(build_report(records, max_rows=args.max_rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
