#!/usr/bin/env python
"""Fault-injection harness CLI (the operator-facing half of
dalle_pytorch_tpu/training/resilience.py).

Two ways to break a training run on purpose:

* **In-process** — pass `--inject_fault KIND@STEP` to train_dalle/train_vae
  (kinds: kill-process, preempt, corrupt-checkpoint, truncate-checkpoint,
  stall-data, drop-remote-stream, oom, shrink, grow; stall-data accepts
  `@STEP:SECONDS`).  The training loop drives the fault at exactly the
  named step — this is what the crash-and-resume equivalence tests use.
  `oom@STEP` provokes a RESOURCE_EXHAUSTED (real allocations on TPU, a
  faithfully-shaped simulated error on CPU) so the OOM forensic path —
  oom_report_*.txt + exit code 77 — is exercisable end to end.
  `shrink@STEP` / `grow@STEP` are the ELASTIC drills: the process SIGKILLs
  itself at the step (a preemption that will hand back a different machine
  shape) and the supervisor relaunches on a smaller / larger device count
  with `--resume auto` — the elastic resume detects the topology change
  (ReshardRequired), preflights the target's memory ledger, and reshards
  through the partitioning registry instead of failing.
* **From outside** — this CLI damages artifacts or signals a live run:

      python tools/chaos.py corrupt  CKPT.npz      # garbage bytes into it
      python tools/chaos.py truncate CKPT.npz --frac 0.5
      python tools/chaos.py validate CKPT.npz      # what would resume say?
      python tools/chaos.py preempt  PID           # SIGTERM (graceful path)
      python tools/chaos.py kill     PID           # SIGKILL (hard crash)

      # the full elastic drill, end to end (CPU devices, dummy model):
      # run on 8 virtual devices, shrink@4, relaunch on 4, diff the losses
      python tools/chaos.py elastic --devices 8 --resume_devices 4 --step 4

The repeatable experiment: start a run with `--save_every_n_steps N`, break
it (either way), restart with `--resume auto`, and diff the per-step loss
sequence against an uninterrupted run — tests/test_resilience.py and the
shrink-resume test in tests/test_resharding.py automate exactly that.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dalle_pytorch_tpu.training.resilience import (  # noqa: E402
    FAULT_KINDS,
    CheckpointInvalidError,
    Fault,
    FaultInjector,
    corrupt_file,
    parse_fault,
    truncate_file,
    validate_checkpoint,
)

__all__ = [
    "FAULT_KINDS",
    "Fault",
    "FaultInjector",
    "corrupt_file",
    "parse_fault",
    "truncate_file",
    "validate_checkpoint",
]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("corrupt", help="overwrite bytes near the head of a file")
    p.add_argument("path")
    p.add_argument("--nbytes", type=int, default=64)

    p = sub.add_parser("truncate", help="cut a file to a fraction of its size")
    p.add_argument("path")
    p.add_argument("--frac", type=float, default=0.5)

    p = sub.add_parser("validate", help="run resume validation on a checkpoint")
    p.add_argument("path")

    p = sub.add_parser("preempt", help="SIGTERM a live run (graceful shutdown)")
    p.add_argument("pid", type=int)

    p = sub.add_parser("kill", help="SIGKILL a live run (hard crash)")
    p.add_argument("pid", type=int)

    p = sub.add_parser(
        "elastic",
        help="shrink/grow drill: dummy-run train_dalle on N CPU devices "
             "with --inject_fault shrink@STEP, relaunch with --resume auto "
             "on M devices, and check the stitched loss trajectory")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual CPU device count for the first run")
    p.add_argument("--resume_devices", type=int, default=4,
                   help="device count for the relaunch (fewer = shrink, "
                        "more = grow)")
    p.add_argument("--step", type=int, default=4, help="fault step")
    p.add_argument("--steps", type=int, default=8, help="total dummy steps")
    p.add_argument("--batch_size", type=int, default=8,
                   help="global batch (pinned so both runs see the same "
                        "data stream; must divide by both device counts)")
    p.add_argument("--workdir", type=str, default=None,
                   help="where run artifacts land (default: a tmp dir)")

    p = sub.add_parser(
        "flood",
        help="serving admission-control drill: Poisson load + a "
             "flood@ITER:COUNT burst through cli/serve.py; the service must "
             "queue/refuse (reported) instead of OOMing")
    p.add_argument("--requests", type=int, default=4,
                   help="organic Poisson requests")
    p.add_argument("--burst", type=int, default=16,
                   help="synthetic requests injected by the flood fault")
    p.add_argument("--at", type=int, default=2,
                   help="engine iteration the burst fires at")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--max_queue", type=int, default=4)
    p.add_argument("--workdir", type=str, default=None)

    p = sub.add_parser(
        "crash-replay",
        help="durable-serving crash drill: SIGKILL the whole serve process "
             "mid-load (kill-fleet@AT) with --journal armed, restart with "
             "the same journal; every accepted-but-unacknowledged request "
             "must replay to completion with zero duplicate acks")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--at", type=int, default=10,
                   help="engine iteration the SIGKILL fires at")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--workdir", type=str, default=None)

    p = sub.add_parser(
        "stall-replica",
        help="circuit-breaker drill: wedge one replica alive-but-stalled "
             "mid-run (stall-replica@AT:IDX); the breaker must open (one "
             "replica_circuit_open alarm), deadline-burning requests hedge "
             "onto survivors (first-completion-wins), and the breaker must "
             "half-open and recover once the wedge expires")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--at", type=int, default=8,
                   help="fleet iteration the wedge fires at")
    p.add_argument("--victim", type=int, default=1,
                   help="replica index to wedge")
    p.add_argument("--wedge_s", type=float, default=2.0,
                   help="how long the victim stays wedged")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--workdir", type=str, default=None)

    p = sub.add_parser(
        "poison",
        help="poison-quarantine drill: NaN one in-flight request's decode "
             "logits (poison-request@AT); the engine must retry it K times, "
             "quarantine it with a terminal `poisoned` record, and complete "
             "every other request undisturbed")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--at", type=int, default=6,
                   help="engine iteration the poison fires at")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--workdir", type=str, default=None)

    p = sub.add_parser(
        "kill-replica",
        help="serving fleet preemption drill: 2 replicas under Poisson "
             "load, kill one mid-run via kill-replica@ITER:IDX; every "
             "accepted request must complete on the survivors (requeued, "
             "zero drops) with ONE replica_lost alarm")
    p.add_argument("--requests", type=int, default=6,
                   help="organic Poisson requests")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--at", type=int, default=4,
                   help="fleet iteration the kill fires at")
    p.add_argument("--victim", type=int, default=0,
                   help="replica index to kill")
    p.add_argument("--disaggregate", action="store_true",
                   help="also run the drill with prefill/decode split")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--workdir", type=str, default=None)

    args = parser.parse_args(argv)
    if args.cmd == "corrupt":
        corrupt_file(args.path, nbytes=args.nbytes)
        print(f"corrupted {args.path}")
    elif args.cmd == "truncate":
        truncate_file(args.path, frac=args.frac)
        print(f"truncated {args.path}")
    elif args.cmd == "validate":
        try:
            meta = validate_checkpoint(args.path)
        except CheckpointInvalidError as e:
            print(f"INVALID ({type(e).__name__}): {e}")
            return 1
        print(f"valid: epoch={meta.get('epoch')} "
              f"global_step={meta.get('global_step')} "
              f"data_state={meta.get('data_state')}")
    elif args.cmd == "preempt":
        os.kill(args.pid, signal.SIGTERM)
        print(f"sent SIGTERM to {args.pid} (expect exit code 75 + emergency "
              "checkpoint; restart with --resume auto)")
    elif args.cmd == "kill":
        os.kill(args.pid, signal.SIGKILL)
        print(f"sent SIGKILL to {args.pid} (restart with --resume auto)")
    elif args.cmd == "elastic":
        return elastic_drill(
            devices=args.devices, resume_devices=args.resume_devices,
            step=args.step, steps=args.steps, batch_size=args.batch_size,
            workdir=args.workdir,
        )
    elif args.cmd == "flood":
        return flood_drill(
            requests=args.requests, burst=args.burst, at=args.at,
            slots=args.slots, max_queue=args.max_queue, workdir=args.workdir,
        )
    elif args.cmd == "kill-replica":
        return kill_replica_drill(
            requests=args.requests, replicas=args.replicas, at=args.at,
            victim=args.victim, disaggregate=args.disaggregate,
            slots=args.slots, workdir=args.workdir,
        )
    elif args.cmd == "crash-replay":
        return crash_replay_drill(
            requests=args.requests, at=args.at, slots=args.slots,
            workdir=args.workdir,
        )
    elif args.cmd == "stall-replica":
        return stall_replica_drill(
            requests=args.requests, replicas=args.replicas, at=args.at,
            victim=args.victim, wedge_s=args.wedge_s, slots=args.slots,
            workdir=args.workdir,
        )
    elif args.cmd == "poison":
        return poison_drill(
            requests=args.requests, at=args.at, slots=args.slots,
            workdir=args.workdir,
        )
    return 0


def _run_train(cli_args, cwd, devices, timeout=600):
    """One train_dalle subprocess on `devices` virtual CPU devices — the
    shared launch recipe (tests/test_resharding.py drives its subprocess
    runs through this, so the env scrub stays in one place)."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent)
                         + os.pathsep + env.get("PYTHONPATH", ""))
    # scrub any inherited device-count flag so OURS wins
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={devices}"])
    return subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.train_dalle",
         *cli_args],
        cwd=str(cwd), env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def flood_drill(requests=4, burst=16, at=2, slots=2, max_queue=4,
                workdir=None, timeout=600) -> int:
    """Serving admission-control drill: run the serve CLI under Poisson load
    with `--inject_fault flood@AT:BURST` and verify the service DEGRADES —
    every admitted request completes, excess load is queued/refused (counted
    in the SLO report), and the process neither OOMs (exit 77) nor crashes.

    Observability assertions ride along: the run declares an impossible
    TTFT SLO so the burn-rate alarm must fire during the flood, exactly ONE
    rate-limited profiler capture lands, and every arrival (organic + burst)
    leaves a `kind:"request"` record whose phase durations sum to its
    latency.  Returns 0 on success."""
    import json
    import subprocess
    import tempfile

    cwd = Path(workdir) if workdir else Path(tempfile.mkdtemp(prefix="flood_"))
    cwd.mkdir(parents=True, exist_ok=True)
    report_path = cwd / "flood_report.json"
    tele_dir = cwd / "tele"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent)
                         + os.pathsep + env.get("PYTHONPATH", ""))
    print(f"[flood] serve CLI: {requests} Poisson requests + "
          f"flood@{at}:{burst} burst into a {slots}-slot engine "
          f"(queue cap {max_queue}; workdir {cwd})")
    r = subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.serve",
         "--synthetic", "--dim", "32", "--depth", "2", "--heads", "2",
         "--dim_head", "8", "--text_seq_len", "8", "--num_text_tokens", "64",
         "--num_image_tokens", "32", "--image_fmap_size", "4",
         "--loadgen", str(requests), "--rate", "20", "--streams", "2",
         "--slots", str(slots), "--block_size", "8",
         "--max_queue", str(max_queue), "--no_vae",
         "--inject_fault", f"flood@{at}:{burst}",
         # observability under fire: an impossible TTFT target guarantees
         # an slo_burn_rate alarm, which the on-alarm trigger must turn
         # into exactly one (rate-limited) profiler capture
         "--telemetry", str(tele_dir), "--telemetry_every", "4",
         "--slo_ttft_p99", "1e-6", "--profile_on_alarm", "2",
         "--status_json", str(cwd / "status.json"),
         "--report_json", str(report_path)],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=timeout,
    )
    if r.returncode == 77:
        print(f"[flood] FAIL: the service OOMed under the burst (exit 77)\n"
              f"{r.stdout[-2000:]}")
        return 1
    if r.returncode != 0:
        print(f"[flood] FAIL: serve rc={r.returncode}\n{r.stderr[-2000:]}")
        return 1
    report = json.loads(report_path.read_text())
    # the degradation contract: the service keeps MAKING PROGRESS (organic
    # completions + refusals account for every arrival — refusing organic
    # load while the burst clogs the queue IS valid shedding), something was
    # actually shed, and the process neither OOMed nor crashed
    organic_done = report["requests_completed"]
    organic_refused = report["requests_refused"]
    shed = (report.get("refused_total") or 0) + (report.get("backpressure_alarms") or 0)
    if organic_done + organic_refused < requests:
        print(f"[flood] FAIL: {organic_done} completed + {organic_refused} "
              f"refused < {requests} organic arrivals — requests were LOST, "
              f"not shed\n{r.stdout[-2000:]}")
        return 1
    if organic_done < 1:
        print(f"[flood] FAIL: no organic request completed — the service "
              f"stopped making progress under the burst\n{r.stdout[-2000:]}")
        return 1
    if shed <= 0:
        print("[flood] FAIL: the burst produced no refusals/backpressure — "
              "the drill did not stress admission control")
        return 1

    # --- observability assertions over the telemetry stream ---------------
    spans_path = tele_dir / "serve.spans.jsonl"
    records = [json.loads(ln) for ln in spans_path.read_text().splitlines()
               if ln.strip()]
    counters = {}
    for rec in records:
        if rec.get("kind") == "metrics":
            for name in ("serving/submitted", "serving/refused"):
                c = (rec.get("metrics") or {}).get(name)
                if c and c.get("total") is not None:
                    counters[name] = c["total"]
    arrivals = counters.get("serving/submitted", 0) + counters.get(
        "serving/refused", 0)
    req_recs = [rec for rec in records if rec.get("kind") == "request"]
    if len(req_recs) != arrivals or arrivals == 0:
        print(f"[flood] FAIL: {len(req_recs)} request records != "
              f"{arrivals:.0f} arrivals — the lifecycle trace lost requests")
        return 1
    bad_sums = []
    for rec in req_recs:
        if rec.get("outcome") != "completed":
            continue
        lat = rec.get("latency_s") or 0.0
        ssum = sum((rec.get("phases") or {}).values())
        if abs(ssum - lat) > max(0.05, 0.15 * lat):
            bad_sums.append((rec.get("request_id"), ssum, lat))
    if bad_sums:
        print(f"[flood] FAIL: phase durations do not sum to latency: "
              f"{bad_sums}")
        return 1
    slo_alarms = [rec for rec in records if rec.get("kind") == "alarm"
                  and rec.get("type") == "slo_burn_rate"]
    if not slo_alarms:
        print("[flood] FAIL: the impossible TTFT SLO never fired a "
              "burn-rate alarm")
        return 1
    captures = [rec for rec in records if rec.get("kind") == "trace_capture"
                and rec.get("action") == "start"]
    if len(captures) != 1:
        print(f"[flood] FAIL: expected exactly 1 rate-limited profiler "
              f"capture, got {len(captures)}")
        return 1
    outcomes = {}
    for rec in req_recs:
        outcomes[rec.get("outcome")] = outcomes.get(rec.get("outcome"), 0) + 1
    print(f"[flood] obs OK: {len(req_recs)} request records cover all "
          f"{arrivals:.0f} arrivals {outcomes}; phases sum to latency; "
          f"{len(slo_alarms)} slo_burn_rate alarm(s); exactly 1 profiler "
          f"capture ({captures[0].get('reason')})")
    print(f"[flood] OK: {organic_done} organic completed + {organic_refused} "
          f"organic refused (all {requests} accounted for); "
          f"{report.get('synthetic_completed', 0)} of the burst served, "
          f"{report.get('refused_total'):.0f} total refusals "
          f"(p99 TTFT {report.get('ttft_p99_s'):.3f}s) — no OOM, no crash")
    return 0


def kill_replica_drill(requests=6, replicas=2, at=4, victim=0,
                       disaggregate=False, slots=2, workdir=None,
                       timeout=600) -> int:
    """Serving fleet preemption drill: run the serve CLI with `--replicas N`
    under Poisson load and `--inject_fault kill-replica@AT:VICTIM`, then
    verify serve-through-preemption — every accepted request completes on
    the survivors (drained + requeued, ZERO silent drops), exactly one
    `replica_lost` alarm lands in the telemetry stream, request records are
    replica-tagged, and the report still carries a finite p99 TTFT.
    Returns 0 on success."""
    import json
    import subprocess
    import tempfile

    cwd = Path(workdir) if workdir else Path(tempfile.mkdtemp(prefix="killrep_"))
    cwd.mkdir(parents=True, exist_ok=True)
    report_path = cwd / "kill_replica_report.json"
    tele_dir = cwd / "tele"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent)
                         + os.pathsep + env.get("PYTHONPATH", ""))
    print(f"[kill-replica] serve CLI: {requests} Poisson requests across "
          f"{replicas} replicas, killing replica {victim} at fleet "
          f"iteration {at}"
          + (" (disaggregated prefill)" if disaggregate else "")
          + f"; workdir {cwd}")
    r = subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.serve",
         "--synthetic", "--dim", "32", "--depth", "2", "--heads", "2",
         "--dim_head", "8", "--text_seq_len", "8", "--num_text_tokens", "64",
         "--num_image_tokens", "32", "--image_fmap_size", "4",
         "--loadgen", str(requests), "--rate", "20", "--streams", "2",
         "--slots", str(slots), "--block_size", "8", "--no_vae",
         "--replicas", str(replicas),
         *(["--disaggregate"] if disaggregate else []),
         "--inject_fault", f"kill-replica@{at}:{victim}",
         "--telemetry", str(tele_dir), "--telemetry_every", "4",
         "--report_json", str(report_path)],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=timeout,
    )
    if r.returncode != 0:
        print(f"[kill-replica] FAIL: serve rc={r.returncode}\n"
              f"{r.stderr[-2000:]}")
        return 1
    report = json.loads(report_path.read_text())
    # zero drops: every organic arrival is either completed (possibly as a
    # requeued reincarnation on a survivor) or a counted refusal
    done = report["requests_completed"]
    refused = report["requests_refused"]
    if done + refused < requests:
        print(f"[kill-replica] FAIL: {done} completed + {refused} refused < "
              f"{requests} arrivals — requests were silently dropped\n"
              f"{r.stdout[-2000:]}")
        return 1
    if report.get("replicas_lost", 0) != 1:
        print(f"[kill-replica] FAIL: expected 1 replica lost, report says "
              f"{report.get('replicas_lost')}")
        return 1
    if report.get("replicas_alive") != replicas - 1:
        print(f"[kill-replica] FAIL: {report.get('replicas_alive')} alive "
              f"!= {replicas - 1}")
        return 1
    if report.get("ttft_p99_s") is None or report.get(
            "images_per_sec_per_chip") in (None, 0):
        print("[kill-replica] FAIL: the post-kill report lost its SLO "
              "columns (no p99 TTFT / throughput)")
        return 1
    if disaggregate and not report.get("handoff_requests"):
        print("[kill-replica] FAIL: disaggregated run recorded no prefill "
              "handoffs")
        return 1

    # --- telemetry assertions: ONE replica_lost alarm, replica-tagged
    # request records, and a terminal record for every arrival -------------
    spans_path = tele_dir / "serve.spans.jsonl"
    records = [json.loads(ln) for ln in spans_path.read_text().splitlines()
               if ln.strip()]
    lost = [rec for rec in records if rec.get("kind") == "alarm"
            and rec.get("type") == "replica_lost"]
    if len(lost) != 1:
        print(f"[kill-replica] FAIL: expected exactly 1 replica_lost alarm, "
              f"got {len(lost)}")
        return 1
    if lost[0].get("replica") != victim:
        print(f"[kill-replica] FAIL: alarm blames replica "
              f"{lost[0].get('replica')}, not the victim {victim}")
        return 1
    req_recs = [rec for rec in records if rec.get("kind") == "request"]
    tagged = {rec.get("replica") for rec in req_recs if "replica" in rec}
    if len(tagged) < 2:
        print(f"[kill-replica] FAIL: request records name replicas {tagged} "
              f"— expected records from at least 2 replicas")
        return 1
    deferred = [rec for rec in req_recs if rec.get("outcome") == "deferred"
                and rec.get("requeued")]
    if len(deferred) != lost[0].get("requeued", -1):
        print(f"[kill-replica] FAIL: {len(deferred)} deferred/requeued "
              f"records != alarm's requeued={lost[0].get('requeued')}")
        return 1
    print(f"[kill-replica] obs OK: 1 replica_lost alarm (replica {victim}, "
          f"{lost[0].get('requeued')} requeued), records from replicas "
          f"{sorted(tagged)}, {len(deferred)} drain records")
    print(f"[kill-replica] OK: {done} completed + {refused} refused "
          f"(all {requests} accounted for), "
          f"{report.get('requeued_total', 0):.0f} requeued onto survivors, "
          f"p99 TTFT {report['ttft_p99_s']:.3f}s — zero drops, no crash")
    return 0


# tiny random-init model every serving drill uses (seconds on CPU)
_TINY_MODEL = ["--synthetic", "--dim", "32", "--depth", "2", "--heads", "2",
               "--dim_head", "8", "--text_seq_len", "8",
               "--num_text_tokens", "64", "--num_image_tokens", "32",
               "--image_fmap_size", "4"]


def _serve_env():
    """Env scrub shared by the serving drills: force CPU, drop any inherited
    accelerator pool, and put the repo root on PYTHONPATH."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(Path(__file__).resolve().parent.parent)
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


def crash_replay_drill(requests=4, at=10, slots=2, workdir=None,
                       timeout=600) -> int:
    """Durable-serving crash drill: phase 1 runs the serve CLI under Poisson
    load with `--journal` armed and `--inject_fault kill-fleet@AT` — the
    process SIGKILLs ITSELF mid-load (no cleanup, no close(): the hard-crash
    case the journal exists for).  Phase 2 restarts with the SAME journal
    directory and no other traffic: every accepted-but-unacknowledged
    request must replay to completion (replay is a plain resubmit of
    (text, key, knobs); the per-request RNG stream regenerates the exact
    codes the crashed process was producing) with ZERO duplicate acks.
    Returns 0 on success."""
    import json
    import subprocess
    import tempfile

    cwd = Path(workdir) if workdir else Path(
        tempfile.mkdtemp(prefix="crashrep_"))
    cwd.mkdir(parents=True, exist_ok=True)
    jdir = cwd / "journal"
    report_path = cwd / "crash_replay_report.json"
    env = _serve_env()
    base = [sys.executable, "-m", "dalle_pytorch_tpu.cli.serve",
            *_TINY_MODEL, "--slots", str(slots), "--block_size", "8",
            "--no_vae", "--journal", str(jdir)]
    print(f"[crash-replay] phase 1: {requests} Poisson requests, SIGKILL "
          f"at engine iteration {at} (journal {jdir})")
    a = subprocess.run(
        [*base, "--loadgen", str(requests), "--rate", "50", "--streams", "2",
         "--inject_fault", f"kill-fleet@{at}"],
        cwd=str(cwd), env=env, capture_output=True, text=True,
        timeout=timeout)
    if a.returncode != -signal.SIGKILL:
        print(f"[crash-replay] FAIL: expected SIGKILL death, got "
              f"rc={a.returncode}\n{a.stderr[-2000:]}")
        return 1
    recs = [json.loads(ln) for ln in
            (jdir / "journal.jsonl").read_text().splitlines() if ln.strip()]
    accepted = {r["uid"] for r in recs if r["kind"] == "accepted"}
    acked = {r["uid"] for r in recs if r["kind"] == "ack"}
    unacked = accepted - acked
    if not accepted or not unacked:
        print(f"[crash-replay] FAIL: the crash left {len(accepted)} accepted"
              f" / {len(unacked)} unacknowledged — the kill did not "
              "interrupt in-flight work (tune --at)")
        return 1
    print(f"[crash-replay] crash left {len(accepted)} accepted, "
          f"{len(acked)} acked, {len(unacked)} unacknowledged")
    print("[crash-replay] phase 2: restart with the same --journal, no new "
          "traffic — the journal IS the traffic source")
    b = subprocess.run(
        [*base, "--report_json", str(report_path)],
        cwd=str(cwd), env=env, capture_output=True, text=True,
        timeout=timeout)
    if b.returncode != 0:
        print(f"[crash-replay] FAIL: restart rc={b.returncode}\n"
              f"{b.stderr[-2000:]}")
        return 1
    report = json.loads(report_path.read_text())
    checks = [
        ("journal_replayed", len(unacked)),
        ("journal_replay_completed", len(unacked)),
        ("journal_unacknowledged", 0),
        ("journal_duplicate_acks", 0),
    ]
    for key, want in checks:
        if report.get(key) != want:
            print(f"[crash-replay] FAIL: {key}={report.get(key)} != {want}"
                  f"\n{b.stdout[-2000:]}")
            return 1
    print(f"[crash-replay] OK: all {len(unacked)} unacknowledged request(s) "
          f"replayed to completion after a hard SIGKILL; zero duplicate "
          f"acks, journal fully acknowledged "
          f"({report['journal_acked']}/{report['journal_accepted']})")
    return 0


def stall_replica_drill(requests=6, replicas=2, at=8, victim=1, wedge_s=2.0,
                        slots=2, workdir=None, timeout=600) -> int:
    """Circuit-breaker drill: wedge replica VICTIM alive-but-stalled for
    `wedge_s` mid-run (`--inject_fault stall-replica@AT:VICTIM` — its poll()
    becomes a no-op; the process never dies, so mark_lost never fires) under
    deadline-carrying Poisson load, then verify the breaker story: it trips
    open with exactly ONE `replica_circuit_open` alarm (episode discipline),
    deadline-burning requests hedge onto the survivors with first-
    completion-wins dedup, and once the wedge expires the breaker half-
    opens, sees progress, and closes — nobody is marked lost, nothing is
    dropped.  Returns 0 on success."""
    import json
    import subprocess
    import tempfile

    cwd = Path(workdir) if workdir else Path(
        tempfile.mkdtemp(prefix="stallrep_"))
    cwd.mkdir(parents=True, exist_ok=True)
    report_path = cwd / "stall_replica_report.json"
    tele_dir = cwd / "tele"
    env = _serve_env()
    print(f"[stall-replica] serve CLI: {requests} Poisson requests across "
          f"{replicas} replicas, wedging replica {victim} for {wedge_s}s at "
          f"fleet iteration {at}; workdir {cwd}")
    r = subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.serve",
         *_TINY_MODEL, "--loadgen", str(requests), "--rate", "20",
         "--streams", "2", "--slots", str(slots), "--block_size", "8",
         "--no_vae", "--replicas", str(replicas),
         "--deadline_s", "2.0", "--stall_wedge_s", str(wedge_s),
         "--stall_after_s", "0.3", "--hedge_frac", "0.25",
         "--inject_fault", f"stall-replica@{at}:{victim}",
         "--telemetry", str(tele_dir), "--telemetry_every", "4",
         "--report_json", str(report_path)],
        cwd=str(cwd), env=env, capture_output=True, text=True,
        timeout=timeout)
    if r.returncode != 0:
        print(f"[stall-replica] FAIL: serve rc={r.returncode}\n"
              f"{r.stderr[-2000:]}")
        return 1
    report = json.loads(report_path.read_text())
    done = report["requests_completed"]
    refused = report["requests_refused"]
    if done + refused < requests:
        print(f"[stall-replica] FAIL: {done} completed + {refused} refused "
              f"< {requests} arrivals — requests were lost behind the wedge"
              f"\n{r.stdout[-2000:]}")
        return 1
    if report.get("replicas_lost", 0) != 0 or (
            report.get("replicas_alive") != replicas):
        print(f"[stall-replica] FAIL: a stalled replica must NOT be marked "
              f"lost (lost={report.get('replicas_lost')}, "
              f"alive={report.get('replicas_alive')})")
        return 1
    if not report.get("breaker_opens"):
        print("[stall-replica] FAIL: the breaker never opened on the "
              "wedged replica")
        return 1
    if not report.get("breaker_recoveries"):
        print("[stall-replica] FAIL: the breaker never closed again after "
              "the wedge expired")
        return 1
    if not report.get("hedged"):
        print("[stall-replica] FAIL: no deadline-burning request was hedged "
              "off the stalled replica")
        return 1
    spans_path = tele_dir / "serve.spans.jsonl"
    records = [json.loads(ln) for ln in spans_path.read_text().splitlines()
               if ln.strip()]
    breaker_alarms = [rec for rec in records if rec.get("kind") == "alarm"
                      and rec.get("type") == "replica_circuit_open"]
    if len(breaker_alarms) != 1:
        print(f"[stall-replica] FAIL: expected exactly 1 "
              f"replica_circuit_open alarm, got {len(breaker_alarms)}")
        return 1
    if breaker_alarms[0].get("replica") != victim:
        print(f"[stall-replica] FAIL: alarm blames replica "
              f"{breaker_alarms[0].get('replica')}, not the victim {victim}")
        return 1
    print(f"[stall-replica] OK: {done} completed + {refused} refused (all "
          f"{requests} accounted for); breaker opened "
          f"{report['breaker_opens']:.0f}x and recovered "
          f"{report['breaker_recoveries']:.0f}x on replica {victim}, "
          f"{report['hedged']:.0f} hedged "
          f"({report['hedge_duplicates']:.0f} duplicate completions "
          f"suppressed), 1 replica_circuit_open alarm — no replica lost")
    return 0


def poison_drill(requests=4, at=6, slots=2, workdir=None,
                 timeout=600) -> int:
    """Poison-quarantine drill: `--inject_fault poison-request@AT` NaNs one
    in-flight request's decode logits inside the jit (re-poisoned every
    retry hop — a persistently-bad request).  The engine must retry it
    `poison_max_retries` times, then quarantine it with a terminal
    `poisoned` record, while every OTHER request completes undisturbed (the
    injection is a per-lane where, so cohabiting lanes are bit-identical to
    an uninjected run — pinned exactly in tests/test_serving_durability.py).
    Returns 0 on success."""
    import json
    import subprocess
    import tempfile

    cwd = Path(workdir) if workdir else Path(
        tempfile.mkdtemp(prefix="poison_"))
    cwd.mkdir(parents=True, exist_ok=True)
    report_path = cwd / "poison_report.json"
    tele_dir = cwd / "tele"
    env = _serve_env()
    print(f"[poison] serve CLI: {requests} Poisson requests, poisoning one "
          f"at engine iteration {at}; workdir {cwd}")
    r = subprocess.run(
        [sys.executable, "-m", "dalle_pytorch_tpu.cli.serve",
         *_TINY_MODEL, "--loadgen", str(requests), "--rate", "20",
         "--streams", "2", "--slots", str(slots), "--block_size", "8",
         "--no_vae", "--inject_fault", f"poison-request@{at}",
         "--telemetry", str(tele_dir), "--telemetry_every", "4",
         "--report_json", str(report_path)],
        cwd=str(cwd), env=env, capture_output=True, text=True,
        timeout=timeout)
    if r.returncode != 0:
        print(f"[poison] FAIL: serve rc={r.returncode}\n{r.stderr[-2000:]}")
        return 1
    report = json.loads(report_path.read_text())
    if report.get("quarantined") != 1:
        print(f"[poison] FAIL: quarantined={report.get('quarantined')} != 1"
              f"\n{r.stdout[-2000:]}")
        return 1
    if not report.get("poison_retries"):
        print("[poison] FAIL: the poisoned request was never retried before "
              "quarantine")
        return 1
    if report["requests_completed"] != requests - 1:
        print(f"[poison] FAIL: {report['requests_completed']} completed != "
              f"{requests - 1} — a healthy request was disturbed")
        return 1
    spans_path = tele_dir / "serve.spans.jsonl"
    records = [json.loads(ln) for ln in spans_path.read_text().splitlines()
               if ln.strip()]
    poisoned_recs = [rec for rec in records if rec.get("kind") == "request"
                     and rec.get("outcome") == "poisoned"]
    if len(poisoned_recs) != 1:
        print(f"[poison] FAIL: expected exactly 1 terminal `poisoned` "
              f"record, got {len(poisoned_recs)}")
        return 1
    print(f"[poison] OK: 1 request quarantined after "
          f"{report['poison_retries']:.0f} retries (terminal `poisoned` "
          f"record, reason={poisoned_recs[0].get('reason')!r}); the other "
          f"{requests - 1} completed undisturbed")
    return 0


def elastic_drill(devices=8, resume_devices=4, step=4, steps=8,
                  batch_size=8, workdir=None) -> int:
    """The shrink/grow experiment end to end: SIGKILL at `step` on
    `devices` CPU devices, relaunch on `resume_devices` with --resume auto,
    and verify the stitched per-step loss trajectory is complete and
    finite.  Returns 0 on success (also the engine behind the subprocess
    test in tests/test_resharding.py)."""
    import json
    import tempfile

    kind = "shrink" if resume_devices < devices else "grow"
    cwd = Path(workdir) if workdir else Path(tempfile.mkdtemp(prefix="elastic_"))
    cwd.mkdir(parents=True, exist_ok=True)
    # a reused workdir must not poison this drill: stale metrics rows would
    # fill gaps in the loss check (runs append to drill.metrics.jsonl) and
    # stale checkpoints would hijack --resume auto's discovery
    import shutil

    for leftover in cwd.glob("drill*"):
        shutil.rmtree(leftover) if leftover.is_dir() else leftover.unlink()
    base = ["--dummy_run", str(steps), "--telemetry", "off",
            "--log_every_n_steps", "1", "--batch_size", str(batch_size),
            "--dalle_output_file_name", str(cwd / "drill")]
    print(f"[elastic] phase 1: {devices} devices, --inject_fault "
          f"{kind}@{step}  (workdir {cwd})")
    a = _run_train(
        [*base, "--save_every_n_steps", "1",
         "--inject_fault", f"{kind}@{step}"], cwd, devices)
    if a.returncode != -signal.SIGKILL:
        print(f"[elastic] FAIL: expected SIGKILL death, got rc={a.returncode}"
              f"\n{a.stderr[-2000:]}")
        return 1
    print(f"[elastic] phase 2: relaunch on {resume_devices} devices with "
          "--resume auto")
    b = _run_train(
        [*base, "--save_every_n_steps", "0", "--resume", "auto"],
        cwd, resume_devices)
    if b.returncode != 0:
        print(f"[elastic] FAIL: resume rc={b.returncode}\n{b.stderr[-2000:]}")
        return 1
    if "resharding onto the live mesh" not in b.stdout:
        print("[elastic] FAIL: resume did not detect the topology change")
        return 1
    losses = {}
    for line in open(cwd / "drill.metrics.jsonl"):
        rec = json.loads(line)
        if "loss" in rec:
            losses[rec["step"]] = rec["loss"]
    missing = [s for s in range(steps) if s not in losses]
    bad = [s for s, v in losses.items() if v != v]  # NaN check
    if missing or bad:
        print(f"[elastic] FAIL: missing steps {missing}, NaN steps {bad}")
        return 1
    print(f"[elastic] OK: {kind} drill survived — all {steps} steps logged "
          "finite losses across the topology change; trajectory: "
          + ", ".join(f"{s}:{losses[s]:.4f}" for s in sorted(losses)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
