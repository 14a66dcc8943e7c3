"""Long-lived generation service CLI.

Two traffic sources:

* `--prompts FILE` (or `-` for stdin): one prompt per line, all submitted
  through the continuous-batching engine; images land under
  `--outputs_dir/<prompt>/N.png` exactly like generate.py.
* `--loadgen N`: N synthetic requests under `--streams` Poisson streams at
  `--rate` req/s per stream (tools/loadgen.py) — the SLO bench mode, used
  by the chaos `flood` drill.

Either way the run ends with an SLO report (p50/p99 time-to-first-token,
p50/p99 request latency, images/sec/chip, refusals) printed and optionally
written as JSON (`--report_json`).  `--inject_fault flood@ITER[:COUNT]`
bursts synthetic requests into the queue mid-run so admission control can be
drilled: the service must queue/refuse — never OOM (the paged pool is sized
up front and the ledger-priced admission gate refuses what will not fit).

Observability: `--slo_ttft_p99/--slo_latency_p99/--slo_images_per_sec/
--slo_shed_rate` declare service objectives evaluated over sliding windows
(observability/slo.py) — a sustained breach fires an `slo_burn_rate` alarm
through the hub, which `--profile_on_alarm N` turns into a rate-limited
profiler capture; `--status_json PATH` keeps an atomically-rewritten live
snapshot (the scrape surface for a router); with `--telemetry` every request
leaves a `kind:"request"` phase-attributed record (tools/serving_report.py
renders the waterfall) and a stalled poll() dumps thread stacks + request
phases via the heartbeat (`--telemetry_heartbeat_s`).  The KV-pool flight
recorder (on by default; `--no_pool_recorder`, `--pool_recorder_capacity`)
logs every block alloc/free/defer as `kind:"pool"` records — the status
snapshot and final report carry the pool section (occupancy, high-water,
reserved-unused waste, block-lifetime percentiles, overcommit forecast)
and tools/pool_report.py replays the trace against hypothetical pool
configs; `--zipf S` makes loadgen traffic repeat prompts Zipf-style so the
prefix-sharing forecast has something to share.

Fleet mode: `--replicas N` serves through N engine replicas behind the
load-balancing router (serving/fleet.py); `--disaggregate` moves prefill to
a separate worker pool whose KV handoff is priced as a comms-ledger row.
`--inject_fault kill-replica@ITER[:IDX]` kills replica IDX mid-run — its
queued + in-flight requests drain and requeue onto the survivors (the chaos
`kill-replica` drill asserts zero drops and one `replica_lost` alarm).

Durability (PR 14): `--journal DIR` write-ahead-logs every accepted request
(fsynced JSONL, serving/journal.py) and REPLAYS the accepted-but-
unacknowledged ones at startup — after a full-process crash (`--inject_fault
kill-fleet@ITER`, the chaos `crash-replay` drill) a restart with the same
`--journal` completes every in-flight request bit-identically (per-request
RNG streams make replay a plain resubmit).  `--deadline_s`/`--retries`
attach a budget to loadgen traffic: the fleet router hedges deadline-
burning requests off stalled replicas (`--inject_fault
stall-replica@ITER[:IDX]` wedges one alive; the circuit breaker opens,
probes, and recovers) and bounds requeue hops.  `--degrade` arms the
load-shed ladder (serving/degrade.py): sustained pressure climbs
no-CFG -> capped-candidates -> short-prompts-only -> shed, with hysteresis
both ways.  `--inject_fault poison-request@ITER` flips one in-flight
request's logits to NaN — the engine quarantines it after bounded retries
without disturbing cohabiting lanes (the chaos `poison` drill).

Without `--dalle_path` a `--synthetic` random-init model serves (drills and
smoke tests run without a trained checkpoint)."""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import numpy as np

from dalle_pytorch_tpu.observability import memory as memory_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.observability.slo import SloMonitor, SloTargets
from dalle_pytorch_tpu.training import resilience


def build_parser():
    parser = argparse.ArgumentParser(description="DALL-E generation service")
    src = parser.add_argument_group("model")
    src.add_argument("--dalle_path", type=str, default=None)
    src.add_argument("--allow_legacy_pickle", action="store_true")
    src.add_argument("--vqgan_config_path", type=str, default=None)
    src.add_argument("--synthetic", action="store_true",
                     help="serve a random-init model (no checkpoint needed)")
    src.add_argument("--dim", type=int, default=64)
    src.add_argument("--depth", type=int, default=2)
    src.add_argument("--heads", type=int, default=4)
    src.add_argument("--dim_head", type=int, default=16)
    src.add_argument("--text_seq_len", type=int, default=16)
    src.add_argument("--num_text_tokens", type=int, default=256)
    src.add_argument("--num_image_tokens", type=int, default=256)
    src.add_argument("--image_fmap_size", type=int, default=8)

    eng = parser.add_argument_group("engine")
    eng.add_argument("--slots", type=int, default=4,
                     help="concurrent decode slots (a guided request uses 2)")
    eng.add_argument("--block_size", type=int, default=64,
                     help="KV pool block size in tokens")
    eng.add_argument("--num_blocks", type=int, default=None,
                     help="KV pool size (default: slots x blocks/seq)")
    eng.add_argument("--max_queue", type=int, default=64)
    eng.add_argument("--headroom_frac", type=float, default=0.92,
                     help="defer admissions above this live-HBM usage fraction")
    eng.add_argument("--telemetry_every", type=int, default=32,
                     help="poll iterations per serving telemetry window "
                          "(serving_window events, SLO evaluation, status_json)")
    eng.add_argument("--quantize_weights", choices=["none", "int8", "fp8"],
                     default="none",
                     help="post-training weight quantization applied to the "
                          "loaded params (quantization.quantize_tree)")
    eng.add_argument("--quantize_kv", choices=["none", "int8"],
                     default="none",
                     help="store the paged KV pool quantized (int8 blocks + "
                          "per-token scales)")
    eng.add_argument("--replicas", type=int, default=1,
                     help="engine replicas behind the load-balancing router "
                          "(serving/fleet.py); killing one mid-run drains + "
                          "requeues its work onto survivors")
    eng.add_argument("--disaggregate", action="store_true",
                     help="run prefill on a separate worker pool and hand "
                          "the KV prefix to the decode replicas (priced as a "
                          "comms-ledger handoff row)")
    eng.add_argument("--no_pool_recorder", action="store_true",
                     help="disable the KV-pool flight recorder (block "
                          "lifecycle events + pool gauges; on by default, "
                          "recorder-off is the bench baseline path)")
    eng.add_argument("--pool_recorder_capacity", type=int, default=4096,
                     help="flight-recorder ring size in events; overflow "
                          "drops the oldest and is counted (a dropped trace "
                          "refuses pool_report self-validation)")
    eng.add_argument("--spec_k", type=int, default=0,
                     help="self-speculative decoding: draft this many tokens "
                          "per round through a shallow layer prefix, verify "
                          "them in one full-model pass (0 disables — exactly "
                          "today's sequential path)")
    eng.add_argument("--spec_draft_layers", type=int, default=None,
                     help="layers in the draft prefix (default depth // 2); "
                          "must be in [1, depth)")

    slo = parser.add_argument_group("slo")
    slo.add_argument("--slo_ttft_p99", type=float, default=None,
                     help="p99 time-to-first-token target in seconds; a "
                          "sustained breach fires an slo_burn_rate alarm")
    slo.add_argument("--slo_latency_p99", type=float, default=None,
                     help="p99 end-to-end request latency target in seconds")
    slo.add_argument("--slo_images_per_sec", type=float, default=None,
                     help="completed-images/sec floor")
    slo.add_argument("--slo_shed_rate", type=float, default=None,
                     help="refused/arrivals ceiling (0..1)")
    slo.add_argument("--status_json", type=str, default=None,
                     help="atomically rewritten live-status snapshot (live "
                          "percentiles, queue depth, pool occupancy, active "
                          "alarms) at the telemetry-window cadence")

    dur = parser.add_argument_group("durability")
    dur.add_argument("--journal", type=str, default=None,
                     help="request-journal directory (append-only fsynced "
                          "JSONL WAL): accepted requests survive a process "
                          "crash and are replayed, bit-identically, on the "
                          "next start with the same directory")
    dur.add_argument("--deadline_s", type=float, default=None,
                     help="per-request deadline attached to loadgen traffic; "
                          "requests past --hedge_frac of it on a stalled "
                          "replica are hedged onto a survivor")
    dur.add_argument("--retries", type=int, default=3,
                     help="requeue/poison-retry budget per request before the "
                          "terminal requeue_exhausted/poisoned record")
    dur.add_argument("--degrade", action="store_true",
                     help="arm the load-shed degradation ladder (no-CFG -> "
                          "cap-candidates -> short-prompts -> shed)")
    dur.add_argument("--degrade_enter_s", type=float, default=0.5,
                     help="sustained pressure before climbing one rung")
    dur.add_argument("--degrade_exit_s", type=float, default=2.0,
                     help="sustained calm before descending one rung")
    dur.add_argument("--stall_wedge_s", type=float, default=3.0,
                     help="how long the stall-replica fault wedges its "
                          "victim's poll loop")
    dur.add_argument("--stall_after_s", type=float, default=1.0,
                     help="circuit breaker: busy replica making no decode "
                          "progress for this long -> open")
    dur.add_argument("--hedge_frac", type=float, default=0.5,
                     help="hedge a request off a non-closed replica once "
                          "this fraction of its deadline is burned")
    dur.add_argument("--requeue_budget_s", type=float, default=30.0,
                     help="mark_lost: give up requeueing a drained request "
                          "after this long and shed it (terminal "
                          "requeue_exhausted record) instead of blocking "
                          "forever")

    traffic = parser.add_argument_group("traffic")
    traffic.add_argument("--prompts", type=str, default=None,
                         help="file of prompts (one per line), or - for stdin")
    traffic.add_argument("--loadgen", type=int, default=0,
                         help="generate N synthetic Poisson requests instead")
    traffic.add_argument("--rate", type=float, default=2.0,
                         help="loadgen requests/second per stream")
    traffic.add_argument("--streams", type=int, default=2)
    traffic.add_argument("--zipf", type=float, default=None, metavar="S",
                         help="loadgen prompts drawn Zipf(S)-distributed "
                              "from a fixed pool instead of fresh-random — "
                              "the repeated-prompt workload that exercises "
                              "prefix sharing (tools/pool_report.py)")
    traffic.add_argument("--prompt_pool", type=int, default=16,
                         help="distinct prompts in the --zipf pool")
    traffic.add_argument("--top_k", type=float, default=0.9)
    traffic.add_argument("--temperature", type=float, default=1.0)
    traffic.add_argument("--cond_scale", type=float, default=1.0)
    traffic.add_argument("--seed", type=int, default=0)

    parser.add_argument("--outputs_dir", type=str, default="./outputs")
    parser.add_argument("--no_vae", action="store_true",
                        help="skip VAE decode (codes-only serving: bench mode)")
    parser.add_argument("--telemetry", type=str, default=None)
    parser.add_argument("--telemetry_heartbeat_s", type=float, default=300.0,
                        help="hang-dump deadline: no poll() completing for "
                             "this long dumps thread stacks + request-phase "
                             "state (0 disables; needs --telemetry)")
    parser.add_argument("--profile_on_alarm", type=int, default=0,
                        help="capture an N-poll profiler trace when any alarm "
                             "fires (SLO burn, backpressure, hang); "
                             "rate-limited like the train CLIs "
                             "(needs --telemetry)")
    parser.add_argument("--report_json", type=str, default=None)
    parser.add_argument("--inject_fault", type=str, default=None,
                        help="chaos hook, e.g. flood@8:16 (see tools/chaos.py)")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--hug", action="store_true")
    return parser


def _build_model(args):
    if args.dalle_path:
        from dalle_pytorch_tpu.cli.common import load_dalle_bundle

        return load_dalle_bundle(
            args.dalle_path, allow_legacy_pickle=args.allow_legacy_pickle,
            vqgan_config_path=args.vqgan_config_path,
        )
    assert args.synthetic, "provide --dalle_path or --synthetic"
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig

    cfg = DALLEConfig(
        dim=args.dim, depth=args.depth, heads=args.heads, dim_head=args.dim_head,
        num_text_tokens=args.num_text_tokens, text_seq_len=args.text_seq_len,
        num_image_tokens=args.num_image_tokens,
        image_fmap_size=args.image_fmap_size,
    )
    params = dalle_mod.init_dalle(jax.random.PRNGKey(args.seed), cfg)
    return cfg, params, None, None


def main(argv=None):
    args = build_parser().parse_args(argv)
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    tele = None
    if args.telemetry:
        tele = telemetry.configure(
            args.telemetry, run_name="serve",
            heartbeat_s=args.telemetry_heartbeat_s or None)

    capture = None
    if args.profile_on_alarm and tele is not None:
        from dalle_pytorch_tpu.observability.capture import TraceTrigger

        capture = TraceTrigger(
            dir=str(Path(args.telemetry) / "traces"),
            window_steps=args.profile_on_alarm,
            recorder=tele.spans,
        ).install_sigusr2()
        tele.add_alarm_listener(capture.on_alarm)

    injector = None
    if args.inject_fault:
        injector = resilience.FaultInjector(
            resilience.parse_fault(args.inject_fault)).install()

    dalle_cfg, params, vae_cfg, vae_params = _build_model(args)
    if args.no_vae:
        vae_cfg = vae_params = None
    if args.quantize_weights != "none":
        from dalle_pytorch_tpu import quantization as quant_mod

        if quant_mod.tree_is_quantized(params):
            print("[serving] checkpoint weights already quantized "
                  f"({quant_mod.weight_quant_kind(params)})")
        else:
            plain = params
            params = quant_mod.quantize_tree(params, args.quantize_weights)
            print(f"[serving] weights quantized to {args.quantize_weights}: "
                  f"{quant_mod.weight_reduction(plain, params):.2f}x at-rest "
                  "reduction vs bf16 storage")

    engine_cfg = EngineConfig(
        num_slots=args.slots, block_size=args.block_size,
        num_blocks=args.num_blocks, max_queue=args.max_queue,
        headroom_frac=args.headroom_frac, filter_thres=args.top_k,
        telemetry_every=args.telemetry_every,
        quantize_kv=None if args.quantize_kv == "none" else args.quantize_kv,
        spec_k=args.spec_k, spec_draft_layers=args.spec_draft_layers,
        pool_recorder=not args.no_pool_recorder,
        pool_recorder_capacity=args.pool_recorder_capacity,
    )
    if args.replicas > 1 or args.disaggregate:
        from dalle_pytorch_tpu.serving.fleet import FleetConfig, ServingFleet

        engine = ServingFleet(
            params, dalle_cfg, vae_params, vae_cfg,
            fleet_cfg=FleetConfig(
                replicas=args.replicas, disaggregate=args.disaggregate,
                engine=engine_cfg,
                stall_wedge_s=args.stall_wedge_s,
                stall_after_s=args.stall_after_s,
                hedge_frac=args.hedge_frac,
                requeue_budget_s=args.requeue_budget_s,
            ),
        )
    else:
        engine = GenerationEngine(params, dalle_cfg, vae_params, vae_cfg,
                                  engine_cfg=engine_cfg)
    journal = None
    if args.journal:
        from dalle_pytorch_tpu.serving.journal import RequestJournal

        journal = RequestJournal(args.journal)
        if hasattr(engine, "attach_journal"):
            engine.attach_journal(journal)
        else:
            engine.journal = journal
    ladder = None
    if args.degrade:
        from dalle_pytorch_tpu.serving.degrade import (DegradeConfig,
                                                       DegradeLadder)

        ladder = DegradeLadder(
            DegradeConfig(enter_after_s=args.degrade_enter_s,
                          exit_after_s=args.degrade_exit_s),
            text_seq_len=dalle_cfg.text_seq_len,
            on_alarm=(lambda a: tele.alarm(a.pop("type", "degrade_rung"), **a))
            if tele is not None else None,
        )
        if hasattr(engine, "attach_degrade"):
            engine.attach_degrade(ladder)
        else:
            engine.degrade = ladder
    slo_targets = SloTargets(
        ttft_p99_s=args.slo_ttft_p99, latency_p99_s=args.slo_latency_p99,
        images_per_sec_floor=args.slo_images_per_sec,
        shed_rate_ceiling=args.slo_shed_rate,
    )
    monitor = None
    if slo_targets.any():
        # alarms route through the hub, so the on-alarm TraceTrigger (and
        # any other listener) reacts to an SLO burn like any other alarm
        monitor = SloMonitor(
            slo_targets,
            on_alarm=(lambda a: tele.alarm(a.pop("type", "slo_burn_rate"), **a))
            if tele is not None else None,
        )
    if monitor is not None or args.status_json:
        engine.attach_slo(monitor, status_path=args.status_json)
    if capture is not None:
        engine.attach_capture(capture)
    if tele is not None and tele.heartbeat is not None:
        # a wedged poll() dumps the engine's request-phase state too
        tele.heartbeat.context_fn = engine.phase_state
    ledger = engine.memory_ledger()
    print("[serving] paged-pool ledger:")
    print(memory_mod.format_ledger(ledger))

    replayed = []
    try:
        if journal is not None:
            replayed = _replay_journal(engine, journal)
        if args.loadgen or args.prompts or journal is None:
            report = _run_traffic(args, engine, dalle_cfg, vae_cfg)
        else:
            # journal-replay-only restart (the crash-replay drill's second
            # phase): the journal IS the traffic source
            report = {
                "requests_completed": sum(
                    1 for r in replayed if r.codes is not None),
                "pool_blocks": engine.pool.num_blocks,
            }
    except Exception as e:
        if memory_mod.is_oom_error(e):
            path = memory_mod.write_oom_report(
                args.outputs_dir, error=e, phase="serving", ledger=ledger,
                context={"slots": args.slots, "block_size": args.block_size,
                         "num_blocks": engine.pool.num_blocks},
            )
            print(f"[memory] OUT OF MEMORY while serving: forensic report -> "
                  f"{path or '<unwritable>'}; exiting "
                  f"{resilience.EXIT_OOM}", flush=True)
            raise SystemExit(resilience.EXIT_OOM)
        raise
    finally:
        if injector is not None:
            injector.uninstall()
        engine.close()  # terminal "deferred" records + final window/status
        if journal is not None:
            journal.close()  # queued/in-flight stay unacked -> next replay
        if capture is not None:
            capture.close()
        if tele is not None:
            tele.flush(fleet=False)
            tele.close()

    if journal is not None:
        report["journal_replayed"] = len(replayed)
        report["journal_replay_completed"] = sum(
            1 for r in replayed if r.codes is not None)
        for k, v in journal.stats().items():
            report[f"journal_{k}"] = v
        report["journal_duplicate_acks"] = int(
            obs_metrics.counter("journal/duplicate_acks").value)
    if ladder is not None:
        report["degrade_rung"] = ladder.rung
        report["degrade_max_rung"] = ladder.max_rung_seen
        report["degrade_rungs_entered"] = dict(ladder.rungs_entered)
    print("[serving] SLO report:")
    for k, v in report.items():
        print(f"  {k:>26}: {v}")
    if args.report_json:
        Path(args.report_json).write_text(json.dumps(report))
    return report


def _replay_journal(engine, journal):
    """Resubmit every accepted-but-unacknowledged request from the previous
    process generation and run them to completion BEFORE new traffic starts.
    Replay is a plain resubmit: a request's whole sample path is a pure
    function of (text, key, temperature, cond_scale), so greedy replays are
    bit-identical and stochastic replays re-traverse the exact RNG stream
    the crashed process was consuming."""
    payloads = journal.replay()
    if not payloads:
        return []
    print(f"[journal] replaying {len(payloads)} unacknowledged request(s) "
          f"from {journal.path}")
    from dalle_pytorch_tpu.observability import tracing

    reqs = []
    for p in payloads:
        # replay edge: same journey uid as the crashed process's hops (the
        # uid IS the journal key), so trace_report stitches pre-crash admit
        # spans and this hop into one journey across the two spans files
        tracing.emit("replay", p["uid"], codes_done=p.get("codes_done", 0))
        reqs.append(engine.submit_when_able(
            p["text"], key=p["key"], temperature=p["temperature"],
            cond_scale=p["cond_scale"], deadline_s=p["deadline_s"],
            retries_left=(p["retries_left"]
                          if p["retries_left"] is not None else 3),
            replayed=True))
    engine.run_until_idle()
    done = sum(1 for r in reqs if r.codes is not None)
    print(f"[journal] replay complete: {done}/{len(reqs)} finished")
    return reqs


def _import_loadgen():
    """tools/ is not an installed package — fall back to a path import when
    the repo root is not already on sys.path."""
    try:
        from tools.loadgen import PoissonLoadGen, synthetic_request_maker
    except ImportError:
        import sys

        sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "tools"))
        from loadgen import PoissonLoadGen, synthetic_request_maker
    return PoissonLoadGen, synthetic_request_maker


def _run_traffic(args, engine, dalle_cfg, vae_cfg):
    import sys
    import time

    PoissonLoadGen, synthetic_request_maker = _import_loadgen()

    if args.loadgen:
        gen = PoissonLoadGen(args.loadgen, args.rate, streams=args.streams,
                             seed=args.seed)
        report = gen.run(engine, synthetic_request_maker(
            dalle_cfg, seed=args.seed, temperature=args.temperature,
            cond_scale=args.cond_scale, deadline_s=args.deadline_s,
            retries=args.retries, zipf_s=args.zipf,
            prompt_pool=args.prompt_pool,
        ))
    else:
        assert args.prompts, "provide --loadgen N or --prompts FILE"
        from dalle_pytorch_tpu.cli.generate import get_tokenizer

        tokenizer = get_tokenizer(args)
        lines = (sys.stdin if args.prompts == "-"
                 else open(args.prompts)).read().splitlines()
        lines = [ln.strip() for ln in lines if ln.strip()]
        t0 = time.monotonic()
        reqs, prompts = [], []
        for i, prompt in enumerate(lines):
            toks = tokenizer.tokenize(prompt, dalle_cfg.text_seq_len,
                                      truncate_text=True)
            # blocking submit: a full queue waits (backpressure) rather than
            # refusing a batch caller; can-never-fit still raises
            reqs.append(engine.submit_when_able(
                np.asarray(toks)[0],
                key=jax.random.PRNGKey(args.seed + i),
                temperature=args.temperature,
                cond_scale=args.cond_scale))
            prompts.append(prompt)
        engine.run_until_idle()
        elapsed = time.monotonic() - t0
        # report over ALL submitted requests — completions drained by the
        # blocking submits' internal polls must count too
        done = [r for r in reqs if r.codes is not None]
        if any(r.images is not None for r in done):
            _save_images(args, vae_cfg, reqs, prompts)
        report = PoissonLoadGen(max(len(lines), 1), 1.0).report(
            done, refused=0, elapsed_s=elapsed)
    report["pool_blocks"] = engine.pool.num_blocks
    report["refused_total"] = obs_metrics.counter("serving/refused").value
    report["backpressure_alarms"] = obs_metrics.counter(
        "serving_backpressure_alarms").value
    report["quarantined"] = obs_metrics.counter("serving/quarantined").value
    report["poison_retries"] = obs_metrics.counter(
        "serving/poison_retries").value
    if hasattr(engine, "prefix_redundancy"):
        report["prefix_redundancy"] = engine.prefix_redundancy()
    # same pool section status_json carries: free-list state always, plus
    # the flight-recorder gauges (lifetimes, reserved-unused waste,
    # overcommit forecast) when the recorder is on
    report["pool"] = engine.pool_observability()
    if args.spec_k:
        rounds = obs_metrics.counter("serving/spec_rounds").value
        accepted = obs_metrics.counter("serving/spec_accepted_tokens").value
        report["spec_rounds"] = rounds
        report["spec_accepted_tokens"] = accepted
        report["spec_rejected_tokens"] = obs_metrics.counter(
            "serving/spec_rejected_tokens").value
    if hasattr(engine, "router"):  # fleet: preemption + disaggregation ledger
        report["replicas"] = len(engine.engines)
        report["replicas_alive"] = len(engine.router.alive())
        report["replicas_lost"] = obs_metrics.counter(
            "router/replicas_lost").value
        report["requeued_total"] = obs_metrics.counter("router/requeued").value
        report["router_shed"] = obs_metrics.counter("router/shed").value
        report["breaker_opens"] = obs_metrics.counter(
            "router/breaker_open").value
        report["breaker_recoveries"] = obs_metrics.counter(
            "router/breaker_closed").value
        report["hedged"] = obs_metrics.counter("router/hedged").value
        report["hedge_duplicates"] = obs_metrics.counter(
            "router/hedge_duplicates").value
        report["requeue_exhausted"] = obs_metrics.counter(
            "router/requeue_exhausted").value
        if engine.prefill_worker is not None:
            report["handoff_requests"] = obs_metrics.counter(
                "serving/handoff_requests").value
            report["handoff_bytes"] = obs_metrics.counter(
                "serving/handoff_bytes").value
    return report


def _save_images(args, vae_cfg, reqs, prompts):
    from PIL import Image

    from dalle_pytorch_tpu.models import vae_registry

    outputs_dir = Path(args.outputs_dir)
    for req, prompt in zip(reqs, prompts):
        if req.images is None:
            continue
        out_dir = outputs_dir / prompt.replace(" ", "_")[:100]
        out_dir.mkdir(parents=True, exist_ok=True)
        images = vae_registry.to_display(vae_cfg, req.images)
        arr = (np.clip(np.asarray(images)[0], 0, 1) * 255).astype(np.uint8)
        n = len(list(out_dir.glob("*.png")))
        Image.fromarray(arr.squeeze()).save(out_dir / f"{n}.png")


if __name__ == "__main__":
    main()
