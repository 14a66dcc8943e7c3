#!/usr/bin/env python
"""Poisson load generator for the serving engine.

Models K independent request streams (think: K users, or K upstream
frontends) each emitting requests with exponential inter-arrival gaps at
`rate` requests/second, merged into one arrival schedule.  `run` walks
wall-clock time: due requests are submitted (refusals counted — admission
control shedding load is a measured outcome, not an error), the engine is
polled continuously, and per-request TTFT / latency are collected from the
completed Request records.  The report computes EXACT percentiles from those
records (not the registry's log2-bucket histograms), which is what the
`serving` bench row and cli/serve.py print.

Percentiles are JOURNEY-level: hops of one logical request — the original
placement plus any requeue hops, hedged duplicates, and replays, all sharing
a content uid — collapse into one sample measured from the FIRST hop's
arrival to the FIRST completion (first accept → final ack; a hedge loser
finishing second is not a second sample).  On a single engine with no
chaos, every journey is one hop and these equal the raw per-hop numbers;
the per-hop percentiles stay available as `hop_*` fields.

Usable as a module (cli/serve.py, tests) or a CLI against a synthetic model:

    python tools/loadgen.py --requests 8 --rate 2 --streams 2
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np


def _journey_key(r) -> Any:
    """Stable grouping key across a logical request's hops: the journal /
    trace content uid when stamped (identical for requeues, hedges, and
    replays by construction), else object identity (single-hop)."""
    return (getattr(r, "journal_uid", None) or getattr(r, "trace_uid", None)
            or getattr(r, "hedge_uid", None) or id(r))


class PoissonLoadGen:
    def __init__(self, n_requests: int, rate: float, streams: int = 2,
                 seed: int = 0):
        assert n_requests > 0 and rate > 0 and streams > 0
        rng = np.random.RandomState(seed)
        per_stream = -(-n_requests // streams)  # ceil split across streams
        arrivals = []
        for s in range(streams):
            t = np.cumsum(rng.exponential(1.0 / rate, size=per_stream))
            arrivals.extend((float(ti), s) for ti in t)
        arrivals.sort()
        self.arrivals = arrivals[:n_requests]
        self.streams = streams

    def run(self, engine, make_request: Callable[[int], Dict[str, Any]],
            max_wall_s: Optional[float] = None) -> Dict[str, Any]:
        """Drive `engine` through the arrival schedule.  `make_request(i)`
        returns submit() kwargs for the i-th arrival.  Returns the SLO
        report dict."""
        from dalle_pytorch_tpu.serving.scheduler import AdmissionRefused

        completed: List[Any] = []
        submitted: List[Any] = []
        synthetic_done = 0
        refused = 0
        idx = 0
        t0 = time.monotonic()
        while idx < len(self.arrivals) or engine.busy:
            now = time.monotonic() - t0
            if max_wall_s is not None and now > max_wall_s:
                break
            while idx < len(self.arrivals) and self.arrivals[idx][0] <= now:
                try:
                    submitted.append(engine.submit(**make_request(idx)))
                except AdmissionRefused:
                    refused += 1
                idx += 1
            if engine.busy:
                for r in engine.poll():
                    # flood-fault injections complete through the same poll;
                    # keep them OUT of the organic SLO numbers (the chaos
                    # drill's "every organic request completed" check reads
                    # requests_completed)
                    if getattr(r, "synthetic", False):
                        synthetic_done += 1
                    else:
                        completed.append(r)
            elif idx < len(self.arrivals):
                # idle until the next arrival — sleep in small slices so the
                # loop stays responsive
                time.sleep(min(max(self.arrivals[idx][0] - now, 0.0), 0.02))
        elapsed = time.monotonic() - t0
        report = self.report(completed, refused, elapsed, submitted=submitted)
        report["synthetic_completed"] = synthetic_done
        return report

    def report(self, completed: List[Any], refused: int,
               elapsed_s: float,
               submitted: Optional[List[Any]] = None) -> Dict[str, Any]:
        ttfts = np.asarray([r.ttft_s for r in completed if r.ttft_s is not None])
        lats = np.asarray([r.latency_s for r in completed if r.latency_s is not None])
        # queue_wait comes from the engine's per-request phase trace: the
        # time TTFT spends just WAITING (queue-full backpressure is invisible
        # inside raw TTFT; this makes it a first-class SLO column)
        qwaits = np.asarray([
            r.phases["queue_wait"] for r in completed
            if getattr(r, "phases", None) and "queue_wait" in r.phases
        ])
        # speculative decode: per-request acceptance rate from the SAME
        # completed-Request stream the TTFT/latency percentiles read — the
        # bench row's accepted-tokens/step is a percentile over these, not a
        # separately-sampled gauge
        accepts = np.asarray([
            r.accepted_tokens_per_step for r in completed
            if getattr(r, "accepted_tokens_per_step", None) is not None
        ])

        def pct(a, q):
            return float(np.percentile(a, q)) if a.size else None

        # journey collapse: every hop the caller saw — original submits plus
        # completions delivered by poll (requeue hops and hedge copies arrive
        # only through the latter) — grouped by content uid.  Journey TTFT is
        # first-token-anywhere minus first-hop arrival; journey TTLB is the
        # FIRST completion's finish minus first-hop arrival (a hedge loser or
        # duplicate replay finishing later is not a second sample).
        hops: Dict[Any, Dict[str, Any]] = {}
        for r in list(submitted or []) + list(completed):
            if getattr(r, "synthetic", False):
                continue
            # records without an arrival stamp (bare report() callers) fall
            # back to 0.0 — single-hop journeys then equal the hop numbers
            arr = getattr(r, "arrival_t", None) or 0.0
            j = hops.setdefault(_journey_key(r),
                                {"arrival": arr, "first": [], "final": []})
            j["arrival"] = min(j["arrival"], arr)
            if getattr(r, "ttft_s", None) is not None:
                j["first"].append(arr + r.ttft_s)
            if getattr(r, "latency_s", None) is not None:
                j["final"].append(arr + r.latency_s)
        done = [j for j in hops.values() if j["final"]]
        j_ttfts = np.asarray([min(j["first"]) - j["arrival"]
                              for j in done if j["first"]])
        j_lats = np.asarray([min(j["final"]) - j["arrival"] for j in done])

        n = len(completed)
        spec = {}
        if accepts.size:
            spec = {
                "accepted_tokens_per_step_p50": pct(accepts, 50),
                "accepted_tokens_per_step_mean": float(accepts.mean()),
                "accepted_tokens_per_step_min": float(accepts.min()),
            }
        return {
            "requests_completed": n,
            "requests_refused": refused,
            "journeys_completed": len(done),
            "streams": self.streams,
            "elapsed_s": round(elapsed_s, 4),
            # primary percentiles are journey-level (identical to per-hop on
            # a chaos-free single engine — every journey is one hop)
            "ttft_p50_s": pct(j_ttfts, 50),
            "ttft_p99_s": pct(j_ttfts, 99),
            "queue_wait_p50_s": pct(qwaits, 50),
            "queue_wait_p99_s": pct(qwaits, 99),
            "latency_p50_s": pct(j_lats, 50),
            "latency_p99_s": pct(j_lats, 99),
            # per-hop numbers stay visible: hop TTFT vs journey TTFT is the
            # requeue/hedge tax the durability layer pays
            "hop_ttft_p50_s": pct(ttfts, 50),
            "hop_ttft_p99_s": pct(ttfts, 99),
            "hop_latency_p50_s": pct(lats, 50),
            "hop_latency_p99_s": pct(lats, 99),
            # the engine runs on ONE device; normalize per serving chip
            "images_per_sec_per_chip": (n / elapsed_s if elapsed_s > 0 else None),
            **spec,
        }


def synthetic_request_maker(cfg, seed: int = 0, temperature: float = 1.0,
                            cond_scale: float = 1.0,
                            deadline_s: Optional[float] = None,
                            retries: Optional[int] = None,
                            zipf_s: Optional[float] = None,
                            prompt_pool: int = 16):
    """Random-prompt submit() kwargs factory (drills, bench, smoke tests).
    `deadline_s`/`retries` attach the PR 14 durability budget to every
    request (hedge eligibility + bounded requeue hops).

    `zipf_s` switches from fresh-random prompts to Zipf-distributed draws
    from a fixed pool of `prompt_pool` prompts (rank r drawn with weight
    r^-s): the repeated-prompt workload that makes the KV pool's prefix-
    sharing forecast (tools/pool_report.py) non-trivial — real image
    frontends re-submit trending prompts, they don't draw fresh ones."""
    import jax

    rng = np.random.RandomState(seed)
    pool = None
    weights = None
    if zipf_s is not None:
        assert zipf_s > 0 and prompt_pool > 0
        pool = rng.randint(1, cfg.num_text_tokens,
                           size=(prompt_pool, cfg.text_seq_len))
        ranks = np.arange(1, prompt_pool + 1, dtype=np.float64)
        weights = ranks ** -zipf_s
        weights /= weights.sum()

    def make(i: int) -> Dict[str, Any]:
        if pool is None:
            text = rng.randint(1, cfg.num_text_tokens,
                               size=(cfg.text_seq_len,))
        else:
            text = pool[rng.choice(len(pool), p=weights)]
        kw = {
            "text": text,
            "key": jax.random.PRNGKey(seed * 100003 + i),
            "temperature": temperature,
            "cond_scale": cond_scale,
        }
        if deadline_s is not None:
            kw["deadline_s"] = deadline_s
        if retries is not None:
            kw["retries_left"] = retries
        return kw

    return make


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Poisson load against a synthetic serving engine")
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--rate", type=float, default=2.0,
                        help="requests/second per stream")
    parser.add_argument("--streams", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--zipf", type=float, default=None, metavar="S",
                        help="draw prompts Zipf(S)-distributed from a fixed "
                             "pool instead of fresh-random (prefix-sharing "
                             "workload; see tools/pool_report.py)")
    parser.add_argument("--prompt_pool", type=int, default=16,
                        help="distinct prompts in the --zipf pool")
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--block_size", type=int, default=16)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--image_fmap_size", type=int, default=8)
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

    import jax

    from dalle_pytorch_tpu.cli.common import enable_compile_cache
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    enable_compile_cache()
    cfg = DALLEConfig(
        dim=args.dim, depth=args.depth, num_text_tokens=256, text_seq_len=16,
        heads=4, dim_head=args.dim // 4, num_image_tokens=256,
        image_fmap_size=args.image_fmap_size,
    )
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(
        params, cfg,
        engine_cfg=EngineConfig(num_slots=args.slots, block_size=args.block_size),
    )
    gen = PoissonLoadGen(args.requests, args.rate, streams=args.streams,
                         seed=args.seed)
    report = gen.run(engine, synthetic_request_maker(
        cfg, seed=args.seed, zipf_s=args.zipf, prompt_pool=args.prompt_pool))
    if args.json:
        print(json.dumps(report))
    else:
        for k, v in report.items():
            print(f"{k:>26}: {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
