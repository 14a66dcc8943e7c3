#!/usr/bin/env python
"""Benchmark: DALL-E training-step throughput + MFU on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"device": {platform, kind, count}}.  TPU only: without a chip it exits
non-zero with a message and prints no result — nothing falls back to the CPU,
and a phase that raises ends the run.

The measured config is the largest headline-shaped model that trains on a
single chip (seq=1280 = 256 text + 32x32 image tokens, the reference's
standard geometry; full+axial+conv attention cycle; bf16 compute; Pallas
flash attention).  MFU is FLOPs-per-step / peak-chip-FLOPs (peak from
dalle_pytorch_tpu/core/chips.py); vs_baseline is MFU / 0.45, the BASELINE.md
target ratio.  ROADMAP S0 replaces run_bench whole with a list of cells."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import optax

MFU_TARGET = 0.45  # BASELINE.md:25 — the flagship 1.3B depth-64 bar

from dalle_pytorch_tpu.training.profiling import (
    chip_peak_flops, dalle_step_flops, matmul_param_count,
)


def _sparse_attention_row() -> dict:
    """Dense vs compacted flash-attention grid, per sparse pattern, at the
    train sequence (1280) and the long-context scenario (4096, 64x64 fmaps).

    The static live-tile counts ARE the speedup model — each live tile costs
    the same MXU work, so step time should scale with the live fraction; both
    grids are timed to validate that."""
    import numpy as np

    from dalle_pytorch_tpu.kernels import sparse_index as si
    from dalle_pytorch_tpu.kernels.flash_attention import (
        flash_attention, resolve_block,
    )
    from dalle_pytorch_tpu.models.transformer import TransformerConfig, _pattern_for
    from dalle_pytorch_tpu.ops.masks import block_live_np

    out = {}
    # 1280 runs the production 256x256 tiles — at the train sequence the
    # pattern bands (257 text cols + a 32-token image row) are wider than a
    # tile, so the ratio is ~1 and the row is a no-regression check; the
    # payoff case is 4096 at 128x128 tiles (at 256 a query block spans 4+1
    # image rows and the axial_row ratio sags to ~3x)
    for n, fmap, blk in ((1280, 32, 256), (4096, 64, 128)):
        bq = resolve_block(n, blk)
        nq = n // bq
        dense_tiles = int(si.block_causal_live_np(nq, nq, bq, bq).sum())
        pcfg = TransformerConfig(dim=256, depth=1, seq_len=n, heads=4,
                                 dim_head=64, image_fmap_size=fmap)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (1, 4, n, 64), jnp.float32)
                   for kk in ks)
        per = {"dense_tiles": dense_tiles, "block": bq}
        for pat in ("axial_row", "axial_col", "conv_like", "sparse"):
            mask = np.asarray(_pattern_for(pcfg, pat), bool)
            tabs = si.build_compacted_tables(
                block_live_np(mask, bq, bq), bq, bq)
            live_fwd, _ = si.live_tile_counts(tabs)
            entry = {"live_tiles": live_fwd,
                     "tile_ratio": round(dense_tiles / max(live_fwd, 1), 2)}
            jm = jnp.asarray(mask)
            for grid in ("dense", "compact"):
                f = jax.jit(lambda q, k, v, g=grid: flash_attention(
                    q, k, v, mask=jm, block_q=bq, block_k=bq, grid=g))
                f(q, k, v).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(10):
                    o = f(q, k, v)
                o.block_until_ready()
                entry[f"{grid}_ms"] = round(
                    (time.perf_counter() - t0) / 10 * 1e3, 3)
            entry["speedup"] = round(
                entry["dense_ms"] / max(entry["compact_ms"], 1e-9), 2)
            per[pat] = entry
        out[f"seq{n}"] = per
    return out


def run_bench() -> dict:
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        # no chip, no number: there is no CPU mode and no fallback — a time
        # from XLA's CPU backend is not a measurement of this system
        raise SystemExit(
            f"bench.py: no TPU found (jax.default_backend() = "
            f"{jax.default_backend()!r}); the bench measures the chip and "
            "has no CPU mode.  Run it on the TPU machine "
            "(`chiprun -- python bench.py`); `--candidate` gates a saved "
            "result without running anything."
        )
    peak_flops = chip_peak_flops()  # raises on a TPU the chip table lacks

    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    # largest headline-shaped config that trains on one chip with good MXU
    # shapes: DALL-E width (dim 2048 — K=2048 matmuls run ~2x the TFLOP/s
    # of K=1024 on v5e), seq 1280, ~610M params + f32 adam.  Microbatch 8
    # (the best single-chip shape) with 8-step gradient accumulation —
    # a real large-scale training configuration (the reference's
    # --ga_steps) that amortizes the Adam update across microbatches.
    cfg = DALLEConfig(
        dim=2048, depth=8, heads=16, dim_head=128,
        num_text_tokens=10000, text_seq_len=256,
        num_image_tokens=8192, image_fmap_size=32,
        attn_types=("full", "axial_row", "axial_col", "conv_like"),
        shift_tokens=True, rotary_emb=True, execution="sequential",
        share_input_output_emb=True,
    )
    batch, grad_accum = 64, 8
    steps, warmup = 4, 2

    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True)

    settings = StepSettings(compute_dtype=jnp.bfloat16, grad_accum=grad_accum)
    init_fn, step_fn = make_train_step(loss_fn, optax.adam(1e-4), settings=settings)
    state = init_fn(params)

    batch_data = {
        "text": jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.text_seq_len), 0, cfg.num_text_tokens),
        "image_codes": jax.random.randint(jax.random.PRNGKey(2), (batch, cfg.image_seq_len), 0, cfg.num_image_tokens),
    }

    n_matmul = matmul_param_count(state.params)

    from dalle_pytorch_tpu.observability import (
        CompileWatcher, SpanRecorder, step_cost_analysis,
    )

    watcher = CompileWatcher().start()

    # timing ends with a device->host value fetch of the last step's loss
    for i in range(warmup):
        state, metrics = step_fn(state, batch_data, jax.random.PRNGKey(i))
    float(metrics["loss"])
    watcher.arm()  # steady state: any compile in the measured loop is news

    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = step_fn(state, batch_data, jax.random.PRNGKey(100 + i))
    final_loss = float(metrics["loss"])  # forces the chained steps to completion
    dt = time.perf_counter() - t0
    # snapshot NOW: the telemetry pass below (and a cost-analysis compile
    # fallback) may fire further compile events that are not loop recompiles
    loop_recompiles = watcher.recompiles

    step_time = dt / steps
    img_tok_per_sec = batch * cfg.image_seq_len / step_time
    # tile granularity: MFU against the FLOPs the kernels actually execute
    # (whole live tiles), not the element-granular algorithmic density —
    # sparse configs otherwise read as having more headroom than they do
    flops = dalle_step_flops(cfg, batch, n_matmul, granularity="tile")
    mfu = flops / step_time / peak_flops

    # span breakdown beside the MFU number: a SEPARATE short synced pass
    # (per-step blocking inside the timed loop would break the chained
    # dispatch the throughput row measures), plus XLA's own FLOPs estimate
    # vs the analytic model the MFU is priced with
    rec = SpanRecorder(None)  # in-memory; summaries only
    tele_steps = []
    for i in range(2):
        rec.start_step(i)
        with rec.span("dispatch"):
            state, metrics = step_fn(state, batch_data, jax.random.PRNGKey(200 + i))
        with rec.span("block"):
            float(metrics["loss"])
        tele_steps.append(rec.end_step())

    # fleet skew row (ISSUE 4): the same FleetAggregator the multi-host CLIs
    # run, fed this process's synced pass — on one process the skew is
    # trivially 1.0, but the gather/reduce/gauge path is the real one, and
    # the row documents the numbers a multi-host bench would report
    from dalle_pytorch_tpu.observability.fleet import FleetAggregator

    fleet_agg = FleetAggregator(process_index=0, process_count=1)
    fleet_rec = None
    for i, s in enumerate(tele_steps):
        fleet_rec = fleet_agg.observe_window(
            i, s.get("spans", {}), s.get("dur_s", 0.0), 1
        ) or fleet_rec
    fleet_row = None
    if fleet_rec is not None:
        fleet_row = {
            "processes": fleet_rec["processes"],
            "step_time_median_s": round(fleet_rec["step_time"]["median_s"], 5),
            "skew_ratio": fleet_rec["skew_ratio"],
            "slowest_process": fleet_rec["slowest_process"],
        }
    ca = step_cost_analysis(step_fn, state, batch_data, jax.random.PRNGKey(201))
    compiled_flops = (ca or {}).get("flops")
    watcher.stop()
    telemetry_row = {
        "dispatch_s": round(
            sum(s["spans"].get("dispatch", 0.0) for s in tele_steps) / len(tele_steps), 5
        ),
        "block_s": round(
            sum(s["spans"].get("block", 0.0) for s in tele_steps) / len(tele_steps), 5
        ),
        "compiles": watcher.compiles,
        "recompiles_in_measured_loop": loop_recompiles,
        "compile_time_s": round(watcher.compile_time_s, 2),
        "flops_compiled_over_analytic": (
            round(compiled_flops / flops, 4) if compiled_flops else None
        ),
    }
    params_million = round(
        sum(x.size for x in jax.tree_util.tree_leaves(state.params)) / 1e6, 1
    )

    # comms ledger + roofline (ISSUE 4): the analytic wire-bytes model for
    # this config on a representative multi-axis mesh (dp4 x tp2), priced
    # without devices — the per-axis bytes the multi-chip run of THIS model
    # would move per step, and whether it would be comms- or compute-bound
    # at the chip's peak/ICI numbers
    from dalle_pytorch_tpu.observability import comms as comms_mod

    comms_mesh = {"dp": 4, "tp": 2}
    comms_ledger = comms_mod.dalle_step_comms(
        comms_mesh, state.params, cfg, batch, settings=settings,
        registry=getattr(step_fn, "registry", None),
    )
    comms_row = {
        "mesh": comms_mesh,
        "per_axis_mb": {r["axis"]: round(r["bytes_per_step"] / 1e6, 3)
                        for r in comms_ledger["per_axis"]},
        "total_mb_per_step": round(comms_ledger["total_bytes_per_step"] / 1e6, 3),
        "roofline": {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in comms_mod.comms_roofline(
                comms_ledger["total_bytes_per_step"], flops,
                n_chips=comms_mesh["dp"] * comms_mesh["tp"],
            ).items()
        },
    }

    # diagnostic-step overhead (ISSUE 2): step time with the in-graph health
    # diagnostics (with_health=True — per-leaf norms, nonfinite masks, the
    # activation-tap probe forward) vs the plain step.  This is the cost of a
    # `--health_every 1` run; at cadence N the amortized tax is 1/N of it,
    # and the plain executable is unchanged (zero overhead when off).
    state, hm = step_fn(state, batch_data, jax.random.PRNGKey(300), with_health=True)
    float(hm["loss"])  # compile + settle the second executable
    t0 = time.perf_counter()
    for i in range(steps):
        state, hm = step_fn(
            state, batch_data, jax.random.PRNGKey(301 + i), with_health=True
        )
    float(hm["loss"])
    health_step_time = (time.perf_counter() - t0) / steps
    health_row = {
        "health_step_time_s": round(health_step_time, 4),
        "plain_step_time_s": round(step_time, 4),
        "overhead_frac": round(health_step_time / step_time - 1.0, 4),
        "tracked_leaves": len(jax.tree_util.tree_leaves(state.params)),
    }

    # async-checkpoint stall (ISSUE 3): what a periodic save costs the step
    # loop — synchronous (gather + serialize + fsync inline) vs the async
    # writer (gather + enqueue only; serialize/fsync on the writer thread).
    # Same payload both ways: this model's full weights + optimizer state.
    import tempfile

    from dalle_pytorch_tpu.training.checkpoint import save_checkpoint, to_host
    from dalle_pytorch_tpu.training.resilience import AsyncCheckpointWriter

    with tempfile.TemporaryDirectory() as ckpt_dir:
        t0 = time.perf_counter()
        ckpt_trees = {"weights": to_host(state.params),
                      "opt_state": to_host(state.opt_state)}
        gather_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        save_checkpoint(f"{ckpt_dir}/sync.npz", ckpt_trees, {"step": 0})
        sync_write_s = time.perf_counter() - t0
        # one 7 GB snapshot and one 7 GB file at a time: the one-chip machine
        # has 40 GiB of host memory and its disk may be backed by it
        del ckpt_trees
        os.remove(f"{ckpt_dir}/sync.npz")
        ckpt_writer = AsyncCheckpointWriter()
        t0 = time.perf_counter()
        ckpt_trees = {"weights": to_host(state.params),
                      "opt_state": to_host(state.opt_state)}
        ckpt_writer.submit(f"{ckpt_dir}/async.npz", ckpt_trees, {"step": 0})
        async_stall_s = time.perf_counter() - t0
        del ckpt_trees
        ckpt_writer.close()
    sync_stall_s = gather_s + sync_write_s
    async_checkpoint_row = {
        "gather_s": round(gather_s, 4),
        "serialize_fsync_s": round(sync_write_s, 4),
        "sync_stall_s": round(sync_stall_s, 4),
        "async_stall_s": round(async_stall_s, 4),
        "stall_reduction": round(1.0 - async_stall_s / max(sync_stall_s, 1e-9), 4),
    }

    # HBM row (ISSUE 5): analytic per-chip ledger vs the compiled
    # executable's own memory_analysis vs the live allocator peak, so
    # BENCH_*.json tracks an HBM trajectory beside step time.  The
    # memory_analysis costs one extra compile of the measured step.
    from dalle_pytorch_tpu.observability import memory as memory_mod
    from dalle_pytorch_tpu.observability.xla import device_memory_stats

    mem_ledger = memory_mod.dalle_step_memory(
        None, state.params, state.opt_state, cfg, batch, settings=settings,
        registry=getattr(step_fn, "registry", None),
    )
    mem_xla = memory_mod.step_memory_analysis(
        step_fn, state, batch_data, jax.random.PRNGKey(400)
    )
    live = device_memory_stats()
    memory_row = {
        "analytic_mb": {r["name"]: round(r["bytes"] / 1e6, 2)
                        for r in mem_ledger["rows"]},
        "analytic_total_mb": round(mem_ledger["total_bytes"] / 1e6, 2),
        "dominant": mem_ledger["dominant"],
        "fits": mem_ledger["fits"],
        "capacity_gb": (round(mem_ledger["capacity_bytes"] / 1e9, 1)
                        if mem_ledger["capacity_bytes"] else None),
        "xla_mb": ({k.replace("_bytes", ""): round(v / 1e6, 2)
                    for k, v in mem_xla.items()} if mem_xla else None),
        "xla_over_analytic": (
            round(mem_xla["total_bytes"] / mem_ledger["total_bytes"], 4)
            if mem_xla and mem_ledger["total_bytes"] else None
        ),
        "donation_ok": (memory_mod.audit_donation(
            mem_xla,
            sum(r["bytes"] for r in mem_ledger["rows"]
                if r["name"] in ("params", "opt_state")),
        )["ok"] if mem_xla else None),
        "live_peak_mb": (round(live["peak_bytes_in_use"] / 1e6, 2)
                         if live and "peak_bytes_in_use" in live else None),
    }

    # sparse-attention row (ISSUE 10): dense vs compacted grid per pattern
    # at seq 1280 and the 4096 long-context scenario
    sparse_attention_row = _sparse_attention_row()

    # generation wall-clock (BASELINE.md row 3): KV-cached sampling, same
    # model; plus the FULL generate-images pipeline (codes -> VAE decode ->
    # CLIP scores), the generate.py-with-rerank path the BASELINE row names
    gen_batch = 8
    from dalle_pytorch_tpu.core.pytree import cast_floating
    from dalle_pytorch_tpu.models import clip as clip_mod
    from dalle_pytorch_tpu.models import vae as vae_mod
    from dalle_pytorch_tpu.models.clip import CLIPConfig
    from dalle_pytorch_tpu.models.sampling import generate_images, sample_image_codes
    from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig

    gen_params = cast_floating(state.params, jnp.bfloat16)  # deployment dtype
    text = jax.random.randint(jax.random.PRNGKey(5), (gen_batch, cfg.text_seq_len), 1, cfg.num_text_tokens)
    codes = sample_image_codes(gen_params, cfg, text, jax.random.PRNGKey(6))
    int(codes[0, 0])  # force
    t0 = time.perf_counter()
    codes = sample_image_codes(gen_params, cfg, text, jax.random.PRNGKey(7))
    int(codes[0, 0])
    gen_s_per_image = (time.perf_counter() - t0) / gen_batch

    # full pipeline: dVAE decode (8192 codes, 32x32 fmap, 128px) + CLIP
    # rerank — random weights; wall-clock depends on architecture only
    vcfg = DiscreteVAEConfig(image_size=128, num_tokens=cfg.num_image_tokens,
                             codebook_dim=256, num_layers=2, hidden_dim=64)
    vparams = cast_floating(vae_mod.init_discrete_vae(jax.random.PRNGKey(8), vcfg), jnp.bfloat16)
    ccfg = CLIPConfig(num_text_tokens=cfg.num_text_tokens, text_seq_len=cfg.text_seq_len,
                      visual_image_size=128, visual_patch_size=16)
    cparams = cast_floating(clip_mod.init_clip(jax.random.PRNGKey(9), ccfg), jnp.bfloat16)

    @jax.jit
    def full_gen(key):
        images, scores = generate_images(
            gen_params, cfg, vparams, vcfg, text, key,
            clip_params=cparams, clip_cfg=ccfg,
        )
        return images, scores

    images, scores = full_gen(jax.random.PRNGKey(10))
    float(scores[0])  # force
    t0 = time.perf_counter()
    images, scores = full_gen(jax.random.PRNGKey(11))
    float(scores[0])
    gen_full_s_per_image = (time.perf_counter() - t0) / gen_batch

    # serving row (ISSUE 8): the continuous-batching engine + paged KV pool
    # under 2-stream Poisson load — p50/p99 time-to-first-token, per-request
    # latency, and images/sec/chip, the SLO numbers the ROADMAP's serving
    # north star is tracked by.  Codes-only (no VAE): the row isolates the
    # engine + paged-decode path the subsystem added.
    from dalle_pytorch_tpu.cli.serve import _import_loadgen
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    PoissonLoadGen, synthetic_request_maker = _import_loadgen()

    sparams = gen_params
    s_engine = GenerationEngine(
        sparams, cfg,
        engine_cfg=EngineConfig(num_slots=2,
                                block_size=64),
    )
    # the Poisson run is TRACED (ISSUE 16): the row doubles as the
    # journey-reconstruction assertion — every span emitted under real
    # 2-stream load must stitch into a journey with zero orphans

    from dalle_pytorch_tpu.observability import telemetry as _tele_mod

    trace_dir = tempfile.mkdtemp(prefix="bench_serving_trace_")
    s_tele = _tele_mod.configure(trace_dir, run_name="serving_bench",
                                 heartbeat_s=None, watch_compiles=False)
    try:
        s_gen = PoissonLoadGen(4, rate=2.0, streams=2,
                               seed=0)
        serving_row = s_gen.run(
            s_engine, synthetic_request_maker(cfg, seed=0),
            max_wall_s=600,
        )
        # terminal records for anything the wall cutoff left in flight —
        # a journey without a terminal would count as orphan spans
        s_engine.close()
    finally:
        s_tele.flush(fleet=False)
        s_tele.close()
    serving_row["paged_pool_mb"] = round(
        s_engine.pool.bytes(2) / 1e6, 2)
    serving_row["slots"] = 2
    serving_row["prefix_redundancy"] = s_engine.prefix_redundancy()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tools"))
    import trace_report as _trace_report

    _tv = _trace_report.validate_journeys(_trace_report.build_journeys(
        _trace_report.load_records([trace_dir])))
    serving_row["trace_orphan_spans"] = _tv["orphan_spans"]
    serving_row["trace_multi_ack_journeys"] = _tv["multi_ack_journeys"]
    serving_row["trace_max_phase_sum_err_s"] = _tv["max_phase_sum_err_s"]
    # the same trace carries the pool flight-recorder events (ISSUE 17):
    # the row asserts the capacity simulator reproduces THIS recorded
    # run exactly (admit/defer decisions, occupancy, high-water) and
    # reports the reservation waste expected-block admission would
    # reclaim
    import pool_report as _pool_report

    _psec = _pool_report.pool_section(
        _trace_report.load_records([trace_dir]))
    serving_row["pool_selfcheck_ok"] = (
        _psec is not None and _psec["validation_ok"])
    if _psec and _psec["pools"]:
        _pfirst = next(iter(_psec["pools"].values()))
        serving_row["reserved_unused_frac"] = (
            _pfirst["reserved_unused_frac"])

    # tracing-overhead row (ISSUE 16): the same engine geometry serving the
    # same synthetic traffic untraced vs traced.  Journey tracing promises
    # timestamps at EXISTING sync points only (PR 11 discipline), so the
    # traced run must cost ~nothing; overhead_frac gates like health_overhead

    _, synthetic_request_maker = _import_loadgen()

    tparams = gen_params
    t_engine = GenerationEngine(
        tparams, cfg,
        engine_cfg=EngineConfig(num_slots=2,
                                block_size=64),
    )
    t_make = synthetic_request_maker(cfg, seed=3)

    def _timed_batch(first_i: int, n: int = 3) -> float:
        t0 = time.perf_counter()
        for i in range(first_i, first_i + n):
            t_engine.submit_when_able(**t_make(i))
        t_engine.run_until_idle()
        return (time.perf_counter() - t0) / n

    _timed_batch(0)  # warm: jit compiles + first-admit work
    untraced = _timed_batch(10)
    ovh_dir = tempfile.mkdtemp(prefix="bench_tracing_ovh_")
    t_tele = _tele_mod.configure(ovh_dir, run_name="tracing_overhead",
                                 heartbeat_s=None, watch_compiles=False)
    try:
        traced = _timed_batch(20)
    finally:
        t_tele.flush(fleet=False)
        t_tele.close()
    t_engine.close()
    tracing_overhead_row = {
        "untraced_s_per_request": round(untraced, 4),
        "traced_s_per_request": round(traced, 4),
        "overhead_frac": round(traced / untraced - 1.0, 4),
    }

    # pool-observability row (ISSUE 17): the KV-pool flight recorder's two
    # promises, measured.  (1) Cost: the same guided-zipf traffic served
    # recorder-off vs recorder-on — overhead_frac gates like
    # tracing_overhead (the recorder is deque appends at existing sync
    # points; it must cost ~nothing).  (2) Value: the recorded trace fed to
    # tools/pool_report.py must self-validate exactly, and its what-if
    # forecast (expected-blocks admission + prefix sharing vs worst-case
    # whole-sequence reservation, same pool bytes) reports how many more
    # requests this pool could admit for the repeated-prompt workload.

    _, synthetic_request_maker = _import_loadgen()

    sys.path.insert(0, str(Path(__file__).resolve().parent / "tools"))

    pparams = gen_params
    p_bs = 64
    p_engine = GenerationEngine(
        pparams, cfg,
        engine_cfg=EngineConfig(num_slots=2, block_size=p_bs,
                                num_blocks=6 * -(-(
                                    cfg.text_seq_len + cfg.image_seq_len)
                                    // p_bs),
                                telemetry_every=4),
    )
    # guided + Zipf-repeated prompts: two lanes per request, and a
    # prompt mix where prefix sharing has something to share
    p_make = synthetic_request_maker(cfg, seed=5, cond_scale=2.0,
                                     zipf_s=1.5, prompt_pool=4)

    pool_dir = tempfile.mkdtemp(prefix="bench_pool_obs_")
    p_tele = _tele_mod.configure(pool_dir, run_name="pool_obs",
                                 heartbeat_s=None, watch_compiles=False)
    try:
        for i in range(6):
            p_engine.submit_when_able(**p_make(i))
        p_engine.run_until_idle()
        # drain the recorder ring: the trace must be COMPLETE from
        # engine birth or replay-validation would be fiction
        p_engine.pool.recorder.flush(p_tele.spans, replica=None)
    finally:
        p_tele.flush(fleet=False)
        p_tele.close()
    _pools = _pool_report.build_pools(
        _pool_report.load_records([pool_dir]))
    _val = _pool_report.validate(_pools)
    _worst = _pool_report.simulate(_pools, policy="worst", sharing=False)
    _best = _pool_report.simulate(_pools, policy="expected", sharing=True)
    _ratio = (
        round(_best["admissible_slots"] / _worst["admissible_slots"], 2)
        if _worst.get("admissible_slots") else None)

    def _pool_timed(first_i: int, n: int = 3) -> float:
        t0 = time.perf_counter()
        for i in range(first_i, first_i + n):
            p_engine.submit_when_able(**p_make(i))
        p_engine.run_until_idle()
        return (time.perf_counter() - t0) / n

    _rec = p_engine.pool.recorder
    p_engine.pool.recorder = None  # recorder-off baseline path
    rec_off = _pool_timed(10)
    p_engine.pool.recorder = _rec
    rec_on = _pool_timed(20)
    p_engine.close()
    pool_observability_row = {
        "recorder_off_s_per_request": round(rec_off, 4),
        "recorder_on_s_per_request": round(rec_on, 4),
        "overhead_frac": round(rec_on / rec_off - 1.0, 4),
        "selfcheck_ok": _val["ok"],
        "worst_case_admissible_slots": _worst.get("admissible_slots"),
        "expected_sharing_admissible_slots": _best.get(
            "admissible_slots"),
        "overcommit_slots_ratio": _ratio,
    }

    # serving fleet row (ISSUE 12): the same Poisson load against 2 engine
    # replicas behind the load-balancing router, plus a kill-one variant
    # (replica 0 dies mid-run via kill_at_iter) proving the fleet serves
    # THROUGH preemption: completions still account for every arrival
    # (drain + requeue), at a degraded-but-bounded throughput/p99 TTFT.
    from dalle_pytorch_tpu.serving.engine import EngineConfig
    from dalle_pytorch_tpu.serving.fleet import FleetConfig, ServingFleet

    PoissonLoadGen, synthetic_request_maker = _import_loadgen()

    flparams = gen_params
    fl_ecfg = EngineConfig(num_slots=2, block_size=64)
    fleet_sv = ServingFleet(
        flparams, cfg,
        fleet_cfg=FleetConfig(replicas=2, engine=fl_ecfg))
    fl_gen = PoissonLoadGen(6, rate=2.0,
                            streams=2, seed=0)
    serving_fleet_row = fl_gen.run(
        fleet_sv, synthetic_request_maker(cfg, seed=0),
        max_wall_s=600,
    )
    serving_fleet_row["replicas"] = 2

    fleet_kill = ServingFleet(
        flparams, cfg,
        fleet_cfg=FleetConfig(replicas=2, engine=fl_ecfg,
                              kill_at_iter=4))
    kill_gen = PoissonLoadGen(6, rate=2.0,
                              streams=2, seed=0)
    kill_row = kill_gen.run(
        fleet_kill, synthetic_request_maker(cfg, seed=0),
        max_wall_s=600,
    )
    serving_fleet_row["kill_one"] = {
        "requests_completed": kill_row["requests_completed"],
        "requests_refused": kill_row["requests_refused"],
        "images_per_sec_per_chip": kill_row["images_per_sec_per_chip"],
        "ttft_p99_s": kill_row["ttft_p99_s"],
    }

    # quantized serving row (ISSUE 13): the SAME Poisson load against an
    # int8-weights + int8-KV engine at DOUBLE the slot count — the capacity
    # the byte savings buy.  p50/p99 TTFT and images/sec/chip sit next to
    # the bf16 `serving` row so the tradeoff (more lanes vs dequant
    # overhead per step) is measured, not asserted.
    from dalle_pytorch_tpu import quantization as quant_mod

    PoissonLoadGen, synthetic_request_maker = _import_loadgen()

    qplain = gen_params
    qparams = quant_mod.quantize_tree(qplain, "int8")
    q_engine = GenerationEngine(
        qparams, cfg,
        engine_cfg=EngineConfig(num_slots=4,  # 2x the bf16 serving row
                                block_size=64,
                                quantize_kv="int8"),
    )
    q_gen = PoissonLoadGen(4, rate=2.0, streams=2, seed=0)
    quantized_serving_row = q_gen.run(
        q_engine, synthetic_request_maker(cfg, seed=0),
        max_wall_s=600,
    )
    quantized_serving_row["paged_pool_mb"] = round(
        q_engine.pool.bytes(2) / 1e6, 2)
    quantized_serving_row["slots"] = 4
    quantized_serving_row["weight_reduction"] = round(
        quant_mod.weight_reduction(qplain, qparams), 4)
    quantized_serving_row["kv_pool_reduction"] = round(
        quant_mod.kv_pool_reduction(cfg.dim_head), 4)
    quantized_serving_row["quantization"] = q_engine.quantization_state()

    # quantized parity row (ISSUE 13): the NUMERICS gate for the row above.
    # Greedy paged decode on the same text, bf16/f32 params vs int8 weights
    # + int8 KV, drift measured relative to the baseline logits' spread.
    # `within_budget` is what `--gate` checks — capacity wins that cost
    # correctness would be regressions, not improvements.

    pplain = gen_params
    pq = quant_mod.quantize_tree(pplain, "int8")
    ptext = jax.random.randint(
        jax.random.PRNGKey(5), (1, cfg.text_seq_len), 1, cfg.num_text_tokens)
    psteps = 64
    base = quant_mod.paged_greedy_logits(pplain, cfg, ptext, steps=psteps)
    quant = quant_mod.paged_greedy_logits(
        pq, cfg, ptext, quantize_kv_mode="int8", steps=psteps)
    parity = quant_mod.greedy_parity_metrics(base, quant)
    quantized_parity_row = {
        **{k: round(float(v), 6) for k, v in parity.items()},
        "steps": psteps,
        "rel_budget": quant_mod.FULL_PARITY_REL_BUDGET,
        "within_budget": bool(
            parity["greedy_logit_drift_rel"]
            <= quant_mod.FULL_PARITY_REL_BUDGET),
    }

    # serving durability row (ISSUE 14): Poisson load with per-request
    # deadlines against a 2-replica fleet where one replica is WEDGED
    # alive-but-stalled from the start (the breaker must open and the
    # hedger must route around it) and one extra request is persistently
    # poisoned (NaN decode logits; quarantined after bounded retries).
    # Completion rate over the organic arrivals, p99 TTFT, and the degrade
    # rungs entered are the survival numbers `--gate` tracks.
    import numpy as _np

    from dalle_pytorch_tpu.observability import metrics as _obs_metrics
    from dalle_pytorch_tpu.serving.degrade import (DegradeConfig,
                                                   DegradeLadder)

    PoissonLoadGen, synthetic_request_maker = _import_loadgen()

    dparams = gen_params
    d_fleet = ServingFleet(
        dparams, cfg,
        fleet_cfg=FleetConfig(
            replicas=2,
            engine=EngineConfig(num_slots=2,
                                block_size=64),
            stall_after_s=0.3, probe_after_s=0.5, hedge_frac=0.25))
    d_ladder = DegradeLadder(DegradeConfig(), text_seq_len=cfg.text_seq_len)
    d_fleet.attach_degrade(d_ladder)
    # counters are process-global: diff around the row
    def _snap():
        return {n: _obs_metrics.counter(n).value
                for n in ("serving/quarantined", "router/breaker_open",
                          "router/hedged", "router/hedge_duplicates")}
    drng = _np.random.RandomState(123)
    # warm BOTH replicas first (each engine owns its jitted closures):
    # a cold compile inside the first poll would outlast the wedge and
    # the breaker would never see a frozen-iteration replica
    for wseed in (996, 997):
        d_fleet.submit(
            drng.randint(1, cfg.num_text_tokens,
                         size=(cfg.text_seq_len,)),
            key=jax.random.PRNGKey(wseed), synthetic=True)
    d_fleet.run_until_idle()
    before = _snap()
    # one persistently-poisoned request riding along with the load
    poison_req = d_fleet.submit(
        drng.randint(1, cfg.num_text_tokens, size=(cfg.text_seq_len,)),
        key=jax.random.PRNGKey(999))
    poison_req.poison_victim = True
    # a deadline-carrying request placed on the soon-to-stall replica
    # (synthetic: it must not pollute the organic SLO numbers), then
    # wedge that replica — busy + not advancing is what trips the
    # breaker, and the stuck request is what the hedger rescues
    stuck_req = d_fleet.submit(
        drng.randint(1, cfg.num_text_tokens, size=(cfg.text_seq_len,)),
        key=jax.random.PRNGKey(998), synthetic=True, deadline_s=1.0)
    victim_eng = next(
        e for e in d_fleet.engines
        if any(r is stuck_req for r in list(e._inflight) + list(e.queue._q)))
    victim_eng.wedge(2.0)
    d_requests = 6
    d_gen = PoissonLoadGen(d_requests, rate=2.0,
                           streams=2, seed=0)
    serving_durability_row = d_gen.run(
        d_fleet, synthetic_request_maker(cfg, seed=0, deadline_s=2.0),
        max_wall_s=600,
    )
    d_fleet.run_until_idle()  # flush the poison retries to quarantine
    delta = {n: _snap()[n] - before[n] for n in before}
    serving_durability_row["completion_rate"] = round(
        serving_durability_row["requests_completed"] / d_requests, 4)
    serving_durability_row["quarantined"] = delta["serving/quarantined"]
    serving_durability_row["breaker_opens"] = delta["router/breaker_open"]
    serving_durability_row["hedged"] = delta["router/hedged"]
    serving_durability_row["hedge_duplicates"] = delta[
        "router/hedge_duplicates"]
    serving_durability_row["degrade_rungs_entered"] = dict(
        d_ladder.rungs_entered)
    serving_durability_row["degrade_max_rung"] = d_ladder.max_rung_seen

    # speculative decoding row (ISSUE 15): the fused sampler with and
    # without the shallow-prefix drafter on a small dedicated geometry
    # (kept small: the row checks parity and acceptance, not width).
    # Greedy-exact by construction, so `parity` is a hard equality, and the honest numbers are accepted tokens per verify round
    # (must beat 1.0 for a round to out-produce one sequential step) and
    # end-to-end seconds/image against the k=0 baseline.

    from dalle_pytorch_tpu.models import dalle as _sdalle
    from dalle_pytorch_tpu.models import speculative as _sspec
    from dalle_pytorch_tpu.models.dalle import DALLEConfig as _SDCfg
    from dalle_pytorch_tpu.models.sampling import (_prefill_phase,
                                                   sample_image_codes)

    s_cfg = _SDCfg(dim=128, depth=2, heads=4, dim_head=32,
                   num_text_tokens=1000, text_seq_len=32,
                   num_image_tokens=512, image_fmap_size=8)
    s_params = _sdalle.init_dalle(jax.random.PRNGKey(21), s_cfg)
    s_text = jax.random.randint(jax.random.PRNGKey(22),
                                (2, s_cfg.text_seq_len), 1,
                                s_cfg.num_text_tokens)
    s_key = jax.random.PRNGKey(23)
    spec_k, spec_d = 4, s_cfg.depth - 1  # deep drafter: acceptance lever

    base = _np.asarray(sample_image_codes(s_params, s_cfg, s_text, s_key))
    t0 = time.perf_counter()
    _np.asarray(sample_image_codes(s_params, s_cfg, s_text, s_key))
    base_s = (time.perf_counter() - t0) / s_text.shape[0]

    @jax.jit
    def spec_sample(p, t, k):
        cache, last = _prefill_phase(p, s_cfg, t, None, 0, 1.0)
        return _sspec.fused_spec_decode(
            p, s_cfg, cache, last, k, 0.5, 1.0, 1.0, None, 0,
            spec_k, spec_d, return_stats=True)

    s_codes, s_stats = spec_sample(s_params, s_text, s_key)
    s_codes = _np.asarray(s_codes)  # warm + parity pull
    t0 = time.perf_counter()
    s_codes2, s_stats = spec_sample(s_params, s_text, s_key)
    _np.asarray(s_codes2)
    spec_s = (time.perf_counter() - t0) / s_text.shape[0]
    rounds = int(s_stats["spec_rounds"])
    speculative_row = {
        "parity": bool(_np.array_equal(base, s_codes)),
        "spec_k": spec_k,
        "draft_layers": spec_d,
        "rounds": rounds,
        # first code comes from prefill; every later token costs a round
        "accepted_tokens_per_step": round(
            (s_cfg.image_seq_len - 1) / max(rounds, 1), 3),
        "seconds_per_image": round(spec_s, 4),
        "baseline_seconds_per_image": round(base_s, 4),
        "speedup": round(base_s / spec_s, 3) if spec_s > 0 else None,
    }

    # the dim-2048/depth-8 single-chip row is the headline.  The flagship
    # 1.3B / 1.7B / numerics rows ran tools/flagship_sweep.py and
    # tools/numerics_smoke.py as CHILD processes after this process had
    # opened the chip — with one process per chip they cannot open it, so
    # the rows are gone (PR 21); run those tools on their own.
    proxy_row = {
        "mfu": round(mfu, 4),
        "img_tok_per_sec": round(img_tok_per_sec, 1),
        "step_time_s": round(step_time, 4),
        "params_million": params_million,
        "batch": batch,
        "loss": final_loss,
    }
    dev = jax.devices()[0]
    return {
        "metric": "img-tokens/sec/chip (DALL-E train step, dim 2048 depth 8, seq=1280)",
        "value": round(img_tok_per_sec, 1),
        "unit": "img-tokens/s/chip",
        "vs_baseline": round(mfu / MFU_TARGET, 4),
        "proxy_dim2048_depth8": proxy_row,
        "telemetry": telemetry_row,
        "fleet": fleet_row,
        "comms": comms_row,
        "health_overhead": health_row,
        "async_checkpoint": async_checkpoint_row,
        "memory": memory_row,
        "serving": serving_row,
        "tracing_overhead": tracing_overhead_row,
        "pool_observability": pool_observability_row,
        "serving_fleet": serving_fleet_row,
        "quantized_serving": quantized_serving_row,
        "quantized_parity": quantized_parity_row,
        "serving_durability": serving_durability_row,
        "speculative": speculative_row,
        "sparse_attention": sparse_attention_row,
        "gen_seconds_per_image": round(gen_s_per_image, 3),
        "gen_full_pipeline_seconds_per_image": round(gen_full_s_per_image, 3),
        "backend": jax.default_backend(),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }


# ---------------------------------------------------------------------------
# regression gate (ROADMAP item 5): compare a bench result against the
# persisted best-known numbers and fail loudly on regression.
#
#   python bench.py --gate --update_baseline        # run, gate, persist bests
#   python bench.py --gate --candidate out.json     # gate a saved result only
#
# Per-metric relative tolerances are deliberately loose: these rows time real
# work on shared machines, and the gate's job is catching the 2x cliffs a
# bad merge causes, not 10% scheduler noise.  Only metrics present (numeric,
# non-null) in BOTH the candidate and the same-backend baseline are compared
# — a row missing from either side is skipped.

GATE_SPECS = {
    # dotted path in the bench JSON -> (direction, relative tolerance)
    "proxy_dim2048_depth8.img_tok_per_sec": ("higher", 0.5),
    "proxy_dim2048_depth8.mfu": ("higher", 0.5),
    "serving.ttft_p99_s": ("lower", 0.5),
    "serving.latency_p99_s": ("lower", 0.5),
    "serving.queue_wait_p99_s": ("lower", 1.0),
    "serving.images_per_sec_per_chip": ("higher", 0.5),
    "serving_fleet.ttft_p99_s": ("lower", 0.5),
    "serving_fleet.images_per_sec_per_chip": ("higher", 0.5),
    # the preempted variant runs degraded by design: gate it loosely, just
    # enough to catch serve-through-preemption falling off a cliff
    "serving_fleet.kill_one.ttft_p99_s": ("lower", 1.0),
    "serving_fleet.kill_one.images_per_sec_per_chip": ("higher", 0.75),
    # quantized serving runs 2x the slots of the bf16 row: throughput and
    # tail latency gate against their own baseline, same tolerances as the
    # bf16 serving row
    "quantized_serving.ttft_p99_s": ("lower", 0.5),
    "quantized_serving.images_per_sec_per_chip": ("higher", 0.5),
    # the numerics gate: greedy logit drift vs bf16 must not grow (tol 1.0
    # absorbs seed-level jitter; the hard budget is asserted in the row
    # itself via within_budget), and greedy token agreement must hold
    "quantized_parity.greedy_logit_drift_rel": ("lower", 1.0),
    "quantized_parity.token_match_frac": ("higher", 0.05),
    # durability row runs with one wedged replica + one poisoned request:
    # completion over the ORGANIC arrivals must stay at/near 1.0 and the
    # hedged/degraded p99 TTFT bounded — survival is the gated outcome
    "serving_durability.completion_rate": ("higher", 0.05),
    "serving_durability.ttft_p99_s": ("lower", 1.0),
    # speculative decoding: accepted tokens per verify round must stay above
    # 1.0 (a round that commits one token is pure draft overhead) and the
    # end-to-end seconds/image must not fall off a cliff vs its own baseline
    "speculative.accepted_tokens_per_step": ("higher", 0.5),
    "speculative.seconds_per_image": ("lower", 0.5),
    "health_overhead.overhead_frac": ("lower", 1.0),
    # journey tracing emits spans only at existing sync points, so serving
    # the same traffic traced must not cost more than noise — same loose
    # doubling tolerance as the health-overhead gate
    "tracing_overhead.overhead_frac": ("lower", 1.0),
    # the KV-pool flight recorder is deque appends at existing sync points —
    # recorder-on serving must cost no more than noise vs recorder-off
    "pool_observability.overhead_frac": ("lower", 1.0),
    "gen_seconds_per_image": ("lower", 0.5),
    "gen_full_pipeline_seconds_per_image": ("lower", 0.5),
}


def _lookup(result: dict, dotted: str):
    """Numeric value at a dotted path, or None (missing / null / non-dict)."""
    cur = result
    for part in dotted.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    if isinstance(cur, bool) or not isinstance(cur, (int, float)):
        return None
    return float(cur)


def gate_compare(candidate: dict, baseline_metrics: dict,
                 specs=GATE_SPECS) -> dict:
    """Compare one bench result against a flat {dotted_path: value} baseline.

    Returns {"checked", "regressions", "improvements"}; a metric regresses
    when it is worse than baseline by more than its relative tolerance."""
    checked, regressions, improvements = [], [], []
    for path, (direction, tol) in specs.items():
        c = _lookup(candidate, path)
        b = baseline_metrics.get(path)
        if c is None or b is None or b <= 0:
            continue
        ratio = c / b
        rec = {"metric": path, "candidate": c, "baseline": b,
               "ratio": round(ratio, 4), "direction": direction,
               "rel_tol": tol}
        checked.append(rec)
        if (ratio < 1.0 - tol) if direction == "higher" else (ratio > 1.0 + tol):
            regressions.append(rec)
        elif (ratio > 1.0) if direction == "higher" else (ratio < 1.0):
            improvements.append(rec)
    return {"checked": checked, "regressions": regressions,
            "improvements": improvements}


def _best(direction: str, a: float, b: float) -> float:
    return max(a, b) if direction == "higher" else min(a, b)


def load_result(path: str) -> dict:
    """Parse a saved bench output: last non-empty line is the JSON record
    (earlier lines may be the serving engine's ledger prints)."""
    lines = [ln for ln in Path(path).read_text().splitlines() if ln.strip()]
    if not lines:
        raise ValueError(f"{path}: empty result file")
    return json.loads(lines[-1])


def run_gate(result: dict, baseline_path: str, gate: bool,
             update: bool) -> int:
    """Gate `result` against the baseline file; optionally persist bests.

    The baseline file is keyed by the result's backend ({"tpu": {...}, ...})
    so a result from one platform never gates — or clobbers — another's.  With
    `update`, improvements (and newly-seen metrics) merge in best-of style;
    a regression is NEVER written back.  Returns the process exit code."""
    backend = result.get("backend", "unknown")
    baseline_all = {}
    p = Path(baseline_path)
    if p.exists():
        baseline_all = json.loads(p.read_text())
    entry = baseline_all.get(backend) or {}
    baseline_metrics = entry.get("metrics") or {}

    cmp = gate_compare(result, baseline_metrics)
    # the parity budget is ABSOLUTE, not relative-to-baseline: a quantized
    # run whose greedy logit drift blew its declared budget fails the gate
    # even on a first run with no baseline yet
    parity = result.get("quantized_parity")
    if isinstance(parity, dict) and parity.get("within_budget") is False:
        cmp["regressions"].append({
            "metric": "quantized_parity.within_budget",
            "candidate": parity.get("greedy_logit_drift_rel"),
            "baseline": parity.get("rel_budget"),
            "ratio": None, "direction": "lower",
            "rel_tol": 0.0})
    for rec in cmp["checked"]:
        tag = ("REGRESSION" if rec in cmp["regressions"]
               else "improved" if rec in cmp["improvements"] else "ok")
        print(f"[gate] {rec['metric']}: {rec['candidate']:.6g} vs baseline "
              f"{rec['baseline']:.6g} (ratio {rec['ratio']}, "
              f"{rec['direction']}-is-better, tol {rec['rel_tol']}) {tag}",
              file=sys.stderr)
    if not baseline_metrics:
        print(f"[gate] no {backend} baseline at {baseline_path} — "
              "nothing to compare" + (" (creating one)" if update else
                                      "; run with --update_baseline"),
              file=sys.stderr)

    if cmp["regressions"]:
        from dalle_pytorch_tpu.observability import telemetry as _telemetry

        tele = _telemetry.active()
        for rec in cmp["regressions"]:
            if tele is not None:
                tele.alarm("bench_regression", **rec)
        print(f"[gate] FAIL: {len(cmp['regressions'])} metric(s) regressed "
              f"past tolerance", file=sys.stderr)

    if update and not cmp["regressions"]:
        merged = dict(baseline_metrics)
        for path, (direction, _tol) in GATE_SPECS.items():
            c = _lookup(result, path)
            if c is None:
                continue
            prev = merged.get(path)
            merged[path] = c if prev is None else _best(direction, prev, c)
        baseline_all[backend] = {"metrics": merged,
                                 "metric_count": len(merged),
                                 "source_metric": result.get("metric")}
        tmp = str(p) + ".tmp"
        Path(tmp).write_text(json.dumps(baseline_all, indent=2, sort_keys=True) + "\n")
        os.replace(tmp, str(p))
        print(f"[gate] baseline updated: {len(merged)} {backend} metric(s) "
              f"-> {baseline_path}", file=sys.stderr)

    if gate and cmp["regressions"]:
        return 1
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="DALL-E bench: throughput/MFU/serving rows + regression gate")
    parser.add_argument("--baseline",
                        default=str(Path(__file__).resolve().parent
                                    / "BENCH_BASELINE.json"),
                        help="best-known-numbers file (JSON, keyed by backend)")
    parser.add_argument("--gate", action="store_true",
                        help="exit nonzero if any gated metric regresses past "
                             "its tolerance vs the baseline")
    parser.add_argument("--update_baseline", action="store_true",
                        help="merge this run's improvements into the baseline "
                             "(best-of per metric; never writes on regression)")
    parser.add_argument("--candidate", default=None, metavar="PATH",
                        help="gate a previously-saved bench JSON instead of "
                             "running the bench")
    parser.add_argument("--out", default=None, metavar="PATH",
                        help="also write the result JSON to PATH")
    args = parser.parse_args(argv)

    if args.candidate:
        out = load_result(args.candidate)
    else:
        out = run_bench()
        print(json.dumps(out))
    if args.out:
        Path(args.out).write_text(json.dumps(out) + "\n")
    if args.gate or args.update_baseline:
        return run_gate(out, args.baseline, gate=args.gate,
                        update=args.update_baseline)
    return 0


if __name__ == "__main__":
    sys.exit(main())
