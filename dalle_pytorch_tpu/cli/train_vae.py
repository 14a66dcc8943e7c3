"""Discrete VAE training CLI — parity with /root/reference/train_vae.py
(flags, temperature annealing every 100 steps, checkpointing a plain file
with {hparams, weights}, codebook-usage logging), running as a jitted TPU
train step with the temperature as a traced scalar (no recompiles while
annealing)."""
from __future__ import annotations

import argparse
import functools
import math
import time

import jax
import jax.numpy as jnp
import optax

from dalle_pytorch_tpu.data.loader import ImageDataset, iterate_image_batches, prefetch_to_device
from dalle_pytorch_tpu.models import vae as vae_mod
from dalle_pytorch_tpu.observability import health as health_pure
from dalle_pytorch_tpu.observability import health_host as health_mod
from dalle_pytorch_tpu.observability import memory as memory_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig
from dalle_pytorch_tpu.parallel import backend as backend_mod
from dalle_pytorch_tpu.training import resilience
from dalle_pytorch_tpu.training.checkpoint import save_checkpoint, to_host
from dalle_pytorch_tpu.training.logging import MetricLogger
from dalle_pytorch_tpu.version import __version__


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="Train the discrete VAE image tokenizer")
    parser.add_argument("--image_folder", type=str, required=True)
    parser.add_argument("--image_size", type=int, default=128)
    parser.add_argument("--num_tokens", type=int, default=8192)
    parser.add_argument("--num_layers", type=int, default=3)
    parser.add_argument("--num_resnet_blocks", type=int, default=2)
    parser.add_argument("--smooth_l1_loss", action="store_true")
    parser.add_argument("--emb_dim", type=int, default=512)
    parser.add_argument("--hidden_dim", type=int, default=256)
    parser.add_argument("--kl_loss_weight", type=float, default=0.0)
    parser.add_argument("--transparent", action="store_true")
    parser.add_argument("--straight_through", action="store_true")
    parser.add_argument("--reinmax", action="store_true")
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--epochs", type=int, default=20)
    parser.add_argument("--learning_rate", type=float, default=1e-3)
    parser.add_argument("--lr_decay_rate", type=float, default=0.98)
    parser.add_argument("--starting_temp", type=float, default=1.0)
    parser.add_argument("--temp_min", type=float, default=0.5)
    parser.add_argument("--anneal_rate", type=float, default=1e-6)
    parser.add_argument("--num_images_save", type=int, default=4)
    parser.add_argument("--vae_output_file_name", type=str, default="vae")
    parser.add_argument("--save_every_n_steps", type=int, default=1000)
    parser.add_argument("--num_workers", type=int, default=4,
                        help="decode/crop worker threads (0 = load in the training loop)")
    parser.add_argument("--prefetch_batches", type=int, default=2,
                        help="device-side prefetch depth (0 disables async transfer)")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--wandb", action="store_true", help="log to Weights & Biases")
    parser.add_argument("--wandb_name", type=str, default="dalle_train_vae")
    parser.add_argument("--telemetry", type=str, default=None, metavar="DIR",
                        help="telemetry output directory (spans JSONL, hang "
                             "dumps).  Defaults to <output>.telemetry; "
                             "'off' disables telemetry entirely")
    parser.add_argument("--telemetry_heartbeat_s", type=float, default=900.0,
                        help="hang-monitor deadline in seconds (0 disables)")
    parser.add_argument("--telemetry_sync", type=int, default=1,
                        help="1 (default): block on each step's result so "
                             "per-step time splits into data_wait / dispatch "
                             "/ block; 0: never block")
    parser.add_argument("--fleet", type=int, default=1,
                        help="1 (default): cross-host fleet aggregation at "
                             "the log cadence (skew gauges, slowest-host id, "
                             "straggler alarm); 0 disables")
    parser.add_argument("--profile_on_alarm", type=int, default=3, metavar="N",
                        help="capture a jax.profiler trace of the next N "
                             "steps whenever an alarm fires (rate-limited); "
                             "0 disables.  SIGUSR2 requests one manually")
    parser.add_argument("--profile_steps", type=str, default=None,
                        metavar="A:B",
                        help="manually capture a profiler trace of steps "
                             "[A, B) into <telemetry>/traces")
    parser.add_argument("--fleet_inject_skew", type=float, default=0.0,
                        metavar="SECONDS",
                        help="test hook: sleep this long inside every step "
                             "on THIS process (deliberate straggler)")
    parser.add_argument("--hbm_headroom_frac", type=float, default=0.9,
                        metavar="FRAC",
                        help="live-HBM headroom alarm threshold (fraction of "
                             "per-device capacity; 0 disables).  An OOM at "
                             "compile or step time writes oom_report_*.txt "
                             "and exits code 77")
    parser.add_argument("--health_every", type=int, default=0, metavar="N",
                        help="run the in-graph health diagnostic step every N "
                             "steps (0 disables): per-layer grad/param/update "
                             "norms, NaN/Inf localization, codebook usage/"
                             "perplexity, gumbel-temperature tracking, and "
                             "codebook-collapse alarms")
    parser.add_argument("--resume", type=str, default=None, metavar="auto|PATH",
                        help="'auto': if <vae_output_file_name>.pt exists and "
                             "validates, restore its weights (and hparams) "
                             "and continue — the flag a supervisor restarts "
                             "with after a preemption (exit code 75); a path "
                             "resumes from that checkpoint.  The optimizer "
                             "state starts fresh (the VAE checkpoint stores "
                             "weights only)")
    parser.add_argument("--async_checkpoint", type=int, default=1,
                        help="1 (default): serialize+fsync checkpoints on a "
                             "background writer thread; 0: synchronous saves")
    parser.add_argument("--inject_fault", type=str, default=None,
                        metavar="KIND@STEP",
                        help="fault-injection harness (tools/chaos.py); "
                             "testing only")
    return backend_mod.wrap_arg_parser(parser)


def save_model(path: str, params, cfg: DiscreteVAEConfig, health_state=None,
               fleet_state=None, memory_state=None, topology=None,
               writer=None):
    """Gather + write the VAE checkpoint.  With `writer` (an
    AsyncCheckpointWriter) only the host gather runs here; serialization +
    fsync + rename happen on the writer thread.  `topology`
    (parallel/registry.topology_meta) records the device count + registry
    fingerprint the run trained under — the VAE step is replicated (no
    mesh), so a changed topology restores transparently, but the record
    keeps the check uniform across both CLIs."""
    trees = {"weights": to_host(params)}
    meta = {"hparams": cfg.to_dict(), "version": __version__,
            "health_state": health_state, "fleet_state": fleet_state,
            "memory_state": memory_state, "topology": topology}
    if writer is not None:
        writer.submit(path, trees, meta)
        return
    save_checkpoint(path, trees, meta)


def main(argv=None):
    args = build_parser().parse_args(argv)
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()

    be = backend_mod.set_backend_from_args(args)
    be.initialize()
    is_root = be.is_root_worker()

    cfg = DiscreteVAEConfig(
        image_size=args.image_size,
        num_tokens=args.num_tokens,
        codebook_dim=args.emb_dim,
        num_layers=args.num_layers,
        num_resnet_blocks=args.num_resnet_blocks,
        hidden_dim=args.hidden_dim,
        channels=4 if args.transparent else 3,
        smooth_l1_loss=args.smooth_l1_loss,
        temperature=args.starting_temp,
        straight_through=args.straight_through,
        reinmax=args.reinmax,
        kl_div_loss_weight=args.kl_loss_weight,
    )

    # --resume: restore weights + hparams from a previous run's checkpoint
    # (the supervisor-restart path after an exit-75 preemption).  'auto'
    # quietly starts fresh when nothing resumable exists; a bad file fails
    # with validate_checkpoint's distinct error.  Optimizer state starts
    # fresh — the VAE checkpoint stores weights only.
    # topology identity (device count + partitioning-registry fingerprint):
    # stamped into every checkpoint; the VAE state is replicated so a
    # changed device count restores transparently — the check below is
    # informational parity with train_dalle's elastic resume
    from dalle_pytorch_tpu.parallel import registry as registry_mod

    live_topology = registry_mod.topology_meta(
        {}, device_count=jax.device_count())

    resume_params = None
    resume_meta = None
    if args.resume is not None:
        rpath = (f"{args.vae_output_file_name}.pt" if args.resume == "auto"
                 else args.resume)
        try:
            meta = resilience.validate_checkpoint(rpath)
            try:
                resilience.check_topology(meta, live_topology, path=rpath)
            except resilience.ReshardRequired as rr:
                if is_root:
                    print(f"[resilience] {rr}")
                    print("[resilience] VAE weights are replicated — "
                          "restoring onto the live devices")
        except resilience.CheckpointInvalidError as e:
            if args.resume != "auto":
                raise
            meta = None
            if is_root:
                print(f"[resilience] --resume auto: {e}; starting fresh")
        if meta is not None:
            from dalle_pytorch_tpu.training.checkpoint import load_checkpoint

            trees, meta = load_checkpoint(rpath)
            cfg = DiscreteVAEConfig(**meta["hparams"])
            resume_meta = meta
            resume_params = jax.tree_util.tree_map(jnp.asarray, trees["weights"])
            if is_root:
                print(f"[resilience] resumed VAE weights from {rpath} "
                      "(hparams taken from the checkpoint; fresh optimizer)")

    dataset = ImageDataset(args.image_folder, cfg.image_size, transparent=args.transparent)
    assert len(dataset) > 0, f"no images found in {args.image_folder}"
    be.check_batch_size(args.batch_size)

    params = (resume_params if resume_params is not None
              else vae_mod.init_discrete_vae(jax.random.PRNGKey(args.seed), cfg))
    # adam with the lr applied as a traced scalar inside the step, so the
    # per-epoch ExponentialLR decay (reference train_vae.py:157-158) never
    # triggers a recompile
    opt = optax.chain(optax.scale_by_adam(), optax.scale(-1.0))
    opt_state = opt.init(params)
    lr = args.learning_rate

    logger = MetricLogger(
        run_name=args.vae_output_file_name, use_wandb=args.wandb,
        wandb_kwargs={"name": args.wandb_name}, config=cfg.to_dict(), is_root=is_root,
    )

    tele = None
    capture = None
    fleet_agg = None
    if args.telemetry != "off":
        from pathlib import Path as _Path

        tele_dir = args.telemetry or f"{args.vae_output_file_name}.telemetry"
        tele = telemetry.configure(
            dir=tele_dir,
            run_name=_Path(args.vae_output_file_name).name,
            heartbeat_s=args.telemetry_heartbeat_s or None,
            process_index=be.get_rank(),
        )
        if args.fleet:
            from dalle_pytorch_tpu.observability.fleet import FleetAggregator

            fleet_agg = tele.attach_fleet(FleetAggregator(
                process_index=be.get_rank(), process_count=be.get_world_size(),
            ))
            fleet_agg.load_state_dict((resume_meta or {}).get("fleet_state"))
        from dalle_pytorch_tpu.observability import capture as capture_mod

        manual_window = (capture_mod.parse_profile_steps(args.profile_steps)
                         if args.profile_steps else None)
        if args.profile_on_alarm or manual_window is not None:
            capture = capture_mod.TraceTrigger(
                dir=str(_Path(tele_dir) / "traces"),
                window_steps=args.profile_on_alarm or 1,
                manual_window=manual_window,
                recorder=tele.spans,
                process_index=be.get_rank(),
            ).install_sigusr2()
            if args.profile_on_alarm:
                tele.add_alarm_listener(capture.on_alarm)

    # memory observability: the VAE has no priced activation geometry (conv
    # stacks), so the ledger is the tree-based LOWER bound — still enough to
    # name the dominant row in an OOM report — plus the live headroom alarm
    hbm_monitor = None
    mem_ledger = memory_mod.generic_memory_ledger(params, opt_state)
    if tele is not None:
        memory_mod.publish_gauges(mem_ledger, obs_metrics.REGISTRY)
        tele.spans.write_event("mem_ledger", **mem_ledger)
        if args.hbm_headroom_frac:
            hbm_monitor = tele.attach_memory(memory_mod.HbmMonitor(
                headroom_frac=args.hbm_headroom_frac,
            ))
            hbm_monitor.load_state_dict((resume_meta or {}).get("memory_state"))

    def oom_bail(e, phase):
        from dalle_pytorch_tpu.observability.xla import record_memory_gauges

        report_dir = (args.telemetry if args.telemetry not in (None, "off")
                      else f"{args.vae_output_file_name}.telemetry")
        try:
            live = record_memory_gauges()
        except Exception:
            live = None
        path = memory_mod.write_oom_report(
            report_dir, error=e, phase=phase, ledger=mem_ledger,
            live_stats=live,
            context={"global_step": global_step, "batch_size": args.batch_size,
                     "image_size": args.image_size},
            process_index=be.get_rank(),
        )
        print(f"[memory] OUT OF MEMORY during {phase}: forensic report -> "
              f"{path or '<unwritable>'}; exiting with code "
              f"{resilience.EXIT_OOM}", flush=True)
        raise SystemExit(resilience.EXIT_OOM)

    @functools.partial(jax.jit, static_argnames=("with_health",))
    def train_step(params, opt_state, images, key, temp, lr, with_health=False):
        def loss_fn(p):
            return vae_mod.forward(p, cfg, images, key=key, return_loss=True, temp=temp)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state)
        updates = jax.tree_util.tree_map(lambda u: u * lr, updates)
        new_params = optax.apply_updates(params, updates)
        health = None
        if with_health:
            # in-graph diagnostics (health-step executable only): per-leaf
            # numerics + the dVAE-specific codebook health — usage below the
            # monitor's floor is the gumbel-softmax collapse alarm
            with jax.named_scope("health"):
                health = health_pure.tree_health(params, grads, new_params)
                health["loss_nonfinite"] = (~jnp.isfinite(loss)).astype(jnp.int32)
                logits = vae_mod.encode_logits(params, cfg, images)
                health.update(
                    vae_mod.codebook_health_from_logits(logits, cfg.num_tokens)
                )
                health["gumbel_temp"] = jnp.asarray(temp, jnp.float32)
        return new_params, opt_state, loss, health

    @jax.jit
    def codebook_indices(params, images):
        return vae_mod.get_codebook_indices(params, cfg, images)

    @jax.jit
    def recon_pair(params, images, key, temp):
        """(soft recon via the gumbel path, hard recon via argmax codes) —
        the two grids the reference logs (train_vae.py:252-266)."""
        soft = vae_mod.forward(params, cfg, images, key=key, temp=temp)
        hard = vae_mod.decode_indices(
            params, cfg, vae_mod.get_codebook_indices(params, cfg, images)
        )
        return soft, hard

    denorm = lambda x: vae_mod.denormalize_images(cfg, x)  # noqa: E731

    health_monitor = None
    health_paths = None
    if args.health_every:
        health_paths = health_mod.leaf_paths(params)
        health_monitor = health_mod.DivergenceMonitor(
            on_alarm=health_mod.make_alarm_writer(tele, registry=obs_metrics.REGISTRY)
        )
        if is_root:
            print(f"[health] diagnostics every {args.health_every} step(s); "
                  "codebook usage/perplexity + per-layer numerics")

    def _health_state():
        return health_monitor.state_dict() if health_monitor is not None else None

    def _fleet_state():
        return fleet_agg.state_dict() if fleet_agg is not None else None

    def _memory_state():
        return hbm_monitor.state_dict() if hbm_monitor is not None else None

    out_file = f"{args.vae_output_file_name}.pt"
    # async checkpoint writer + preemption-safe shutdown (training/resilience)
    writer = resilience.AsyncCheckpointWriter() if args.async_checkpoint else None
    shutdown = resilience.ShutdownHandler().install()
    injector = None
    if args.inject_fault is not None:
        injector = resilience.FaultInjector(
            resilience.parse_fault(args.inject_fault)
        ).install()

    # fail fast on unwritable output before burning compute (flushed through
    # the async writer so the failure still lands before compilation)
    save_model(out_file, params, cfg, topology=live_topology, writer=writer)
    if writer is not None:
        writer.flush()

    def exit_preempted():
        # counted here, not in the signal handler (registry locks are not
        # signal-safe)
        obs_metrics.counter("shutdown_requests").inc()
        if is_root:
            save_model(out_file, params, cfg, health_state=_health_state(),
                       fleet_state=_fleet_state(),
                       memory_state=_memory_state(),
                       topology=live_topology, writer=writer)
        if writer is not None:
            writer.flush()
        if is_root:
            print(f"[resilience] preemption checkpoint written; exiting with "
                  f"code {resilience.EXIT_PREEMPTED}", flush=True)
        if tele is not None:
            # fleet=False: a preempting process is not step-synchronized
            # with its peers — it must not block in the fleet gather
            tele.flush(logger, step=global_step, fleet=False)
            tele.close()
        logger.finish()
        # the SystemExit unwinds through the training loop's try/finally,
        # which uninstalls the handlers and closes the writer
        raise SystemExit(resilience.EXIT_PREEMPTED)

    temp = args.starting_temp
    global_step = 0
    key = jax.random.PRNGKey(args.seed + 1)
    compiled_variants = set()
    import contextlib as _ctx
    try:
        for epoch in range(args.epochs):
            t0 = time.time()
            batches = iterate_image_batches(
                dataset, args.batch_size, seed=args.seed + epoch,
                process_index=be.get_rank(), process_count=be.get_world_size(),
                num_workers=args.num_workers,
            )
            if args.prefetch_batches > 0:
                batches = prefetch_to_device(batches, size=args.prefetch_batches)
            batch_it = iter(batches)
            while True:
                if injector is not None:
                    injector.at_step(global_step)
                if tele is not None:
                    tele.begin_step(global_step)
                if capture is not None:
                    capture.on_step_start(global_step)
                with telemetry.span("data_wait"):
                    images = next(batch_it, None)
                if images is None:
                    if tele is not None:
                        tele.abort_step()
                    break
                key, sk = jax.random.split(key)
                health_step = bool(args.health_every) and (
                    global_step % args.health_every == 0
                )
                # first post-arm dispatch of a new executable variant (plain
                # vs diagnostic) legitimately compiles — shield it from the
                # steady-state recompile alarm
                new_variant = health_step not in compiled_variants
                compiled_variants.add(health_step)
                suspend = (
                    tele.compile_watcher.suspended()
                    if (new_variant and tele is not None
                        and tele.compile_watcher is not None
                        and tele.compile_watcher.armed)
                    else _ctx.nullcontext()
                )
                with telemetry.span("dispatch"), suspend:
                    params, opt_state, loss, health = train_step(
                        params, opt_state, jnp.asarray(images), sk, jnp.asarray(temp), jnp.asarray(lr),
                        with_health=health_step,
                    )
                if tele is not None and args.telemetry_sync:
                    with telemetry.span("block"):
                        jax.block_until_ready(loss)
                obs_metrics.counter("train_steps").inc()
                if health_step:
                    with telemetry.span("health_publish"):
                        health_mod.publish_and_observe(
                            health, health_paths, health_monitor, global_step,
                            tele=tele, registry=obs_metrics.REGISTRY,
                            echo=print if is_root else None,
                        )

                if global_step % 100 == 0:
                    # temperature annealing (reference train_vae.py:276-278)
                    temp = max(temp * math.exp(-args.anneal_rate * global_step), args.temp_min)
                    idx = codebook_indices(params, jnp.asarray(images))
                    used = int(jnp.sum(jnp.bincount(idx.reshape(-1), length=cfg.num_tokens) > 0))
                    logger.log(
                        {"loss": float(loss), "temperature": temp, "lr": lr,
                         "codebook_used": used, "epoch": epoch},
                        step=global_step,
                    )
                    if tele is not None:
                        tele.flush(logger, step=global_step)
                    if is_root:
                        # recon grids + hard recons + codebook histogram
                        # (reference train_vae.py:252-271)
                        k = min(args.num_images_save, images.shape[0])
                        sample = jnp.asarray(images[:k])
                        soft, hard = recon_pair(params, sample, sk, jnp.asarray(temp))
                        logger.log_images(
                            {
                                "original images": sample,
                                "reconstructions": denorm(soft),
                                "hard reconstructions": denorm(hard),
                            },
                            step=global_step,
                        )
                        logger.log_histogram("codebook_indices", idx, step=global_step)
                if global_step and args.save_every_n_steps and global_step % args.save_every_n_steps == 0 and is_root:
                    # NB: not `t0` — that's the epoch wall-clock timer, and
                    # shadowing it here corrupted epoch_time_s
                    t_save = time.perf_counter()
                    with telemetry.span("checkpoint"):
                        # async writer: the span covers only the host gather
                        # + enqueue; serialize/fsync run on the writer thread
                        save_model(out_file, params, cfg,
                                   health_state=_health_state(),
                                   fleet_state=_fleet_state(),
                                   memory_state=_memory_state(),
                                   topology=live_topology, writer=writer)
                    obs_metrics.histogram("checkpoint_save_s").observe(
                        time.perf_counter() - t_save
                    )
                    if injector is not None and injector.wants_checkpoint_fault():
                        if writer is not None:
                            writer.flush()
                        injector.after_checkpoint(out_file, global_step)
                if args.fleet_inject_skew > 0:
                    time.sleep(args.fleet_inject_skew)  # deliberate straggler
                if capture is not None:
                    capture.on_step_end(global_step)
                if tele is not None:
                    tele.finish_step(global_step)
                if shutdown.requested:
                    # the in-flight step finished; leave cleanly with an
                    # emergency checkpoint (exit 75 — supervisor restarts)
                    exit_preempted()
                global_step += 1

            lr *= args.lr_decay_rate
            if is_root:
                save_model(out_file, params, cfg,
                           health_state=_health_state(),
                           fleet_state=_fleet_state(),
                           memory_state=_memory_state(),
                           topology=live_topology, writer=writer)
                logger.log({"epoch_time_s": time.time() - t0, "epoch": epoch}, step=global_step)
    except Exception as e:
        # RESOURCE_EXHAUSTED at compile or step time: forensic report +
        # EXIT_OOM (the finally below still drains the writer / handlers)
        if memory_mod.is_oom_error(e):
            oom_bail(e, "compile" if global_step == 0 else "train_step")
        raise
    finally:
        # an exception mid-training must still drain queued async saves
        # (and surface their write errors) and restore the signal handlers
        shutdown.uninstall()
        if capture is not None:
            capture.close()  # stop an in-flight trace + restore SIGUSR2
        if injector is not None:
            injector.uninstall()  # the global must not leak across main()s
        if writer is not None:
            writer.close()
    if tele is not None:
        tele.flush(logger, step=global_step, fleet=False)  # tail: not synced
        tele.close()
    logger.finish()
    return params, cfg


if __name__ == "__main__":
    main()
