"""DALL-E training CLI — parity with /root/reference/train_dalle.py: VAE
reconstitution from a trained vae checkpoint, resume from a dalle checkpoint,
tokenizer selection, folder or tar-shard data pipelines, checkpoint rotation,
save-before-train fail-fast, throughput metric, periodic sample generation —
with distribution through the mesh backend (pjit sharding + ZeRO stages +
gradient accumulation + bf16) instead of DeepSpeed/Horovod engines."""
from __future__ import annotations

import argparse
import dataclasses as _dc
import json
import time
from glob import glob
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dalle_pytorch_tpu.data import tokenizer as tokenizer_mod
from dalle_pytorch_tpu.data.loader import (
    TextImageDataset,
    batch_tar_stream,
    iterate_batches,
    iterate_tar_shards,
    prefetch_to_device,
)
from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models import vae_registry
from dalle_pytorch_tpu.observability import health_host as health_mod
from dalle_pytorch_tpu.observability import memory as memory_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.sampling import generate_images
from dalle_pytorch_tpu.models.vae import DiscreteVAEConfig
from dalle_pytorch_tpu.parallel import backend as backend_mod
from dalle_pytorch_tpu.parallel import registry as registry_mod
from dalle_pytorch_tpu.parallel.mesh import MeshConfig
from dalle_pytorch_tpu.parallel.train_step import StepSettings, TrainState
from dalle_pytorch_tpu.training import resilience
from dalle_pytorch_tpu.training.checkpoint import (
    is_sharded_checkpoint,
    load_checkpoint,
    unflatten_like,
    load_sharded,
    rotate_checkpoints,
    save_checkpoint,
    save_sharded,
    to_host,
)
from dalle_pytorch_tpu.training.logging import MetricLogger
from dalle_pytorch_tpu.version import __version__


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="Train DALL-E on text/image pairs")
    group = parser.add_mutually_exclusive_group(required=False)
    group.add_argument("--vae_path", type=str, default=None, help="path to trained discrete VAE")
    group.add_argument("--dalle_path", type=str, default=None, help="path to partially-trained DALL-E to resume")
    parser.add_argument("--image_text_folder", type=str, default=None,
                        help="folder of image+text files, or a glob of .tar "
                             "shards with --wds (required unless --dummy_run)")
    parser.add_argument("--taming", action="store_true",
                        help="use a pretrained taming VQGAN as the image tokenizer")
    parser.add_argument("--vqgan_model_path", type=str, default=None,
                        help="taming checkpoint (.ckpt); downloads the published default when omitted")
    parser.add_argument("--vqgan_config_path", type=str, default=None,
                        help="taming config yaml matching --vqgan_model_path")
    parser.add_argument("--wds", action="store_true",
                        help="treat image_text_folder as tar shards: a local glob, or a "
                             "streaming http(s)://... / gs://... URL spec with {000..NNN} "
                             "brace expansion (e.g. 'https://host/shard-{000..009}.tar')")
    parser.add_argument("--truncate_captions", action="store_true")
    parser.add_argument("--random_resize_crop_lower_ratio", type=float, default=0.75)
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--hug", action="store_true")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--dalle_output_file_name", type=str, default="dalle")
    parser.add_argument("--allow_legacy_pickle", action="store_true",
                        help="permit loading pre-v3 (pickled-treedef) "
                             "checkpoints via --vae_path/--dalle_path.  Only "
                             "for files from trusted sources: legacy formats "
                             "can execute code on load.  Re-saving migrates "
                             "to the pickle-free v3 format")
    parser.add_argument("--bf16", action="store_true", help="bf16 compute (TPU-native mixed precision)")
    parser.add_argument("--fp16", action="store_true",
                        help="reference-compat fp16 mode: bf16 compute + DYNAMIC loss "
                             "scaling with overflow-skip, reproducing the DeepSpeed fp16 "
                             "engine's behavior for parity experiments")
    parser.add_argument("--loss_scale", type=str, default=None,
                        help="fp16-style loss scaling: 'dynamic' or a static factor "
                             "(e.g. 32768). bf16 on TPU does not need this; it exists "
                             "for parity with the reference's fp16/AMP runs")
    parser.add_argument("--amp", action="store_true",
                        help="reference-compat alias: mapped to bf16")
    parser.add_argument("--wandb", action="store_true")
    parser.add_argument("--wandb_name", type=str, default="dalle_train_transformer")
    parser.add_argument("--wandb_entity", type=str, default=None)
    parser.add_argument("--stable_softmax", action="store_true")
    # model
    parser.add_argument("--dim", type=int, default=512)
    parser.add_argument("--text_seq_len", type=int, default=256)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--dim_head", type=int, default=64)
    parser.add_argument("--reversible", action="store_true")
    parser.add_argument("--attn_dropout", type=float, default=0.0)
    parser.add_argument("--ff_dropout", type=float, default=0.0)
    parser.add_argument("--execution", type=str, default=None, choices=[None, "sequential", "remat", "reversible"])
    parser.add_argument("--scan_layers", action="store_true",
                        help="lax.scan over stacked layers (near-constant compile time in depth)")
    parser.add_argument("--remat_policy", type=str, default="full",
                        choices=["full", "flash", "flash_qkv", "flash_qkv_ff"],
                        help="selective remat save policy for --execution remat")
    parser.add_argument("--param_dtype", type=str, default="float32",
                        choices=["float32", "bfloat16"],
                        help="param STORAGE dtype. bfloat16 = no f32 master copy "
                             "(halves resident param memory; T5-style), optimizer "
                             "math in f32, stochastic-rounded weight updates")
    parser.add_argument("--loss_img_weight", type=int, default=7)
    parser.add_argument("--attn_types", type=str, default="full",
                        help="comma-separated cycle of full,axial_row,axial_col,conv_like,sparse")
    parser.add_argument("--block_json", type=str, default=None,
                        help="a JSON file whose keys that name DALLEConfig fields describe the "
                             "block (norm, kv_heads, window*, rope_yarn_*, attn_gate, gdn_*, moe_*, "
                             "mla_*, dense_*, mtp_*, "
                             "attn_types, ...): a hybrid trunk from the normal entry point; "
                             "a benchmark configuration file is one.  Sizes the command line "
                             "also sets (dim, depth, heads, ...) are the file's")
    parser.add_argument("--sparse_per_head", action="store_true",
                        help="'sparse' layers draw a random block layout PER HEAD "
                             "(DeepSpeed sparse-attention parity); costs heads x seq^2 "
                             "mask memory per distinct layout, and requires the "
                             "unrolled engines (not --scan_layers)")
    parser.add_argument("--shift_tokens", help="use token shift", action="store_true")
    parser.add_argument("--rotary_emb", help="use rotary embeddings", action="store_true")
    parser.add_argument("--shared_attn_ids", type=str, default=None)
    parser.add_argument("--shared_ff_ids", type=str, default=None)
    parser.add_argument("--share_input_output_emb", action="store_true")
    parser.add_argument("--num_text_tokens", type=int, default=None, help="override tokenizer vocab size")
    # training
    parser.add_argument("--epochs", type=int, default=20)
    # None = unset (resolved to 1000 / 0-under-dummy_run in main) so an
    # EXPLICIT --save_every_n_steps survives the dummy-run defaults
    parser.add_argument("--save_every_n_steps", type=int, default=None,
                        help="checkpoint cadence (default 1000; 0 disables)")
    parser.add_argument("--keep_n_checkpoints", type=int, default=None)
    # fault tolerance (training/resilience.py)
    parser.add_argument("--resume", type=str, default=None, metavar="auto|PATH",
                        help="'auto': resume from the newest VALID checkpoint "
                             "next to --dalle_output_file_name (corrupt or "
                             "truncated files are skipped with a warning; "
                             "fresh start when none exists) — the flag an "
                             "outer supervisor restarts with after a "
                             "preemption (exit code 75).  A path resumes "
                             "from that checkpoint (same as --dalle_path)")
    parser.add_argument("--async_checkpoint", type=int, default=1,
                        help="1 (default): serialize+fsync checkpoints on a "
                             "background writer thread — the step loop only "
                             "pays the device->host gather.  0: fully "
                             "synchronous saves.  (orbax --sharded_checkpoint "
                             "saves are collective and always synchronous)")
    parser.add_argument("--rollback_retries", type=int, default=2,
                        help="on a sustained-nonfinite health alarm "
                             "(--health_every must be on), roll back to the "
                             "newest valid checkpoint and retry, at most this "
                             "many times; then abort with exit code 76.  0 "
                             "disables automatic rollback")
    parser.add_argument("--inject_fault", type=str, default=None,
                        metavar="KIND@STEP",
                        help="fault-injection harness (tools/chaos.py): "
                             f"KIND in {{{','.join(resilience.FAULT_KINDS)}}} "
                             "fired at STEP — e.g. kill-process@40, "
                             "stall-data@10:30.  Testing only")
    parser.add_argument(
        "--sharded_checkpoint", action="store_true",
        help="save checkpoints in the orbax sharded directory format: every "
             "host writes only its own shards, so ZeRO-3-sharded params and "
             "optimizer state are never gathered to one host (the npz path "
             "gathers — multi-GB at billion-param scale and a non-starter "
             "multi-host).  Checkpoint paths become directories; --dalle_path "
             "accepts them for resume.")
    # None = unset (resolved to 4 in main; --dummy_run defaults to
    # 2x device count) so an EXPLICIT --batch_size survives the dummy-run
    # defaults — the elastic shrink/grow drills pin it so the data stream
    # is identical across different device counts
    parser.add_argument("--batch_size", type=int, default=None,
                        help="global batch size (default 4; --dummy_run "
                             "defaults to 2x device count unless set)")
    parser.add_argument("--ga_steps", type=int, default=1, help="gradient accumulation steps")
    parser.add_argument("--learning_rate", type=float, default=3e-4)
    parser.add_argument("--clip_grad_norm", type=float, default=0.5)
    parser.add_argument("--lr_decay", action="store_true")
    parser.add_argument("--sample_every_n_steps", type=int, default=None,
                        help="sample-generation cadence (default 100; 0 disables)")
    parser.add_argument("--log_every_n_steps", type=int, default=10,
                        help="loss/throughput logging cadence (reference logs every 10 iters)")
    parser.add_argument("--num_workers", type=int, default=4,
                        help="decode/crop worker threads (0 = load in the training loop)")
    parser.add_argument("--prefetch_batches", type=int, default=2,
                        help="device-side prefetch depth (0 disables async transfer)")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--debug_nans", action="store_true",
                        help="abort with a traceback on the first NaN (jax_debug_nans)")
    # mesh / ZeRO
    parser.add_argument("--zero_stage", type=int, default=0, choices=[0, 1, 2, 3])
    parser.add_argument("--mesh_dp", type=int, default=-1)
    parser.add_argument("--mesh_fsdp", type=int, default=1)
    parser.add_argument("--mesh_tp", type=int, default=1)
    parser.add_argument("--mesh_sp", type=int, default=1)
    parser.add_argument("--mesh_pp", type=int, default=1,
                        help="pipeline stages (GPipe over the stacked-layer axis; "
                             "requires --scan_layers and depth %% pp == 0)")
    parser.add_argument("--pp_num_micro", type=int, default=None,
                        help="pipeline microbatches (default: auto)")
    parser.add_argument("--pp_interleave", type=int, default=1,
                        help="circular pipeline: chunks per device (bubble time "
                             "drops ~v-fold; needs depth %% (pp*v) == 0 and "
                             "num_micro >= pp)")
    parser.add_argument("--flops_profiler", action="store_true",
                        help="capture a jax profiler trace around step 200 and stop at 201")
    # telemetry (observability/): on by default, JSONL-only — headless CPU
    # runs keep full observability without any profiler infrastructure
    parser.add_argument("--telemetry", type=str, default=None, metavar="DIR",
                        help="telemetry output directory (spans JSONL, hang "
                             "dumps).  Defaults to <output>.telemetry; "
                             "'off' disables telemetry entirely")
    parser.add_argument("--telemetry_heartbeat_s", type=float, default=900.0,
                        help="hang-monitor deadline: if no step completes "
                             "within this many seconds, dump thread stacks + "
                             "last spans to the telemetry dir (0 disables)")
    parser.add_argument("--telemetry_sync", type=int, default=1,
                        help="1 (default): block on each step's result so "
                             "per-step time splits into data_wait / dispatch "
                             "/ block; 0: never block (dispatch-ahead "
                             "preserved, block time reads as 0)")
    # fleet observability (observability/fleet.py + comms.py + capture.py)
    parser.add_argument("--fleet", type=int, default=1,
                        help="1 (default): cross-host fleet aggregation at "
                             "the log cadence — per-phase skew gauges, "
                             "slowest-host id, straggler alarm, and the "
                             "analytic comms ledger (bytes/step per mesh "
                             "axis, cross-checked vs XLA).  0 disables.  "
                             "Collective on multi-process runs (one tiny "
                             "all-gather per log window); the train-step "
                             "HLO is identical either way")
    parser.add_argument("--straggler_factor", type=float, default=1.5,
                        help="straggler alarm threshold: a host whose step "
                             "time exceeds this factor x the fleet median "
                             "(and its EMA) for --straggler_patience "
                             "consecutive log windows is alarmed")
    parser.add_argument("--straggler_patience", type=int, default=3,
                        help="consecutive slow log windows before the "
                             "straggler alarm fires")
    parser.add_argument("--profile_on_alarm", type=int, default=3, metavar="N",
                        help="capture a jax.profiler trace of the next N "
                             "steps whenever an alarm fires (straggler, "
                             "recompile, divergence, health, hang) — rate-"
                             "limited to one capture per 15 min, 2 per run; "
                             "traces land under <telemetry>/traces.  0 "
                             "disables.  SIGUSR2 requests the same capture "
                             "manually on a live run")
    parser.add_argument("--profile_steps", type=str, default=None,
                        metavar="A:B",
                        help="manually capture a profiler trace of steps "
                             "[A, B) into <telemetry>/traces (bypasses the "
                             "on-alarm rate limit)")
    parser.add_argument("--fleet_inject_skew", type=float, default=0.0,
                        metavar="SECONDS",
                        help="test hook: sleep this long inside every step "
                             "on THIS process — makes it a deliberate "
                             "straggler so the alarm + capture path can be "
                             "exercised end to end")
    # memory observability (observability/memory.py)
    parser.add_argument("--hbm_headroom_frac", type=float, default=0.9,
                        metavar="FRAC",
                        help="live-HBM headroom alarm: when bytes_in_use "
                             "crosses FRAC x per-device capacity an "
                             "'hbm_headroom' alarm fires (once per episode) "
                             "and — with --profile_on_alarm — captures a "
                             "profiler trace of the next steps.  0 disables. "
                             "The analytic HBM ledger (mem/* gauges, "
                             "kind:'mem_ledger' events, the XLA "
                             "memory_analysis cross-check and donation "
                             "audit) is always on under telemetry")
    # training-health diagnostics (observability/health.py)
    parser.add_argument("--health_every", type=int, default=0, metavar="N",
                        help="run the in-graph health diagnostic step every N "
                             "steps (0 disables): per-layer grad/param/update "
                             "norms, NaN/Inf localization, attention/codebook "
                             "activation taps, divergence alarms.  Compiles a "
                             "second step executable; the normal step's HLO "
                             "is unchanged (zero overhead when off)")
    parser.add_argument("--health_inject_nan", type=str, default=None,
                        metavar="STEP[,STEP...][:PATTERN]",
                        help="test hook: poison the first param leaf whose "
                             "path contains PATTERN (default: first leaf) "
                             "with NaN before each listed STEP (each fires "
                             "once) — exercises NaN localization, the alarm "
                             "path, and (with --rollback_retries) the "
                             "divergence rollback end to end")
    parser.add_argument("--dummy_run", "--dummy-run", type=int, nargs="?",
                        const=6, default=None, metavar="N",
                        help="telemetry smoke mode: train N steps (default 6) "
                             "of a tiny model on synthetic data — no dataset "
                             "or VAE checkpoint needed; exercises the full "
                             "telemetry path incl. a deliberate ragged final "
                             "batch (recompile event)")
    return backend_mod.wrap_arg_parser(parser)


def get_tokenizer(args):
    if args.chinese:
        return tokenizer_mod.ChineseTokenizer()
    if args.hug:
        assert args.bpe_path is not None, "--hug requires --bpe_path"
        return tokenizer_mod.HugTokenizer(args.bpe_path)
    if args.bpe_path is not None:
        suffix = Path(args.bpe_path).suffix
        if suffix == ".json":
            return tokenizer_mod.HugTokenizer(args.bpe_path)
        return tokenizer_mod.YttmTokenizer(args.bpe_path)
    return tokenizer_mod.tokenizer


def reconstitute_vae(args, resume=None):
    """Load the frozen VAE (weights + config) that tokenizes training images —
    a trained DiscreteVAE checkpoint, a taming VQGAN, or the OpenAI dVAE
    (reference train_dalle.py:246-293).  `resume` is the already-loaded
    (trees, meta) of the dalle checkpoint, which carries the VAE."""
    if resume is not None:
        trees, meta = resume
        assert "vae_weights" in trees, "resume checkpoint is missing VAE weights"
        cfg = vae_registry.config_from_meta(
            meta.get("vae_class_name", "DiscreteVAE"), meta["vae_params"]
        )
        return trees["vae_weights"], cfg
    if args.vae_path is not None:
        from dalle_pytorch_tpu.models.torch_port import (
            is_torch_checkpoint,
            load_reference_vae_checkpoint,
        )

        if is_torch_checkpoint(args.vae_path):
            # a vae.pt trained with the torch reference — convert on load
            return load_reference_vae_checkpoint(args.vae_path)
        trees, meta = load_checkpoint(
            args.vae_path, allow_legacy_pickle=args.allow_legacy_pickle
        )
        return trees["weights"], DiscreteVAEConfig(**meta["hparams"])
    if (args.vqgan_model_path or args.vqgan_config_path) and not args.taming:
        raise SystemExit(
            "--vqgan_model_path/--vqgan_config_path require --taming "
            "(otherwise they would be silently ignored)"
        )
    from dalle_pytorch_tpu.models import pretrained

    if args.taming:
        return pretrained.load_vqgan_pretrained(
            args.vqgan_model_path, args.vqgan_config_path
        )
    print("using OpenAI's pretrained VAE for encoding images to tokens")
    return pretrained.load_openai_vae_pretrained()


def build_model_payload(state, dalle_cfg, vae_params, vae_cfg, epoch,
                        global_step=0, wandb_run_id=None, health_state=None,
                        data_state=None, fleet_state=None, memory_state=None,
                        topology=None):
    """(trees, meta) for a checkpoint — the device->host gather happens HERE
    (np.asarray inside to_host), so the result is a consistent snapshot that
    can be serialized later on the async writer thread.  `data_state`
    (resilience.data_state_dict) is what makes resume exact: epoch,
    within-epoch batch cursor, shuffle seed, RNG key.  `topology`
    (parallel/registry.topology_meta) records the mesh shape + partitioning
    registry this state was sharded under — what lets a resume on a changed
    topology reshard instead of failing."""
    class_name, vae_meta = vae_registry.config_to_meta(vae_cfg)
    trees = {
        "weights": to_host(state.params),
        "opt_state": to_host(state.opt_state),
        "vae_weights": to_host(vae_params),
    }
    meta = {
        "hparams": dalle_cfg.to_dict(),
        "vae_params": vae_meta,
        "epoch": epoch,
        "global_step": int(global_step),
        "wandb_run_id": wandb_run_id,
        "version": __version__,
        "vae_class_name": class_name,
        "scheduler_state": None,
        "health_state": health_state,
        "data_state": data_state,
        "fleet_state": fleet_state,
        "memory_state": memory_state,
        "topology": topology,
    }
    return trees, meta


def save_model(path, state, dalle_cfg, vae_params, vae_cfg, epoch, keep_n=None,
               global_step=0, wandb_run_id=None, health_state=None,
               data_state=None, fleet_state=None, memory_state=None,
               topology=None, writer=None):
    """Gather + write one npz checkpoint.  With `writer` (an
    AsyncCheckpointWriter), only the gather runs here — serialization,
    fsync, atomic rename, and rotation happen on the writer thread and this
    returns as soon as the job is queued."""
    trees, meta = build_model_payload(
        state, dalle_cfg, vae_params, vae_cfg, epoch, global_step=global_step,
        wandb_run_id=wandb_run_id, health_state=health_state,
        data_state=data_state, fleet_state=fleet_state,
        memory_state=memory_state, topology=topology,
    )
    glob_pat = _rotation_glob(path) if keep_n is not None else None
    if writer is not None:
        writer.submit(path, trees, meta, keep_n=keep_n, rotation_glob=glob_pat)
        return
    save_checkpoint(path, trees, meta)
    if keep_n is not None:
        rotate_checkpoints(str(Path(path).parent), glob_pat, keep_n)


def _rotation_glob(path) -> str:
    """Glob matching this run's step checkpoints.  `path` is the step file
    itself (`<name>_step<N>.npz`), so the run name must be recovered by
    stripping the step suffix — globbing on the full stem matched nothing and
    rotation silently never deleted anything."""
    import re

    p = Path(path)
    stem = re.sub(r"_step\d+$", "", p.stem)
    return stem + "_step*" + p.suffix


def save_model_sharded(path, state, dalle_cfg, vae_params, vae_cfg, epoch,
                       keep_n=None, global_step=0, wandb_run_id=None,
                       health_state=None, data_state=None, fleet_state=None,
                       memory_state=None, topology=None):
    """Distributed save: the TrainState goes through orbax, each host writing
    only the shards it owns — ZeRO-3/pp-sharded params and optimizer state are
    never gathered (`save_model`'s np.asarray would pull the full arrays to
    one host).  The small frozen VAE rides in a sidecar npz inside the
    checkpoint directory.  Collective: call from ALL processes (and always
    synchronous — the async writer covers the npz path only)."""
    class_name, vae_meta = vae_registry.config_to_meta(vae_cfg)
    meta = {
        "hparams": dalle_cfg.to_dict(),
        "vae_params": vae_meta,
        "epoch": epoch,
        "global_step": int(global_step),
        "wandb_run_id": wandb_run_id,
        "version": __version__,
        "vae_class_name": class_name,
        "scheduler_state": None,
        "health_state": health_state,
        "data_state": data_state,
        "fleet_state": fleet_state,
        "memory_state": memory_state,
        "topology": topology,
    }
    path = Path(path)
    if jax.process_index() == 0:
        # the VAE sidecar lands FIRST: save_sharded writes meta.json last,
        # making it the directory's commit marker — a save torn by
        # preemption can never present meta.json with vae.npz missing
        # (validate_checkpoint additionally screens for the sidecar the
        # meta declares, so --resume auto falls back past torn directories)
        path.mkdir(parents=True, exist_ok=True)
        save_checkpoint(
            str(path / "vae.npz"),
            trees={"vae_weights": to_host(vae_params)},
            meta={"vae_params": vae_meta, "vae_class_name": class_name},
        )
    save_sharded(
        str(path),
        {"step": state.step, "weights": state.params, "opt_state": state.opt_state},
        meta,
    )
    if jax.process_index() == 0 and keep_n is not None:
        rotate_checkpoints(str(path.parent), _rotation_glob(path), keep_n)


def _announce_reshard(rr):
    """Root-process log of a ReshardRequired detection — shared by the
    auto-discovery and explicit-path resume branches so the loud
    rules-changed warning cannot be dropped from one of them."""
    print(f"[resilience] {rr}")
    if rr.rules_changed:
        print("[resilience] WARNING: the partitioning REGISTRY changed "
              "since this checkpoint was saved — restoring under the "
              "current rules (review parallel/registry.py changes if "
              "placement parity matters)")
    print("[resilience] elastic resume: resharding onto the live mesh "
          "(memory preflight below)")


def _apply_dummy_run_defaults(args):
    """--dummy_run: shrink to a CPU-friendly synthetic smoke config that
    still exercises every telemetry code path (spans, metrics, recompile
    counting, FLOPs cross-check, report rendering)."""
    args.dim, args.depth, args.heads, args.dim_head = 64, 2, 2, 16
    args.text_seq_len, args.num_text_tokens = 16, 256
    # 2x device count: the deliberately ragged final batch (half size) must
    # still shard over the default dp mesh axis.  An EXPLICIT --batch_size
    # wins — the elastic shrink/grow drills resume on a different device
    # count and need the same batch stream on both sides
    import jax as _jax

    if args.batch_size is None:
        args.batch_size = 2 * _jax.device_count()
    args.epochs = 1
    args.num_workers = min(args.num_workers, 2)
    # respect EXPLICIT cadences (the crash-and-resume tests run dummy mode
    # with --save_every_n_steps 1); only unset (None) cadences go quiet
    if args.save_every_n_steps is None:
        args.save_every_n_steps = 0
    if args.sample_every_n_steps is None:
        args.sample_every_n_steps = 0
    args.log_every_n_steps = max(1, min(args.log_every_n_steps, 2))
    return args


def main(argv=None):
    args = build_parser().parse_args(argv)
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()
    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    if args.dummy_run is not None:
        args = _apply_dummy_run_defaults(args)
    elif args.image_text_folder is None:
        raise SystemExit("--image_text_folder is required (unless --dummy_run)")
    # resolve unset cadences (None sentinel so --dummy_run can tell an
    # explicit value from an untouched default)
    if args.save_every_n_steps is None:
        args.save_every_n_steps = 1000
    if args.sample_every_n_steps is None:
        args.sample_every_n_steps = 100
    if args.batch_size is None:
        args.batch_size = 4

    be = backend_mod.set_backend_from_args(args)
    be.initialize()
    is_root = be.is_root_worker()

    out_file = f"{args.dalle_output_file_name}.pt"

    # the partitioning registry: the ONE rule table that places params and
    # optimizer state, stamps checkpoint topology, and prices the ledgers
    registry = registry_mod.default_registry()
    # the mesh this run will distribute over — built ONCE, so the stamped
    # checkpoint topology, the memory ledger, and the actual distribution
    # below all derive from the same resolution
    mesh_cfg = MeshConfig(
        args.mesh_dp, args.mesh_fsdp, args.mesh_tp, args.mesh_sp, args.mesh_pp
    )
    # this run's topology identity (mesh shape + device count + registry
    # fingerprint) — stamped into every checkpoint and compared against the
    # one a resumed checkpoint was saved under
    try:
        live_axes = _dc.asdict(mesh_cfg.resolve(jax.device_count()))
    except Exception:
        live_axes = {}
    live_topology = registry_mod.topology_meta(
        live_axes, registry, device_count=jax.device_count())

    # --resume: 'auto' discovers the newest VALID checkpoint next to the
    # output file (falling back past truncated/corrupt ones; orbax sharded
    # checkpoint DIRECTORIES are discovered too), a path resumes from that
    # file.  Either way it feeds the existing --dalle_path plumbing.  A
    # checkpoint saved under a DIFFERENT topology (a preemption gave back
    # fewer chips, a dp8 file restored for tp4xdp2 serving) no longer fails:
    # the restore reshards onto the live mesh through the registry, gated by
    # the memory-ledger preflight below.
    reshard_note = None
    if args.resume is not None:
        if args.dalle_path is not None:
            raise SystemExit("--resume and --dalle_path are mutually exclusive")
        if args.resume == "auto":
            if be.get_world_size() > 1 and is_root:
                # every process globs its own disk; without a shared
                # filesystem the workers would silently fresh-start
                print("[resilience] --resume auto on a multi-process run "
                      "assumes the output dir is on a SHARED filesystem "
                      "(all processes must discover the same checkpoint)")
            found, found_meta = resilience.find_latest_valid_checkpoint(
                out_file, log=print if is_root else None
            )
            if found is not None:
                try:
                    resilience.check_topology(found_meta, live_topology,
                                              path=found)
                except resilience.ReshardRequired as rr:
                    reshard_note = rr
                    if is_root:
                        _announce_reshard(rr)
                args.dalle_path = found
                if is_root:
                    print(f"[resilience] --resume auto: resuming from {found}")
            elif is_root:
                print("[resilience] --resume auto: no valid checkpoint found "
                      f"next to {out_file}; starting fresh")
        else:
            args.dalle_path = args.resume

    # fault-injection harness (--inject_fault KIND@STEP, tools/chaos.py)
    injector = None
    if args.inject_fault is not None:
        injector = resilience.FaultInjector(
            resilience.parse_fault(args.inject_fault)
        ).install()

    tokenizer = get_tokenizer(args)

    ref_resume = None
    if args.dalle_path is not None:
        from dalle_pytorch_tpu.models.torch_port import (
            is_torch_checkpoint,
            load_reference_dalle_checkpoint,
        )

        if is_torch_checkpoint(args.dalle_path):
            # a dalle.pt trained with the torch reference: convert the model
            # + embedded VAE and continue training (optimizer starts fresh —
            # torch Adam state is not portable).  VQGanVAE-class checkpoints
            # need their taming yaml (--vqgan_config_path)
            taming_config = None
            if args.vqgan_config_path:
                from dalle_pytorch_tpu.models.pretrained import parse_taming_yaml

                taming_config = parse_taming_yaml(args.vqgan_config_path)
            ref_resume = load_reference_dalle_checkpoint(
                args.dalle_path, taming_config=taming_config
            )
            if is_root:
                print(f"resuming from reference checkpoint {args.dalle_path} "
                      f"(epoch {ref_resume['epoch']}, fresh optimizer state)")
    sharded_resume = None
    if (args.dalle_path is not None and ref_resume is None
            and is_sharded_checkpoint(args.dalle_path)):
        # orbax sharded checkpoint directory: read the cheap parts now (meta
        # json + VAE sidecar); the sharded TrainState is restored onto THIS
        # run's mesh after distribution — no host gather at any point
        import json as _json

        sharded_resume = args.dalle_path
        vae_trees, vae_side_meta = load_checkpoint(
            str(Path(args.dalle_path) / "vae.npz"),
            allow_legacy_pickle=args.allow_legacy_pickle,
        )
        meta = _json.loads((Path(args.dalle_path) / "meta.json").read_text())
        meta.update(vae_side_meta)
        resume = ({"vae_weights": vae_trees["vae_weights"]}, meta)
    else:
        resume = (
            load_checkpoint(args.dalle_path,
                            allow_legacy_pickle=args.allow_legacy_pickle)
            if args.dalle_path is not None and ref_resume is None
            else None
        )

    # explicit-path resumes (--dalle_path / --resume PATH) get the same
    # topology check the auto discovery ran: a changed mesh shape or device
    # count reshards (preflighted below) instead of surfacing as a cryptic
    # placement failure
    if resume is not None and reshard_note is None:
        try:
            resilience.check_topology(resume[1], live_topology,
                                      path=str(args.dalle_path))
        except resilience.ReshardRequired as rr:
            reshard_note = rr
            if is_root:
                _announce_reshard(rr)

    if args.dummy_run is not None:
        # tiny randomly-initialized image tokenizer: the smoke path must not
        # depend on a trained VAE checkpoint or a pretrained download
        from dalle_pytorch_tpu.models import vae as vae_mod

        vae_cfg = DiscreteVAEConfig(
            image_size=32, num_tokens=128, codebook_dim=32, num_layers=2,
            num_resnet_blocks=0, hidden_dim=16,
        )
        vae_params = vae_mod.init_discrete_vae(jax.random.PRNGKey(args.seed), vae_cfg)
    elif ref_resume is not None:
        vae_params, vae_cfg = ref_resume["vae_params"], ref_resume["vae_config"]
    else:
        vae_params, vae_cfg = reconstitute_vae(args, resume)

    resume_meta = None
    if ref_resume is not None:
        dalle_cfg = ref_resume["config"]
        start_params = ref_resume["params"]
        resume_meta = {"epoch": ref_resume["epoch"]}
        trees = {}
    elif resume is not None:
        trees, resume_meta = resume
        dalle_cfg = DALLEConfig.from_dict(resume_meta["hparams"])
        if sharded_resume is not None:
            # weights arrive sharded after be.distribute; init placeholders
            start_params = dalle_mod.init_dalle(jax.random.PRNGKey(args.seed), dalle_cfg)
        else:
            # pre-round-5 checkpoints carry the fused-GEGLU / [q|k|v] qkv
            # layouts — migrate on load (no-op when current)
            start_params = dalle_mod.migrate_param_layout(trees["weights"], dalle_cfg)
    else:
        num_text_tokens = args.num_text_tokens or tokenizer.vocab_size
        dalle_cfg = DALLEConfig.from_vae(
            vae_cfg,
            dim=args.dim,
            depth=args.depth,
            num_text_tokens=num_text_tokens,
            text_seq_len=args.text_seq_len,
            heads=args.heads,
            dim_head=args.dim_head,
            reversible=args.reversible,
            attn_dropout=args.attn_dropout,
            ff_dropout=args.ff_dropout,
            execution=args.execution,
            scan_layers=args.scan_layers,
            remat_policy=args.remat_policy,
            loss_img_weight=args.loss_img_weight,
            attn_types=tuple(args.attn_types.split(",")),
            sparse_per_head=args.sparse_per_head,
            stable=args.stable_softmax,
            shift_tokens=args.shift_tokens,
            rotary_emb=args.rotary_emb,
            shared_attn_ids=_parse_ids(args.shared_attn_ids),
            shared_ff_ids=_parse_ids(args.shared_ff_ids),
            share_input_output_emb=args.share_input_output_emb,
        )
        if args.block_json:
            block = json.loads(Path(args.block_json).read_text())
            block = {f.name: block[f.name] for f in _dc.fields(DALLEConfig)
                     if f.name in block and f.name not in ("num_image_tokens", "image_fmap_size")}
            dalle_cfg = _dc.replace(dalle_cfg, **dalle_mod.tupled_hparams(block))
        start_params = dalle_mod.init_dalle(jax.random.PRNGKey(args.seed), dalle_cfg)

    # pipeline engagement follows THIS run's mesh, not the checkpoint's: a
    # resume with --mesh_pp must activate the pipeline (and vice versa)
    dalle_cfg = _dc.replace(
        dalle_cfg,
        pipeline_axis="pp" if args.mesh_pp > 1 else None,
        pp_num_micro=args.pp_num_micro,
        pp_interleave=args.pp_interleave,
    )

    from dalle_pytorch_tpu.cli.common import warn_vocab_mismatch

    warn_vocab_mismatch(dalle_cfg.num_text_tokens, tokenizer, is_root)

    # data
    be.check_batch_size(args.batch_size)
    if args.dummy_run is not None:
        def _dummy_batches(epoch):
            rs = np.random.RandomState(args.seed + epoch)
            n = int(args.dummy_run)
            for i in range(n):
                # the final batch is deliberately ragged (half size): the
                # telemetry smoke must observe a real recompile event
                bs = args.batch_size
                if i == n - 1 and n >= 2 and bs >= 2:
                    bs //= 2
                yield {
                    "text": rs.randint(
                        0, dalle_cfg.num_text_tokens,
                        (bs, dalle_cfg.text_seq_len)).astype(np.int32),
                    "image": rs.rand(
                        bs, vae_cfg.image_size, vae_cfg.image_size, 3
                    ).astype(np.float32),
                }

        def data_iter(epoch, skip=0):
            import itertools

            # islice keeps the RandomState draw sequence identical to an
            # uninterrupted run, so a resumed dummy run sees the same batches
            return itertools.islice(_dummy_batches(epoch), skip, None)
    elif args.wds:
        from dalle_pytorch_tpu.data.loader import expand_shard_spec, is_remote_shard

        if is_remote_shard(args.image_text_folder):
            # remote shard spec, e.g. https://host/shard-{000..099}.tar or
            # gs://bucket/data-{000..511}.tar — streamed with retry +
            # warn-and-continue (reference train_dalle.py:195-218)
            shards = expand_shard_spec(args.image_text_folder)
        else:
            shards = sorted(glob(args.image_text_folder))
        assert shards, f"no tar shards match {args.image_text_folder}"

        def data_iter(epoch, skip=0):
            import itertools

            stream = iterate_tar_shards(
                shards, vae_cfg.image_size, dalle_cfg.text_seq_len, tokenizer,
                truncate_captions=args.truncate_captions,
                process_index=be.get_rank(), process_count=be.get_world_size(),
                seed=args.seed + epoch, num_workers=args.num_workers,
            )
            # tar streams have no random access: the fast-forward re-reads
            # (and discards) the first `skip` batches — resume is exact, it
            # just pays the stream bytes for the skipped prefix
            return itertools.islice(
                batch_tar_stream(stream, args.batch_size), skip, None
            )
    else:
        dataset = TextImageDataset(
            args.image_text_folder,
            text_len=dalle_cfg.text_seq_len,
            image_size=vae_cfg.image_size,
            truncate_captions=args.truncate_captions,
            resize_ratio=args.random_resize_crop_lower_ratio,
            tokenizer=tokenizer,
            shuffle=True,
        )
        assert len(dataset) > 0, "dataset is empty"

        def data_iter(epoch, skip=0):
            return iterate_batches(
                dataset, args.batch_size, seed=args.seed + epoch,
                process_index=be.get_rank(), process_count=be.get_world_size(),
                num_workers=args.num_workers, skip_batches=skip,
            )

    use_bf16 = args.bf16 or args.fp16 or args.amp

    # loss: raw pixels -> frozen VAE codes -> DALLE CE loss.  The frozen
    # VAE's conv encode runs in the compute dtype too — it only produces
    # argmax code ids, and f32 convs would otherwise dominate the host of a
    # bf16 step on real data
    from dalle_pytorch_tpu.core.pytree import cast_floating

    encode_vae_params = (
        cast_floating(vae_params, jnp.bfloat16) if use_bf16 else vae_params
    )

    def loss_fn(params, batch, key):
        image = batch["image"]
        if use_bf16:
            image = image.astype(jnp.bfloat16)
        codes = vae_registry.get_codebook_indices(encode_vae_params, vae_cfg, image)
        # a routed trunk also hands back its experts' load (device scalars
        # that ride in the step's metrics beside the loss)
        return dalle_mod.forward(
            params, dalle_cfg, batch["text"], jax.lax.stop_gradient(codes),
            return_loss=True, key=key,
            return_aux=dalle_cfg.moe_experts > 0 or dalle_cfg.mtp_depth > 0,
        )

    optimizer = optax.adam(args.learning_rate)
    if args.lr_decay:
        # ReduceLROnPlateau parity (reference train_dalle.py:451-459:
        # factor 0.5, patience 10, cooldown 10, min_lr 1e-6)
        optimizer = optax.chain(
            optimizer,
            optax.contrib.reduce_on_plateau(
                factor=0.5, patience=10, cooldown=10, min_scale=1e-6 / args.learning_rate
            ),
        )
    if args.fp16 and is_root:
        print("note: --fp16 runs bf16 compute + dynamic loss scaling with "
              "overflow-skip (DeepSpeed-fp16 parity semantics)")
    elif args.amp and is_root:
        print("note: --amp maps to bf16 on TPU (add --loss_scale dynamic for "
              "AMP's scaling behavior)")
    settings = StepSettings(
        grad_accum=args.ga_steps,
        compute_dtype=jnp.bfloat16 if use_bf16 else jnp.float32,
        clip_grad_norm=args.clip_grad_norm,
        zero_stage=args.zero_stage,
        # explicit float32 (not None) so resuming a bf16 checkpoint into an
        # f32 run re-materializes f32 masters rather than keeping bf16
        param_dtype=jnp.bfloat16 if args.param_dtype == "bfloat16" else jnp.float32,
        loss_scale=(
            args.loss_scale if args.loss_scale in (None, "dynamic")
            else float(args.loss_scale)
        ) if args.loss_scale is not None else ("dynamic" if args.fp16 else None),
    )
    # --- memory observability (observability/memory.py) --------------------
    # The ledger is priced BEFORE distribution (placement itself can OOM) from
    # the resolved mesh shape + start params (optimizer moments estimated),
    # and refreshed from the live trees at the crosscheck site below.
    # `live_axes` is the same resolution the checkpoint topology was stamped
    # from (mesh_cfg, built once at the top of main).
    mem_axes = live_axes
    mem_ledger = memory_mod.dalle_step_memory(
        mem_axes, start_params, None, dalle_cfg, args.batch_size,
        settings=settings, registry=registry,
    )

    # elastic-resume preflight: the checkpoint is moving to a DIFFERENT
    # topology — refuse BEFORE distribution touches a device when the
    # target's analytic ledger says it cannot fit (a dp8 state only fit
    # because it was 8-way sharded; shrinking to dp2 must fail with a
    # ledger, not a RESOURCE_EXHAUSTED after minutes of compilation)
    if reshard_note is not None and mem_ledger.get("fits") is False:
        if is_root:
            print("[resilience] reshard REFUSED: the target topology "
                  f"{mem_ledger.get('mesh')} needs "
                  f"{mem_ledger['total_bytes'] / 1e9:.2f}GB per chip "
                  f"(dominant: {mem_ledger['dominant']}) but capacity is "
                  f"{mem_ledger['capacity_bytes'] / 1e9:.2f}GB — use more "
                  "chips, a higher --zero_stage, --execution remat, or "
                  "bf16 param storage.  Exiting with code "
                  f"{resilience.EXIT_OOM} (do not auto-restart this "
                  "config)", flush=True)
        raise SystemExit(resilience.EXIT_OOM)

    def oom_bail(e, phase, step=None):
        """RESOURCE_EXHAUSTED forensics: write oom_report_*.txt (ledger
        breakdown, memory_analysis, live allocator stats, ranked
        suggestions) and exit EXIT_OOM — the one exit code a supervisor
        must NOT auto-restart (the same config will OOM again)."""
        from dalle_pytorch_tpu.observability.xla import record_memory_gauges

        report_dir = (args.telemetry if args.telemetry not in (None, "off")
                      else f"{args.dalle_output_file_name}.telemetry")
        try:
            live = record_memory_gauges()
        except Exception:
            live = None
        tele_now = telemetry.active()
        path = memory_mod.write_oom_report(
            report_dir, error=e, phase=phase, ledger=mem_ledger,
            analysis=getattr(tele_now, "last_memory_analysis", None),
            live_stats=live,
            context={"global_step": step, "mesh": mem_ledger.get("mesh"),
                     "batch_size": args.batch_size,
                     "ga_steps": args.ga_steps,
                     "zero_stage": args.zero_stage},
            settings=settings, process_index=be.get_rank(),
        )
        print(f"[memory] OUT OF MEMORY during {phase}: forensic report -> "
              f"{path or '<unwritable>'}; exiting with code "
              f"{resilience.EXIT_OOM} (do not auto-restart this config)",
              flush=True)
        raise SystemExit(resilience.EXIT_OOM)

    try:
        state, step_fn, _, _ = be.distribute(
            loss_fn=loss_fn, params=start_params, optimizer=optimizer,
            mesh_config=mesh_cfg, settings=settings, registry=registry,
            param_rule=dalle_mod.param_rule(dalle_cfg),
        )
    except Exception as e:
        if memory_mod.is_oom_error(e):
            oom_bail(e, "init")
        raise
    if sharded_resume is not None:
        # restore shard-by-shard onto this run's state (its shardings define
        # the placement — the save mesh may have had a different shape)
        try:
            restored, _ = load_sharded(
                sharded_resume,
                {"step": state.step, "weights": state.params, "opt_state": state.opt_state},
            )
            state = TrainState(restored["step"], restored["weights"], restored["opt_state"])
        except Exception:
            # pre-round-5 sharded checkpoint: the file's structure predates
            # the qkv/GEGLU relayout, so the template restore cannot match.
            # Fall back to a template-free weights restore + layout
            # migration; the optimizer state is not mechanically mappable
            # across the relayout and starts fresh.
            restored, _ = load_sharded(sharded_resume, only=("weights", "step"))
            migrated = dalle_mod.migrate_param_layout(restored["weights"], dalle_cfg)
            if migrated is restored["weights"]:
                raise  # current layout — the failure was something real
            print(
                "note: sharded checkpoint predates the round-5 parameter "
                "layout — weights migrated, optimizer state starts fresh"
            )
            state, step_fn, _, _ = be.distribute(
                loss_fn=loss_fn, params=migrated, optimizer=optimizer,
                mesh_config=mesh_cfg, settings=settings, registry=registry,
                param_rule=dalle_mod.param_rule(dalle_cfg),
            )
            state = TrainState(jnp.asarray(restored["step"]), state.params, state.opt_state)
    elif resume_meta is not None and "opt_state" in trees:
        # v3 files return optimizer states as a TreeBundle (no pickled node
        # types in the file) — this run's freshly-initialized opt_state is
        # the structure template
        try:
            saved_opt = unflatten_like(state.opt_state, trees["opt_state"])
        except ValueError as e:
            # a pre-round-5 opt_state (fused-w1 moment leaves) cannot map
            # onto the split-GEGLU template — weights already migrated;
            # momentum restarts rather than aborting the resume
            print(f"note: optimizer state not restored ({e}); starting fresh "
                  "optimizer (weights restored + migrated)")
            saved_opt = None
        if saved_opt is not None:
            # each restored moment lands directly on the FRESH leaf's
            # sharding (the registry placement init_fn just computed for the
            # live mesh) — jnp.asarray would commit the full host array to
            # one default device, discarding the placement and materializing
            # unsharded moments exactly where the elastic preflight said
            # only sharded ones fit
            def _restore_opt_leaf(cur, saved):
                if not hasattr(cur, "dtype"):
                    return saved
                host = np.asarray(saved).astype(cur.dtype)
                return jax.device_put(host, getattr(cur, "sharding", None))

            state = TrainState(state.step, state.params, jax.tree_util.tree_map(
                _restore_opt_leaf, state.opt_state, saved_opt,
            ))

    logger = MetricLogger(
        run_name=args.dalle_output_file_name, use_wandb=args.wandb,
        wandb_kwargs={"name": args.wandb_name, "entity": args.wandb_entity},
        config=dalle_cfg.to_dict(), is_root=is_root,
        resume_run_id=(resume_meta or {}).get("wandb_run_id"),
    )

    # telemetry: on by default (JSONL-only — no profiler infrastructure
    # needed); --telemetry DIR redirects it, --telemetry off disables
    tele = None
    fleet_agg = None
    capture = None
    hbm_monitor = None
    if args.telemetry != "off":
        tele_dir = args.telemetry or f"{args.dalle_output_file_name}.telemetry"
        tele = telemetry.configure(
            dir=tele_dir, run_name=Path(args.dalle_output_file_name).name,
            heartbeat_s=args.telemetry_heartbeat_s or None,
            process_index=be.get_rank(),
        )
        if is_root:
            print(f"[telemetry] spans + metrics + hang dumps -> {tele_dir} "
                  f"(render with tools/telemetry_report.py)")
        # fleet observability: cross-host skew gauges + straggler alarm at
        # the log cadence (observability/fleet.py); merged offline with
        # tools/fleet_report.py
        if args.fleet:
            from dalle_pytorch_tpu.observability.fleet import FleetAggregator

            fleet_agg = tele.attach_fleet(FleetAggregator(
                process_index=be.get_rank(), process_count=be.get_world_size(),
                skew_factor=args.straggler_factor,
                patience=args.straggler_patience,
            ))
            # straggler EMA/streaks survive restarts through checkpoint meta
            # (same discipline as the DivergenceMonitor state)
            fleet_agg.load_state_dict((resume_meta or {}).get("fleet_state"))
            if is_root and be.get_world_size() > 1:
                print(f"[fleet] skew gauges + straggler alarm over "
                      f"{be.get_world_size()} processes (render with "
                      "tools/fleet_report.py)")
        # on-alarm / manual / SIGUSR2 profiler capture (observability/capture)
        from dalle_pytorch_tpu.observability import capture as capture_mod

        manual_window = (capture_mod.parse_profile_steps(args.profile_steps)
                         if args.profile_steps else None)
        if args.profile_on_alarm or manual_window is not None:
            capture = capture_mod.TraceTrigger(
                dir=str(Path(tele_dir) / "traces"),
                window_steps=args.profile_on_alarm or 1,
                manual_window=manual_window,
                recorder=tele.spans,
                process_index=be.get_rank(),
            ).install_sigusr2()
            if args.profile_on_alarm:
                tele.add_alarm_listener(capture.on_alarm)
        # memory observability: publish the analytic HBM ledger (mem/*
        # gauges + a kind:"mem_ledger" event with the fits verdict) and
        # attach the live headroom monitor — its hbm_headroom alarm routes
        # through the hub into the on-alarm profiler capture above
        memory_mod.publish_gauges(mem_ledger, obs_metrics.REGISTRY)
        tele.spans.write_event("mem_ledger", **mem_ledger)
        if is_root:
            fits = mem_ledger.get("fits")
            verdict = ("fits" if fits else "DOES NOT FIT" if fits is not None
                       else "capacity unknown")
            print("[memory] analytic HBM ledger: "
                  + ", ".join(f"{r['name']}={r['bytes'] / 1e9:.2f}GB"
                              for r in mem_ledger["rows"])
                  + f" per chip ({verdict}; dominant: {mem_ledger['dominant']};"
                    " render with tools/memory_report.py)")
        if args.hbm_headroom_frac:
            hbm_monitor = tele.attach_memory(memory_mod.HbmMonitor(
                headroom_frac=args.hbm_headroom_frac,
            ))
            # headroom-episode state survives restarts through checkpoint
            # meta (DivergenceMonitor discipline)
            hbm_monitor.load_state_dict((resume_meta or {}).get("memory_state"))

    # training-health diagnostics: per-layer numerics + divergence alarms on
    # a second jitted step every --health_every steps (observability/health)
    health_monitor = None
    health_paths = None
    if args.health_every:
        health_paths = health_mod.leaf_paths(state.params)
        health_monitor = health_mod.DivergenceMonitor(
            on_alarm=health_mod.make_alarm_writer(tele, registry=obs_metrics.REGISTRY)
        )
        # alarm state (EMA, divergence onset) survives restarts through the
        # checkpoint metadata — a resumed run keeps its armed thresholds
        health_monitor.load_state_dict((resume_meta or {}).get("health_state"))
        if is_root:
            print(f"[health] diagnostics every {args.health_every} step(s) "
                  f"({len(health_paths)} tracked param leaves; render with "
                  "tools/health_report.py)")
    inject_steps = []
    inject_pattern = ""
    if args.health_inject_nan is not None:
        # STEP[,STEP...][:PATTERN] — each entry fires once, in order; a
        # repeated step (e.g. "3,3") re-poisons after a rollback replays it,
        # which is how the rollback-budget-exhaustion path is exercised
        part = args.health_inject_nan.split(":", 1)
        inject_steps = [int(s) for s in part[0].split(",")]
        inject_pattern = part[1] if len(part) > 1 else ""

    # exact-resume cursor: prefer the checkpoint's data_state (epoch,
    # within-epoch batch cursor, RNG key) over the coarse epoch number, so a
    # mid-epoch resume continues batch-for-batch instead of replaying or
    # skipping work
    data_state = (resume_meta or {}).get("data_state") or {}
    resume_epoch = data_state.get("epoch", (resume_meta or {}).get("epoch", 0))
    pending_skip = data_state.get("epoch_batches", 0) or 0
    # restoring the step counter keeps save/sample cadences and checkpoint
    # rotation continuous across resume (the reference's resume restores its
    # global step through the DeepSpeed engine, train_dalle.py:531-532)
    global_step = (resume_meta or {}).get("global_step", 0) or 0
    if data_state.get("rng_key") is not None:
        key = resilience.decode_rng_key(data_state["rng_key"])
    else:
        key = jax.random.PRNGKey(args.seed + 1)
    if pending_skip and is_root:
        print(f"[resilience] resuming mid-epoch: epoch {resume_epoch}, "
              f"fast-forwarding {pending_skip} batch(es)")

    # async checkpoint writer: serialization/fsync/rename off the step loop
    # (the orbax sharded path is collective and stays synchronous)
    writer = None
    if args.async_checkpoint and not args.sharded_checkpoint:
        writer = resilience.AsyncCheckpointWriter()
    # preemption-safe shutdown: SIGTERM/SIGINT finish the in-flight step,
    # write an emergency checkpoint, and exit EXIT_PREEMPTED (75) so a
    # supervisor can restart with --resume auto
    shutdown = resilience.ShutdownHandler().install()

    def save(path, epoch, keep_n=None, step=None, ds_epoch=0, ds_batches=0):
        # `step` is the NEXT step to run after resume; mid-loop callers pass
        # global_step + 1 (the increment happens at loop end).  ds_epoch /
        # ds_batches are the exact-resume cursor: the epoch a resumed run
        # re-enters and how many of its batches to fast-forward.  The
        # `checkpoint` span covers only the device->host gather (+ enqueue)
        # when the async writer is on — the serialize/fsync tail runs on the
        # writer thread and shows up in checkpoint_write_s instead.
        ds = resilience.data_state_dict(
            epoch=ds_epoch, epoch_batches=ds_batches,
            seed=args.seed, rng_key=key,
        )
        t0 = time.perf_counter()
        health_state = (health_monitor.state_dict()
                        if health_monitor is not None else None)
        fleet_state = (fleet_agg.state_dict() if fleet_agg is not None else None)
        memory_state = (hbm_monitor.state_dict()
                        if hbm_monitor is not None else None)
        with telemetry.span("checkpoint", path=str(path)):
            if args.sharded_checkpoint:
                save_model_sharded(
                    path, state, dalle_cfg, vae_params, vae_cfg, epoch,
                    keep_n=keep_n,
                    global_step=global_step if step is None else step,
                    wandb_run_id=logger.run_id, health_state=health_state,
                    data_state=ds, fleet_state=fleet_state,
                    memory_state=memory_state, topology=live_topology)
            else:
                save_model(
                    path, state, dalle_cfg, vae_params, vae_cfg, epoch,
                    keep_n=keep_n,
                    global_step=global_step if step is None else step,
                    wandb_run_id=logger.run_id, health_state=health_state,
                    data_state=ds, fleet_state=fleet_state,
                    memory_state=memory_state, topology=live_topology,
                    writer=writer)
        obs_metrics.histogram("checkpoint_save_s").observe(time.perf_counter() - t0)
        if writer is None:
            # the async writer counts completions itself (checkpoints_saved)
            obs_metrics.counter("checkpoints_saved").inc()

    # orbax saves are collective (every host writes its shards), so they run
    # on all processes; the npz path writes from the root host only
    save_here = is_root or args.sharded_checkpoint
    first_window = True
    flops_checked = False
    checked_recompiles = 0
    # the plain and diagnostic steps are two executables; the FIRST dispatch
    # of each variant legitimately compiles and must not read as a
    # steady-state recompile alarm (e.g. step 0 is a health step, so the
    # plain executable first compiles at step 1 — after the watcher armed)
    compiled_variants = set()
    # deferred bad-step accounting: the per-step `skipped` flags stay on
    # device and are fetched at the log cadence (by which point those steps
    # have completed), so the guard costs no extra host sync per step
    skip_pending: list = []
    rollback_attempts = 0
    import contextlib as _ctx

    def drain_skips():
        if not skip_pending:
            return
        n = sum(int(s) for s in skip_pending)
        skip_pending.clear()
        if n:
            obs_metrics.counter("nonfinite_step_skips").inc(n)
            if settings.loss_scale is not None:
                obs_metrics.counter("loss_scale_skips").inc(n)
            if is_root:
                print(f"[resilience] skipped {n} poisoned step(s) since "
                      "the last log (nonfinite gradients)")

    def finish_telemetry():
        if tele is not None:
            # fleet=False: exit paths are not step-synchronized across
            # processes — a lone flusher must not block in the fleet gather
            tele.flush(logger, step=global_step, fleet=False)
            tele.close()
        logger.finish()

    def exit_preempted(epoch, epoch_batches):
        """Tail of the graceful-shutdown path (the in-flight step already
        finished): emergency checkpoint, flush it durable, hand the
        supervisor EXIT_PREEMPTED."""
        # counted here, not in the signal handler (registry locks are not
        # signal-safe)
        obs_metrics.counter("shutdown_requests").inc()
        if be.get_world_size() > 1:
            # no cross-process agreement on the signal exists: a peer that
            # checked the flag just before delivery may already be inside
            # step N+1's collectives, and a collective emergency save (orbax,
            # or a gather of cross-host-sharded params) would deadlock
            # against it.  Exit cleanly; resume falls back to the last
            # periodic checkpoint (at most save_every_n_steps of lost work).
            if is_root:
                print("[resilience] multi-process preemption: skipping the "
                      "emergency checkpoint (no cross-process signal "
                      "barrier); resume from the last periodic save",
                      flush=True)
        elif save_here:
            step_file = f"{args.dalle_output_file_name}_step{global_step}.npz"
            save(step_file, epoch, keep_n=args.keep_n_checkpoints,
                 step=global_step + 1, ds_epoch=epoch, ds_batches=epoch_batches)
        if writer is not None:
            writer.flush()
        if is_root:
            print(f"[resilience] preemption checkpoint written; exiting with "
                  f"code {resilience.EXIT_PREEMPTED} (restart with "
                  "--resume auto)", flush=True)
        finish_telemetry()
        raise SystemExit(resilience.EXIT_PREEMPTED)

    try:
        # save-before-train fail-fast (reference train_dalle.py:591-594);
        # flushed through the async writer so a dead output disk still
        # fails before compilation burns minutes
        if save_here:
            save(out_file, resume_epoch,
                 ds_epoch=resume_epoch, ds_batches=pending_skip)
            if writer is not None:
                writer.flush()

        while True:  # rollback retry loop
          try:
            for epoch in range(resume_epoch, args.epochs):
                t_window = time.time()
                window_start = global_step  # reset with t_window: a stale
                # window start would count the previous epoch's tail steps
                # against a dt that excludes their wall time
                skip_now, pending_skip = pending_skip, 0
                batches = data_iter(epoch, skip=skip_now)
                if args.prefetch_batches > 0:
                    # async host->device transfer, overlapping decode + DMA
                    # with the running step (the reference's DataLoader
                    # workers + async .cuda())
                    batches = prefetch_to_device(batches, size=args.prefetch_batches)
                # the cursor counts ABSOLUTE position in the epoch so the
                # data_state written mid-epoch is a valid fast-forward
                epoch_batches = skip_now
                batch_it = iter(batches)
                while True:
                    if injector is not None:
                        injector.at_step(global_step)
                    if tele is not None:
                        tele.begin_step(global_step)
                    if capture is not None:
                        # starts a pending/manual/SIGUSR2 profiler window —
                        # on the training thread, before this step dispatches
                        capture.on_step_start(global_step)
                    with telemetry.span("data_wait"):
                        device_batch = next(batch_it, None)
                    if device_batch is None:
                        if tele is not None:
                            tele.abort_step()  # the wait that found the epoch's end
                        break
                    epoch_batches += 1
                    key, sk = jax.random.split(key)
                    device_batch = {
                        "text": jnp.asarray(device_batch["text"]),
                        "image": jnp.asarray(device_batch["image"]),
                    }
                    recompiles_now = (
                        tele.compile_watcher.recompiles
                        if tele is not None and tele.compile_watcher is not None else 0
                    )
                    if tele is not None and (not flops_checked
                                             or recompiles_now > checked_recompiles):
                        # XLA-vs-analytic FLOPs cross-check: one extra trace (no
                        # second backend compile), shapes taken from the real batch.
                        # Re-checked after every detected recompile — consecutive
                        # divergent checks are what arm the persistent-divergence
                        # alarm (a one-off ragged-batch lowering is not)
                        flops_checked = True
                        checked_recompiles = recompiles_now
                        with telemetry.span("flops_crosscheck"):
                            from dalle_pytorch_tpu.observability import comms as comms_mod
                            from dalle_pytorch_tpu.training.profiling import (
                                dalle_step_flops, matmul_param_count,
                            )

                            # tile granularity: the compiled step's cost
                            # analysis includes the kernels' tile-granular
                            # CostEstimate, so the analytic side must price
                            # whole live tiles or sparse configs drift
                            analytic = dalle_step_flops(
                                dalle_cfg, int(device_batch["text"].shape[0]),
                                matmul_param_count(state.params),
                                granularity="tile",
                            )
                            # comms ledger: analytic bytes/step per mesh axis
                            # from the mesh + sharding settings, published as
                            # gauges + a JSONL event, cross-checked against
                            # cost_analysis bytes-accessed, and priced on the
                            # comms-vs-compute roofline
                            ledger = comms_mod.dalle_step_comms(
                                getattr(step_fn, "mesh", None), state.params,
                                dalle_cfg, int(device_batch["text"].shape[0]),
                                settings=settings,
                                registry=getattr(step_fn, "registry", registry),
                            )
                            ledger_bytes = None
                            if ledger is not None and args.fleet:
                                import math as _math

                                comms_mod.publish_gauges(ledger, obs_metrics.REGISTRY)
                                ledger["roofline"] = comms_mod.comms_roofline(
                                    ledger["total_bytes_per_step"], analytic,
                                    n_chips=_math.prod(ledger["mesh"].values()),
                                )
                                tele.spans.write_event("comms_ledger", **ledger)
                                ledger_bytes = ledger["total_bytes_per_step"]
                            ratio = tele.crosscheck_flops(
                                step_fn, (state, device_batch, sk), analytic,
                                analytic_comms_bytes=ledger_bytes,
                            )
                            if tele.compile_watcher is not None:
                                # re-snapshot: anything the crosscheck itself fired
                                # must not re-trigger it next step
                                checked_recompiles = tele.compile_watcher.recompiles
                            if is_root and ratio is not None:
                                print(f"[telemetry] compiled/analytic FLOPs ratio: "
                                      f"{ratio:.3f}")
                            if is_root and ledger_bytes:
                                roof = ledger["roofline"]  # None on CPU: no peaks
                                print("[fleet] comms ledger: "
                                      + ", ".join(
                                          f"{r['axis']}={r['bytes_per_step'] / 1e6:.2f}MB"
                                          for r in ledger["per_axis"])
                                      + " per step"
                                      + (f" ({roof['bound']}-bound at peak)"
                                         if roof else ""))
                            # HBM ledger refreshed from the LIVE trees (the
                            # pre-distribution pricing estimated the
                            # optimizer moments), cross-checked against the
                            # compiled executable's memory_analysis — one
                            # extra compile, shielded from the recompile
                            # counter — including the donation audit
                            mem_ledger = memory_mod.dalle_step_memory(
                                getattr(step_fn, "mesh", None) or mem_axes,
                                state.params, state.opt_state, dalle_cfg,
                                int(device_batch["text"].shape[0]),
                                settings=settings,
                                registry=getattr(step_fn, "registry", registry),
                            )
                            memory_mod.publish_gauges(
                                mem_ledger, obs_metrics.REGISTRY)
                            tele.spans.write_event("mem_ledger", **mem_ledger)
                            mem_ratio = tele.crosscheck_memory(
                                step_fn, (state, device_batch, sk), mem_ledger,
                            )
                            if is_root and mem_ratio is not None:
                                print(f"[memory] xla/analytic HBM ratio: "
                                      f"{mem_ratio:.3f} (analytic "
                                      f"{mem_ledger['total_bytes'] / 1e9:.2f}GB"
                                      f" per chip)")
                    health_step = bool(args.health_every) and (
                        global_step % args.health_every == 0
                    )
                    if inject_steps and global_step == inject_steps[0]:
                        # test hook: poison one param leaf so the localization path
                        # (finite-mask -> first offending path -> alarm) is exercised.
                        # Each listed step fires ONCE — a transient corruption — so
                        # a divergence rollback replaying this step recovers unless
                        # the spec deliberately repeats it
                        inject_steps.pop(0)
                        state = TrainState(
                            state.step,
                            health_mod.inject_nan(state.params, inject_pattern),
                            state.opt_state,
                        )
                        if is_root:
                            print(f"[health] injected NaN into params "
                                  f"(pattern {inject_pattern!r}) before step {global_step}")
                    new_variant = health_step not in compiled_variants
                    compiled_variants.add(health_step)
                    # shield only post-arm first compiles: pre-arm compiles should
                    # still count toward the compile totals/time
                    suspend = (
                        tele.compile_watcher.suspended()
                        if (new_variant and tele is not None
                            and tele.compile_watcher is not None
                            and tele.compile_watcher.armed)
                        else _ctx.nullcontext()
                    )
                    with telemetry.span("dispatch"), suspend:
                        state, metrics = step_fn(
                            state, device_batch, sk, with_health=health_step
                        )
                    if health_step:
                        # the one deliberate device->host sync of the diagnostics
                        # path: fetch the health pytree, name the leaves, publish
                        with telemetry.span("health_publish"):
                            _, alarms = health_mod.publish_and_observe(
                                metrics.pop("health"), health_paths, health_monitor,
                                global_step, tele=tele, registry=obs_metrics.REGISTRY,
                                echo=print if is_root else None,
                            )
                        if (args.rollback_retries
                                and not args.sharded_checkpoint
                                and any(a["type"] == "sustained_nonfinite"
                                        for a in alarms)):
                            # the run is NOT recovering on its own: rewind to
                            # the last good checkpoint (bounded retries below).
                            # Sharded (orbax) runs keep the pre-rollback
                            # alarm-only behavior — discovery/validation
                            # covers the npz format only
                            raise resilience.RollbackRequested(
                                global_step, "sustained nonfinite diagnostics"
                            )
                    if args.telemetry_sync and tele is not None:
                        # wait for THIS step's result: per-step wall-clock splits
                        # into data_wait / dispatch / block, the attribution the
                        # telemetry report renders.  --telemetry_sync 0 (or
                        # --telemetry off) restores unbounded dispatch-ahead
                        # (block reads as 0)
                        with telemetry.span("block"):
                            jax.block_until_ready(metrics["loss"])
                    if "skipped" in metrics:
                        # defer the fetch: counted at the log cadence by
                        # drain_skips() (no per-step forced sync)
                        skip_pending.append(metrics["skipped"])
                    obs_metrics.counter("train_steps").inc()

                    if global_step % args.log_every_n_steps == 0:
                        with telemetry.span("log"):
                            dt = time.time() - t_window
                            steps_done = global_step - window_start + 1
                            record = {"loss": float(be.average_all(metrics["loss"])), "epoch": epoch}
                            for name in ("moe_load_max_over_mean", "moe_pairs_here", "moe_overflow_share",
                                         "moe_bias_abs_max", "main_loss", "mtp_loss"):
                                if name in metrics:  # a routed trunk's load, a prediction module's
                                    # two losses: fetched with the loss
                                    record[name] = float(metrics[name])
                            if not first_window:
                                # the process's first window spans jit compilation —
                                # minutes for billion-parameter configs — so its rate
                                # is not a throughput measurement
                                record["sample_per_sec"] = args.batch_size * steps_done / max(dt, 1e-9)
                                obs_metrics.gauge("tokens_per_sec").set(
                                    args.batch_size * dalle_cfg.total_seq_len
                                    * steps_done / max(dt, 1e-9)
                                )
                            drain_skips()
                            if "loss_scale" in metrics:
                                obs_metrics.gauge("loss_scale").set(
                                    float(metrics["loss_scale"])
                                )
                            first_window = False
                            t_window = time.time()
                            window_start = global_step + 1
                            logger.log(record, step=global_step)
                            if tele is not None:
                                tele.flush(logger, step=global_step)
                    if args.save_every_n_steps and global_step and global_step % args.save_every_n_steps == 0 and save_here:
                        step_file = f"{args.dalle_output_file_name}_step{global_step}.npz"
                        save(step_file, epoch, keep_n=args.keep_n_checkpoints,
                             step=global_step + 1,
                             ds_epoch=epoch, ds_batches=epoch_batches)
                        if injector is not None and injector.wants_checkpoint_fault():
                            # chaos corrupt/truncate applies to the DURABLE
                            # file, so drain the writer first
                            if writer is not None:
                                writer.flush()
                            injector.after_checkpoint(step_file, global_step)
                    if args.sample_every_n_steps and global_step and global_step % args.sample_every_n_steps == 0 and is_root:
                        with telemetry.span("sample"):
                            _log_sample(logger, state, dalle_cfg, vae_params, vae_cfg, device_batch, tokenizer, global_step)
                    if args.flops_profiler:
                        if global_step == 199:
                            jax.profiler.start_trace("./profile_trace")
                        if global_step == 200:
                            jax.profiler.stop_trace()
                            print("profiler trace written to ./profile_trace; stopping (parity with --flops_profiler)")
                            logger.finish()
                            if tele is not None:
                                tele.close()
                            return state, dalle_cfg
                    if args.fleet_inject_skew > 0:
                        # test hook: make THIS process a straggler (inside
                        # the step window so the skew shows up in dur_s)
                        time.sleep(args.fleet_inject_skew)
                    if capture is not None:
                        capture.on_step_end(global_step)
                    if tele is not None:
                        tele.finish_step(global_step)
                    if shutdown.requested:
                        # the in-flight step finished; leave cleanly with an
                        # emergency checkpoint the supervisor can resume from
                        drain_skips()
                        exit_preempted(epoch, epoch_batches)
                    global_step += 1

                if epoch_batches == 0:
                    # a local-glob spec fails fast at the `assert shards` above, but
                    # remote --wds URLs expand unconditionally and dead shards are
                    # warn-and-continue'd per shard — without this, a typo'd URL
                    # spec would "train" through every epoch in seconds and save an
                    # untrained model (code-review finding, round 5).  (A resume
                    # landing exactly on an epoch boundary has epoch_batches ==
                    # skip_now > 0 and legitimately rolls straight over.)
                    raise RuntimeError(
                        f"epoch {epoch} produced ZERO batches from "
                        f"{args.image_text_folder!r} — every shard failed to stream "
                        "(see '[tar pipeline] skipping' warnings above) or the "
                        "dataset is smaller than one batch"
                    )

                if save_here:
                    save(out_file, epoch + 1, ds_epoch=epoch + 1, ds_batches=0)
                    if writer is not None:
                        writer.flush()  # artifact logging wants the file durable
                    if is_root:
                        logger.log_artifact(out_file, name="trained-dalle", metadata=dalle_cfg.to_dict())
            drain_skips()  # count the tail window's skipped steps too
            break  # all epochs done
          except resilience.RollbackRequested as rb:
            obs_metrics.counter("rollbacks").inc()
            rollback_attempts += 1
            try:
                # release the abandoned data pipeline — the prefetch
                # producer thread holds device batches in its bounded queue
                # that the replay would otherwise leave pinned in HBM
                batch_it.close()
            except Exception:  # noqa: BLE001 — islice etc. have no close
                pass
            if writer is not None:
                writer.flush()
            found = found_meta = None
            if rollback_attempts <= args.rollback_retries:
                # check_finite: a checkpoint saved AFTER the divergence is
                # structurally valid but poisoned — roll past it to the last
                # finite ("good") one
                found, found_meta = resilience.find_latest_valid_checkpoint(
                    out_file, log=print if is_root else None, check_finite=True
                )
            if found is None:
                if is_root:
                    why = ("rollback budget exhausted"
                           if rollback_attempts > args.rollback_retries
                           else "no valid checkpoint to roll back to")
                    print(f"[resilience] {why} after {rb.reason} at step "
                          f"{rb.step}; aborting with exit code "
                          f"{resilience.EXIT_DIVERGED}", flush=True)
                finish_telemetry()
                raise SystemExit(resilience.EXIT_DIVERGED)
            trees_rb, meta_rb = load_checkpoint(
                found, allow_legacy_pickle=args.allow_legacy_pickle
            )
            params_rb = dalle_mod.migrate_param_layout(trees_rb["weights"], dalle_cfg)
            opt_rb = unflatten_like(state.opt_state, trees_rb["opt_state"])
            state = TrainState(
                state.step,
                resilience.place_like(state.params, params_rb),
                resilience.place_like(state.opt_state, opt_rb),
            )
            ds_rb = meta_rb.get("data_state") or {}
            resume_epoch = ds_rb.get("epoch", meta_rb.get("epoch", 0))
            pending_skip = ds_rb.get("epoch_batches", 0) or 0
            global_step = meta_rb.get("global_step", 0) or 0
            key = (resilience.decode_rng_key(ds_rb["rng_key"])
                   if ds_rb.get("rng_key") is not None
                   else jax.random.PRNGKey(args.seed + 1))
            skip_pending.clear()
            if health_monitor is not None:
                health_monitor.load_state_dict(meta_rb.get("health_state"))
            if fleet_agg is not None:
                fleet_agg.load_state_dict(meta_rb.get("fleet_state"))
            if hbm_monitor is not None:
                hbm_monitor.load_state_dict(meta_rb.get("memory_state"))
            if is_root:
                print(f"[resilience] rolled back to {found} (attempt "
                      f"{rollback_attempts}/{args.rollback_retries}) after "
                      f"{rb.reason} at step {rb.step}; resuming at step "
                      f"{global_step}", flush=True)

        if save_here:
            save(out_file, args.epochs, ds_epoch=args.epochs, ds_batches=0)
            if writer is not None:
                writer.flush()
            if is_root:
                logger.log_artifact(out_file, name="trained-dalle-final", metadata=dalle_cfg.to_dict())
    except Exception as e:
        # OOM forensics: RESOURCE_EXHAUSTED at compile time (the first
        # dispatch) or at step time both land here — write the report
        # (ledger + memory_analysis + live stats + suggestions), then exit
        # EXIT_OOM through the finally cleanup below
        if memory_mod.is_oom_error(e):
            oom_bail(e, "compile" if first_window else "train_step",
                     step=global_step)
        raise
    finally:
        shutdown.uninstall()
        if capture is not None:
            capture.close()  # stop an in-flight trace + restore SIGUSR2
        if injector is not None:
            injector.uninstall()  # the global must not leak across main()s
        if writer is not None:
            writer.close()
    if tele is not None:
        # fleet=False: the epoch loop's tail is not step-synchronized
        # (save/sample cadences differ per process role)
        tele.flush(logger, step=global_step, fleet=False)
        if is_root:
            print(f"[telemetry] run summary: {tele.summary()}")
        tele.close()
    logger.finish()
    return state, dalle_cfg


def _log_sample(logger, state, dalle_cfg, vae_params, vae_cfg, batch, tokenizer, step):
    """Generated-sample logging at the sampling cadence (reference
    train_dalle.py:639-649: wandb.Image of a generation for the first
    caption in the batch)."""
    try:
        text = batch["text"][:1]
        images = generate_images(
            state.params, dalle_cfg, vae_params, vae_cfg, text, jax.random.PRNGKey(step)
        )
        arr = np.asarray(vae_registry.to_display(vae_cfg, images[0]))
        caption = tokenizer.decode(np.asarray(text[0]))
        logger.log({"sample_min": float(arr.min()), "sample_max": float(arr.max())},
                   step=step, quiet=True)
        logger.log_images({"image": arr}, step=step, captions={"image": caption})
    except Exception as e:  # sampling must never kill training
        print(f"[sample] generation failed: {e!r}")


def _parse_ids(s):
    if s is None:
        return None
    return tuple(int(x) for x in s.split(","))


if __name__ == "__main__":
    main()
