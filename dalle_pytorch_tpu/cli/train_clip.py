"""CLIP training CLI.

The reference ships the CLIP model and README usage but no trainer
(/root/reference/README.md:262-304); generations are reranked with an
externally-trained CLIP.  This trainer closes that gap using the same data
pipeline and mesh-sharded step as train_dalle."""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import optax

from dalle_pytorch_tpu.data import tokenizer as tokenizer_mod
from dalle_pytorch_tpu.data.loader import TextImageDataset, iterate_batches
from dalle_pytorch_tpu.models import clip as clip_mod
from dalle_pytorch_tpu.models.clip import CLIPConfig
from dalle_pytorch_tpu.parallel import backend as backend_mod
from dalle_pytorch_tpu.parallel.mesh import MeshConfig
from dalle_pytorch_tpu.parallel.train_step import StepSettings
from dalle_pytorch_tpu.training.checkpoint import save_checkpoint, to_host
from dalle_pytorch_tpu.training.logging import MetricLogger
from dalle_pytorch_tpu.version import __version__


def build_parser():
    parser = argparse.ArgumentParser(description="Train CLIP on text/image pairs")
    parser.add_argument("--image_text_folder", type=str, required=True)
    parser.add_argument("--truncate_captions", action="store_true")
    parser.add_argument("--clip_output_file_name", type=str, default="clip")
    parser.add_argument("--dim_text", type=int, default=512)
    parser.add_argument("--dim_image", type=int, default=512)
    parser.add_argument("--dim_latent", type=int, default=512)
    parser.add_argument("--text_enc_depth", type=int, default=6)
    parser.add_argument("--text_seq_len", type=int, default=256)
    parser.add_argument("--text_heads", type=int, default=8)
    parser.add_argument("--visual_enc_depth", type=int, default=6)
    parser.add_argument("--visual_heads", type=int, default=8)
    parser.add_argument("--visual_image_size", type=int, default=256)
    parser.add_argument("--visual_patch_size", type=int, default=32)
    parser.add_argument("--epochs", type=int, default=20)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--learning_rate", type=float, default=1e-3)
    parser.add_argument("--clip_grad_norm", type=float, default=0.5)
    parser.add_argument("--bf16", action="store_true")
    parser.add_argument("--zero_stage", type=int, default=0, choices=[0, 1, 2, 3])
    parser.add_argument("--mesh_dp", type=int, default=-1)
    parser.add_argument("--mesh_fsdp", type=int, default=1)
    parser.add_argument("--mesh_tp", type=int, default=1)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--wandb", action="store_true")
    parser.add_argument("--save_every_n_steps", type=int, default=1000)
    return backend_mod.wrap_arg_parser(parser)


def main(argv=None):
    args = build_parser().parse_args(argv)
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()
    be = backend_mod.set_backend_from_args(args)
    be.initialize()
    is_root = be.is_root_worker()

    tokenizer = tokenizer_mod.tokenizer
    cfg = CLIPConfig(
        dim_text=args.dim_text, dim_image=args.dim_image, dim_latent=args.dim_latent,
        num_text_tokens=tokenizer.vocab_size,
        text_enc_depth=args.text_enc_depth, text_seq_len=args.text_seq_len,
        text_heads=args.text_heads, visual_enc_depth=args.visual_enc_depth,
        visual_heads=args.visual_heads, visual_image_size=args.visual_image_size,
        visual_patch_size=args.visual_patch_size,
    )
    params = clip_mod.init_clip(jax.random.PRNGKey(args.seed), cfg)

    dataset = TextImageDataset(
        args.image_text_folder, text_len=cfg.text_seq_len,
        image_size=cfg.visual_image_size, truncate_captions=args.truncate_captions,
        tokenizer=tokenizer, shuffle=True,
    )
    assert len(dataset) > 0, "dataset is empty"
    be.check_batch_size(args.batch_size)

    def loss_fn(p, batch, key):
        mask = batch["text"] != 0
        return clip_mod.forward(p, cfg, batch["text"], batch["image"],
                                text_mask=mask, return_loss=True)

    settings = StepSettings(
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        clip_grad_norm=args.clip_grad_norm, zero_stage=args.zero_stage,
    )
    state, step_fn, _, _ = be.distribute(
        loss_fn=loss_fn, params=params, optimizer=optax.adam(args.learning_rate),
        mesh_config=MeshConfig(args.mesh_dp, args.mesh_fsdp, args.mesh_tp, 1),
        settings=settings,
    )

    logger = MetricLogger(run_name=args.clip_output_file_name, use_wandb=args.wandb,
                          config=cfg.to_dict(), is_root=is_root)

    def save(path):
        save_checkpoint(path, trees={"weights": to_host(state.params)},
                        meta={"hparams": cfg.to_dict(), "version": __version__})

    if is_root:
        save(f"{args.clip_output_file_name}.pt")

    key = jax.random.PRNGKey(args.seed + 1)
    step = 0
    for epoch in range(args.epochs):
        for batch in iterate_batches(
            dataset, args.batch_size, seed=args.seed + epoch,
            process_index=be.get_rank(), process_count=be.get_world_size(),
        ):
            key, sk = jax.random.split(key)
            state, metrics = step_fn(
                state, {"text": jnp.asarray(batch["text"]), "image": jnp.asarray(batch["image"])}, sk
            )
            if step % 10 == 0:
                logger.log({"loss": float(be.average_all(metrics["loss"])), "epoch": epoch}, step=step)
            if args.save_every_n_steps and step and step % args.save_every_n_steps == 0 and is_root:
                save(f"{args.clip_output_file_name}.pt")
            step += 1
        if is_root:
            save(f"{args.clip_output_file_name}.pt")
    logger.finish()
    return state, cfg


if __name__ == "__main__":
    main()
