"""Image generation CLI — parity with /root/reference/generate.py: loads a
trained checkpoint ({hparams, vae_params, weights, vae_class_name, version}),
validates it, splits prompts on '|', optionally completes prompts first
(--gentxt), samples in batch_size chunks, and saves PNGs per prompt
directory."""
from __future__ import annotations

import argparse
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.data import tokenizer as tokenizer_mod
from dalle_pytorch_tpu.models import vae_registry
from dalle_pytorch_tpu.models.sampling import generate_images, generate_texts
from dalle_pytorch_tpu.observability import memory as memory_mod
from dalle_pytorch_tpu.training import resilience


def build_parser():
    parser = argparse.ArgumentParser(description="Generate images from a trained DALL-E")
    parser.add_argument("--dalle_path", type=str, required=True)
    parser.add_argument("--text", type=str, required=True, help="prompt(s), | separated")
    parser.add_argument("--num_images", type=int, default=128)
    parser.add_argument("--batch_size", type=int, default=4)
    parser.add_argument("--top_k", type=float, default=0.9, help="filter threshold (0.5-1.0)")
    parser.add_argument("--temperature", type=float, default=1.0)
    parser.add_argument("--cond_scale", type=float, default=1.0, help="classifier-free guidance scale")
    parser.add_argument("--outputs_dir", type=str, default="./outputs")
    parser.add_argument("--gentxt", action="store_true", help="complete the prompt with DALL-E first")
    parser.add_argument("--taming", action="store_true",
                        help="the checkpoint's VAE is a taming VQGAN (reference-format "
                             "checkpoints need its yaml via --vqgan_config_path)")
    parser.add_argument("--vqgan_config_path", type=str, default=None,
                        help="taming config yaml for a reference VQGanVAE checkpoint")
    parser.add_argument("--vqgan_model_path", type=str, default=None,
                        help="unused for conversion (weights are embedded in the "
                             "checkpoint); accepted for reference CLI parity")
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--hug", action="store_true")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--allow_legacy_pickle", action="store_true",
                        help="permit loading pre-v3 (pickled-treedef) "
                             "checkpoints — trusted sources only (legacy "
                             "formats can execute code on load)")
    parser.add_argument("--engine", action="store_true",
                        help="route sampling through the continuous-batching "
                             "serving engine (serving/) instead of the batch "
                             "sampler: each image is its own request with its "
                             "own PRNG stream (bit-identical to a batch-1 "
                             "fused sample with that key), so the CLI and the "
                             "service share one code path")
    parser.add_argument("--engine_slots", type=int, default=4,
                        help="decode slots for --engine")
    parser.add_argument("--engine_block_size", type=int, default=64,
                        help="KV pool block size (tokens) for --engine")
    parser.add_argument("--spec_k", type=int, default=0,
                        help="self-speculative decoding: draft this many "
                             "tokens per round through a shallow layer "
                             "prefix, verify in one full pass (0 disables; "
                             "greedy-exact, so images are bit-identical)")
    parser.add_argument("--spec_draft_layers", type=int, default=None,
                        help="draft-prefix depth (default depth // 2)")
    return parser


def get_tokenizer(args):
    if args.chinese:
        return tokenizer_mod.ChineseTokenizer()
    if args.hug:
        return tokenizer_mod.HugTokenizer(args.bpe_path)
    if args.bpe_path is not None:
        suffix = Path(args.bpe_path).suffix
        return (
            tokenizer_mod.HugTokenizer(args.bpe_path)
            if suffix == ".json"
            else tokenizer_mod.YttmTokenizer(args.bpe_path)
        )
    return tokenizer_mod.tokenizer


def main(argv=None):
    args = build_parser().parse_args(argv)
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()

    path = Path(args.dalle_path)
    from dalle_pytorch_tpu.cli.common import load_dalle_bundle

    dalle_cfg, params, vae_cfg, vae_params = load_dalle_bundle(
        path, allow_legacy_pickle=args.allow_legacy_pickle,
        vqgan_config_path=args.vqgan_config_path,
    )

    tokenizer = get_tokenizer(args)
    from dalle_pytorch_tpu.cli.common import warn_vocab_mismatch

    warn_vocab_mismatch(dalle_cfg.num_text_tokens, tokenizer)
    key = jax.random.PRNGKey(args.seed)
    outputs_dir = Path(args.outputs_dir)

    # sampling-path HBM ledger: params + the KV cache the cached decode loop
    # carries + the per-position logits — the numbers an OOM report needs
    # (the KV cache is linear in --batch_size, the usual lever)
    mem_ledger = memory_mod.sampling_memory_ledger(
        dalle_cfg, args.batch_size, params
    )

    def oom_bail(e):
        from dalle_pytorch_tpu.observability.xla import record_memory_gauges

        try:
            live = record_memory_gauges()
        except Exception:
            live = None
        report = memory_mod.write_oom_report(
            str(outputs_dir), error=e, phase="sampling", ledger=mem_ledger,
            live_stats=live,
            context={"batch_size": args.batch_size,
                     "num_images": args.num_images,
                     "cond_scale": args.cond_scale},
        )
        print(f"[memory] OUT OF MEMORY during sampling: forensic report -> "
              f"{report or '<unwritable>'}; exiting with code "
              f"{resilience.EXIT_OOM} (shrink --batch_size)", flush=True)
        raise SystemExit(resilience.EXIT_OOM)

    engine = None
    if args.engine:
        from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

        engine = GenerationEngine(
            params, dalle_cfg, vae_params, vae_cfg,
            engine_cfg=EngineConfig(num_slots=args.engine_slots,
                                    block_size=args.engine_block_size,
                                    filter_thres=args.top_k,
                                    spec_k=args.spec_k,
                                    spec_draft_layers=args.spec_draft_layers),
        )

    paths = []
    try:
        return _generate_all(args, params, dalle_cfg, vae_params, vae_cfg,
                             tokenizer, key, outputs_dir, paths, engine=engine)
    except Exception as e:
        if memory_mod.is_oom_error(e):
            oom_bail(e)
        raise


def _generate_all(args, params, dalle_cfg, vae_params, vae_cfg, tokenizer,
                  key, outputs_dir, paths, engine=None):
    for raw_text in args.text.split("|"):
        raw_text = raw_text.strip()
        if args.gentxt:
            prompt_ids = jnp.asarray(tokenizer.tokenize(raw_text, dalle_cfg.text_seq_len, truncate_text=True))
            n0 = int((np.asarray(prompt_ids)[0] != 0).sum())
            key, gk = jax.random.split(key)
            completed = generate_texts(params, dalle_cfg, gk, text=prompt_ids[:, :max(n0, 1)])
            pad_tokens = set(
                range(dalle_cfg.num_text_tokens_padded - dalle_cfg.text_seq_len,
                      dalle_cfg.num_text_tokens_padded)
            )
            raw_text = tokenizer.decode(np.asarray(completed[0]), pad_tokens=pad_tokens)
            print(f"completed text: {raw_text}")

        text_tokens = tokenizer.tokenize(raw_text, dalle_cfg.text_seq_len, truncate_text=True)
        text_tokens = np.repeat(text_tokens, args.num_images, axis=0)

        out_dir = outputs_dir / raw_text.replace(" ", "_")[:100]
        out_dir.mkdir(parents=True, exist_ok=True)

        produced = 0
        for i in range(0, args.num_images, args.batch_size):
            chunk = jnp.asarray(text_tokens[i : i + args.batch_size])
            key, sk = jax.random.split(key)
            if engine is not None:
                # one request per image, each on its own derived key — each
                # is bit-identical to a batch-1 fused sample with that key
                row_keys = jax.random.split(sk, chunk.shape[0])
                reqs = engine.generate(
                    np.asarray(chunk), keys=list(row_keys),
                    temperature=args.temperature, cond_scale=args.cond_scale,
                )
                images = jnp.asarray(np.concatenate([r.images for r in reqs]))
            else:
                images = generate_images(
                    params, dalle_cfg, vae_params, vae_cfg, chunk, sk,
                    filter_thres=args.top_k, temperature=args.temperature,
                    cond_scale=args.cond_scale, spec_k=args.spec_k,
                    spec_draft_layers=args.spec_draft_layers,
                )
            from PIL import Image

            # display space (the reference's save_image(normalize=True),
            # generate.py:138-141 — DiscreteVAE decodes into normalized space)
            images = vae_registry.to_display(vae_cfg, images)
            for img in np.asarray(images):
                arr = (np.clip(img, 0, 1) * 255).astype(np.uint8)
                fp = out_dir / f"{produced}.png"
                Image.fromarray(arr.squeeze()).save(fp)
                paths.append(fp)
                produced += 1

        print(f"created {produced} images at {str(out_dir)}")
    return paths


if __name__ == "__main__":
    main()
