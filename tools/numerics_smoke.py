#!/usr/bin/env python
"""At-scale numerics smoke.

Runs N real optimization steps at dim>=1024 with the full low-memory recipe —
bf16 compute, bf16 grads, PURE-bf16 param storage with stochastic rounding,
adafactor — on a small repeating batch, and asserts the loss actually
DECREASES.  This is where subtle numerics first bite (sub-ulp updates,
factored second moments, rounding bias); throughput rows time 4 steps on
random weights and cannot see any of it.

Prints one JSON line with the loss curve (first/last and a decimated trace).

    python tools/numerics_smoke.py                  # flagship-width, TPU
    python tools/numerics_smoke.py --dim 128 --depth 2 --steps 40   # CPU check
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import optax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=1152)
    ap.add_argument("--depth", type=int, default=8,
                    help="depth 8 keeps the smoke under ~15 min while the "
                         "width (where the numerics live) stays flagship")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim_head", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", default="flash_qkv")
    ap.add_argument("--param_dtype", default="bfloat16", choices=["float32", "bfloat16"])
    ap.add_argument("--text_tokens", type=int, default=10000)
    args = ap.parse_args()

    from dalle_pytorch_tpu.cli.common import enable_compile_cache
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    enable_compile_cache()
    small = args.dim < 512  # CPU harness check
    try:
        cfg = DALLEConfig(
            dim=args.dim, depth=args.depth, heads=args.heads, dim_head=args.dim_head,
            num_text_tokens=args.text_tokens,
            text_seq_len=64 if small else 256,
            num_image_tokens=512 if small else 8192,
            image_fmap_size=8 if small else 32,
            attn_types=("full", "axial_row", "axial_col", "conv_like"),
            shift_tokens=True, rotary_emb=True,
            execution="remat", scan_layers=True, remat_policy=args.policy,
            share_input_output_emb=True,
        )
        params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)

        def loss_fn(p, b, key):
            return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True)

        settings = StepSettings(
            compute_dtype=jnp.bfloat16,
            grad_dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16 if args.param_dtype == "bfloat16" else None,
        )
        init_fn, step_fn = make_train_step(loss_fn, optax.adafactor(args.lr), settings=settings)
        state = init_fn(params)
        del params

        # small FIXED dataset of 4 batches, cycled — the loss on memorizable
        # data must fall if and only if updates actually accumulate in the
        # bf16 weights (the whole point of stochastic rounding)
        batches = []
        for i in range(4):
            kt, ki = jax.random.split(jax.random.PRNGKey(100 + i))
            batches.append({
                "text": jax.random.randint(kt, (args.batch, cfg.text_seq_len), 0, cfg.num_text_tokens),
                "image_codes": jax.random.randint(ki, (args.batch, cfg.image_seq_len), 0, cfg.num_image_tokens),
            })

        t0 = time.perf_counter()
        losses = []
        for i in range(args.steps):
            state, m = step_fn(state, batches[i % len(batches)], jax.random.PRNGKey(i))
            if i % 5 == 0 or i == args.steps - 1:
                losses.append((i, round(float(m["loss"]), 4)))
        dt = time.perf_counter() - t0
    except Exception as e:
        print(json.dumps({"config": vars(args), "error": str(e)[:300]}))
        raise SystemExit(1)

    first = losses[0][1]
    tail = [v for _, v in losses[-4:]]
    last = sum(tail) / len(tail)
    decreased = last < first * 0.95
    out = {
        "config": vars(args),
        "backend": jax.default_backend(),
        "steps": args.steps,
        "loss_first": first,
        "loss_last_mean4": round(last, 4),
        "decreased": bool(decreased),
        "wall_s": round(dt, 1),
        "loss_curve": losses,
    }
    print(json.dumps(out))
    if not decreased:
        raise SystemExit(2)


if __name__ == "__main__":
    main()
