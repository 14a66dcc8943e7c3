#!/usr/bin/env python
"""Single-config flagship throughput probe (one process = one clean HBM arena).

Used to sweep remat policy x batch x geometry for the depth-64 flagship
(BASELINE.md row 1).  Prints one JSON line with step time, honest MFU, and
peak HBM.  Run repeatedly from a driver shell, e.g.:

    for p in full flash flash_qkv; do python tools/flagship_sweep.py --policy $p; done
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
    ap.add_argument("--dim", type=int, default=1280)
    ap.add_argument("--depth", type=int, default=64)
    ap.add_argument("--heads", type=int, default=10)
    ap.add_argument("--dim_head", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ga", type=int, default=1, help="gradient accumulation steps")
    ap.add_argument("--policy", default="full",
                    choices=["full", "flash", "flash_qkv", "flash_qkv_ff"])
    ap.add_argument("--execution", default="remat", choices=["remat", "sequential"])
    ap.add_argument("--grad_dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--param_dtype", default="float32", choices=["float32", "bfloat16"],
                    help="bfloat16 = no f32 master, stochastic-rounded updates")
    ap.add_argument("--opt", default="adafactor", choices=["adafactor", "adam"])
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()

    from dalle_pytorch_tpu.cli.common import enable_compile_cache
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models.dalle import DALLEConfig
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step
    from dalle_pytorch_tpu.training.profiling import (
        chip_peak_flops, dalle_step_flops, matmul_param_count,
    )

    enable_compile_cache()
    try:  # init OOMs for billion-param configs must yield a JSON row too
        cfg = DALLEConfig(
            dim=args.dim, depth=args.depth, heads=args.heads, dim_head=args.dim_head,
            num_text_tokens=10000, text_seq_len=256,
            num_image_tokens=8192, image_fmap_size=32,
            attn_types=("full", "axial_row", "axial_col", "conv_like"),
            shift_tokens=True, rotary_emb=True,
            execution=args.execution, scan_layers=True, remat_policy=args.policy,
            share_input_output_emb=True,
        )
        params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)

        def loss_fn(p, b, key):
            return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True)

        opt = optax.adafactor(1e-3) if args.opt == "adafactor" else optax.adam(1e-4)
        settings = StepSettings(
            compute_dtype=jnp.bfloat16,
            grad_dtype=jnp.bfloat16 if args.grad_dtype == "bfloat16" else jnp.float32,
            grad_accum=args.ga,
            param_dtype=jnp.bfloat16 if args.param_dtype == "bfloat16" else None,
        )
        init_fn, step_fn = make_train_step(loss_fn, opt, settings=settings)
        state = init_fn(params)
        del params

        batch = args.batch * args.ga
        bd = {
            "text": jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.text_seq_len), 0, cfg.num_text_tokens),
            "image_codes": jax.random.randint(jax.random.PRNGKey(2), (batch, cfg.image_seq_len), 0, cfg.num_image_tokens),
        }

        n_matmul = matmul_param_count(state.params)
        for i in range(max(args.warmup, 1)):  # >=1: the timed loop must not include compile
            state, m = step_fn(state, bd, jax.random.PRNGKey(i))
        float(m["loss"])
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, m = step_fn(state, bd, jax.random.PRNGKey(10 + i))
        loss = float(m["loss"])
        dt = (time.perf_counter() - t0) / args.steps
    except Exception as e:  # OOM etc.
        print(json.dumps({"config": vars(args), "error": str(e)[:300]}))
        return

    flops = dalle_step_flops(cfg, batch, n_matmul, granularity="tile")
    peak = chip_peak_flops()  # None on CPU: a CPU time is not an MFU
    stats = jax.local_devices()[0].memory_stats() or {}
    print(json.dumps({
        "config": vars(args),
        "params_million": round(sum(x.size for x in jax.tree_util.tree_leaves(state.params)) / 1e6, 1),
        "step_time_s": round(dt, 4),
        "img_tok_per_sec": round(batch * cfg.image_seq_len / dt, 1),
        "mfu": round(flops / dt / peak, 4) if peak else None,
        "peak_hbm_gb": round(stats.get("peak_bytes_in_use", 0) / 2**30, 2),
        "loss": round(loss, 4),
    }))


if __name__ == "__main__":
    main()
