#!/usr/bin/env python
"""Where does flagship wall-time go?  Times the flash attention kernel
(fwd+bwd) in isolation at flagship shapes, per pattern type, and compares
the implied 64-layer attention share against the whole-step measurement and
against the FLOPs model's attention share.  If wall-share >> flop-share the
kernel (launch overhead, small-K tile matmuls, dead-tile bookkeeping) is the
next optimization target, not remat.

    PYTHONPATH=. python tools/attn_share.py --dim 1152 --heads 8 --batch 8
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim_head", type=int, default=128)
    ap.add_argument("--seq", type=int, default=1280)
    ap.add_argument("--fmap", type=int, default=32)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()

    from dalle_pytorch_tpu.cli.common import enable_compile_cache
    from dalle_pytorch_tpu.kernels.flash_attention import flash_attention
    from dalle_pytorch_tpu.ops.masks import _pattern_mask_np

    enable_compile_cache()
    b, h, n, d = args.batch, args.heads, args.seq, args.dim_head
    bh = b * h
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, n, d), jnp.bfloat16)
        for i in range(3)
    )

    def bench_one(name, mask_np):
        def fwd(q, k, v):
            return flash_attention(q, k, v, mask=mask_np, causal=True).sum()

        g = jax.jit(jax.grad(fwd, argnums=(0, 1, 2)))
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, mask=mask_np, causal=True))
        out = f(q, k, v)
        float(jnp.sum(out.astype(jnp.float32)))  # force
        dq, dk, dv = g(q, k, v)
        float(jnp.sum(dq.astype(jnp.float32)))
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = f(q, k, v)
        float(jnp.sum(out.astype(jnp.float32)))
        t_f = (time.perf_counter() - t0) / args.steps
        t0 = time.perf_counter()
        for _ in range(args.steps):
            dq, dk, dv = g(q, k, v)
        float(jnp.sum(dq.astype(jnp.float32)))
        t_fb = (time.perf_counter() - t0) / args.steps

        if mask_np is None:
            density = (np.tril(np.ones((n, n))) > 0).mean()
        else:
            causal = np.tril(np.ones((n, n), bool))
            density = (np.asarray(mask_np) & causal).mean()
        flops_f = 4.0 * bh * n * n * d * density  # QK^T + PV on live elements
        return {
            "pattern": name,
            "fwd_ms": round(t_f * 1e3, 3),
            "fwd_bwd_ms": round(t_fb * 1e3, 3),
            "live_density": round(float(density), 4),
            "fwd_tflops_eff": round(flops_f / t_f / 1e12, 2),
        }

    rows = [bench_one("full", None)]
    for t in ("axial_row", "axial_col", "conv_like"):
        rows.append(bench_one(t, _pattern_mask_np(t, n, args.fmap, 11, 1)))

    # 64-layer cycle = 16x each pattern; fwd happens once + bwd pass
    per_layer = {r["pattern"]: r for r in rows}
    cycle = ["full", "axial_row", "axial_col", "conv_like"]
    step_attn_s = sum(16 * per_layer[t]["fwd_bwd_ms"] for t in cycle) / 1e3
    print(json.dumps({
        "config": vars(args),
        "rows": rows,
        "implied_depth64_attn_fwd_bwd_s": round(step_attn_s, 4),
        "note": "compare against flagship step_time_s; fwd-only share adds "
                "one more fwd per layer under full remat",
    }))


if __name__ == "__main__":
    main()
