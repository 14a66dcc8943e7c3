"""Traffic kind `train_steps_mtp`: `train_steps` for a loss that returns aux
and names parameters for a rule (a bias-balanced router, a prediction module).

The loop, the window and the rate are `train_steps.run`'s, line for line (one
step always in flight behind the one being waited for; the rate is all the
window's steps over the time from the first starting to the last one's loss
arriving on the host; the traced stretch follows the window).  What differs,
and why the kind exists (`train_steps.py` is not this PR's to edit; PERF.md
section 7 names the two kinds for the `benchmark` issue that folds them):
  * the loss is `forward(return_aux=True)` and the step gets the
    configuration's `param_rule`, so the balancing bias moves as in training;
  * each window step's `moe_pairs_here`, `moe_overflow_share`, `main_loss`,
    `mtp_loss` and `moe_bias_abs_max` are kept (device scalars, fetched after
    the window closes) and land in `records` and in the line's `window` detail;
  * no batch is used twice: the window starts behind the warm-up's two, and
    the 128 batches are one vmapped draw (the same values as train_steps'
    draws one by one, which would be 256 generators to compile);
  * `correct` is decided by harness/correct_mtp.py, which sees the module.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import build, correct_mtp, device, stats
from benchmark.kinds.train_steps import _optimizer

KEPT = ("moe_pairs_here", "moe_overflow_share", "moe_load_max_over_mean", "moe_bias_abs_max",
        "main_loss", "mtp_loss")
WARM_UP = 2


def run(sizes: dict, traffic: dict, seed: int, seconds: float, tracer, compiles) -> dict:
    """Returns {"end_to_end": {...}, "records": {...}, "attempted", "failed", "correct"}."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    recipe = sizes["train_recipe"]
    cfg = build.dalle_config(sizes, execution=recipe["execution"],
                             scan_layers=recipe["scan_layers"],
                             remat_policy=recipe.get("remat_policy", "full"))
    micro, accum = int(traffic["microbatch"]), int(traffic["grad_accum"])
    batch = micro * accum
    param_dtype = build.dtype(recipe["param_dtype"])

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True,
                                 return_aux=True)

    settings = StepSettings(
        compute_dtype=build.dtype(recipe["compute_dtype"]),
        grad_dtype=build.dtype(recipe["grad_dtype"]),
        grad_accum=accum,
        param_dtype=param_dtype if param_dtype != jnp.float32 else None,
    )
    init_fn, step_fn = make_train_step(loss_fn, _optimizer(recipe), settings=settings,
                                       param_rule=dalle_mod.param_rule(cfg))

    # weights, optimizer state and every batch: on the device, from the seed,
    # in two jitted calls
    state = jax.jit(lambda k: init_fn(dalle_mod.init_dalle(k, cfg)))(build.seed_key(seed, 0))
    n_batches = int(traffic["distinct_batches"])

    def make_batches(k):
        # train_steps' draws, value for value, as ONE vmapped draw: 128 batches
        # drawn one by one are 256 generators to compile (47 s on the CPU)
        kt, ki = jax.random.split(k)

        def one(i):
            return {"text": jax.random.randint(jax.random.fold_in(kt, i), (batch, cfg.text_seq_len),
                                               1, cfg.num_text_tokens, jnp.int32),
                    "image_codes": jax.random.randint(jax.random.fold_in(ki, i),
                                                      (batch, cfg.image_seq_len),
                                                      0, cfg.num_image_tokens, jnp.int32)}

        drawn = jax.vmap(one)(jnp.arange(n_batches))
        return [jax.tree_util.tree_map(lambda a: a[i], drawn) for i in range(n_batches)]

    batches = jax.jit(make_batches)(build.seed_key(seed, 2))
    # warm-up: the compile, then one warm step for the time estimate
    for i in range(WARM_UP):
        t_w = time.monotonic()
        state, m = step_fn(state, batches[i % n_batches], build.raw_key(seed, i))
        float(m["loss"])
        est = time.monotonic() - t_w
    dispatch_ms, done_t, losses, skipped, kept = [], [], [], 0, []
    pending: deque = deque()
    n = 0

    def dispatch_one() -> None:
        nonlocal state, n
        with tracer.span("dispatch"):
            td = time.perf_counter()
            state, m = step_fn(state, batches[(WARM_UP + n) % n_batches],
                               build.raw_key(seed, WARM_UP + n))
            dispatch_ms.append((time.perf_counter() - td) * 1e3)
        pending.append(m)
        n += 1

    def finish_one() -> None:
        nonlocal skipped
        with tracer.span("wait_loss"):
            m0 = pending.popleft()
            losses.append(float(m0["loss"]))  # the loss arriving on the host ends the step
            done_t.append(time.monotonic())
            skipped += int(m0.get("skipped", 0))
            kept.append({k: m0[k] for k in KEPT if k in m0})  # still on the device

    gc.collect()
    gc.freeze()
    compiles.armed = True
    t0 = time.monotonic()
    while True:
        dispatch_one()
        if len(pending) > 1:
            finish_one()
        if done_t:
            est = (done_t[-1] - t0) / len(done_t)
        if (n + 1) * est > seconds:
            break
    while pending:
        finish_one()
    compiles.armed = False
    at_close = device.memory_snapshot()
    t_end = done_t[-1]
    steps, window_dispatch_ms, window_losses, window_skipped = n, dispatch_ms[:], losses[:], skipped
    per_step = {k: [float(m[k]) for m in kept[:steps]] for k in KEPT if kept and k in kept[0]}

    if tracer.enabled:
        # the traced stretch follows the window, in the same chained rhythm:
        # starting and stopping the profiler stalls the host for seconds, and
        # inside the window that would be read as the system's own stall
        dispatch_one()
        tracer.start()
        for _ in range(int(traffic["trace_steps"]) + 1):
            dispatch_one()
            finish_one()
        tracer.stop()
        while pending:
            finish_one()

    tokens = batch * cfg.image_seq_len * steps
    finite = bool(np.isfinite(window_losses).all())
    ok, detail = correct_mtp.train_forward_agrees(state.params, cfg, sizes, settings.compute_dtype, seed)
    window_detail = {"steps": steps, "elapsed_s": t_end - t0,
                     "batches_used_twice": max(0, WARM_UP + n - n_batches)}  # 0 at a real size
    for k, xs in per_step.items():
        window_detail[k] = {"first8_mean": float(np.mean(xs[:8])), "last8_mean": float(np.mean(xs[-8:])),
                            "max": float(np.max(xs))}
    return {
        "end_to_end": {"train_img_tok_per_s": stats.rate_over_span(tokens, t0, t_end)},
        "records": {
            "steps": steps, "batch": batch, "elapsed_s": t_end - t0,
            "host_dispatch_ms": window_dispatch_ms, "window_compiles": compiles.count,
            "losses": window_losses, "correct_detail": detail, "memory_at_close": at_close,
            "window_detail": window_detail, **per_step,
        },
        "attempted": steps, "failed": window_skipped, "correct": bool(ok and finite), "t_open": t0,
    }
