"""Traffic kind `train_steps_routed`: `train_steps_mtp`'s loop, window, rate,
traced stretch, fresh batches and kept aux (each window step's
`moe_pairs_here`, `moe_overflow_share`, `moe_load_max_over_mean`), for a
routed trunk WITHOUT a prediction module.  What differs is `correct`, decided
by harness/correct_routed.py, and what it reads:
  * the forward on the parameters the window left, against the plain
    reference (`correct_mtp.py` reads the module's logits, and `correct.py`'s
    RMS limit of 0.03 lets the reference in e4m3 products through);
  * the TIMED step itself: once the window and the traced stretch are over,
    it is run again from the state the window started from, on the window's
    first batch; its accumulated gradient is held to the reference's, and
    the parameters' change to a plain Adam step on that gradient.  That needs
    the step function in hand, which `train_steps_mtp.run` keeps to itself.

The window's loop is `train_steps_mtp.run`'s, line for line: an accepted
kind is not edited by the change that adds a cell, so until the three train
kinds are folded into one with a comparison argument (PERF.md section 7),
tests/benchmark/test_bench_rehearsal_laguna.py holds this loop equal to that
one.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import build, correct_routed, device, stats
from benchmark.kinds.train_steps import _optimizer
from benchmark.kinds.train_steps_mtp import KEPT, WARM_UP


def build_step(sizes: dict, traffic: dict):
    """(cfg, settings, init, step_fn) of the cell: `init(key)` the jitted
    weights and optimizer state, `step_fn` the program's own
    `make_train_step` under the configuration's recipe."""
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step

    recipe = sizes["train_recipe"]
    cfg = build.dalle_config(sizes, execution=recipe["execution"],
                             scan_layers=recipe["scan_layers"],
                             remat_policy=recipe.get("remat_policy", "full"))
    param_dtype = build.dtype(recipe["param_dtype"])

    def loss_fn(p, b, key):
        return dalle_mod.forward(p, cfg, b["text"], b["image_codes"], return_loss=True,
                                 return_aux=True)

    settings = StepSettings(
        compute_dtype=build.dtype(recipe["compute_dtype"]),
        grad_dtype=build.dtype(recipe["grad_dtype"]),
        grad_accum=int(traffic["grad_accum"]),
        param_dtype=param_dtype if param_dtype != jnp.float32 else None,
    )
    init_fn, step_fn = make_train_step(loss_fn, _optimizer(recipe), settings=settings,
                                       param_rule=dalle_mod.param_rule(cfg))
    return cfg, settings, jax.jit(lambda k: init_fn(dalle_mod.init_dalle(k, cfg))), step_fn


def draw_batches(cfg, batch: int, n_batches: int, k):
    """`n_batches` batches of `batch` seeded random sequences, as ONE vmapped
    draw (train_steps' values, drawn one by one there)."""
    kt, ki = jax.random.split(k)

    def one(i):
        return {"text": jax.random.randint(jax.random.fold_in(kt, i), (batch, cfg.text_seq_len),
                                           1, cfg.num_text_tokens, jnp.int32),
                "image_codes": jax.random.randint(jax.random.fold_in(ki, i),
                                                  (batch, cfg.image_seq_len),
                                                  0, cfg.num_image_tokens, jnp.int32)}

    drawn = jax.vmap(one)(jnp.arange(n_batches))
    return [jax.tree_util.tree_map(lambda a: a[i], drawn) for i in range(n_batches)]


def run(sizes: dict, traffic: dict, seed: int, seconds: float, tracer, compiles) -> dict:
    """Returns {"end_to_end": {...}, "records": {...}, "attempted", "failed", "correct"}."""
    cfg, settings, init, step_fn = build_step(sizes, traffic)
    batch = int(traffic["microbatch"]) * int(traffic["grad_accum"])
    # weights, optimizer state and every batch: on the device, from the seed,
    # in two jitted calls
    state = init(build.seed_key(seed, 0))
    n_batches = int(traffic["distinct_batches"])
    batches = jax.jit(lambda k: draw_batches(cfg, batch, n_batches, k))(build.seed_key(seed, 2))
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
    # the window's state leaves the device, so that the step made again has
    # the memory it had in the window, and no other program beside it
    final = jax.device_get(state.params)
    state = m = None
    step_ok, step_detail = correct_routed.train_step_agrees(
        step_fn, lambda: init(build.seed_key(seed, 0)), batches[0], build.raw_key(seed, 0), sizes)
    ok, detail = correct_routed.train_forward_agrees(jax.device_put(final), cfg, sizes,
                                                     settings.compute_dtype, seed)
    detail = {**detail, "step": step_detail,
              "refused_by": detail["refused_by"] + step_detail["refused_by"]}
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
        "attempted": steps, "failed": window_skipped, "correct": bool(ok and step_ok and finite),
        "t_open": t0,
    }
