"""The readings `harness/correct_routed.py`'s limits are set from, through its
own `verdict` and `step_verdict`: on seeded weights at a configuration's
sizes, the system's forward in the recipe's compute type (must come out
correct), then `correct_routed.controls` (the reference with bfloat16-rounded
products must come out correct; with e4m3-rounded products, with every row
or one row answered by its neighbour, and with one row in twelve off by 8 %,
it must not); then the traffic's training step (`kinds/train_steps_routed`'s
own) from its first state on its first batch (must come out correct), and
`correct_routed.step_controls` (a step that leaves the state as it was, one
that trains on its first microbatch only, one at twice the learning rate:
none may).

    python3 benchmark/tools/correct_routed_controls.py laguna_xs2_ep8_d5 4300000029          # on a v5e
    JAX_PLATFORMS=cpu python3 benchmark/tools/correct_routed_controls.py tiny_laguna 3 \
        --traffic tiny_steps_routed --manifest benchmark/rehearsal/manifest_laguna.json

One JSON line a reading; exit code 1 if any comes out the other way.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--traffic", default="steps_adam_b4_fresh128")
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)

    import gc
    import itertools

    import jax
    import jax.numpy as jnp
    from benchmark.harness import build, correct_routed, manifest
    from benchmark.kinds import train_steps_routed
    from dalle_pytorch_tpu.cli.common import enable_compile_cache

    enable_compile_cache()  # as run.py: the cell's runs that follow find its programs there
    sizes = manifest.config_sizes(manifest.load(args.manifest), args.config)
    traffic = manifest.traffic(args.traffic)
    cfg = build.dalle_config(sizes)
    compute = build.dtype(sizes["train_recipe"]["compute_dtype"])
    rate = float(sizes["train_recipe"]["learning_rate"])
    _, _, init, step_fn = train_steps_routed.build_step(sizes, traffic)
    wrong = 0

    def report(readings, seed, expected_correct):
        nonlocal wrong
        for name, (ok, detail) in readings.items():
            expected = name in expected_correct
            wrong += ok != expected
            print(json.dumps({"reading": name, "seed": seed, "platform": jax.devices()[0].platform,
                              "correct": ok, "expected": expected, **detail}), flush=True)

    for seed in args.seeds:
        # the training step first, as the cell's run makes it again after its
        # window: with the memory it has there, and no other program beside it
        gc.collect()
        jax.clear_caches()
        batch = train_steps_routed.draw_batches(
            cfg, int(traffic["microbatch"]) * int(traffic["grad_accum"]), 1, build.seed_key(seed, 2))[0]
        system = correct_routed.first_step(step_fn, lambda: init(build.seed_key(seed, 0)), batch,
                                           build.raw_key(seed, 0))
        grads = correct_routed.reference_gradients(system[0], sizes, batch)
        first = next(grads)
        reference = correct_routed.mean_tree(itertools.chain([first], grads))
        readings = {"step_system": correct_routed.step_verdict(system, reference, rate)}
        readings.update(correct_routed.step_controls(system, reference, first, rate))
        report(readings, seed, ("step_system",))
        del system, reference, first, grads
        params = build.make_weights(cfg, seed, jnp.float32)
        readings = {"system": correct_routed.train_forward_agrees(params, cfg, sizes, compute, seed)}
        readings.update(correct_routed.controls(params, cfg, sizes, seed))
        del params
        report(readings, seed, ("system", "bfloat16_products"))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
