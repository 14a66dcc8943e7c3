"""The readings `harness/correct_mtp.py`'s limits are set from, through its own
`verdict`: on seeded weights at a configuration's sizes, the system's forward
in the recipe's compute type (must come out correct), then `correct_mtp.controls`
(the reference with bfloat16-rounded products must come out correct; with
e4m3-rounded products, with every row or one row answered by its neighbour,
and with one row in twelve off by 8 %, it must not).

    chiprun -- python3 benchmark/tools/correct_mtp_controls.py glm47_flash_ep8_d5 7000000001 6100000007
    JAX_PLATFORMS=cpu python3 benchmark/tools/correct_mtp_controls.py tiny_glm 3 --manifest benchmark/rehearsal/manifest_glm.json

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
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.harness import build, correct_mtp, manifest

    sizes = manifest.config_sizes(manifest.load(args.manifest), args.config)
    cfg = build.dalle_config(sizes)
    compute = build.dtype(sizes["train_recipe"]["compute_dtype"])
    wrong = 0
    for seed in args.seeds:
        params = build.make_weights(cfg, seed, jnp.float32)
        readings = {"system": correct_mtp.train_forward_agrees(params, cfg, sizes, compute, seed)}
        readings.update(correct_mtp.controls(params, cfg, sizes, seed))
        for name, (ok, detail) in readings.items():
            expected = name in ("system", "bfloat16_products")
            wrong += ok != expected
            print(json.dumps({"reading": name, "seed": seed, "platform": jax.devices()[0].platform,
                              "correct": ok, "expected": expected, **detail}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
