"""The readings the limits of `harness/correct_state.py` (and, for a bfloat16
cell, `correct.serve_replay_agrees`) are set from, through their own verdicts:
the cell's engine as it is must come out correct, and two doctored engines
must not.

Each reading builds the cell's `GenerationEngine` at the cell's slot count and
drives it as `closed_loop` does (one request a lane, a stagger of image tokens
// clients polls apart, no VAE) until the first request completes: every lane
is then busy at its own offset, as at any moment of the cell's window.  That
request is replayed through `correct.serve_replay_agrees` and the engine's
`recurrent_snapshot()` goes through `correct_state.state_agrees`, both against
the reference on the UNDOCTORED weights.

  * `system`: must be correct by both.
  * `state_bfloat16`: the state of every `gated_delta` layer rounded to 8
    exponent and 7 mantissa bits after every poll (what keeping it in the
    pool's type would do).  `state_float32_share` must refuse it; its
    `state_rms_err` and `outside_top_k_share` are reported beside the system's,
    to show that no limit on them could.
  * `e4m3_weights`: every weight matrix scaled by a power of two so that its
    largest entry lies in [64, 128), rounded to 4 exponent and 3 mantissa bits
    (the nearest precision below bfloat16; scaled, so that no weight is lost
    under the format's smallest number) and scaled back.  `state_rms_err` must
    refuse it; the replay's share is reported.
Roundings are `lax.reduce_precision`: a pair of casts is what XLA's TPU
pipeline removes under its default `xla_allow_excess_precision`.

    chiprun -- python3 benchmark/tools/serve_state_controls.py serve_olmoh_s32 3300003001
    JAX_PLATFORMS=cpu python3 benchmark/tools/serve_state_controls.py tiny_olmoh_serve 3 \\
        --manifest benchmark/rehearsal/manifest_olmoh.json

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
    ap.add_argument("workload")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--readings", default="system,state_bfloat16,e4m3_weights")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import build, correct, correct_state, manifest
    from dalle_pytorch_tpu.cli.common import enable_compile_cache
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    if jax.default_backend() != "cpu":
        enable_compile_cache()
    man = manifest.load(args.manifest)
    cell = manifest.cell(man, args.workload)
    sizes, traffic = manifest.config_sizes(man, cell["config"]), manifest.traffic(cell["traffic"])
    cfg = build.dalle_config(sizes, execution="sequential", scan_layers=False)
    thres = float(traffic["filter_thres"])
    slots, stagger = int(traffic["slots"]), cfg.image_seq_len // int(traffic["clients"])

    @jax.jit
    def e4m3(p):
        def one(a):
            if a.ndim < 2 or not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            a32 = a.astype(jnp.float32)
            scale = 2.0 ** jnp.ceil(jnp.log2(jnp.max(jnp.abs(a32)))) / 128.0
            return (jax.lax.reduce_precision(a32 / scale, 4, 3) * scale).astype(a.dtype)
        return jax.tree_util.tree_map(one, p)

    round_states = jax.jit(lambda states: [jax.lax.reduce_precision(s, 8, 7) for s in states],
                           donate_argnums=0)

    def serve(params, seed: int, state_bfloat16: bool):
        """(the first completed request as `serve_replay_agrees` takes it, the
        engine's snapshot at that moment)."""
        engine = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
            num_slots=slots, block_size=int(traffic["block_size"]), filter_thres=thres))
        rng = np.random.default_rng([seed, 3])
        polls, sent = 0, 0
        while True:
            if sent < int(traffic["clients"]) and polls >= sent * stagger:
                text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,), dtype=np.int64)
                engine.submit(text.astype(np.int32), key=build.raw_key(seed, sent),
                              temperature=float(traffic["temperature"]))
                sent += 1
            done = engine.poll()
            polls += 1
            if state_bfloat16:  # the doctored system: a private member, on purpose
                layers = engine._state["pool"]["layers"]
                rounded = iter(round_states([l["state"] for l in layers if "state" in l]))
                engine._state = dict(engine._state, pool=dict(engine._state["pool"], layers=[
                    dict(l, state=next(rounded)) if "state" in l else l for l in layers]))
            if done:
                req = done[0]
                return ({"text": req.text, "codes": req.codes, "image": None},
                        engine.recurrent_snapshot())

    wrong = 0
    for seed in args.seeds:
        params = build.make_weights(cfg, seed, build.dtype(sizes["serve_recipe"]["param_dtype"]))
        readings = {
            "system": (lambda: serve(params, seed, False), True),
            "state_bfloat16": (lambda: serve(params, seed, True), False),
            "e4m3_weights": (lambda: serve(e4m3(params), seed, False), False),
        }
        for name in args.readings.split(","):
            run, expected = readings[name]
            delivered, snapshot = run()
            replay_ok, replay = correct.serve_replay_agrees(params, sizes, None, None, thres, 1.0,
                                                            [delivered])
            state_ok, state = correct_state.state_agrees(params, sizes, snapshot)
            ok = replay_ok and state_ok
            wrong += ok != expected
            print(json.dumps({"reading": name, "workload": args.workload, "seed": seed,
                              "platform": jax.devices()[0].platform, "correct": ok,
                              "expected": expected, "replay_correct": replay_ok,
                              "state_correct": state_ok, **replay, **state}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
