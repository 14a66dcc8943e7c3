"""A served request's LOGITS against the plain reference, teacher-forced, at a
configuration's own sizes: what `closed_loop`'s replay (ranks of the delivered
codes) cannot say in numbers.

One request is served by a `GenerationEngine` at the cell's slot count (the
program the cell times).  Its text and delivered codes are then fed again
through what the engine is made of, `sampling._prefill_phase` +
`transformer.write_prefill_to_pool` + `transformer.paged_decode_step` on a
paged pool with per-slot state (lane 0 the request, lane 1 idle), and every
image position's logits (`dalle.to_image_logits`) are compared with the
reference's full forward (float32, "highest", token-by-token recurrence; one
head's score matrix at a time: it fits beside the pool): root-mean-square
difference over the `num_image_tokens` columns of all image positions, as a
share of the reference's root mean square (`correct.logits_error`).

LIMIT 0.03, and why (`correct.TOLERANCE`'s reason, measured again here).  The
system STORES its weights, activations and logits in bfloat16 and multiplies
them with float32 accumulation: a relative rounding step of 2**-8 = 0.4 % a
product and a stored value; the state, decay, beta and every norm's statistics
are float32.  Its readings on the v5e and the control's stand in PERF.md
section 6 (PR 33) with their seeds; 0.03 is twice the system's.  The control
that must come out OVER the limit, through the same code path:
  * `e4m3_weights`: every weight matrix scaled by a power of two so that its
    largest entry lies in [64, 128), rounded to 4 exponent and 3 mantissa
    bits (the nearest precision below) and scaled back: scaled, because
    unscaled weights of +-0.016 lie under e4m3's smallest normal number and
    most of them are lost (this script's first version read 104 % that way:
    a destroyed model, not a lower precision).
And a reading that is REPORTED and decides nothing, because no limit on
logits can see it:
  * `state_bfloat16`: the recurrent state rounded to bfloat16's 8 exponent and
    7 mantissa bits after every step (what keeping it in the pool's type would
    do).  It read 1.531 % where the system reads 1.528 %: the state's
    roundings add 0.1 % of the logits' RMS in quadrature, under a fifteenth of
    what bfloat16 activations already cost, on seeded random weights whose
    decay forgets a rounding within a few positions.  What refuses it is
    `harness/correct_state.py`'s `state_float32_share`, inside the cell's own
    `correct` (`benchmark/tools/serve_state_controls.py` shows it).
This runs BESIDE the cell, in a jit of its own at two slots: the timed program
(`serve_decode_step` at 32 slots) hands out codes and state, never logits, so
what is read from the timed program itself is the replay's ranks and
`correct_state`'s state error.
Exit code 1 if the system is over the limit or the e4m3 control under it.

    chiprun -- python3 benchmark/tools/serve_logits_olmoh.py serve_olmoh_s32 3300000707 3300003103
    JAX_PLATFORMS=cpu python3 benchmark/tools/serve_logits_olmoh.py tiny_olmoh_serve 3 \\
        --manifest benchmark/rehearsal/manifest_olmoh.json
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

LIMIT = 0.03


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import build, correct, manifest
    from dalle_pytorch_tpu.models import dalle as dalle_mod
    from dalle_pytorch_tpu.models import sampling
    from dalle_pytorch_tpu.models import transformer as tr
    from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine

    man = manifest.load(args.manifest)
    cell = manifest.cell(man, args.workload)
    sizes, traffic = manifest.config_sizes(man, cell["config"]), manifest.traffic(cell["traffic"])
    cfg = build.dalle_config(sizes, execution="sequential", scan_layers=False)
    tcfg = cfg.transformer_config()
    dtype = build.dtype(sizes["serve_recipe"]["param_dtype"])
    block = int(traffic["block_size"])
    wrong = 0
    for seed in args.seeds:
        params = build.make_weights(cfg, seed, dtype)

        # ---- one request, served by the cell's engine
        engine = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
            num_slots=int(traffic["slots"]), block_size=block, filter_thres=float(traffic["filter_thres"])))
        rng = np.random.default_rng([seed, 3])
        text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,), dtype=np.int64).astype(np.int32)
        request = engine.submit(text, key=build.raw_key(seed, 0), temperature=float(traffic["temperature"]))
        engine.run_until_idle()
        codes = np.asarray(request.codes, np.int32)
        del engine

        # ---- the reference, once
        ref = manifest.reference(sizes)
        ts, split = cfg.text_seq_len, cfg.num_text_tokens_padded
        want = jax.jit(lambda p: ref.forward_logits(p, sizes, text, codes)[ts:, split:])(params)

        # ---- the served path, teacher-forced
        n_pre, n_gen, slots = ts + 1, cfg.image_seq_len, 2
        per_seq = tr.paged_blocks_per_seq(tcfg, block)
        tables = jnp.zeros((slots, per_seq), jnp.int32).at[0].set(1 + jnp.arange(per_seq))

        # a control rounds with `lax.reduce_precision`, an operation of its own: a
        # pair of casts (float32 -> bfloat16 -> float32) is what XLA's TPU pipeline
        # REMOVES under its default `xla_allow_excess_precision` (this script's first
        # chip run read the three paths equal to sixteen digits)
        def rounded(a, exponent_bits, mantissa_bits):
            return jax.lax.reduce_precision(a, exponent_bits, mantissa_bits)

        def served_logits(p, state_bits=None):
            @jax.jit
            def admit(p):
                cache, _ = sampling._prefill_phase(p, cfg, jnp.asarray(text)[None], None, 0, 1.0)
                pool = tr.init_paged_pool(tcfg, slots * per_seq + 1, block, dtype, num_slots=slots)
                return tr.write_prefill_to_pool(pool, tables[:1], cache["layers"], n_pre, block,
                                                slots=jnp.asarray([0]))

            @jax.jit
            def decode(p, pool):
                head = dalle_mod.image_head(p, cfg)

                def step(carry, code_and_index):
                    pool, offsets = carry
                    code, i = code_and_index
                    emb = dalle_mod.embed_image_codes(p, cfg, jnp.stack([code, code])[:, None], start=i)
                    out, pool, _ = tr.paged_decode_step(p["transformer"], tcfg, emb, pool, tables,
                                                        offsets, None, block)
                    if state_bits is not None:
                        pool = {"layers": [dict(layer, state=rounded(layer["state"], *state_bits))
                                           if "state" in layer else layer for layer in pool["layers"]]}
                    logits = dalle_mod.to_image_logits(p, cfg, head, out[:1])[0, 0]
                    return (pool, offsets.at[0].add(1)), logits.astype(jnp.float32)

                offsets = jnp.asarray([n_pre, 0], jnp.int32)
                _, rows = jax.lax.scan(step, (pool, offsets),
                                       (jnp.asarray(codes[:n_gen - 1]), jnp.arange(n_gen - 1)))
                return rows  # row i: the logits of image position i + 1 (position 0's come from prefill)

            return decode(p, admit(p))

        @jax.jit
        def e4m3(p):
            def one(a):
                if a.ndim < 2:
                    return a
                a32 = a.astype(jnp.float32)
                scale = 2.0 ** jnp.ceil(jnp.log2(jnp.max(jnp.abs(a32)))) / 128.0
                return (rounded(a32 / scale, 4, 3) * scale).astype(a.dtype)
            return jax.tree_util.tree_map(one, p)

        readings = {
            "system": lambda: served_logits(params),
            "state_bfloat16": lambda: served_logits(params, state_bits=(8, 7)),
            "e4m3_weights": lambda: served_logits(e4m3(params)),
        }
        for name, run in readings.items():
            err, worst = jax.jit(correct.logits_error)(run(), want[1:])
            ok = bool(np.isfinite(float(err)) and float(err) <= LIMIT)
            expected = {"system": True, "e4m3_weights": False}.get(name)  # None: reported only
            wrong += int(expected is not None and ok != expected)
            print(json.dumps({"reading": name, "workload": args.workload, "seed": seed,
                              "platform": jax.devices()[0].platform, "positions": int(n_gen - 1),
                              "logits_rms_err": float(err), "logits_worst_row_err": float(worst),
                              "limit": LIMIT, "within_limit": ok, "expected": expected}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
