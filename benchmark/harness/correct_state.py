"""The comparison that holds a served trunk's per-slot RECURRENT STATE, beside
`correct.serve_replay_agrees`, for a configuration whose file states a float32
state (`kinds/closed_loop_state.py` calls both).

The replay holds the delivered codes to the reference's top k, and with them
the whole served path's logits to about a bfloat16 pass.  It cannot see what
the state is KEPT in: on seeded weights (A_log = log(uniform(0, 16)), dt_bias
1) most heads forget a position within a few steps, so a state rounded to
bfloat16 after every step moves the logits by 0.1 % of their RMS beside the
1.5 % that bfloat16 weights and activations already cost (PERF.md section 6,
PR 33: 1.531 against 1.528 %), and no limit on logits or ranks lies between
the two.  A trained model's slow heads (decay near 1) add such roundings up
over thousands of positions; the configuration states float32 for them, and a
later change that halves the state's traffic by storing bfloat16 has to be
refused here.  So the state is read back from the engine that served the
window, as the timed program left it at the window's slot count with every
lane at its own offset (`GenerationEngine.recurrent_snapshot`: public, one
sync), for two in-flight requests, the one furthest along and the one in the
middle, and two numbers are taken of every `gated_delta` layer of each:

  * `state_rms_err`: the root-mean-square difference from the state the
    reference's token-by-token recurrence reaches on the request's own text and
    codes so far (`recurrent_states`, float32 at "highest"), as a share of that
    state's root mean square; the largest over layers and requests.  It holds
    the state to the reference as the window left it: a wrong slot, position,
    decay, tap or a state leaked from the slot's last request reads of order 1;
    so does a precision below bfloat16 in what feeds it.  STATE_TOLERANCE 0.05:
    see the readings under it.
  * `state_float32_share`: the share of the state's entries that a bfloat16
    could NOT hold (the low 16 bits of the float32 are not all zero); the
    smallest over layers and requests.  A state the program keeps in float32
    reads 1 - 2**-16; one stored in bfloat16 (or rounded to it each step) reads
    0, whatever type it is handed back in.  FLOAT32_SHARE 0.5 lies between.
    This is the limit that refuses the nearest precision below the stated one
    for the state; the error above cannot (0.1 % in quadrature, as the logits).

Readings on the v5e, through this module's own verdict on the cell's 32-slot
engine (`benchmark/tools/serve_state_controls.py`, seeds 3300003001 /
3300005237, lanes at 4,096 and 2,176 positions; my chip runs, PR 33; PERF.md
section 6 has every run): the system `state_rms_err` 1.53 / 1.63 % (1.34-1.83 %
over the cell's own nineteen runs: the state of a fast-forgetting head is the
last few tokens' keys and values, so the reading moves with them),
`state_float32_share` 0.99996-0.99998: correct.  `state_bfloat16` (the state
rounded to 8 exponent and 7 mantissa bits after every poll): 1.49 / 1.51 % and
0.0, refused by `state_float32_share` ALONE (its replay reads 0.32 / 0.63 %
outside the top k where the system reads 0.44 %: no other limit sees it).
`e4m3_weights` (every weight matrix scaled by a power of two, rounded to 4
exponent and 3 mantissa bits, scaled back): 25.9 / 24.1 %, refused by
`state_rms_err` (and by the replay: 7.9 / 6.9 % outside, 86 / 75 codes beyond
`NEAR`).  STATE_TOLERANCE 0.05 is 2.7 times the largest of the system's
readings and a fifth of the control's smallest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest

STATE_TOLERANCE = 0.05
FLOAT32_SHARE = 0.5


def float32_share(state) -> float:
    """The share of a float32 array's entries whose low 16 bits are not all
    zero: what no bfloat16 holds."""
    bits = np.ascontiguousarray(np.asarray(state, np.float32)).view(np.uint32)
    return float(np.mean((bits & 0xFFFF) != 0))


def verdict(err: float, share: float, extra: dict):
    detail = dict(extra, state_rms_err=err, state_tolerance=STATE_TOLERANCE,
                  state_float32_share=share, float32_share_limit=FLOAT32_SHARE)
    ok = np.isfinite(err) and err <= STATE_TOLERANCE and share >= FLOAT32_SHARE
    return bool(ok), detail


def state_agrees(params, sizes: dict, snapshot: list):
    """`snapshot`: `GenerationEngine.recurrent_snapshot()` of the engine that
    served the window.  Returns (ok, detail)."""
    ref = manifest.reference(sizes)
    n_gen = int(sizes["image_fmap_size"]) ** 2

    @jax.jit
    def reference(p, text, codes, positions):
        return ref.recurrent_states(p, sizes, text, codes, positions)

    by_progress = sorted(snapshot, key=lambda s: s["positions"])
    chosen = [by_progress[i] for i in sorted({len(snapshot) - 1, len(snapshot) // 2}, reverse=True)
              ] if snapshot else []
    err, share, positions = 0.0, 1.0, []
    for lane in chosen:
        codes = np.zeros((n_gen,), np.int32)
        codes[:len(lane["codes"])] = lane["codes"]
        want = reference(params, jnp.asarray(lane["request"].text, jnp.int32), jnp.asarray(codes),
                         jnp.asarray(lane["positions"], jnp.int32))
        for got, ref_state in zip(lane["states"], want):
            ref_state = np.asarray(ref_state)
            err = max(err, float(np.sqrt(((np.asarray(got, np.float32) - ref_state) ** 2).mean()
                                         / (ref_state ** 2).mean())))
            share = min(share, float32_share(got))
        positions.append(lane["positions"])
    if not positions:
        err = float("nan")
    return verdict(err, share, {"state_lanes_at": positions,
                                "state_layers": len(chosen[0]["states"]) if chosen else 0})
