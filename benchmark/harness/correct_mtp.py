"""The comparison that decides `correct` for a trunk with a prediction module,
outside the measured window: `correct.train_forward_agrees` hands the
reference's `loss_from_logits` the main logits alone, so a loss with a second
head can never agree with it.

On one seeded full sequence at the timed sizes (`correct._seeded_sequence`),
through the parameters as the window left them (the balancing bias included),
the system's forward in its compute type against the plain reference the
configuration's file names: the MAIN logits and the MODULE's logits, each by
`correct.logits_error`, and the WHOLE loss (main + mtp_loss_weight x module).

Four limits, each from two readings on the v5e at the published widths (my
chip runs, PR 30; PERF.md section 6 lists every one).  The second reading of
each went through `verdict` below, the function that decides the cell's
`correct` (`controls`, run by tools/correct_mtp_controls.py), and came out
not correct by the limit named.

* The logits' RMS error: `correct.TOLERANCE` 0.03 of the reference's RMS, for
  correct.py's reason (bfloat16 products round at 2**-8 = 0.4 % each).  This
  change over 22 seeds (twenty runs of the cell, three forwards on
  untrained weights): 0.70-0.93 % (main), 0.59-0.99 % (module).  The
  reference's own forward with every product's operands rounded to e4m3
  (`products_rounded_to`): 5.8 % and 6.6 %, refused by `rms`.  0.03 is 3.0
  times the first and half the second.
* The whole loss: `correct.LOSS_TOLERANCE` 0.2 %.  This change: at most
  0.0011 %.  (The e4m3 reading is 0.009 %: a mean over 4,224 positions of
  logits of random weights does not see precision; the limit holds a wrong
  weighting, target or lambda, which move the loss by order 1, and it is the
  RMS limit that fails a lower precision.)
* The worst row: WORST_ROW 0.3, NOT correct.py's 3 x TOLERANCE = 0.09, which
  ISSUE 30 asked for and this architecture cannot keep in bfloat16: over
  the 22 seeds this change's worst row read 4.3-9.1 % (main) and 5.3-10.7 %
  (module), over 0.09 in twelve of them, and the REFERENCE ITSELF with
  bfloat16-rounded operands and nothing else changed reads 4.8-6.9 % and
  8.2-11.4 % (five seeds).
  The cause is the router, not a fault: 4,224 tokens x 5 routed layers pick
  the top 4 of 64 sigmoid scores, some of them with the 4th and 5th score
  closer than a bfloat16 rounding of the hidden state moves them, and a
  token whose choice flips swaps one expert's whole output at weight ~0.45
  (top-10 of 512 at ~0.1 in `train_q3n_ep16`): that row moves 4-11 %,
  whatever the precision (the e4m3 reading's worst row is 9.6-10.2 % /
  13.1-13.9 %).  So the worst row cannot separate precisions here and is set
  for what it is for, "one bad position cannot hide in the mean": ONE row
  answered with its neighbour's logits (a mask, target or position off by
  one) reads 1.39-1.44 and is 2.2 % in the RMS, refused by `worst_row` alone.
  0.3 is 2.6 times the largest reading of a sound run (11.4 %) and under a
  quarter of a wrong row.
* The COUNT of rows over `correct.TOLERANCE`: ROWS_OVER 0.05 of the rows (211
  of 4,224; never fewer than one row, so that a single flipped choice stays
  sound at a rehearsal's 24), because under the worst-row limit alone any
  number of rows could sit at 30 % while the RMS holds.  A row over 3 % is a
  row whose routing flipped (the 99th-percentile row reads 0.3-0.4 %), so
  the count grows with the noise that flips them: the bfloat16-rounded
  reference 10-18 (main) and 20-25 (module); this change, which also STORES
  its activations in bfloat16, 62-96 and 6-97 over eleven seeds (at most
  2.3 % of the rows: the review's "1 %" would have refused sound runs);
  e4m3 EVERY row, refused by `rows_over` (and `rms`).  What only the count
  sees: every 12th row off by 8 % of the logits' RMS (352 rows, 2.3 % in
  the RMS, worst row 0.08) is refused by `rows_over` alone.  211 is 2.2
  times the largest sound reading and a twentieth of the precision below.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import correct, manifest

WORST_ROW = 0.3
ROWS_OVER = 0.05


def rows_over(system, reference, level: float = correct.TOLERANCE):
    """How many rows' own error is over `level` of the reference's RMS over
    all rows: the quantity `correct.logits_error` takes the maximum of,
    counted instead."""
    ok = jnp.isfinite(reference)
    sys32 = jnp.where(ok, system.astype(jnp.float32), 0.0)
    ref32 = jnp.where(ok, reference, 0.0)
    cnt = jnp.maximum(ok.sum(axis=-1), 1)
    row_err = jnp.sqrt(((sys32 - ref32) ** 2).sum(axis=-1) / cnt)
    row_ref = jnp.sqrt((ref32 ** 2).sum(axis=-1) / cnt)
    return (row_err / jnp.sqrt((row_ref ** 2).mean()) > level).sum()


def verdict(system, reference):
    """(ok, detail) of one forward against another: each a triple (main
    logits, the module's logits, the whole loss).  `refused_by` names every
    limit that failed."""
    detail, held = {}, {"rms": True, "worst_row": True, "rows_over": True}
    for head, got, want in (("logits", system[0], reference[0]), ("mtp_logits", system[1], reference[1])):
        err, worst = (float(x) for x in jax.jit(correct.logits_error)(got, want))
        over = int(jax.jit(rows_over)(got, want))
        # a share of the rows, and never fewer than one: a single row under WORST_ROW is sound at any length
        allowed = max(int(ROWS_OVER * want.shape[0]), 1)
        detail.update({f"{head}_rms_err": err, f"{head}_worst_row_err": worst, f"{head}_rows_over": over})
        held["rms"] &= bool(np.isfinite(err)) and err <= correct.TOLERANCE
        held["worst_row"] &= bool(np.isfinite(worst)) and worst <= WORST_ROW
        held["rows_over"] &= over <= allowed
    loss_err = abs(float(system[2]) - float(reference[2])) / abs(float(reference[2]))
    held["loss"] = bool(np.isfinite(loss_err)) and loss_err <= correct.LOSS_TOLERANCE
    detail.update({"rows": int(reference[0].shape[0]), "loss_system": float(system[2]),
                   "loss_reference": float(reference[2]), "loss_rel_err": loss_err,
                   "tolerance": correct.TOLERANCE, "worst_row_limit": WORST_ROW, "rows_over_limit": allowed,
                   "loss_tolerance": correct.LOSS_TOLERANCE,
                   "refused_by": [name for name, ok in held.items() if not ok]})
    return not detail["refused_by"], detail


def _reference_forward(ref, sizes, text, codes):
    return lambda p: (ref.forward_logits(p, sizes, text, codes),
                      ref.forward_mtp_logits(p, sizes, text, codes), ref.loss(p, sizes, text, codes))


def train_forward_agrees(params, cfg, sizes: dict, compute_dtype, seed: int):
    """(ok, detail): main logits, module logits and the whole loss of the
    system's forward against the reference."""
    ref = manifest.reference(sizes)
    from dalle_pytorch_tpu.core.pytree import cast_floating
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    text, codes = correct._seeded_sequence(cfg, seed, cfg.image_seq_len)

    @jax.jit
    def system(p):
        p = cast_floating(p, compute_dtype)
        t, c = jnp.asarray(text)[None], jnp.asarray(codes)[None]
        main, module = dalle_mod.forward(p, cfg, t, c, with_mtp_logits=True)
        return main[0], module[0], dalle_mod.forward(p, cfg, t, c, return_loss=True)

    return verdict(system(params), jax.jit(_reference_forward(ref, sizes, text, codes))(params))


def controls(params, cfg, sizes: dict, seed: int) -> dict:
    """{name: (ok, detail)}: `verdict` on forwards whose answer is known
    beforehand, each against the reference in float32 on the cell's seeded
    sequence.  `bfloat16_products` is the reference with every product's
    operands rounded to bfloat16 (the stated precision, nothing else changed)
    and must come out correct; every other entry must not:
    `float8_e4m3fn_products` (the nearest precision below), `rows_shifted_by_one`
    (every row answered with its neighbour's logits: a mask, target or
    position off by one), `one_row_wrong` (the same for a single row: what the
    worst-row limit is for), `one_row_in_12_off_by_8pct` (every 12th row moved
    by 8 % of the logits' RMS, 2.3 % in the RMS: what only the count of rows
    sees)."""
    ref = manifest.reference(sizes)
    text, codes = correct._seeded_sequence(cfg, seed, cfg.image_seq_len)
    forward = _reference_forward(ref, sizes, text, codes)
    want = jax.jit(forward)(params)
    out = {}
    for dtype in (jnp.bfloat16, jnp.float8_e4m3fn):
        with ref.products_rounded_to(dtype):  # read while tracing: jit a new function under it
            out[f"{jnp.dtype(dtype).name}_products"] = verdict(jax.jit(lambda p: forward(p))(params), want)

    def neighbour(lg):  # row i answered with row i + 1's logits, where row i permits an entry
        return jnp.where(jnp.isfinite(lg), jnp.nan_to_num(jnp.roll(lg, -1, axis=0), neginf=0.0), lg)

    def one_row(lg):
        r = lg.shape[0] - 3  # an image row whose neighbour permits the same entries
        return lg.at[r].set(neighbour(lg)[r])

    def twelfth(lg):
        ok = jnp.isfinite(lg)
        rms = jnp.sqrt((jnp.where(ok, lg, 0.0) ** 2).sum() / ok.sum())
        rows = (jnp.arange(lg.shape[0]) % 12 == 5)[:, None]
        return jnp.where(ok & rows, lg + 0.08 * rms, lg)

    for name, fault in (("rows_shifted_by_one", neighbour), ("one_row_wrong", one_row),
                        ("one_row_in_12_off_by_8pct", twelfth)):
        out[name] = verdict((fault(want[0]), fault(want[1]), want[2]), want)
    return out
