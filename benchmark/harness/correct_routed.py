"""The comparison that decides `correct` for a routed trunk whose forward
`correct.py`'s limits cannot hold, outside the measured window, in two
halves (PERF.md section 6 has every reading below, with its seed):

* the FORWARD (`train_forward_agrees`): the main logits and the loss of the
  system's forward on one seeded full sequence (`correct._seeded_sequence`),
  through the parameters as the window left them, in its compute type,
  against the plain reference the configuration's file names.
  `correct_mtp.py` is the same comparison for a trunk with a prediction
  module; this one has no second head;
* the TRAINING STEP (`train_step_agrees`): the timed step function, made
  again from the state the window started from on the window's first batch,
  against the reference's `jax.grad` of the same loss at the same
  parameters: the step's accumulated gradient, and the parameters' change.

Why not `correct.py`: its RMS limit, correct.TOLERANCE 0.03, lets the
reference with every product in e4m3 (the nearest precision below the
recipe's bfloat16) through, at 2.98 % at the published widths; its worst-row
limit of 3 x TOLERANCE = 0.09 held every sound run (4.9-6.2 %) and is not
what fails.  A top-k router in bfloat16 flips a token's choice where its
k-th and (k+1)-th probability are a rounding apart; such a token swaps one
expert's output at weight ~routed_scale / top_k (2.5 / 8 in Laguna-XS.2), so
the worst row cannot separate precisions, and as in `correct_mtp.py` a
WRONG row is held to WORST_ROW and the COUNT of rows over correct.TOLERANCE
to ROWS_OVER of the rows (never fewer than one).

Each limit lies between two readings at the published widths on the v5e:
the system over its seeds, and a control whose answer is known beforehand
(the reference with every product's operands rounded to e4m3, or a forward
or a step with a known fault, through the same verdict: `controls`,
`step_controls`, run by tools/correct_routed_controls.py).  The forward,
over ten runs of the cell and two control seeds (4300000029, 4300011003):

* the logits' RMS error: RMS 0.025 of the reference's RMS.  The system read
  0.86-0.90 % (bfloat16 products round at 2**-8 = 0.4 % each); e4m3 2.98 %
  and 2.97 %, which correct.TOLERANCE 0.03 would let through.  0.025 is 2.8
  times the first and 0.84 of the second, and above the 2.2-2.3 %
  that one wrong row or one row in twelve off by 8 % adds, which the two
  limits below are for;
* the whole loss: `correct.LOSS_TOLERANCE` 0.2 % (the system up to 2.5e-5,
  e4m3 2.6e-5 and 5.5e-5: a mean over 4,224 positions does not see precision; the
  limit holds a wrong target or weighting, which move it by order 1);
* the worst row: WORST_ROW 0.3, `correct_mtp`'s.  The system 4.9-6.2 %,
  e4m3 5.5 %, the bfloat16-rounded reference 6.1 %: no separation; it holds
  ONE row answered with its neighbour's logits, 1.42, refused by it alone
  at 4,224 rows (2.2 % in the RMS);
* the rows over correct.TOLERANCE: ROWS_OVER 0.05 of the rows (211 of
  4,224), `correct_mtp`'s.  The system 45-68, e4m3 430 and 492 (refused by
  it and by RMS); every 12th row off by 8 % of the RMS (352 rows, 2.3 % in
  the RMS) is refused by it alone.  211 is 3.1 times the first and under
  half the second.

The step (seeds 4300013009 and 4300011003):

* the gradient: GRAD_WORST_LEAF 0.25, ||g - g_ref|| / ||g_ref|| of the
  worst leaf, g read back from Adam's first moment after the one step.  The
  system read 0.119 and 0.105, both in a routed layer's router (a near-tied
  expert that flips moves the router's gradient more than any other leaf's;
  the median leaf 0.014-0.015).  A step that hands its state back reads 1
  in every leaf, one that trains on its first microbatch only 1.83 (its
  median leaf 1.73: the microbatches' gradients differ by more than they
  share).  0.25 is 2.1 times the largest sound reading and a quarter of
  the smallest fault's;
* the update: UPDATE_WORST_LEAF 0.01, the parameters' change against a plain
  Adam step on the step's own gradient, by its worst leaf: the optimizer
  and where its update lands.  The system read 3.0e-4 and 3.1e-4 (float32
  rounding of a 1e-4 step on weights near 1); one at twice the rate reads
  1.00.  Not against Adam on the reference's gradient, whose first step is
  the learning rate times each gradient's SIGN: that reading is 0.37-0.40
  (`step_verdict` says why).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import correct, correct_mtp, manifest

RMS = 0.025
WORST_ROW = correct_mtp.WORST_ROW
ROWS_OVER = correct_mtp.ROWS_OVER
GRAD_WORST_LEAF = 0.25
UPDATE_WORST_LEAF = 0.01
# Adam's published defaults (Kingma & Ba 2015, Algorithm 1), which the
# recipe's optax.adam takes
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def verdict(system, reference):
    """(ok, detail) of one forward against another: each a pair (logits, the
    whole loss).  `refused_by` names every limit that failed."""
    err, worst = (float(x) for x in jax.jit(correct.logits_error)(system[0], reference[0]))
    over = int(jax.jit(correct_mtp.rows_over)(system[0], reference[0]))
    allowed = max(int(ROWS_OVER * reference[0].shape[0]), 1)
    loss_err = abs(float(system[1]) - float(reference[1])) / abs(float(reference[1]))
    held = {"rms": bool(np.isfinite(err)) and err <= RMS,
            "worst_row": bool(np.isfinite(worst)) and worst <= WORST_ROW,
            "rows_over": over <= allowed,
            "loss": bool(np.isfinite(loss_err)) and loss_err <= correct.LOSS_TOLERANCE}
    detail = {"logits_rms_err": err, "logits_worst_row_err": worst, "logits_rows_over": over,
              "rows": int(reference[0].shape[0]), "loss_system": float(system[1]),
              "loss_reference": float(reference[1]), "loss_rel_err": loss_err,
              "tolerance": RMS, "worst_row_limit": WORST_ROW,
              "rows_over_limit": allowed, "loss_tolerance": correct.LOSS_TOLERANCE,
              "refused_by": [name for name, ok in held.items() if not ok]}
    return not detail["refused_by"], detail


def _reference_forward(ref, sizes, text, codes):
    def forward(p):
        logits = ref.forward_logits(p, sizes, text, codes)
        return logits, ref.loss_from_logits(logits, sizes, text, codes)

    return forward


def train_forward_agrees(params, cfg, sizes: dict, compute_dtype, seed: int):
    """(ok, detail): logits and loss of the system's forward against the reference."""
    ref = manifest.reference(sizes)
    from dalle_pytorch_tpu.core.pytree import cast_floating
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    text, codes = correct._seeded_sequence(cfg, seed, cfg.image_seq_len)

    @jax.jit
    def system(p):
        p = cast_floating(p, compute_dtype)
        t, c = jnp.asarray(text)[None], jnp.asarray(codes)[None]
        return dalle_mod.forward(p, cfg, t, c)[0], dalle_mod.forward(p, cfg, t, c, return_loss=True)

    return verdict(system(params), jax.jit(_reference_forward(ref, sizes, text, codes))(params))


def controls(params, cfg, sizes: dict, seed: int) -> dict:
    """{name: (ok, detail)}: `verdict` on forwards whose answer is known
    beforehand, each against the reference in float32 on the cell's seeded
    sequence.  `bfloat16_products` (the reference with every product's
    operands rounded to bfloat16: the stated precision, nothing else changed)
    must come out correct; `float8_e4m3fn_products` (the nearest precision
    below), `rows_shifted_by_one`, `one_row_wrong` and
    `one_row_in_12_off_by_8pct` must not (correct_mtp.controls says what
    each stands for)."""
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
        out[name] = verdict((fault(want[0]), want[1]), want)
    return out


# ------------------------------------------------------- the training step
def first_step(step_fn, init, batch, key):
    """What the timed step makes of the state the window starts from, on the
    window's first batch (the warm-up's first step, made again): (params
    before, the step's accumulated gradient, params after), host float32
    trees.  The gradient is read back from Adam's first moment, which one
    step from zero leaves at (1 - b1) g."""
    import optax

    state = init()
    before = jax.device_get(state.params)
    state, _ = step_fn(state, batch, key)
    mu = jax.device_get(optax.tree_utils.tree_get(state.opt_state, "mu"))
    after = jax.device_get(state.params)
    del state
    return before, jax.tree.map(lambda m: m / np.float32(1.0 - ADAM_B1), mu), after


def reference_gradients(params, sizes: dict, batch):
    """Each sequence's gradient of the plain reference's loss at `params` (a
    host tree), in turn: host float32 trees.  A step's loss is the mean of
    its sequences' (`mean_tree`)."""
    ref = manifest.reference(sizes)
    grad_one = jax.jit(jax.grad(lambda p, t, c: ref.loss(p, sizes, t, c)))
    p = jax.device_put(params)
    for t, c in zip(batch["text"], batch["image_codes"]):
        yield jax.device_get(grad_one(p, t, c))


def mean_tree(trees):
    """The leafwise mean of host trees of one structure."""
    total, n = None, 0
    for t in trees:
        total, n = (t if total is None else jax.tree.map(np.add, total, t)), n + 1
    return jax.tree.map(lambda a: a / np.float32(n), total)


def adam_first_update(grad, learning_rate):
    """A plain Adam step from zero moments, the first one (bias-corrected),
    in float32."""
    g = jnp.asarray(grad, jnp.float32)
    m, v = (1 - ADAM_B1) * g, (1 - ADAM_B2) * g * g
    return -learning_rate * (m / (1 - ADAM_B1)) / (jnp.sqrt(v / (1 - ADAM_B2)) + ADAM_EPS)


@jax.jit
def _leaf_norms(p0, g, p1, gr, learning_rate):
    """One leaf's [||g - gr||, ||gr||, ||change - Adam(g)||, ||Adam(g)||,
    ||change - Adam(gr)||, ||Adam(gr)||], float32 on the device (the host
    takes minutes over 691 M parameters)."""
    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x)))

    change = p1.astype(jnp.float32) - p0.astype(jnp.float32)
    g, gr = g.astype(jnp.float32), gr.astype(jnp.float32)
    own, ref = adam_first_update(g, learning_rate), adam_first_update(gr, learning_rate)
    return jnp.stack([norm(g - gr), norm(gr), norm(change - own), norm(own),
                      norm(change - ref), norm(ref)])


def _ratio(diff: float, ref: float) -> float:
    """||x - y|| / ||y||; 0 where both are zero."""
    return diff / ref if ref else (0.0 if not diff else float("inf"))


def step_verdict(system, reference, learning_rate: float):
    """(ok, detail) of one training step against the reference: `system` is
    `first_step`'s (before, gradient, after), `reference` the reference's
    gradient at `before`.  Each is held by its WORST leaf: the step's
    gradient to the reference's, and the parameters' change to a plain Adam
    step on the step's own gradient.  Not on the reference's: Adam's first
    step moves each weight by the learning rate times the SIGN of its
    gradient, so a weight whose gradient lies inside the compute type's
    rounding turns its whole step round; that reading
    (`update_vs_reference_worst_leaf_err`, kept in the detail) measures how
    many gradients are near zero, not the update.  A step that leaves the
    state as it was reads 1 in the gradient."""
    before, grad, after = system
    flat, _ = jax.tree_util.tree_flatten_with_path(before)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    grad_err, update_err, against_ref = [], [], []
    for p0, g, p1, gr in zip(jax.tree.leaves(before), jax.tree.leaves(grad),
                             jax.tree.leaves(after), jax.tree.leaves(reference)):
        norms = [float(x) for x in np.asarray(_leaf_norms(p0, g, p1, gr, learning_rate))]
        grad_err.append(_ratio(*norms[0:2]))
        update_err.append(_ratio(*norms[2:4]))
        against_ref.append(_ratio(*norms[4:6]))
    worst_g, worst_u = int(np.nanargmax(grad_err)), int(np.nanargmax(update_err))
    held = {"grad": bool(np.isfinite(grad_err).all()) and grad_err[worst_g] <= GRAD_WORST_LEAF,
            "update": bool(np.isfinite(update_err).all()) and update_err[worst_u] <= UPDATE_WORST_LEAF}
    detail = {"grad_worst_leaf_err": grad_err[worst_g], "grad_worst_leaf": names[worst_g],
              "grad_median_leaf_err": float(np.median(grad_err)),
              "update_worst_leaf_err": update_err[worst_u], "update_worst_leaf": names[worst_u],
              "update_vs_reference_worst_leaf_err": float(np.nanmax(against_ref)),
              "grad_worst_leaf_limit": GRAD_WORST_LEAF, "update_worst_leaf_limit": UPDATE_WORST_LEAF,
              "refused_by": [name for name, ok in held.items() if not ok]}
    return not detail["refused_by"], detail


def train_step_agrees(step_fn, init, batch, key, sizes: dict):
    """(ok, detail): the timed step, made again from the window's first
    state on its first batch, against the reference's gradient at the cell's
    sizes (`step_verdict`)."""
    system = first_step(step_fn, init, batch, key)
    reference = mean_tree(reference_gradients(system[0], sizes, batch))
    return step_verdict(system, reference, float(sizes["train_recipe"]["learning_rate"]))


def step_controls(system, reference, first_sequence, learning_rate: float) -> dict:
    """{name: (ok, detail)}: `step_verdict` on steps whose fault is known
    beforehand, made from a sound step's readings (`system`) and the
    reference's gradients: a step that leaves the state as it was, one that
    trains on its first microbatch only (the reference's gradient of the
    first sequence and a plain Adam step on it), and one that makes its
    update at twice the learning rate.  None may come out correct."""
    before, grad, after = system

    def stepped(g, rate):
        return jax.tree.map(lambda p, gi: (p + adam_first_update(gi, rate)).astype(p.dtype), before, g)

    zero = jax.tree.map(np.zeros_like, before)
    return {"state_unchanged": step_verdict((before, zero, before), reference, learning_rate),
            "first_microbatch_only": step_verdict(
                (before, first_sequence, stepped(first_sequence, learning_rate)), reference, learning_rate),
            "twice_the_rate": step_verdict((before, grad, stepped(grad, 2 * learning_rate)),
                                           reference, learning_rate)}
