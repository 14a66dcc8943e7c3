"""The comparisons that decide `correct`, always outside the measured window.

Both go through the plain reference that the configuration's file names under
`reference` and through nothing of the program but what a user of it holds:
the parameter tree, `models/dalle.forward` (train), and the requests the engine
handed back (serve).

TRAIN: logits and loss of the system's forward on one seeded sequence against
the reference.  The error is the root-mean-square difference over the permitted
vocabulary entries, as a share of the reference's root mean square over them.
TOLERANCE 0.03, and why.  The reference is float32 at "highest" precision.  The
system multiplies in bfloat16 with float32 accumulation: 8 mantissa bits, a
relative rounding step of 2**-8 = 0.4 % on each product, which measured 1.0 %
of the logits' RMS in both train cells (depth 8 and 24, worst row 1.2 %) on the
v5e (benchmark/runs/).  0.03 is three times that.  An 8-bit float multiply
(e4m3: 3 mantissa bits, a step of 6 %) lands an order of magnitude above it, so
a silent drop below bf16 fails; a wrong mask, shift or rotary table moves
single rows by order 1 and fails too.  The worst single row is held to 3 *
TOLERANCE so that one bad position cannot hide in the mean.  The loss, a mean
over a thousand positions, measured 2e-5 off and is held to 0.2 %.

SERVE: the window's own deliveries are replayed.  For the first and the last
request that completed inside the window, the reference runs its full forward
on the request's text and the 1,024 codes the engine delivered (and, for a
guided request, on the all-pad text too: logits = null + (cond - null) *
cond_scale, as classifier-free guidance is defined), and every delivered code
has to lie where the sampler's definition puts it: among the k = int((1 -
filter_thres) * vocabulary) largest logits of the position that made it.  That
holds the whole served path to the reference at once, as it ran in the window:
prefill, ingest, the fused decode step at the cell's slot count, the paged
pool, the guidance gathers between a lane pair, the top-k filter and the
sampler, with no private member of the engine touched.  A wrong K/V block,
offset, mask, partner lane or guidance scale makes the ranks uniform (70 % of
the codes outside the top k at filter_thres 0.9); a sampler that does not
filter reads the same.  Rounding moves only codes that sit at the k-th logit:
with the system's logits off by 0.7 % of their RMS (one bf16 pass; this PR's
earlier teacher-forced comparison), 0.20-0.49 % of the delivered codes lay just
outside the reference's top k in `serve_batch` and 0.05-0.15 % in
`serve_guided` on the v5e (4-10 and 1-3 codes of 2,048; guidance at scale 3
sharpens the distribution, so fewer samples fall near the k-th logit; every
run is in benchmark/runs/).  OUTSIDE_SHARE 0.02 is the most that may lie
outside, four times the worst reading (weights and prompts change with the
seed); NEAR 1.25 is the factor of k beyond which none may lie (0.2 standard
deviations of the logits past the k-th: 30 times the rounding error).  The
share grows with the error, so an 8-bit multiply (e4m3: a rounding step 16
times bf16's) puts 3-8 % outside and fails.  The pixels delivered with those
codes are held to the VAE's decode of them (`models/vae_registry.decode_indices`,
called here at "highest" precision) within PIXEL_TOLERANCE 0.03 of their RMS
(measured 0.03-0.10 %).  That holds the decode's arithmetic, scale and layout
as the engine delivers it; it does NOT tell one request's picture from
another's, because the picture of an untrained VAE depends on its codes by
only about 1 % of its RMS.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest

TOLERANCE = 0.03
LOSS_TOLERANCE = 0.002
OUTSIDE_SHARE = 0.02
NEAR = 1.25
PIXEL_TOLERANCE = 0.03


def _seeded_sequence(cfg, seed: int, n_codes: int):
    rng = np.random.default_rng([seed, 7])
    text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,), dtype=np.int64)
    text[-(cfg.text_seq_len // 8):] = 0  # a padded tail: the per-position pad ids are used
    codes = rng.integers(0, cfg.num_image_tokens, (n_codes,), dtype=np.int64)
    return text.astype(np.int32), codes.astype(np.int32)


def logits_error(system, reference):
    """(rms error share, worst row's share) over the reference's finite entries."""
    ok = jnp.isfinite(reference)
    sys32 = jnp.where(ok, system.astype(jnp.float32), 0.0)
    ref32 = jnp.where(ok, reference, 0.0)
    cnt = jnp.maximum(ok.sum(axis=-1), 1)
    row_err = jnp.sqrt(((sys32 - ref32) ** 2).sum(axis=-1) / cnt)
    row_ref = jnp.sqrt((ref32 ** 2).sum(axis=-1) / cnt)
    total = jnp.sqrt((row_err ** 2).mean()) / jnp.sqrt((row_ref ** 2).mean())
    return total, (row_err / jnp.sqrt((row_ref ** 2).mean())).max()


def _verdict(err, worst, extra: dict):
    err, worst = float(err), float(worst)
    detail = dict(extra, logits_rms_err=err, logits_worst_row_err=worst, tolerance=TOLERANCE)
    ok = np.isfinite(err) and err <= TOLERANCE and worst <= 3 * TOLERANCE
    return bool(ok), detail


def train_forward_agrees(params, cfg, sizes: dict, compute_dtype, seed: int):
    """The system's forward (its kernels, its compute type) against the
    reference on one seeded full sequence: logits and loss."""
    ref = manifest.reference(sizes)
    from dalle_pytorch_tpu.core.pytree import cast_floating
    from dalle_pytorch_tpu.models import dalle as dalle_mod

    text, codes = _seeded_sequence(cfg, seed, cfg.image_seq_len)

    @jax.jit
    def system(p):
        p = cast_floating(p, compute_dtype)
        t, c = jnp.asarray(text)[None], jnp.asarray(codes)[None]
        return (dalle_mod.forward(p, cfg, t, c)[0],
                dalle_mod.forward(p, cfg, t, c, return_loss=True))

    @jax.jit
    def reference(p):
        logits = ref.forward_logits(p, sizes, text, codes)
        return logits, ref.loss_from_logits(logits, sizes, text, codes)

    sys_logits, sys_loss = system(params)
    ref_logits, ref_loss = reference(params)
    err, worst = jax.jit(logits_error)(sys_logits, ref_logits)
    loss_err = abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss))
    ok, detail = _verdict(err, worst, {"loss_system": float(sys_loss),
                                       "loss_reference": float(ref_loss),
                                       "loss_rel_err": loss_err})
    return ok and loss_err <= LOSS_TOLERANCE, detail


def _top_k(vocabulary: int, filter_thres: float) -> int:
    return max(int((1.0 - filter_thres) * vocabulary), 1)


def serve_replay_agrees(params, sizes: dict, vae_params, vae_cfg, filter_thres: float,
                        cond_scale: float, delivered: list):
    """`delivered`: dicts with the `text`, `codes` and `image` of requests the
    window completed.  Each code's rank among the reference's (guided) logits
    of its position, and the pixels against the VAE's decode of the codes."""
    from dalle_pytorch_tpu.models import vae_registry

    ref = manifest.reference(sizes)
    ts = int(sizes["text_seq_len"])
    split = int(sizes["num_text_tokens"]) + ts
    k = _top_k(split + int(sizes["num_image_tokens"]), filter_thres)

    @jax.jit
    def ranks(p, text, codes):
        lg = ref.forward_logits(p, sizes, text, codes)[ts:, split:]
        if cond_scale != 1.0:
            null = ref.forward_logits(p, sizes, jnp.zeros_like(text), codes)[ts:, split:]
            lg = null + (lg - null) * cond_scale
        chosen = jnp.take_along_axis(lg, codes[:, None], axis=1)
        return (lg > chosen).sum(axis=1)  # 0 = the largest logit of its position

    @jax.jit
    def pixels_error(vp, codes, image):
        with jax.default_matmul_precision("highest"):
            want = vae_registry.decode_indices(vp, vae_cfg, codes[None]).astype(jnp.float32)
        got = image.astype(jnp.float32).reshape(want.shape)
        return jnp.sqrt(((got - want) ** 2).mean() / (want ** 2).mean())

    outside = beyond = total = 0
    pixel_err = 0.0
    for d in delivered:
        codes = jnp.asarray(d["codes"], jnp.int32)
        r = np.asarray(ranks(params, jnp.asarray(d["text"], jnp.int32), codes))
        outside += int((r >= k).sum())
        beyond += int((r >= NEAR * k).sum())
        total += r.size
        if vae_params is not None:
            pixel_err = max(pixel_err, float(pixels_error(vae_params, codes,
                                                          jnp.asarray(d["image"]))))
    share = outside / total if total else float("nan")
    detail = {"replayed": len(delivered), "codes": total, "top_k": k,
              "outside_top_k_share": share, "beyond_near": beyond,
              "outside_limit": OUTSIDE_SHARE, "pixels_rms_err": pixel_err,
              "pixel_tolerance": PIXEL_TOLERANCE}
    ok = total > 0 and share <= OUTSIDE_SHARE and beyond == 0 \
        and np.isfinite(pixel_err) and pixel_err <= PIXEL_TOLERANCE
    return bool(ok), detail
