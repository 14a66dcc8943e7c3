"""Autoregressive sampling for DALLE.

Parity with /root/reference/dalle_pytorch/dalle_pytorch.py:459-574
(generate_images / generate_texts / forward_with_cond_scale), redesigned for
XLA: the image loop is a single lax.scan over fixed-shape carried state (KV
cache + token-shift ring buffers), prefill consumes the whole text prompt in
one pass, and classifier-free guidance runs as a doubled batch ([cond; null])
through one network evaluation per step instead of the reference's two
sequential forwards with a copied cache dict — mathematically identical,
twice the MXU utilization.

Image priming takes a static primer length (static shapes are what XLA
compiles); the reference's 0.4375 * image_seq_len default is preserved.

With sparse attention patterns the decode loop is sparse-aware by default
(DALLEConfig.sparse_decode): each step gathers only the pattern-permitted
keys from the KV cache (kernels/sparse_index.build_decode_tables) instead
of reading and row-masking the whole prefix — the difference between O(seq)
and O(Kmax) cache reads per token, which is what makes image_fmap_size=64
(seq 4096+) sampling tractable.  The gathered softmax is reduction-order-ulp
close (not bit-identical) to the full-cache read; parity-RNG comparisons
against pre-gather implementations should pin sparse_decode=False.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.quantization import weight_dtype as _weight_dtype
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.transformer import (
    apply_transformer, decode_step, init_cache, prefill, refuse_hybrid,
)
from dalle_pytorch_tpu.ops.sampling import gumbel_sample, top_k_filter
from dalle_pytorch_tpu.ops.stable import divide_max

DEFAULT_PRIME_FRACTION = 0.4375  # OpenAI used 14 * 32 initial tokens to prime


@jax.named_scope("sample")
def _logits_at(params, cfg: DALLEConfig, out_last: jnp.ndarray, position) -> jnp.ndarray:
    """Masked vocab logits from the transformer output at `position` (the row
    index selects the logits-mask slice, matching dalle_pytorch.py:646-652)."""
    if cfg.stable:
        out_last = divide_max(out_last)
    logits = dalle_mod.to_logits(params, cfg, out_last)
    mask_row = dalle_mod.logits_mask_slice(cfg, cfg.total_seq_len)
    row = jax.lax.dynamic_slice(mask_row, (position, 0), (1, cfg.total_tokens))[0]
    return jnp.where(row[None, :], jnp.finfo(logits.dtype).min, logits[:, 0])


def _cfg_combine(logits: jnp.ndarray, cond_scale: float) -> jnp.ndarray:
    """[cond; null] stacked logits -> guided logits (Crowson CFG)."""
    b = logits.shape[0] // 2
    cond, null = logits[:b], logits[b:]
    return null + (cond - null) * cond_scale


def _prefill_phase(
    params: dict,
    cfg: DALLEConfig,
    text: jnp.ndarray,
    primer_codes: Optional[jnp.ndarray],
    prime_len: int,
    cond_scale: float,
):
    """Everything before the first sampled token: CFG batch doubling, bos +
    text (+ primer) embedding, KV-cache prefill, and the logits for the
    first generated position.  Returns (cache, last_logits).  Split out so
    telemetry-enabled callers can dispatch prefill and decode as separate
    jits and attribute wall-clock per phase; `sample_image_codes` fuses both
    phases into one jit (the graph is identical either way)."""
    tcfg = cfg.transformer_config()
    guided = cond_scale != 1.0

    if guided:
        text = jnp.concatenate([text, jnp.zeros_like(text)], axis=0)
        if primer_codes is not None:
            primer_codes = jnp.concatenate([primer_codes, primer_codes], axis=0)
    bb = text.shape[0]

    # ---- prefill: bos + text (+ primer) in one pass ----------------------
    text_ids = dalle_mod.remap_and_bos(cfg, text)
    tokens = dalle_mod.embed_text_ids(params, cfg, text_ids)
    if prime_len > 0:
        assert primer_codes is not None
        tokens = jnp.concatenate(
            [tokens, dalle_mod.embed_image_codes(params, cfg, primer_codes, start=0)], axis=1
        )
    n_pre = tokens.shape[1]

    cache = init_cache(tcfg, bb, dtype=_weight_dtype(params))
    out, cache = prefill(params["transformer"], tcfg, tokens, cache)
    last_logits = _logits_at(params, cfg, out[:, -1:], n_pre - 1)
    return cache, last_logits


def _decode_phase(
    params: dict,
    cfg: DALLEConfig,
    cache,
    last_logits: jnp.ndarray,
    key: jax.Array,
    filter_thres: float,
    temperature,
    cond_scale: float,
    primer_codes: Optional[jnp.ndarray],
    prime_len: int,
    noise_override: Optional[jnp.ndarray],
    collect_stats: bool = False,
):
    """The autoregressive image loop from a prefilled cache.  `primer_codes`
    is the ORIGINAL (un-doubled) primer.  With collect_stats=True also
    returns {"logit_max", "entropy_mean"} over the (guided, top-k-filtered)
    sampling distributions — the sampling-time logit numerics."""
    guided = cond_scale != 1.0
    b = last_logits.shape[0] // 2 if guided else last_logits.shape[0]
    tcfg = cfg.transformer_config()
    n_gen = cfg.image_seq_len - prime_len
    assert n_gen > 0, "primer must be shorter than the image sequence"

    def sample_token(logits, k, noise):
        if guided:
            logits = _cfg_combine(logits, cond_scale)
        filtered = top_k_filter(logits, thres=filter_thres)
        if noise is not None:
            tok = jnp.argmax(filtered / temperature + noise, axis=-1)
        else:
            tok = gumbel_sample(k, filtered, temperature=temperature)
        code = jnp.clip(tok - cfg.num_text_tokens_padded, 0, cfg.num_image_tokens - 1)
        if not collect_stats:
            return code, None
        f32 = filtered.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(f32, axis=-1)
        p = jax.nn.softmax(f32, axis=-1)
        # filtered entries are -inf with p == 0: mask before multiplying
        # (0 * -inf is NaN, not the 0 the entropy identity needs)
        plog = jnp.where(jnp.isfinite(f32), p * f32, 0.0)
        ent = lse - jnp.sum(plog, axis=-1)
        return code, {"logit_max": jnp.max(f32), "entropy_mean": jnp.mean(ent)}

    key, k0 = jax.random.split(key)
    first_code, first_stats = sample_token(
        last_logits, k0, noise_override[0] if noise_override is not None else None
    )

    step_keys = jax.random.split(key, max(n_gen - 1, 1))

    # NB: positions — the transformer output at sequence position p produces
    # the logits for sequence position p+1; the logits-mask row is p (the
    # reference masks rows by the producing position).
    def body(carry, xs):
        step_key, noise = xs if noise_override is not None else (xs, None)
        cache, prev_code, img_pos = carry
        feed = jnp.tile(prev_code, (2,)) if guided else prev_code
        x = dalle_mod.embed_image_codes(params, cfg, feed[:, None], start=img_pos)
        out, cache = decode_step(params["transformer"], tcfg, x, cache)
        logits = _logits_at(params, cfg, out, cache["offset"] - 1)
        code, stats = sample_token(logits, step_key, noise)
        ys = (code, stats) if collect_stats else code
        return (cache, code, img_pos + 1), ys

    init = (cache, first_code, jnp.asarray(prime_len, jnp.int32))
    step_stats = None
    if n_gen > 1:
        xs = step_keys[: n_gen - 1]
        if noise_override is not None:
            xs = (xs, noise_override[1:n_gen])
        (_, _, _), rest = jax.lax.scan(body, init, xs)
        if collect_stats:
            rest, step_stats = rest
        codes = jnp.concatenate([first_code[None], rest], axis=0).T  # (b, n_gen)
    else:
        codes = first_code[:, None]

    if prime_len > 0:
        codes = jnp.concatenate([primer_codes[:b], codes], axis=1)
    if not collect_stats:
        return codes
    if step_stats is not None:
        logit_max = jnp.maximum(first_stats["logit_max"],
                                jnp.max(step_stats["logit_max"]))
        entropy_mean = (
            first_stats["entropy_mean"] + jnp.sum(step_stats["entropy_mean"])
        ) / n_gen
    else:
        logit_max = first_stats["logit_max"]
        entropy_mean = first_stats["entropy_mean"]
    return codes, {"logit_max": logit_max, "entropy_mean": entropy_mean}


# jitted per-phase variants for the telemetry path (generate_images): two
# dispatches with a block between them is what turns "sampling is slow" into
# "prefill-bound vs decode-bound"
_prefill_jit = partial(
    jax.jit, static_argnames=("cfg", "cond_scale", "prime_len")
)(_prefill_phase)
_decode_jit = partial(
    jax.jit,
    static_argnames=("cfg", "filter_thres", "cond_scale", "prime_len",
                     "collect_stats"),
)(_decode_phase)


@partial(
    jax.jit,
    static_argnames=("cfg", "filter_thres", "cond_scale", "prime_len",
                     "return_logit_stats", "spec_k", "spec_draft_layers",
                     "spec_stochastic"),
)
def sample_image_codes(
    params: dict,
    cfg: DALLEConfig,
    text: jnp.ndarray,
    key: jax.Array,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    cond_scale: float = 1.0,
    primer_codes: Optional[jnp.ndarray] = None,
    prime_len: int = 0,
    noise_override: Optional[jnp.ndarray] = None,
    return_logit_stats: bool = False,
    spec_k: int = 0,
    spec_draft_layers: Optional[int] = None,
    spec_stochastic: bool = False,
) -> jnp.ndarray:
    """text: (b, text_seq_len) raw token ids (0 = pad).  primer_codes:
    optional (b, prime_len) VAE codes to prime the image with.
    noise_override: optional (n_gen, b, total_tokens) pre-generated gumbel
    noise consumed instead of key-derived noise — the parity-RNG mode for
    bit-exact comparison against other implementations (SURVEY.md §7 hard
    part #1).  Returns (b, image_seq_len) image codes (primer included);
    with return_logit_stats=True returns (codes, {"logit_max",
    "entropy_mean"}) — sampling-distribution numerics for health telemetry.

    spec_k > 0 turns on self-speculative decoding (models/speculative):
    draft spec_k tokens through the first `spec_draft_layers` layers, verify
    all of them in one full-model pass, accept the longest exact prefix.
    The default match mode re-derives each position's token from the SAME
    per-position step key the sequential scan would have used, so the output
    is bit-identical to spec_k=0 at any temperature; spec_stochastic=True
    swaps in standard rejection/residual sampling (same marginals, different
    RNG stream).  spec_k=0 is exactly today's path — same jit graph."""
    refuse_hybrid(cfg.transformer_config(), "sample_image_codes")
    if spec_k > 0:
        assert noise_override is None, "speculation owns the RNG stream"
        assert not return_logit_stats, "logit stats live on the scan path"
        from dalle_pytorch_tpu.models import speculative as spec_mod

        cache, last_logits = _prefill_phase(
            params, cfg, text, primer_codes, prime_len, cond_scale
        )
        return spec_mod.fused_spec_decode(
            params, cfg, cache, last_logits, key, filter_thres, temperature,
            cond_scale, primer_codes, prime_len, spec_k, spec_draft_layers,
            stochastic=spec_stochastic,
        )
    cache, last_logits = _prefill_phase(
        params, cfg, text, primer_codes, prime_len, cond_scale
    )
    return _decode_phase(
        params, cfg, cache, last_logits, key, filter_thres, temperature,
        cond_scale, primer_codes, prime_len, noise_override,
        collect_stats=return_logit_stats,
    )


class ExecutableCache:
    """AOT-compiled prefill/decode executables keyed by (batch, cond_scale,
    prime_len, filter_thres).

    `jax.jit` already caches traces per (shapes, statics), but every
    dispatch still walks the trace-cache lookup, canonicalizes statics, and
    — after anything flushed the global jit caches (telemetry lowering,
    cross-checks) — silently re-traces.  A serving-adjacent caller (api.DALLE
    repeatedly sampling the same batch shape) instead holds the COMPILED
    executables and invokes them directly: zero retrace risk, and the
    hit/miss counters make the compile bill observable
    (`gen/exec_cache_hits` / `gen/exec_cache_misses`).  Temperature and the
    PRNG key stay dynamic, so neither is part of the cache key."""

    def __init__(self):
        self._cache = {}

    def _key(self, text, cond_scale, prime_len, filter_thres):
        return (int(text.shape[0]), float(cond_scale), int(prime_len),
                float(filter_thres))

    def entries(self):
        return dict(self._cache)

    def get_or_compile(self, params, cfg, text, primer_codes, prime_len,
                       cond_scale, filter_thres, key, temperature):
        k = self._key(text, cond_scale, prime_len, filter_thres)
        entry = self._cache.get(k)
        if entry is not None:
            obs_metrics.counter("gen/exec_cache_hits").inc()
            return entry
        obs_metrics.counter("gen/exec_cache_misses").inc()
        pre = _prefill_jit.lower(
            params, cfg, text, primer_codes, prime_len, cond_scale
        ).compile()
        abs_cache, abs_logits = jax.eval_shape(
            lambda p, t, pc: _prefill_phase(p, cfg, t, pc, prime_len, cond_scale),
            params, text, primer_codes,
        )
        dec = _decode_jit.lower(
            params, cfg, abs_cache, abs_logits, key, filter_thres,
            temperature, cond_scale, primer_codes, prime_len, None,
            collect_stats=False,
        ).compile()
        entry = (pre, dec)
        self._cache[k] = entry
        return entry

    def sample(self, params, cfg, text, key, filter_thres, temperature,
               cond_scale, primer_codes, prime_len):
        """Codes via the cached executables, with per-phase wall-clock.
        Returns (codes, prefill_s, decode_s).  `temperature` stays a python
        float (WEAK dtype) so promotion inside the executable matches the
        jitted path bit-for-bit under low-precision params."""
        temperature = float(temperature)
        pre, dec = self.get_or_compile(
            params, cfg, text, primer_codes, prime_len, cond_scale,
            filter_thres, key, temperature,
        )
        t0 = time.perf_counter()
        cache, last_logits = pre(params, text, primer_codes)
        jax.block_until_ready(last_logits)
        prefill_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        codes = dec(params, cache, last_logits, key, temperature,
                    primer_codes, None)
        jax.block_until_ready(codes)
        return codes, prefill_s, time.perf_counter() - t0


def generate_images(
    params: dict,
    cfg: DALLEConfig,
    vae_params: dict,
    vae_cfg,
    text: jnp.ndarray,
    key: jax.Array,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    img: Optional[jnp.ndarray] = None,
    num_init_img_tokens: Optional[int] = None,
    cond_scale: float = 1.0,
    clip_params: Optional[dict] = None,
    clip_cfg=None,
    exec_cache: Optional[ExecutableCache] = None,
    spec_k: int = 0,
    spec_draft_layers: Optional[int] = None,
):
    """Full pipeline: sample codes, decode through the VAE (any family —
    DiscreteVAE / VQGAN / OpenAI dVAE, dispatched on the config type),
    optionally score with CLIP.  img: optional (b, H, W, C) raw pixels for
    priming.

    With telemetry active, inference-side metrics land in the registry:
    prefill vs decode wall-clock (dispatched as two jits with a block in
    between — same graph, so parity with the fused path is exact),
    image-tokens/sec, VAE decode time, sampling-logit numerics, and a CFG
    overhead counter when cond_scale != 1 (guidance doubles every network
    evaluation)."""
    refuse_hybrid(cfg.transformer_config(), "generate_images")
    from dalle_pytorch_tpu.models import clip as clip_mod
    from dalle_pytorch_tpu.models import vae_registry

    text = text[:, : cfg.text_seq_len]
    primer = None
    prime_len = 0
    if img is not None:
        indices = vae_registry.get_codebook_indices(vae_params, vae_cfg, img)
        prime_len = (
            num_init_img_tokens
            if num_init_img_tokens is not None
            else int(DEFAULT_PRIME_FRACTION * cfg.image_seq_len)
        )
        assert prime_len < cfg.image_seq_len
        primer = indices[:, :prime_len]

    b = int(text.shape[0])
    n_gen = cfg.image_seq_len - prime_len
    tele = telemetry.active()
    if spec_k > 0:
        # speculative sampling is one fused jit (draft + verify rounds in a
        # while_loop) — the AOT exec-cache and the phase-split telemetry jits
        # don't carry it, so both are bypassed here; wall-clock still lands
        # in the decode histogram (prefill is fused into the same dispatch)
        import contextlib

        suspend = (tele.compile_watcher.suspended()
                   if tele is not None and tele.compile_watcher is not None
                   else contextlib.nullcontext())
        with suspend:
            t0 = time.perf_counter()
            codes = sample_image_codes(
                params, cfg, text, key,
                filter_thres=filter_thres, temperature=temperature,
                cond_scale=cond_scale, primer_codes=primer,
                prime_len=prime_len, spec_k=spec_k,
                spec_draft_layers=spec_draft_layers,
            )
            jax.block_until_ready(codes)
            decode_s = time.perf_counter() - t0
        if tele is not None:
            obs_metrics.histogram("gen/decode_s").observe(decode_s)
            obs_metrics.counter("gen/images").inc(b)
            obs_metrics.counter("gen/image_tokens").inc(b * n_gen)
            obs_metrics.gauge("gen/image_tokens_per_sec").set(
                b * n_gen / max(decode_s, 1e-9)
            )
        return _finish_generate(
            vae_params, vae_cfg, text, codes, clip_params, clip_cfg,
        )
    if exec_cache is not None:
        import contextlib

        suspend = (tele.compile_watcher.suspended()
                   if tele is not None and tele.compile_watcher is not None
                   else contextlib.nullcontext())
        with suspend:
            try:
                codes, prefill_s, decode_s = exec_cache.sample(
                    params, cfg, text, key, filter_thres, temperature,
                    cond_scale, primer, prime_len,
                )
            except Exception:
                # AOT path unavailable on this backend/config — fall back to
                # the jitted path (counted so the fallback is observable)
                obs_metrics.counter("gen/exec_cache_fallbacks").inc()
                codes, prefill_s, decode_s = None, None, None
        if codes is not None and tele is not None:
            obs_metrics.histogram("gen/prefill_s").observe(prefill_s)
            obs_metrics.histogram("gen/decode_s").observe(decode_s)
            obs_metrics.counter("gen/images").inc(b)
            obs_metrics.counter("gen/image_tokens").inc(b * n_gen)
            obs_metrics.gauge("gen/image_tokens_per_sec").set(
                b * n_gen / max(decode_s, 1e-9)
            )
        if codes is not None:
            return _finish_generate(
                vae_params, vae_cfg, text, codes, clip_params, clip_cfg,
            )
    if tele is None:
        codes = sample_image_codes(
            params, cfg, text, key,
            filter_thres=filter_thres, temperature=temperature, cond_scale=cond_scale,
            primer_codes=primer, prime_len=prime_len,
        )
    else:
        import contextlib

        # sampling compiles are expected per shape and are not step-loop
        # thrash — shield them from the steady-state recompile alarm
        suspend = (tele.compile_watcher.suspended()
                   if tele.compile_watcher is not None
                   else contextlib.nullcontext())
        with suspend:
            with telemetry.span("gen_prefill"):
                t0 = time.perf_counter()
                cache, last_logits = _prefill_jit(
                    params, cfg, text, primer, prime_len, cond_scale
                )
                jax.block_until_ready(last_logits)
                prefill_s = time.perf_counter() - t0
            with telemetry.span("gen_decode"):
                t0 = time.perf_counter()
                codes, lstats = _decode_jit(
                    params, cfg, cache, last_logits, key, filter_thres, temperature,
                    cond_scale, primer, prime_len, None, collect_stats=True,
                )
                jax.block_until_ready(codes)
                decode_s = time.perf_counter() - t0
        obs_metrics.histogram("gen/prefill_s").observe(prefill_s)
        obs_metrics.histogram("gen/decode_s").observe(decode_s)
        obs_metrics.counter("gen/images").inc(b)
        obs_metrics.counter("gen/image_tokens").inc(b * n_gen)
        obs_metrics.gauge("gen/image_tokens_per_sec").set(
            b * n_gen / max(decode_s, 1e-9)
        )
        import numpy as np

        obs_metrics.gauge("gen/logit_max").set(float(np.asarray(lstats["logit_max"])))
        obs_metrics.gauge("gen/logit_entropy_mean").set(
            float(np.asarray(lstats["entropy_mean"]))
        )
        if cond_scale != 1.0:
            # every prefill token and every decode step runs twice ([cond;
            # null]); this counter is the guidance bill in token evaluations
            obs_metrics.counter("gen/cfg_extra_token_evals").inc(
                b * (cfg.text_seq_len + 1 + cfg.image_seq_len)
            )

    return _finish_generate(vae_params, vae_cfg, text, codes, clip_params, clip_cfg)


def _finish_generate(vae_params, vae_cfg, text, codes, clip_params, clip_cfg):
    """The shared pipeline tail: VAE decode (+ timing) and optional CLIP
    rerank — used by both the jitted and the exec-cached sampling paths."""
    from dalle_pytorch_tpu.models import clip as clip_mod
    from dalle_pytorch_tpu.models import vae_registry

    t0 = time.perf_counter()
    images = vae_registry.decode_indices(vae_params, vae_cfg, codes)
    if telemetry.active() is not None:
        jax.block_until_ready(images)
        obs_metrics.histogram("gen/vae_decode_s").observe(time.perf_counter() - t0)

    if clip_params is not None:
        scores = clip_mod.forward(clip_params, clip_cfg, text, images)
        return images, scores
    return images


def generate_texts(
    params: dict,
    cfg: DALLEConfig,
    key: jax.Array,
    text: Optional[jnp.ndarray] = None,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    use_cache: bool = True,
) -> jnp.ndarray:
    """Text completion (the reference's generate_texts,
    dalle_pytorch.py:459-504): no bos, no pad-remap.  text: (b, n0) prompt
    ids (defaults to a single 0 token).  Returns (b, text_seq_len) ids.

    use_cache=True runs prefill + KV-cached single-token decode steps —
    O(text_len) work per token instead of the reference's full
    O(text_len^2 * depth) re-forward per token (its own generate_texts never
    caches).  use_cache=False keeps the reference-shaped re-forward loop;
    both paths consume the identical RNG stream, so outputs agree."""
    refuse_hybrid(cfg.transformer_config(), "generate_texts", recurrent_state=False)
    if text is None:
        text = jnp.zeros((1, 1), jnp.int32)
    text = text.astype(jnp.int32)
    b, n0 = text.shape
    ts = cfg.text_seq_len
    if n0 >= ts:
        return text[:, :ts]
    if use_cache:
        return _generate_texts_cached(
            params, cfg, key, text, filter_thres=filter_thres, temperature=temperature
        )
    buf = jnp.zeros((b, ts), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, text, (0, 0))

    tcfg = cfg.transformer_config()
    mask_rows = dalle_mod.logits_mask_slice(cfg, ts)

    def step(cur, carry):
        buf, key = carry
        key, sk = jax.random.split(key)
        emb = jnp.take(dalle_mod._text_table(params, cfg), buf, axis=0, mode="clip")
        if cfg.learned_positions:
            emb = emb + jnp.take(params["text_pos"]["table"], jnp.arange(ts), axis=0)
        out = apply_transformer(params["transformer"], tcfg, emb)
        if cfg.stable:
            out = divide_max(out)
        logits = dalle_mod.to_logits(params, cfg, out)
        logits = jnp.where(mask_rows[None], jnp.finfo(logits.dtype).min, logits)
        row = jax.lax.dynamic_slice(logits, (0, cur - 1, 0), (b, 1, cfg.total_tokens))[:, 0]
        tok = gumbel_sample(sk, top_k_filter(row, thres=filter_thres), temperature=temperature)
        buf = jax.lax.dynamic_update_slice(buf, tok[:, None].astype(jnp.int32), (0, cur))
        return buf, key

    buf, _ = jax.lax.fori_loop(n0, ts, step, (buf, key))
    return buf


@partial(jax.jit, static_argnames=("cfg", "filter_thres", "temperature"))
def _generate_texts_cached(
    params: dict,
    cfg: DALLEConfig,
    key: jax.Array,
    text: jnp.ndarray,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
) -> jnp.ndarray:
    """KV-cached text completion: prefill the (b, n0) prompt once, then one
    decode_step per generated token (text_only — the token shift is the
    identity in the text region)."""
    b, n0 = text.shape
    ts = cfg.text_seq_len
    tcfg = cfg.transformer_config()
    mask_rows = dalle_mod.logits_mask_slice(cfg, ts)
    table = dalle_mod._text_table(params, cfg)

    def embed(ids, start):
        e = jnp.take(table, ids, axis=0, mode="clip")
        if cfg.learned_positions:
            pos = jnp.take(
                params["text_pos"]["table"],
                start + jnp.arange(ids.shape[1]),
                axis=0,
                mode="clip",
            )
            e = e + pos
        return e

    def logits_row(out1, pos):
        if cfg.stable:
            out1 = divide_max(out1)
        lg = dalle_mod.to_logits(params, cfg, out1)[:, 0]
        row = jax.lax.dynamic_slice(mask_rows, (pos, 0), (1, cfg.total_tokens))[0]
        return jnp.where(row[None, :], jnp.finfo(lg.dtype).min, lg)

    def sample_from(lg, sk):
        return gumbel_sample(
            sk, top_k_filter(lg, thres=filter_thres), temperature=temperature
        ).astype(jnp.int32)

    cache = init_cache(tcfg, b, dtype=_weight_dtype(params))
    out, cache = prefill(params["transformer"], tcfg, embed(text, 0), cache)

    key, sk = jax.random.split(key)
    tok0 = sample_from(logits_row(out[:, -1:], n0 - 1), sk)

    def body(carry, _):
        cache, prev, key = carry
        x = embed(prev[:, None], cache["offset"])
        out1, cache = decode_step(params["transformer"], tcfg, x, cache, text_only=True)
        lg = logits_row(out1, cache["offset"] - 1)
        key, sk = jax.random.split(key)
        tok = sample_from(lg, sk)
        return (cache, tok, key), tok

    n_rest = ts - n0 - 1
    if n_rest > 0:
        _, rest = jax.lax.scan(body, (cache, tok0, key), None, length=n_rest)
        gen = jnp.concatenate([tok0[None], rest], axis=0).T  # (b, ts - n0)
    else:
        gen = tok0[:, None]
    return jnp.concatenate([text, gen], axis=1)
