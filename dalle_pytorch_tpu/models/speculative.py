"""Self-speculative decoding: shallow-prefix drafter + batched verification.

The sequential decode loop emits one image token per full-depth network
evaluation.  This module cuts the step COUNT (ROADMAP item 3's decode-loop
attack; PR 8 cut bytes per step, PR 13 bytes at rest): a drafter runs only
the first `d` of `depth` layers of the SAME network (no extra params — the
existing `decode_step`/`paged_decode_step` take a [layer_start, layer_stop)
range, and the "draft head" is the model's own final-norm + logits linear
applied to the layer-d hidden) to propose `k` tokens, then ONE verification
dispatch continues layers [d, depth) from the stored layer-d hiddens, scores
all k positions, and accepts the longest correct prefix plus one corrected
(or bonus) token.  Every accepted round advances `a in [1, k+1]` positions
for the price of roughly one full pass plus k shallow passes.

Exactness (the default, `stochastic=False`): sampling here is gumbel-argmax
with a PRECOMPUTED per-position step key — `token_i = f(logits_i, key_i)` is
deterministic.  Verification computes the full-model token v_i at each
position with that position's sequential step key and accepts while the
draft matched (`d_i == v_i`), emitting v_j at the first mismatch.  Every
emitted token is therefore the token the sequential loop would have emitted,
bit-for-bit, at ANY temperature — not just greedy (tests pin `array_equal`
against the sequential sampler).

Stochastic mode (`stochastic=True`): standard rejection/residual sampling
(Leviathan et al.) — accept draft token x with probability min(1, p(x)/q(x)),
resample the first rejection from the residual max(p - q, 0).  Output
matches the sequential sampling DISTRIBUTION (the parity gate is
statistical), not the sequential RNG stream.

Rollback is cheap by design: KV entries for rejected positions are never
read — the dense cache masks keys at `j <= offset`, the paged gathers mask
the same way, and sparse decode tables fold causality into their gather rows
— and each position's (k, v, per-token int8 scales) column is fully
overwritten on the next write, so rejected KV columns need no cleanup.  The
ONLY destructive state is the token-shift ring buffers, restored per round
from the pre-round snapshot at the rejected positions' slots
(`_restore_ring_slots`); the paged pool's host free-list side is a pure
bookkeeping `truncate_slot` (whole-sequence reservations free no blocks).

Constraints enforced by `validate_spec`:
- sequential execution only (reversible twin-stream layers cannot be split
  at layer d — there is no single hidden state to hand off);
- `depth >= 2` (a drafter needs a strict prefix);
- `k + 1 <= image_fmap_size` when token-shift is on, so one round's window
  of ring-slot writes never wraps onto itself.

Overflow discipline: a round may look past the end of the sequence (draft
positions beyond the last real token).  Those offsets clamp to
`seq_len - 1`; the clamped column/ring slot is only ever written by
REJECTED positions (the per-lane advance is capped at the tokens actually
remaining), so the garbage is never read and is restored/overwritten before
any legitimate use.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models import sampling as sampling_mod
from dalle_pytorch_tpu.models.transformer import decode_step, paged_decode_step, refuse_hybrid
from dalle_pytorch_tpu.ops.sampling import gumbel_noise, gumbel_sample, top_k_filter
from dalle_pytorch_tpu.ops.stable import divide_max
from dalle_pytorch_tpu.quantization import maybe_dequant_weight


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------

def resolve_draft_layers(depth: int, spec_draft_layers: Optional[int]) -> int:
    """Default drafter depth: the first half of the stack."""
    # host-sync-ok: static python config int
    d = depth // 2 if spec_draft_layers is None else int(spec_draft_layers)
    if not (1 <= d < depth):
        raise ValueError(
            f"spec_draft_layers={d} must satisfy 1 <= d < depth ({depth})")
    return d


def validate_spec(tcfg, spec_k: int, spec_draft_layers: Optional[int]):
    """Validate (k, d) against the transformer config; returns the resolved
    pair.  Raises ValueError for configurations speculation cannot run on."""
    k = int(spec_k)  # host-sync-ok: static python config int
    # a rejected token is rolled back by masking its K/V column; a recurrent
    # state has no column to mask (it would have to be kept per drafted token)
    refuse_hybrid(tcfg, f"speculative decoding (spec_k={k})", recurrent_state=False)
    if k < 1:
        raise ValueError(f"spec_k={k} must be >= 1 (0 disables speculation)")
    if tcfg.depth < 2:
        raise ValueError("speculative decoding needs depth >= 2 "
                         "(the drafter is a strict prefix of the stack)")
    if tcfg.execution == "reversible":
        raise ValueError(
            "speculative decoding requires sequential execution; reversible "
            "twin-stream layers cannot be split at the draft boundary")
    if tcfg.shift_tokens and k + 1 > tcfg.image_fmap_size:
        raise ValueError(
            f"spec_k={k} too large for image_fmap_size="
            f"{tcfg.image_fmap_size}: a round writes k+1 token-shift ring "
            "slots and must not wrap within one round")
    d = resolve_draft_layers(tcfg.depth, spec_draft_layers)
    return k, d


# ---------------------------------------------------------------------------
# ring rollback
# ---------------------------------------------------------------------------

def _restore_ring_slots(new_rb, old_rb, slots, a):
    """Restore a shift ring buffer's REJECTED slots from the pre-round
    snapshot.  `slots`: (k+1,) int32 ring slots written this round, in feed
    order; `a`: accepted advance (scalar) — slots i >= a revert to old.  The
    fmap axis is ndim-3 both for the dense cache's (b, fmap, 2, q) and for
    one lane's (fmap, 2, q) under `rollback_slot_rings`' vmap."""
    ax = new_rb.ndim - 3
    rb = new_rb
    for i in range(slots.shape[0]):
        sl = slots[i]
        cur = jax.lax.dynamic_index_in_dim(rb, sl, axis=ax, keepdims=True)
        old = jax.lax.dynamic_index_in_dim(old_rb, sl, axis=ax, keepdims=True)
        rb = jax.lax.dynamic_update_index_in_dim(
            rb, jnp.where(i < a, cur, old), sl, axis=ax)
    return rb


def rollback_cache_rings(new_layers, old_layers, slots, a, tcfg):
    """Fused (dense-cache) ring rollback: one shared slot vector and scalar
    advance for the whole batch (acceptance is lockstep under a single cache
    offset).  KV entries are left as-is — rejected columns are masked out of
    every read and rewritten before reuse."""
    if not tcfg.shift_tokens:
        return new_layers
    return [
        dict(
            nl,
            shift_attn=_restore_ring_slots(
                nl["shift_attn"], ol["shift_attn"], slots, a),
            shift_ff=_restore_ring_slots(
                nl["shift_ff"], ol["shift_ff"], slots, a),
        )
        for nl, ol in zip(new_layers, old_layers)
    ]


def rollback_slot_rings(new_rings, old_rings, slots, a):
    """Engine (paged) ring rollback: per-lane slots (S, k+1) and per-lane
    advance (S,) — vmapped over the slot axis of init_slot_rings state."""
    if new_rings is None:
        return None
    fix = jax.vmap(_restore_ring_slots, in_axes=(0, 0, 0, 0))
    return {"layers": [
        {"shift_attn": fix(nl["shift_attn"], ol["shift_attn"], slots, a),
         "shift_ff": fix(nl["shift_ff"], ol["shift_ff"], slots, a)}
        for nl, ol in zip(new_rings["layers"], old_rings["layers"])
    ]}


# ---------------------------------------------------------------------------
# the engine's per-position emit pipeline (single source of truth)
# ---------------------------------------------------------------------------

@jax.named_scope("sample")
def lane_sample_pipeline(params, cfg, out, key_index, state,
                         filter_thres: float, degraded_filter_thres: float):
    """Transformer output -> per-lane sampled code, exactly the serving
    engine's emit pipeline: image logits, poison injection, CFG across lane
    pairs, nonfinite screen, degrade-capped top-k, per-lane step key, gumbel
    sample, feed-source mirror.  `out`: (S, 1, dim); `key_index`: (S,)
    step-key row per lane.  Returns (code (S,) int32 — feed-mirrored so CFG
    pairs agree — and the per-lane nonfinite `bad` flags).  Extracted from
    the engine's fused decode step so the speculative draft/verify passes and
    the sequential step share ONE pipeline and stay bit-identical by
    construction.

    Every position an engine decodes is an IMAGE position (text is prefilled),
    where `logits_mask_slice` forbids exactly the text columns: the pipeline
    runs over the `num_image_tokens` columns of `state["head"]`
    (`dalle.image_head`) alone and emits what the masked full-vocabulary one
    would — the same k (of the whole vocabulary, clamped to the columns there
    are: past them a full-width top-k only adds `finfo.min` entries no gumbel
    draw can lift), the same noise (drawn at the whole vocabulary's shape,
    image columns used), and the argmax IS the code."""
    S = out.shape[0]
    if cfg.stable:
        out = divide_max(out)
    logits = dalle_mod.to_image_logits(params, cfg, state["head"], out)[:, 0]

    inject = jnp.arange(S, dtype=jnp.int32) == state["poison_lane"]
    logits = jnp.where(inject[:, None],
                       jnp.asarray(jnp.nan, logits.dtype), logits)

    null_lg = jnp.take(logits, state["partner"], axis=0)
    lg = jnp.where(
        state["guided"][:, None],
        null_lg + (logits - null_lg) * state["cscale"][:, None].astype(logits.dtype),
        logits,
    )

    bad = ~jnp.isfinite(lg).all(axis=-1) & state["active"]
    lg = jnp.where(bad[:, None], jnp.zeros_like(lg), lg)

    V, ntp = cfg.total_tokens, cfg.num_text_tokens_padded
    k = min(max(int((1.0 - filter_thres) * V), 1), lg.shape[-1])
    k_cap = min(max(int((1.0 - degraded_filter_thres) * V), 1), k)
    val, ind = jax.lax.top_k(lg, k)
    keep = jnp.where(state["cand_cap"][:, None], jnp.arange(k) < k_cap, True)
    val = jnp.where(keep, val, -jnp.inf)
    filtered = jnp.put_along_axis(
        jnp.full_like(lg, -jnp.inf), ind, val, axis=-1, inplace=False)
    keys_t = jnp.take_along_axis(
        state["keys"],
        jnp.clip(key_index, 0, state["keys"].shape[1] - 1)[:, None, None],
        axis=1,
    )[:, 0]

    def sample_one(lg_row, kk, t):
        # the fused sampler's batch-1 draw, (1, V): a threefry draw at
        # another shape is another draw; float32 whatever the logits' type
        # (ops/sampling.gumbel_sample)
        noise = gumbel_noise(kk, (1, V), jnp.float32)[0, ntp:]
        return jnp.argmax(lg_row.astype(jnp.float32) / t + noise, axis=-1)

    code = jax.vmap(sample_one)(filtered, keys_t,
                                state["temp"].astype(jnp.float32))
    code = jnp.take(code.astype(jnp.int32), state["feed_src"], axis=0)
    return code, bad


@jax.named_scope("embed")
def _embed_prev(params, cfg, head, prev, img_idx):
    """The engine's decode-step embedding of a previous code at per-lane
    image positions (mode="clip" keeps clamped overflow positions legal):
    rows of `head`'s table under a shared embedding, of `image_emb`'s own
    otherwise."""
    table = (head["table"] if cfg.share_input_output_emb
             else params["image_emb"]["table"])
    emb = maybe_dequant_weight(
        jax.tree_util.tree_map(
            lambda t: jnp.take(t, prev[:, None], axis=0, mode="clip"), table))
    pos = dalle_mod.image_pos_table(params, cfg)
    if pos is not None:
        emb = emb + jnp.take(pos, img_idx, axis=0, mode="clip")[:, None]
    return emb


# ---------------------------------------------------------------------------
# serving engine: draft + verify round (paged KV, per-lane acceptance)
# ---------------------------------------------------------------------------

def engine_spec_draft(params, cfg, tcfg, state, *, spec_k: int,
                      draft_layers: int, block_size: int,
                      filter_thres: float, degraded_filter_thres: float):
    """Draft `spec_k` tokens per lane through layers [0, d).  Shares the
    full model's paged KV for the shallow layers (layer_stop=d writes those
    columns in place); the layer-d hidden at every draft position is kept
    for the verification pass to continue from, so draft compute is reused,
    not thrown away.  Returns {"pool", "rings", "drafts" (k, S),
    "hiddens" (k, S, 1, dim)}."""
    k, d = spec_k, draft_layers
    seq = tcfg.seq_len
    pool, rings = state["pool"], state["rings"]
    prev = state["prev_code"]
    drafts, hiddens = [], []
    for i in range(k):
        off_i = jnp.minimum(state["offsets"] + i, seq - 1)
        x = _embed_prev(params, cfg, state["head"], prev,
                        state["img_prev"] + i)
        out, pool, rings = paged_decode_step(
            params["transformer"], tcfg, x, pool, state["block_tables"],
            off_i, rings, block_size, layer_stop=d,
        )
        code, _ = lane_sample_pipeline(
            params, cfg, out, state["img_prev"] + i, state,
            filter_thres, degraded_filter_thres,
        )
        drafts.append(code)
        hiddens.append(out)
        prev = code
    return {"pool": pool, "rings": rings,
            "drafts": jnp.stack(drafts), "hiddens": jnp.stack(hiddens)}


def engine_spec_verify(params, cfg, tcfg, state, draft, *, spec_k: int,
                       draft_layers: int, block_size: int, n_gen: int,
                       filter_thres: float, degraded_filter_thres: float):
    """Score all draft positions with the full model and accept per lane.

    Layers [d, depth) continue from the stored layer-d hiddens (position
    order matters only within this one dispatch: continuation i's attention
    reads the deep-layer KV columns continuations < i just wrote).  One
    extra full pass feeds the last draft token — the round's bonus position
    — so a fully-correct draft advances k+1.  The accepted advance per lane
    is `a = leading_matches + 1`, capped to the tokens the lane still needs
    and zeroed for inactive lanes; every emitted token is the one the
    sequential engine step would have produced with the same per-request
    step keys.  Rejected positions roll back: ring slots restore from the
    pre-round `state`, KV columns are left to be overwritten.  Returns
    (new_state, a)."""
    k, d = spec_k, draft_layers
    seq = tcfg.seq_len
    pool, rings = draft["pool"], draft["rings"]
    offsets, img_prev = state["offsets"], state["img_prev"]
    vs, bads = [], []
    for i in range(k):
        off_i = jnp.minimum(offsets + i, seq - 1)
        out, pool, rings = paged_decode_step(
            params["transformer"], tcfg, draft["hiddens"][i], pool,
            state["block_tables"], off_i, rings, block_size, layer_start=d,
        )
        code, bad = lane_sample_pipeline(
            params, cfg, out, img_prev + i, state,
            filter_thres, degraded_filter_thres,
        )
        vs.append(code)
        bads.append(bad)
    # bonus position: feed the last draft token through the FULL stack
    off_k = jnp.minimum(offsets + k, seq - 1)
    x = _embed_prev(params, cfg, state["head"], draft["drafts"][k - 1],
                    img_prev + k)
    out, pool, rings = paged_decode_step(
        params["transformer"], tcfg, x, pool, state["block_tables"],
        off_k, rings, block_size,
    )
    code, bad = lane_sample_pipeline(
        params, cfg, out, img_prev + k, state,
        filter_thres, degraded_filter_thres,
    )
    vs.append(code)
    bads.append(bad)

    vstack = jnp.stack(vs)        # (k+1, S)
    badstack = jnp.stack(bads)    # (k+1, S)
    match = (draft["drafts"] == vstack[:k]).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(match, axis=0), axis=0) + 1  # (S,)
    # lane pairs advance together (drafts and verifies are feed-mirrored, so
    # this take is an identity on healthy state — kept as a hard guarantee)
    a = jnp.take(a, state["feed_src"], axis=0)
    a = jnp.minimum(a, jnp.maximum(n_gen - 1 - img_prev, 0))
    a = jnp.where(state["active"], a, 0)

    # nonfinite flags accumulate only for steps the lane actually took
    taken = jnp.arange(k + 1, dtype=jnp.int32)[:, None] < a[None, :]
    poisoned = state["poisoned"] | (badstack & taken).any(axis=0)

    codes = state["codes"]
    S = codes.shape[0]
    lane_ids = jnp.arange(S)
    for i in range(k + 1):
        widx = jnp.clip(img_prev + 1 + i, 0, n_gen - 1)
        cur = jnp.take_along_axis(codes, widx[:, None], axis=1)[:, 0]
        codes = codes.at[lane_ids, widx].set(jnp.where(i < a, vstack[i], cur))

    prev2 = jnp.take_along_axis(
        vstack, jnp.clip(a - 1, 0, k)[None, :], axis=0)[0]
    prev_code = jnp.where(a > 0, prev2, state["prev_code"])

    text_len = tcfg.text_len
    fmap = tcfg.image_fmap_size
    slots = jnp.stack([
        jnp.mod(jnp.minimum(offsets + i, seq - 1) - text_len, fmap)
        for i in range(k + 1)
    ], axis=1)  # (S, k+1)
    rings = rollback_slot_rings(rings, state["rings"], slots, a)

    new_state = dict(
        state,
        pool=pool,
        rings=rings,
        offsets=offsets + a,
        img_prev=img_prev + a,
        codes=codes,
        prev_code=prev_code,
        poisoned=poisoned,
    )
    return new_state, a


# ---------------------------------------------------------------------------
# fused sampler: speculative decode phase (dense cache, lockstep acceptance)
# ---------------------------------------------------------------------------

def fused_spec_decode(params, cfg, cache, last_logits, key,
                      filter_thres: float, temperature, cond_scale: float,
                      primer_codes, prime_len: int, spec_k: int,
                      spec_draft_layers: Optional[int],
                      stochastic: bool = False, return_stats: bool = False):
    """`_decode_phase` with draft-k-then-verify rounds over the dense cache.

    The cache offset is a single scalar, so acceptance is LOCKSTEP: the
    round advances by the minimum accepted length across the batch (each
    row's emitted tokens are exact regardless — truncating an accepted
    speculative prefix preserves exactness).  The RNG stream is derived
    exactly as `_decode_phase` derives it; in the default deterministic mode
    every emitted token is bit-identical to the sequential sampler's.  With
    `stochastic=True` the draft is accepted by rejection sampling and the
    first rejection resamples from the residual distribution (distribution
    parity, not stream parity).  With return_stats=True also returns
    {"spec_rounds"} so callers can report accepted-tokens/step."""
    tcfg = cfg.transformer_config()
    k, d = validate_spec(tcfg, spec_k, spec_draft_layers)
    guided = cond_scale != 1.0
    b = last_logits.shape[0] // 2 if guided else last_logits.shape[0]
    n_gen = cfg.image_seq_len - prime_len
    assert n_gen > 0, "primer must be shorter than the image sequence"
    n_pre = cfg.text_seq_len + 1 + prime_len
    seq = tcfg.seq_len
    text_len = tcfg.text_len
    fmap = tcfg.image_fmap_size

    def filtered_logits(logits):
        if guided:
            logits = sampling_mod._cfg_combine(logits, cond_scale)
        return top_k_filter(logits, thres=filter_thres)

    def code_of(tok):
        return jnp.clip(tok - cfg.num_text_tokens_padded, 0,
                        cfg.num_image_tokens - 1).astype(jnp.int32)

    def sample_token(logits, sk):
        return code_of(gumbel_sample(sk, filtered_logits(logits),
                                     temperature=temperature))

    key, k0 = jax.random.split(key)
    first_code = sample_token(last_logits, k0)
    step_keys = jax.random.split(key, max(n_gen - 1, 1))
    nk = step_keys.shape[0]

    codes0 = jnp.zeros((b, n_gen), jnp.int32).at[:, 0].set(first_code)
    if n_gen == 1:
        out_codes = codes0
        rounds0 = jnp.zeros((), jnp.int32)
        if prime_len > 0:
            out_codes = jnp.concatenate([primer_codes[:b], out_codes], axis=1)
        return (out_codes, {"spec_rounds": rounds0}) if return_stats else out_codes

    def step_key_at(rel, i):
        return step_keys[jnp.clip(rel - 1 + i, 0, nk - 1)]

    def feed_of(code):
        return jnp.tile(code, (2,)) if guided else code

    def round_body(carry):
        cache, prev_code, rel, codes, rounds = carry
        old_layers = cache["layers"]
        off0 = n_pre + rel - 1          # cache position of the fed token
        img0 = prime_len + rel - 1      # its image position

        # ---- draft: layers [0, d), proposing k tokens -------------------
        drafts, dtoks, hiddens, qdists = [], [], [], []
        prev = prev_code
        for i in range(k):
            off_i = jnp.minimum(off0 + i, seq - 1)
            x = dalle_mod.embed_image_codes(
                params, cfg, feed_of(prev)[:, None], start=img0 + i)
            out, cache = decode_step(
                params["transformer"], tcfg, x, dict(cache, offset=off_i),
                layer_stop=d)
            lg = filtered_logits(
                sampling_mod._logits_at(params, cfg, out, off_i))
            tok = gumbel_sample(step_key_at(rel, i), lg,
                                temperature=temperature)
            if stochastic:
                dtoks.append(tok)
                qdists.append(jax.nn.softmax(
                    lg.astype(jnp.float32) / temperature, axis=-1))
            code = code_of(tok)
            drafts.append(code)
            hiddens.append(out)
            prev = code

        # ---- verify: layers [d, depth) from the stored layer-d hiddens --
        vlogits = []
        for i in range(k):
            off_i = jnp.minimum(off0 + i, seq - 1)
            out, cache = decode_step(
                params["transformer"], tcfg, hiddens[i],
                dict(cache, offset=off_i), layer_start=d)
            vlogits.append(filtered_logits(
                sampling_mod._logits_at(params, cfg, out, off_i)))
        # bonus position: the last draft token through the full stack
        off_k = jnp.minimum(off0 + k, seq - 1)
        x = dalle_mod.embed_image_codes(
            params, cfg, feed_of(drafts[-1])[:, None], start=img0 + k)
        out, cache = decode_step(
            params["transformer"], tcfg, x, dict(cache, offset=off_k))
        vlogits.append(filtered_logits(
            sampling_mod._logits_at(params, cfg, out, off_k)))

        dstack = jnp.stack(drafts)  # (k, b)
        if not stochastic:
            vstack = jnp.stack([
                code_of(gumbel_sample(step_key_at(rel, i), vlogits[i],
                                      temperature=temperature))
                for i in range(k + 1)
            ])  # (k+1, b)
            mvec = jnp.all(dstack == vstack[:k], axis=1).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(mvec)) + 1
            emit = vstack
        else:
            # rejection sampling: accept draft token x_i with prob
            # min(1, p_i(x)/q_i(x)); the first rejection resamples from the
            # residual max(p - q, 0).  Lockstep truncation to the batch-min
            # accepted length keeps every emitted token's marginal exact.
            accs, resamples = [], []
            for i in range(k):
                p = jax.nn.softmax(
                    vlogits[i].astype(jnp.float32) / temperature, axis=-1)
                q = qdists[i]
                px = jnp.take_along_axis(p, dtoks[i][:, None], axis=1)[:, 0]
                qx = jnp.take_along_axis(q, dtoks[i][:, None], axis=1)[:, 0]
                u = jax.random.uniform(
                    jax.random.fold_in(step_key_at(rel, i), 1), (b,))
                accs.append((u * qx < px).astype(jnp.int32))
                resid = jnp.clip(p - q, 0.0, None)
                rtok = gumbel_sample(
                    jax.random.fold_in(step_key_at(rel, i), 2),
                    jnp.log(jnp.clip(resid, 1e-20, None)))
                resamples.append(code_of(rtok))
            bonus = code_of(gumbel_sample(step_key_at(rel, k), vlogits[k],
                                          temperature=temperature))
            lvec = jnp.sum(jnp.cumprod(jnp.stack(accs), axis=0), axis=0)
            m = jnp.min(lvec)            # lockstep accepted draft count
            a = m + 1
            rstack = jnp.stack(resamples + [bonus])   # (k+1, b)
            dpad = jnp.concatenate([dstack, bonus[None]])
            # row r emits d_i for i < m, then: its own residual resample if
            # it rejected at m, the accepted d_m if it rejected later, the
            # bonus when every row accepted the whole draft (m == k)
            final = jnp.where(lvec == m, rstack[m], dpad[m])
            emit = jnp.concatenate(
                [dstack, jnp.zeros((1, b), jnp.int32)]
            ).at[m].set(final)

        a = jnp.minimum(a, n_gen - rel)
        for i in range(k + 1):
            widx = jnp.minimum(rel + i, n_gen - 1)
            cur = jnp.take(codes, widx, axis=1)
            codes = codes.at[:, widx].set(jnp.where(i < a, emit[i], cur))

        slots = jnp.stack([
            jnp.mod(jnp.minimum(off0 + i, seq - 1) - text_len, fmap)
            for i in range(k + 1)
        ])
        new_layers = rollback_cache_rings(
            cache["layers"], old_layers, slots, a, tcfg)
        cache = dict(cache, offset=(off0 + a).astype(jnp.int32),
                     layers=new_layers)
        prev2 = jnp.take(emit, jnp.clip(a - 1, 0, k), axis=0)
        return (cache, prev2, rel + a, codes, rounds + 1)

    init = (dict(cache, offset=jnp.asarray(n_pre, jnp.int32)), first_code,
            jnp.asarray(1, jnp.int32), codes0, jnp.zeros((), jnp.int32))
    _, _, _, codes, rounds = jax.lax.while_loop(
        lambda c: c[2] < n_gen, round_body, init)

    if prime_len > 0:
        codes = jnp.concatenate([primer_codes[:b], codes], axis=1)
    if return_stats:
        return codes, {"spec_rounds": rounds}
    return codes
