"""Plain float32 reference for a Laguna trunk in the DALL-E token stream:
forward and loss (and, through `jax.grad`, the loss's gradient).

The yardstick `correct` is decided against for the `laguna_*` configurations.
Straightforward `jax.numpy`, one sequence at a time, float32 under
`jax.default_matmul_precision("highest")`; no kernels, no chunking of the
routing, no sorting, no batching.  Its gradient (`jax.grad` of `loss`) is
what `correct` holds a training step's gradient to; the layers and the score
blocks are rematerialised under it (`jax.checkpoint`, which changes no
number) so that it fits one chip at the published widths.  From the program it takes only the
parameter tree (the weights' storage format) and, through `sizes`, the
configuration file's numbers.  The block follows the published model
(https://huggingface.co/poolside/Laguna-XS.2, `config.json`, as the catalog's
row copies it); x is a token's hidden vector, no projection has a bias, and
N(x) = x / sqrt(mean(x^2) + eps) * w:

    h = x + Attn_l(N(x));   y = h + FF_l(N(h));   a final N, then the untied head

  * Attn_l is of kind `gated_full` or `gated_window`, by `attn_types[l % len]`,
    with H = heads (full) or window_heads (window) query heads and kv_heads
    key/value heads of dim_head; u = N(x):
      q_h = W_q,h u, k_j = W_k,j u, v_j = W_v,j u; query head h reads key/value
      head j = floor(h / (H / kv_heads)); q_h and k_j pass a per-head N;
      rotary (rotate-half: channel i with i + rot/2), by stream position p:
        full: YaRN on the first partial_rotary_factor * dim_head channels,
          inv_i = inv_extrap_i (1 - r_i) + inv_extrap_i / factor * r_i,
          inv_extrap_i = theta^(-2i/rot), r_i = clip((i - low) / (high - low), 0, 1),
          low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
          c(b) = rot ln(original_max / (2 pi b)) / (2 ln theta), all in float64;
          cos and sin times the attention factor;
        window: the default rotary, window_rotary_theta, on all of dim_head;
      s_ij = q_i . k_j / sqrt(dim_head) over the FULL score matrix, masked:
        full j <= i, window 0 <= i - j < window;
      o_h = softmax(s) v;  g_h = sigmoid(w_g,h . u), one scalar a head;
      out = W_o [g_1 o_1; ...; g_H o_H].
  * FF_l: layer l < dense_layers a SwiGLU W_d (silu(W_g u) * W_u u) of
    dense_ff_dim; the others routed: p = softmax(W_r u) over ALL moe_experts,
    T = top-moe_top_k(p), w_e = p_e / sum_T p x moe_routed_scale,
    FF(u) = sum_{e in T, e held} w_e E_e(u) + sigmoid(w_s . u) E_s(u), every E a
    SwiGLU of moe_ff_dim (E_s the shared expert); every token, whatever the load.

Departures from the published description, each also under `assumed` or
`reduced` in the configuration's file:
  * the stream is this repository's: [<bos>, text, image raster] with
    per-position pad ids, logits masked so that text positions predict text
    and image positions image, loss = (CE_text + w * CE_image) / (w + 1); the
    model's own tokenizer and vocabulary are not used;
  * only the experts [moe_first_expert, moe_first_expert + moe_experts_held)
    are held: the router still scores all `moe_experts`, and the terms of the
    absent experts are LEFT OUT of the sum, here as in the program (one rank's
    part of an expert-parallel layer; model-configs guide, section 4);
  * four things the row's config does not give are set by the family's
    convention: the gate is one scalar a head (`gating` true; the sibling
    Laguna-S-2.1 spells it "per-head"), q and k pass a per-head N (Qwen3-MoE's
    attention, whose keys the family carries), the softmax router is
    renormalised over the top k (`norm_topk_prob`, which S-2.1 sets) and the
    shared expert sits behind a sigmoid gate (Qwen2-MoE's
    `shared_expert_intermediate_size`);
  * the per-head gate is laid as the last H columns of W_q ([q ; gate]), and
    rotation pairs channel i with i + rot/2 (rotate-half): with seeded random
    weights any such layout is the same model.

`products_rounded_to(dtype)` rounds both operands of every product to `dtype`
first (the router's aside, which the program keeps float32 at full precision):
the reading that says what a lower precision than the stated one would show.
It is read while a function is TRACED: jit a new function under it.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_PRODUCT_DTYPE = None  # None = float32 operands; see products_rounded_to


@contextlib.contextmanager
def products_rounded_to(dtype):
    global _PRODUCT_DTYPE
    before, _PRODUCT_DTYPE = _PRODUCT_DTYPE, dtype
    try:
        yield
    finally:
        _PRODUCT_DTYPE = before


def _f32(a):
    return jnp.asarray(a).astype(F32)


def _dot(a, b):
    if _PRODUCT_DTYPE is not None:
        a, b = a.astype(_PRODUCT_DTYPE).astype(F32), b.astype(_PRODUCT_DTYPE).astype(F32)
    return a @ b


def _mat(p, x):
    return _dot(x, _f32(p["w"]))


def _norm(w, x, eps):
    return _f32(w) * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# ----------------------------------------------------------------- rotary
def yarn_inv_freq(sizes: dict):
    """(inverse frequencies (rot/2,) float64, low, high) of the full kind."""
    rot = int(int(sizes["dim_head"]) * float(sizes["partial_rotary_factor"]))
    theta, factor = float(sizes["rotary_theta"]), float(sizes["rope_yarn_factor"])
    original = float(sizes["rope_yarn_original_max"])

    def c(b):
        return rot * math.log(original / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(float(sizes["rope_yarn_beta_fast"]))), 0)
    high = min(math.ceil(c(float(sizes["rope_yarn_beta_slow"]))), rot - 1)
    extrap = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    r = np.clip((np.arange(rot // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return extrap * (1.0 - r) + extrap / factor * r, low, high


def attention_factor(sizes: dict) -> float:
    """YaRN's attention factor, 0.1 ln factor + 1."""
    return 0.1 * math.log(float(sizes["rope_yarn_factor"])) + 1.0


def rotary_tables(sizes: dict, n: int, kind: str):
    """cos, sin (n, rot): frequency i drives channels i and i + rot/2."""
    if kind == "gated_full":
        inv, _, _ = yarn_inv_freq(sizes)
        scale = attention_factor(sizes)
    else:
        rot = int(sizes["dim_head"])
        inv = float(sizes["window_rotary_theta"]) ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
        scale = 1.0
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (scale * np.cos(ang)).astype(np.float32), (scale * np.sin(ang)).astype(np.float32)


def _rotate_half(t, cos, sin):
    """t: (n, heads, dim_head); the first cos.shape[-1] channels are rotated."""
    rot = cos.shape[-1]
    tr, rest = t[..., :rot], t[..., rot:]
    turned = jnp.concatenate([-tr[..., rot // 2:], tr[..., :rot // 2]], axis=-1)
    return jnp.concatenate([tr * cos[:, None, :] + turned * sin[:, None, :], rest], axis=-1)


def _row_block(n: int, limit: int = 1024) -> int:
    """The largest divisor of n that is at most `limit`: query rows a block."""
    return max(d for d in range(1, min(n, limit) + 1) if n % d == 0)


# ---------------------------------------------------------------- attention
def attention(sizes: dict, p: dict, x, kind: str):
    n = x.shape[0]
    heads = int(sizes["window_heads"] if kind == "gated_window" else sizes["heads"])
    dh, kv_heads = int(sizes["dim_head"]), int(sizes["kv_heads"])
    eps = float(sizes["norm_eps"])
    qg = _mat(p["q"], x)
    q, gate = qg[:, :heads * dh].reshape(n, heads, dh), qg[:, heads * dh:]
    k = _mat(p["k"], x).reshape(n, kv_heads, dh)
    v = _mat(p["v"], x).reshape(n, kv_heads, dh)
    cos, sin = rotary_tables(sizes, n, kind)
    q = _rotate_half(_norm(p["q_norm"]["w"], q, eps), cos, sin)
    k = _rotate_half(_norm(p["k_norm"]["w"], k, eps), cos, sin)
    window = int(sizes["window"]) if kind == "gated_window" else n
    group = heads // kv_heads
    rows = _row_block(n)

    def one_head(h):  # one head's score matrix, a block of query rows at a time, so that it fits
        @jax.checkpoint  # its gradient too: the block's scores are made again, not kept
        def one_block(start):
            qi = jax.lax.dynamic_slice_in_dim(q[:, h], start, rows)
            scores = _dot(qi, k[:, h // group].T) * dh ** -0.5
            d = (start + jnp.arange(rows))[:, None] - jnp.arange(n)[None, :]
            allowed = (d >= 0) & (d < window)
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
            return _dot(probs, v[:, h // group])

        return jax.lax.map(one_block, jnp.arange(0, n, rows)).reshape(n, dh)

    out = jax.lax.map(one_head, jnp.arange(heads))  # (heads, n, dh)
    out = out * jax.nn.sigmoid(gate).T[:, :, None]  # one scalar a head
    return _mat(p["out"], out.transpose(1, 0, 2).reshape(n, heads * dh))


# ----------------------------------------------------------------------- ff
def _expert(wg, wu, wd, x):
    g = _dot(x, wg)
    return _dot((g * jax.nn.sigmoid(g)) * _dot(x, wu), wd)


def dense_ff(p: dict, x):
    return _expert(_f32(p["wg"]["w"]), _f32(p["wu"]["w"]), _f32(p["wd"]["w"]), x)


def routing(sizes: dict, p: dict, x):
    """(n, moe_experts) float32: the weight each expert's output gets for each
    token; zero outside a token's top-k.  The router is never rounded: the
    program keeps it float32 at full precision whatever it computes in."""
    probs = jax.nn.softmax(x @ _f32(p["router"]["w"]), axis=-1)
    k = int(sizes["moe_top_k"])
    chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :k]
    picked = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], chosen].set(1.0)
    weights = probs * picked
    return weights / jnp.sum(weights, axis=-1, keepdims=True) * float(sizes["moe_routed_scale"])


def moe(sizes: dict, p: dict, x):
    total = int(sizes["moe_experts"])
    held = int(sizes.get("moe_experts_held") or total)
    first = int(sizes.get("moe_first_expert", 0))
    weights = routing(sizes, p, x)[:, first:first + held]  # the held experts' columns
    ex = p["experts"]

    def one(acc, args):  # every token through one held expert, weighted (0 where not chosen)
        wg, wu, wd, w_e = args
        return acc + _expert(wg, wu, wd, x) * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (_f32(ex["wg"]), _f32(ex["wu"]), _f32(ex["wd"]), weights.T))
    sh = p["shared"]
    return out + jax.nn.sigmoid(_mat(sh["gate"], x)) * dense_ff(sh, x)


def layer_kind(sizes: dict, l: int) -> str:
    types = sizes["attn_types"]
    return types[l % len(types)]


def block(sizes: dict, t: dict, l: int, x):
    """Layer l.  Under jax.grad its inner values are made again from x, not
    kept (`jax.checkpoint`: the same numbers, in the memory of one layer)."""
    eps = float(sizes["norm_eps"])

    @jax.checkpoint
    def layer(wrap, attn, ff, x):
        x = x + attention(sizes, attn, _norm(wrap["attn_norm"]["w"], x, eps), layer_kind(sizes, l))
        h = _norm(wrap["ff_norm"]["w"], x, eps)
        dense = l < int(sizes.get("dense_layers", 0))
        return x + (dense_ff(ff, h) if dense else moe(sizes, ff, h))

    return layer(t["layers"][l], t["shared_attn"][str(l)], t["shared_ff"][str(l)], x)


# ------------------------------------------------------------------- forward
def _check_supported(sizes: dict) -> None:
    want = {"rotary_emb": True, "shift_tokens": False, "share_input_output_emb": False,
            "norm": "rmsnorm", "layer_scale": False, "moe_router": "softmax",
            "moe_shared_gated": True, "attn_gate": "per_head"}
    for key, value in want.items():
        if sizes.get(key) != value:
            raise ValueError(f"the reference covers {key}={value!r} only (got {sizes.get(key)!r})")
    if set(sizes["attn_types"]) - {"gated_full", "gated_window"}:
        raise ValueError(f"the reference covers gated_full / gated_window layers only "
                         f"(got {sizes['attn_types']})")


def _sequence_len(sizes: dict) -> int:
    return int(sizes["text_seq_len"]) + int(sizes["image_fmap_size"]) ** 2


def token_ids(sizes: dict, text, image_codes):
    """Joint ids [<bos>, text, image], cut to the model's sequence, as int32."""
    ts, vt = int(sizes["text_seq_len"]), int(sizes["num_text_tokens"])
    text = jnp.clip(jnp.asarray(text, jnp.int32), 0, vt - 1)
    text = jnp.where(text == 0, vt + jnp.arange(ts, dtype=jnp.int32), text)
    ids = jnp.concatenate([jnp.zeros((1,), jnp.int32), text,
                           jnp.asarray(image_codes, jnp.int32) + vt + ts])
    return ids[:_sequence_len(sizes)]


def forward_logits(params: dict, sizes: dict, text, image_codes):
    """text: (text_seq_len,) raw ids, 0 = pad; image_codes: (m,).  Returns
    (n, vocabulary) float32 logits, forbidden ids at -inf."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        ids = token_ids(sizes, text, image_codes)
        table = jnp.concatenate([_f32(params["text_emb"]["table"]),
                                 _f32(params["image_emb"]["table"])])
        x = table[ids]
        for l in range(int(sizes["depth"])):
            x = block(sizes, params["transformer"], l, x)
        logits = _mat(params["logits_linear"],
                      _norm(params["logits_norm"]["w"], x, float(sizes["norm_eps"])))
        if "b" in params["logits_linear"]:
            logits = logits + _f32(params["logits_linear"]["b"])
        split = int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"])
        row_is_text = (np.arange(x.shape[0]) < int(sizes["text_seq_len"]))[:, None]
        col_is_text = (np.arange(logits.shape[1]) < split)[None, :]
        return jnp.where(jnp.asarray(row_is_text == col_is_text), logits, -jnp.inf)


def loss_from_logits(logits, sizes: dict, text, image_codes):
    """Position i's logits predict token i + 1; text and image positions are
    averaged apart and the image mean weighs `loss_img_weight` times."""
    with jax.default_matmul_precision("highest"):
        ts = int(sizes["text_seq_len"])
        split = int(sizes["num_text_tokens"]) + ts
        labels = jnp.concatenate([token_ids(sizes, text, image_codes)[1:ts + 1],
                                  jnp.asarray(image_codes, jnp.int32) + split])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        w = float(sizes.get("loss_img_weight", 7.0))
        return (jnp.mean(nll[:ts]) + w * jnp.mean(nll[ts:])) / (w + 1.0)


def loss(params: dict, sizes: dict, text, image_codes):
    return loss_from_logits(forward_logits(params, sizes, text, image_codes), sizes, text, image_codes)
