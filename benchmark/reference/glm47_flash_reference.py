"""Plain float32 reference for a GLM-4.7-Flash trunk in the DALL-E token
stream: forward, the prediction module's forward, and the whole loss (whose
gradients are `jax.grad` of `loss`).

The yardstick `correct` is decided against for the `glm47_flash_*`
configurations.  Straightforward `jax.numpy`, one sequence at a time, float32
under `jax.default_matmul_precision("highest")`; no kernels, no grouped
products, no sorting, no batching.  From the program it takes only the
parameter tree (the weights' storage format) and, through `sizes`, the
configuration file's numbers.  The block follows the published model
(https://huggingface.co/zai-org/GLM-4.7-Flash, `config.json`, `model_type`
glm4_moe_lite; the catalog's row) and, where the config only names a
mechanism, the papers it follows (DeepSeek-V2 for the latent attention,
DeepSeek-V3 sections 2.1.2 and 2.2 for the router and the prediction module).
x is a token's hidden vector and no projection of the block has a bias:

    h = x + MLA(N(x));   y = h + FF(N(h));   N(t) = w * t / sqrt(mean(t^2) + eps), w from 1

  * MLA.  c_q = N(W_qa x);  [q_nope_h ; q_rope_h] = W_qb c_q for each head;
    [c_kv ; k_rope] = W_kva x;  [k_nope_h ; v_h] = W_kvb N(c_kv);  rotary
    (rotate-half over all mla_rope_dim channels, base rotary_theta, position =
    index in the stream) on q_rope_h and on k_rope, ONE vector shared by all
    heads;  k_h = [k_nope_h ; k_rope];  causal softmax(q_h . k_h / sqrt(nope +
    rope)) v_h over the explicit score matrix, in blocks of query rows;
    out = W_o [o_1 .. o_heads].  k_h and v_h are materialised (no absorption).
  * FF of layer l < dense_layers: W_d (silu(W_g x) * (W_u x)) at dense_ff_dim.
    Else MoE(x) = sum_{e chosen, e held} g_e E_e(x) + E_shared(x):
    s = sigmoid(W_r x) over ALL moe_experts; the chosen are the top-k of s + b
    (b the balancing bias; ties to the lower id); g_e = s_e / sum_chosen s *
    moe_routed_scale (b takes no part in the weights); the shared expert is
    added with no gate.  Every token, whatever the load.
  * a final N, then the untied output head (this system's head keeps its bias).
  * Prediction module (depth 1).  With h_i the trunk's output at position i
    BEFORE the final norm and e_{i+1} the embedding of the next input token:
    h'_i = W_m [N_h(h_i) ; N_e(e_{i+1})], one more block of the routed kind
    with its own weights and bias, its own final N, the trunk's own head; the
    logits at i predict token i + 2, over n - 1 positions, masked by that
    target's position.  loss = L_main + mtp_loss_weight * L_mtp.

Departures from the published description, each also under `assumed` or
`reduced` in the configuration's file:
  * the stream is this repository's: [<bos>, text, image raster] with
    per-position pad ids, logits masked so that text positions predict text
    and image positions image, each loss = (CE_text + w * CE_image) / (w + 1);
    the model's own tokenizer and vocabulary are not used;
  * only the experts [moe_first_expert, moe_first_expert + moe_experts_held)
    are held: the router still scores all `moe_experts`, and the terms of the
    absent experts are LEFT OUT of the sum, here as in the program;
  * the order of the module's concatenation ([hidden ; embedding]) and the
    rotary pairing (halves, not interleaved pairs) are conventions the config
    does not settle: with seeded random weights either is the same model.

`products_rounded_to(dtype)`: the same mathematics with both operands of every
matrix product rounded to `dtype` first (accumulation stays float32), for the
reading that says what a lower precision than the stated one would show.
It is read while a function is TRACED: jit a new function under it (jax keeps
a jitted function's trace by the function's identity, not by this setting).
"""
from __future__ import annotations

import contextlib

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


# ----------------------------------------------------------------------- mla
def rotary_tables(sizes: dict, n: int):
    """cos, sin (n, rope): frequency i drives channels i and i + rope/2."""
    rot = int(sizes["mla_rope_dim"])
    inv = 1.0 / (float(sizes["rotary_theta"]) ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rotate_half(t, cos, sin):
    """t: (n, ..., rope), every channel rotated; cos, sin: (n, rope)."""
    half = t.shape[-1] // 2
    turned = jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)
    shape = (t.shape[0],) + (1,) * (t.ndim - 2) + (t.shape[-1],)
    return t * cos.reshape(shape) + turned * sin.reshape(shape)


def _row_block(n: int, limit: int = 1024) -> int:
    """The largest divisor of n that is at most `limit`: query rows a block."""
    return max(d for d in range(1, min(n, limit) + 1) if n % d == 0)


def latent_attention(sizes: dict, p: dict, x):
    n = x.shape[0]
    heads = int(sizes["heads"])
    nope, rope, vd = int(sizes["mla_nope_dim"]), int(sizes["mla_rope_dim"]), int(sizes["mla_v_dim"])
    kv_rank, eps = int(sizes["mla_kv_rank"]), float(sizes["norm_eps"])
    q = _mat(p["q_b"], _norm(p["q_norm"]["w"], _mat(p["q_a"], x), eps)).reshape(n, heads, nope + rope)
    kva = _mat(p["kv_a"], x)
    c_kv, k_rope = kva[:, :kv_rank], kva[:, kv_rank:]
    kv = _mat(p["kv_b"], _norm(p["kv_norm"]["w"], c_kv, eps)).reshape(n, heads, nope + vd)
    cos, sin = rotary_tables(sizes, n)
    q = jnp.concatenate([q[..., :nope], _rotate_half(q[..., nope:], cos, sin)], axis=-1)
    k_rope = _rotate_half(k_rope, cos, sin)  # (n, rope): one for every head
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (n, heads, rope))],
                        axis=-1)
    v = kv[..., nope:]
    rows = _row_block(n)
    scale = (nope + rope) ** -0.5

    def one_head(h):  # one head's score matrix, a block of query rows at a time, so that it fits
        def one_block(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q[:, h], start, rows)
            scores = _dot(q_rows, k[:, h].T) * scale
            allowed = (start + jnp.arange(rows))[:, None] >= jnp.arange(n)[None, :]
            return _dot(jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1), v[:, h])

        return jax.lax.map(one_block, jnp.arange(0, n, rows)).reshape(n, vd)

    out = jax.lax.map(one_head, jnp.arange(heads))  # (heads, n, vd)
    return _mat(p["out"], out.transpose(1, 0, 2).reshape(n, heads * vd))


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
    scores = jax.nn.sigmoid(x @ _f32(p["router"]["w"]))
    k = int(sizes["moe_top_k"])
    chosen = jnp.argsort(-(scores + _f32(p["router"]["bias"])), axis=-1, stable=True)[:, :k]
    picked = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(1.0)
    weights = scores * picked  # the bias chose; it weighs nothing
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
    return out + dense_ff(p["shared"], x)  # no gate


def block(sizes: dict, t: dict, l: int, x, dense: bool):
    """Layer `l` of the parameter tree `t` (a trunk's or the module's)."""
    eps = float(sizes["norm_eps"])
    wrap, ff = t["layers"][l], t["shared_ff"][str(l)]
    x = x + latent_attention(sizes, t["shared_attn"][str(l)], _norm(wrap["attn_norm"]["w"], x, eps))
    h = _norm(wrap["ff_norm"]["w"], x, eps)
    return x + (dense_ff(ff, h) if dense else moe(sizes, ff, h))


# ------------------------------------------------------------------- forward
def _check_supported(sizes: dict) -> None:
    want = {"rotary_emb": True, "shift_tokens": False, "share_input_output_emb": False,
            "norm": "rmsnorm", "layer_scale": False, "moe_router": "sigmoid_bias",
            "moe_shared_gated": False, "attn_types": ["mla"]}
    for key, value in want.items():
        if sizes.get(key) != value:
            raise ValueError(f"the reference covers {key}={value!r} only (got {sizes.get(key)!r})")
    if int(sizes.get("mtp_depth", 0)) not in (0, 1):
        raise ValueError("the reference covers a prediction module of depth 0 or 1")


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


def _trunk(params: dict, sizes: dict, ids):
    """(the input embeddings, the trunk's output before its final norm)."""
    table = jnp.concatenate([_f32(params["text_emb"]["table"]), _f32(params["image_emb"]["table"])])
    e = table[ids]
    x = e
    for l in range(int(sizes["depth"])):
        x = block(sizes, params["transformer"], l, x, dense=l < int(sizes.get("dense_layers", 0)))
    return e, x


def _head(params: dict, sizes: dict, norm_w, x, first_row: int):
    """Final norm, the trunk's head, and the mask by position: row i of `x`
    is stream position first_row + i."""
    logits = _mat(params["logits_linear"], _norm(norm_w, x, float(sizes["norm_eps"])))
    if "b" in params["logits_linear"]:
        logits = logits + _f32(params["logits_linear"]["b"])
    split = int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"])
    row_is_text = (first_row + np.arange(x.shape[0]) < int(sizes["text_seq_len"]))[:, None]
    col_is_text = (np.arange(logits.shape[1]) < split)[None, :]
    return jnp.where(jnp.asarray(row_is_text == col_is_text), logits, -jnp.inf)


def forward_logits(params: dict, sizes: dict, text, image_codes):
    """text: (text_seq_len,) raw ids, 0 = pad; image_codes: (m,).  Returns
    (n, vocabulary) float32 logits, forbidden ids at -inf."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        _, x = _trunk(params, sizes, token_ids(sizes, text, image_codes))
        return _head(params, sizes, params["logits_norm"]["w"], x, 0)


def forward_mtp_logits(params: dict, sizes: dict, text, image_codes):
    """The prediction module's (n - 1, vocabulary) logits: row i predicts
    token i + 2 and is masked as the main logits' row i + 1 is (its target's
    position decides)."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        e, h = _trunk(params, sizes, token_ids(sizes, text, image_codes))
        mtp, eps = params["mtp"], float(sizes["norm_eps"])
        merged = _mat(mtp["merge"], jnp.concatenate(
            [_norm(mtp["h_norm"]["w"], h[:-1], eps), _norm(mtp["e_norm"]["w"], e[1:], eps)], axis=-1))
        x = block(sizes, mtp["block"], 0, merged, dense=False)
        return _head(params, sizes, mtp["norm"]["w"], x, 1)


def _weighted_nll(logits, labels, n_text: int, w: float):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return (jnp.mean(nll[:n_text]) + w * jnp.mean(nll[n_text:])) / (w + 1.0)


def _labels(sizes: dict, text, image_codes):
    """labels[i] = the token at stream position i + 1."""
    ts = int(sizes["text_seq_len"])
    split = int(sizes["num_text_tokens"]) + ts
    return jnp.concatenate([token_ids(sizes, text, image_codes)[1:ts + 1],
                            jnp.asarray(image_codes, jnp.int32) + split])


def loss_from_logits(logits, sizes: dict, text, image_codes):
    """The MAIN loss: position i's logits predict token i + 1; text and image
    positions are averaged apart and the image mean weighs `loss_img_weight`
    times."""
    with jax.default_matmul_precision("highest"):
        return _weighted_nll(logits, _labels(sizes, text, image_codes),
                             int(sizes["text_seq_len"]), float(sizes.get("loss_img_weight", 7.0)))


def mtp_loss_from_logits(mtp_logits, sizes: dict, text, image_codes):
    """The module's loss: its row i predicts token i + 2, so the first
    text_seq_len - 1 rows have text targets."""
    with jax.default_matmul_precision("highest"):
        return _weighted_nll(mtp_logits, _labels(sizes, text, image_codes)[1:],
                             int(sizes["text_seq_len"]) - 1, float(sizes.get("loss_img_weight", 7.0)))


def loss(params: dict, sizes: dict, text, image_codes):
    """L_main + mtp_loss_weight * L_mtp (L_main alone without a module)."""
    total = loss_from_logits(forward_logits(params, sizes, text, image_codes), sizes, text, image_codes)
    if int(sizes.get("mtp_depth", 0)):
        total = total + float(sizes["mtp_loss_weight"]) * mtp_loss_from_logits(
            forward_mtp_logits(params, sizes, text, image_codes), sizes, text, image_codes)
    return total
