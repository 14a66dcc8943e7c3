"""Plain float32 reference for an Olmo-Hybrid trunk in the DALL-E token stream:
forward and loss.

The yardstick `correct` is decided against for the `olmo_hybrid_*`
configurations.  Straightforward `jax.numpy`, one sequence at a time, float32
under `jax.default_matmul_precision("highest")`; no kernels, no cache, no
chunking, no batching.  From the program it takes only the parameter tree (the
weights' storage format) and, through `sizes`, the configuration file's
numbers.  The block follows the published config
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
`model_type` `olmo_hybrid`; the catalog's row); x is a token's hidden vector
and no projection of a layer has a bias:

    h = x + N(Mixer(x));   y = h + N(W_d (silu(W_g h) * (W_u h)));
    N(x) = x / sqrt(mean(x^2) + eps) * w

  * the norm sits on each branch's OUTPUT, inside the residual, and none on
    its input (the Olmo 2 / Olmo 3 placement; the config has no key for it).
  * Mixer of layer l is `attn_types[l % len]` [`layer_types`].
    `gated_delta` [`linear_attention`; Gated DeltaNet, arXiv:2412.06464]:
    [q, k, v, g] = W_qkvz x (key heads x dk, key heads x dk, value heads x dv
    twice), [b, a] = W_ba x (value heads each); (q, k, v) <- silu(causal
    depthwise conv over `gdn_conv_kernel` positions); q, k L2-normalised per
    head, q scaled by dk^-0.5; beta = 2 sigmoid(b) [`linear_allow_neg_eigval`]
    (sigmoid(b) without the key), alpha = exp(-exp(A_log) * softplus(a +
    dt_bias)); per head, with the state S (dk x dv) zero at the start, ONE
    POSITION AT A TIME:
        S' = alpha_t S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t
    out = W_o (rms(o_t) * w_n * silu(g_t)), rms over each head's dv.
    `full` [`full_attention`]: q = N_q(W_q x), k = N_k(W_k x), N over the WHOLE
    heads x dim_head width before the split into heads, v = W_v x; NO rotary
    embedding (`rope_parameters.rope_theta` is null); causal
    softmax(q k^T / sqrt(dim_head)) v over the FULL score matrix; out = W_o.
  * a final N, then the UNTIED output head (its bias vector is this stream's).

Departures from the published description, each also under `assumed` in the
configuration's file:
  * the stream is this repository's: [<bos>, text, image raster] with
    per-position pad ids, logits masked so that text positions predict text
    and image positions image, loss = (CE_text + w * CE_image) / (w + 1);
  * [q, k, v, g] and [b, a] are laid out blocked in two projections and the
    full layer's q, k, v head-major in one, where the published checkpoint
    has a matrix each: with seeded random weights the same model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(a):
    return jnp.asarray(a).astype(F32)


def _norm(w, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _mat(p, x):
    return x @ _f32(p["w"])


def _silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------------------ full
def full_attention(sizes: dict, p: dict, x):
    n = x.shape[0]
    heads, dh = int(sizes["heads"]), int(sizes["dim_head"])
    eps = float(sizes["norm_eps"])
    qkv = _mat(p["qkv"], x).reshape(n, heads, 3, dh)  # head-major columns [h: q | k | v]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if sizes.get("qk_norm"):
        q = _norm(p["q_norm"]["w"], q.reshape(n, heads * dh), eps).reshape(n, heads, dh)
        k = _norm(p["k_norm"]["w"], k.reshape(n, heads * dh), eps).reshape(n, heads, dh)
    causal = jnp.asarray(np.tril(np.ones((n, n), bool)))

    def one_head(h):  # one head's whole score matrix at a time, so that it fits
        scores = (q[:, h] @ k[:, h].T) * dh ** -0.5
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1) @ v[:, h]

    out = jax.lax.map(one_head, jnp.arange(heads))  # (heads, n, dh)
    out = _mat(p["out"], out.transpose(1, 0, 2).reshape(n, heads * dh))
    return out + _f32(p["out"]["b"]) if "b" in p["out"] else out


# ----------------------------------------------------------------- gated_delta
def delta_rule_recurrence(q, k, v, alpha, beta, with_state: bool = False):
    """q, k: (n, heads, dk); v: (n, heads, dv); alpha, beta: (n, heads).
    The rule as it is defined, a `lax.scan` over positions.  Returns o
    (n, heads, dv), and the state after the last position if asked."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[:, None, None]
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * ((v_t - read) * b_t[:, None])[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state, out = jax.lax.scan(step, jnp.zeros((heads, dk, dv), F32), (q, k, v, alpha, beta))
    return (out, state) if with_state else out


def gated_delta_net(sizes: dict, p: dict, x, stop=None):
    """Returns (out, the rule's state after position `stop` - 1; after the
    last position where `stop` is None).  Positions from `stop` on neither
    decay nor write (alpha 1, beta 0), so what they hold changes no state."""
    n = x.shape[0]
    hk, hv = int(sizes["gdn_key_heads"]), int(sizes["gdn_value_heads"])
    dk, dv = int(sizes["gdn_key_dim"]), int(sizes["gdn_value_dim"])
    kd, vd = hk * dk, hv * dv
    qkvz = _mat(p["qkvz"], x)
    ba = _mat(p["ba"], x)
    w = _f32(p["conv"]["w"])  # (taps, channels); the last tap is the current position
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 2 * kd + vd), F32), qkvz[:, :2 * kd + vd]])
    qkv = _silu(sum(padded[j:j + n] * w[j] for j in range(taps)))
    q = qkv[:, :kd].reshape(n, hk, dk)
    k = qkv[:, kd:2 * kd].reshape(n, hk, dk)
    v = qkv[:, 2 * kd:].reshape(n, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv]) * (2.0 if sizes.get("gdn_neg_eigval") else 1.0)
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[:, hv:] + _f32(p["dt_bias"])))
    if stop is not None:
        live = (jnp.arange(n) < stop)[:, None]
        alpha, beta = jnp.where(live, alpha, 1.0), jnp.where(live, beta, 0.0)
    o, state = delta_rule_recurrence(q, k, v, alpha, beta, with_state=True)
    o = _norm(p["norm"]["w"], o, float(sizes["norm_eps"]))
    g = qkvz[:, 2 * kd + vd:].reshape(n, hv, dv)
    return _mat(p["out"], (o * _silu(g)).reshape(n, vd)), state


def swiglu(p: dict, x):
    return _mat(p["wd"], _silu(_mat(p["wg"], x)) * _mat(p["wu"], x))


# ------------------------------------------------------------------- forward
def _check_supported(sizes: dict) -> None:
    want = {"rotary_emb": False, "axial_pos_emb": False, "shift_tokens": False,
            "share_input_output_emb": False, "norm": "rmsnorm", "layer_scale": False,
            "pre_norm": False, "sandwich_norm": True, "attn_bias": False,
            "dense_layers": int(sizes["depth"])}
    for key, value in want.items():
        if sizes.get(key) != value:
            raise ValueError(f"the reference covers {key}={value!r} only (got {sizes.get(key)!r})")
    for t in sizes["attn_types"]:
        if t not in ("gated_delta", "full"):
            raise ValueError(f"the reference has no layer kind {t!r}")


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


def _trunk(params: dict, sizes: dict, ids, stop=None):
    """The layers over one sequence of ids: (hidden states before the final
    norm, the `gated_delta` layers' states in layer order)."""
    split = int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"])
    # row by row from the two tables: no joined copy of an untied vocabulary
    x = jnp.where((ids < split)[:, None],
                  _f32(params["text_emb"]["table"][jnp.minimum(ids, split - 1)]),
                  _f32(params["image_emb"]["table"][jnp.maximum(ids - split, 0)]))
    t = params["transformer"]
    eps = float(sizes["norm_eps"])
    types = list(sizes["attn_types"])
    states = []
    for l in range(int(sizes["depth"])):
        wrap, shared = t["layers"][l], t["shared_attn"][str(l)]
        if types[l % len(types)] == "gated_delta":
            mixed, state = gated_delta_net(sizes, shared, x, stop)
            states.append(state)
        else:
            mixed = full_attention(sizes, shared, x)
        x = x + _norm(wrap["attn_norm_out"]["w"], mixed, eps)
        x = x + _norm(wrap["ff_norm_out"]["w"], swiglu(t["shared_ff"][str(l)], x), eps)
    return x, states


def forward_logits(params: dict, sizes: dict, text, image_codes):
    """text: (text_seq_len,) raw ids, 0 = pad; image_codes: (m,).  Returns
    (n, vocabulary) float32 logits, forbidden ids at -inf."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        ids = token_ids(sizes, text, image_codes)
        n = ids.shape[0]
        split = int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"])
        x, _ = _trunk(params, sizes, ids)
        logits = _mat(params["logits_linear"],
                      _norm(params["logits_norm"]["w"], x, float(sizes["norm_eps"])))
        if "b" in params["logits_linear"]:
            logits = logits + _f32(params["logits_linear"]["b"])
        row_is_text = (np.arange(n) < int(sizes["text_seq_len"]))[:, None]
        col_is_text = (np.arange(logits.shape[1]) < split)[None, :]
        return jnp.where(jnp.asarray(row_is_text == col_is_text), logits, -jnp.inf)


def recurrent_states(params: dict, sizes: dict, text, image_codes, positions):
    """What each `gated_delta` layer's state is after the first `positions`
    (a traced scalar is fine) positions of [<bos>, text, image codes]: a list
    of (heads, dk, dv) float32 in layer order.  `image_codes` may be of any
    length and hold anything from position `positions` on: no state reads it."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        return _trunk(params, sizes, token_ids(sizes, text, image_codes), positions)[1]


def loss(params: dict, sizes: dict, text, image_codes):
    return loss_from_logits(forward_logits(params, sizes, text, image_codes),
                            sizes, text, image_codes)


def loss_from_logits(logits, sizes: dict, text, image_codes):
    """Position i's logits predict token i + 1; text and image positions are
    averaged apart and the image mean weighs `loss_img_weight` times."""
    with jax.default_matmul_precision("highest"):
        ts = int(sizes["text_seq_len"])
        split = int(sizes["num_text_tokens"]) + ts
        fmap = int(sizes["image_fmap_size"])
        full = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                token_ids(sizes, text, image_codes)[1:ts + 1],
                                jnp.asarray(image_codes, jnp.int32) + split])
        labels = full[1:1 + ts + fmap * fmap]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        w = float(sizes.get("loss_img_weight", 7.0))
        return (jnp.mean(nll[:ts]) + w * jnp.mean(nll[ts:])) / (w + 1.0)
