"""Plain float32 reference for a Qwen3-Next trunk in the DALL-E token stream:
forward and loss.

The yardstick `correct` is decided against for the `qwen3_next_*`
configurations.  Straightforward `jax.numpy`, one sequence at a time, float32
under `jax.default_matmul_precision("highest")`; no kernels, no chunking, no
sorting, no batching.  From the program it takes only the parameter tree (the
weights' storage format) and, through `sizes`, the configuration file's
numbers.  The block follows the published model
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `config.json`, and
the `described_as` of the catalog's row); x is a token's hidden vector and no
projection has a bias:

    h = x + Mixer(N(x));   y = h + MoE(N(h));   N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)

  * Mixer of layer l is `attn_types[l % len]`.
    `gated_full`: [q, gate] = W_q x (heads x dim_head each), k = W_k x, v = W_v x
    (kv_heads x dim_head); q and k pass a per-head N; rotary (rotate-half) on
    the first partial_rotary_factor * dim_head channels, base rotary_theta, by
    stream position; causal softmax(q k^T / sqrt(dim_head)) v over the FULL
    score matrix, key/value head j serving query heads j*g..(j+1)*g-1;
    out = W_o (attn * sigmoid(gate)).
    `gated_delta`: [q, k, v, z] = W_qkvz x, [b, a] = W_ba x; (q, k, v) <-
    silu(causal depthwise conv); q, k L2-normalised per head, q scaled by
    dk^-0.5, key head j serving value heads j*g..(j+1)*g-1; beta = sigmoid(b),
    alpha = exp(-exp(A_log) * softplus(a + dt_bias)); per value head, with the
    state S (dk x dv) zero at the start, ONE POSITION AT A TIME:
        S' = alpha_t S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t
    out = W_o (rms(o_t) * w_n * silu(z_t)), rms over each head, plain weight.
  * MoE(x) = sum_{e in top-k(p), e held} p_e E_e(x) + sigmoid(w_s . x) E_shared(x),
    p = softmax(W_r x) over ALL moe_experts, the top-k renormalised to sum 1,
    E(x) = W_d (silu(W_g x) * (W_u x)).  Every token, whatever the load.
  * a final N, then the untied output head.

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
  * [q, gate] and [q, k, v, z] are laid out blocked (all of q, then all of
    gate...), where the published checkpoint interleaves them per head group:
    with seeded random weights the layouts are the same model;
  * no multi-token-prediction module and no auxiliary router loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(a):
    return jnp.asarray(a).astype(F32)


def _norm(w, x, eps, zero_centered=True):
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + _f32(w) if zero_centered else _f32(w))


def _mat(p, x):
    return x @ _f32(p["w"])


# ---------------------------------------------------------------- gated_full
def rotary_tables(sizes: dict, n: int):
    """cos, sin (n, rot): frequency i drives channels i and i + rot/2."""
    rot = int(int(sizes["dim_head"]) * float(sizes["partial_rotary_factor"]))
    inv = 1.0 / (float(sizes["rotary_theta"]) ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rotate_half(t, cos, sin):
    """t: (n, heads, dim_head); the first cos.shape[-1] channels are rotated."""
    rot = cos.shape[-1]
    tr, rest = t[..., :rot], t[..., rot:]
    turned = jnp.concatenate([-tr[..., rot // 2:], tr[..., :rot // 2]], axis=-1)
    return jnp.concatenate([tr * cos[:, None, :] + turned * sin[:, None, :], rest], axis=-1)


def gated_attention(sizes: dict, p: dict, x):
    n = x.shape[0]
    heads, dh = int(sizes["heads"]), int(sizes["dim_head"])
    kv_heads = int(sizes.get("kv_heads") or heads)
    eps = float(sizes["norm_eps"])
    qg = _mat(p["q"], x)
    q, gate = qg[:, :heads * dh].reshape(n, heads, dh), qg[:, heads * dh:]
    k = _mat(p["k"], x).reshape(n, kv_heads, dh)
    v = _mat(p["v"], x).reshape(n, kv_heads, dh)
    cos, sin = rotary_tables(sizes, n)
    q = _rotate_half(_norm(p["q_norm"]["w"], q, eps), cos, sin)
    k = _rotate_half(_norm(p["k_norm"]["w"], k, eps), cos, sin)
    causal = jnp.asarray(np.tril(np.ones((n, n), bool)))
    group = heads // kv_heads

    def one_head(h):  # one head's whole score matrix at a time, so that it fits
        scores = (q[:, h] @ k[:, h // group].T) * dh ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ v[:, h // group]

    out = jax.lax.map(one_head, jnp.arange(heads))  # (heads, n, dh)
    out = out.transpose(1, 0, 2).reshape(n, heads * dh)
    return _mat(p["out"], out * jax.nn.sigmoid(gate))


# --------------------------------------------------------------- gated_delta
def delta_rule_recurrence(q, k, v, alpha, beta):
    """q, k: (n, heads, dk); v: (n, heads, dv); alpha, beta: (n, heads).
    The rule as it is defined, a `lax.scan` over positions."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[:, None, None]
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * ((v_t - read) * b_t[:, None])[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, out = jax.lax.scan(step, jnp.zeros((heads, dk, dv), F32), (q, k, v, alpha, beta))
    return out  # (n, heads, dv)


def gated_delta_net(sizes: dict, p: dict, x):
    n = x.shape[0]
    hk, hv = int(sizes["gdn_key_heads"]), int(sizes["gdn_value_heads"])
    dk, dv = int(sizes["gdn_key_dim"]), int(sizes["gdn_value_dim"])
    kd, vd = hk * dk, hv * dv
    qkvz = _mat(p["qkvz"], x)
    ba = _mat(p["ba"], x)
    w = _f32(p["conv"]["w"])  # (taps, channels); the last tap is the current position
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 2 * kd + vd), F32), qkvz[:, :2 * kd + vd]])
    conv = sum(padded[j:j + n] * w[j] for j in range(taps))
    qkv = conv * jax.nn.sigmoid(conv)  # silu
    q = qkv[:, :kd].reshape(n, hk, dk)
    k = qkv[:, kd:2 * kd].reshape(n, hk, dk)
    v = qkv[:, 2 * kd:].reshape(n, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[:, hv:] + _f32(p["dt_bias"])))
    o = delta_rule_recurrence(q, k, v, alpha, beta)
    o = _norm(p["norm"]["w"], o, float(sizes["norm_eps"]), zero_centered=False)
    z = qkvz[:, 2 * kd + vd:].reshape(n, hv, dv)
    return _mat(p["out"], (o * z * jax.nn.sigmoid(z)).reshape(n, vd))


# ----------------------------------------------------------------------- moe
def _expert(wg, wu, wd, x):
    g = x @ wg
    return ((g * jax.nn.sigmoid(g)) * (x @ wu)) @ wd


def routing(sizes: dict, p: dict, x):
    """(n, moe_experts) float32: the weight each expert's output gets for each
    token; zero outside a token's top-k.  Ties go to the lower expert id."""
    probs = jax.nn.softmax(_mat(p["router"], x), axis=-1)
    k = int(sizes["moe_top_k"])
    chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :k]
    picked = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], chosen].set(1.0)
    weights = probs * picked
    return weights / jnp.sum(weights, axis=-1, keepdims=True)  # norm_topk_prob


def moe(sizes: dict, p: dict, x):
    total = int(sizes["moe_experts"])
    held = int(sizes.get("moe_experts_held") or total)
    first = int(sizes.get("moe_first_expert", 0))
    weights = routing(sizes, p, x)[:, first:first + held]  # the held experts' columns
    ex = p["experts"]

    def one(acc, args):  # every token through one held expert, weighted (0 where not routed)
        wg, wu, wd, w_e = args
        return acc + _expert(wg, wu, wd, x) * w_e[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (_f32(ex["wg"]), _f32(ex["wu"]), _f32(ex["wd"]), weights.T))
    if "shared" in p:
        sh = p["shared"]
        out = out + jax.nn.sigmoid(_mat(sh["gate"], x)) * _expert(
            _f32(sh["wg"]["w"]), _f32(sh["wu"]["w"]), _f32(sh["wd"]["w"]), x)
    return out


# ------------------------------------------------------------------- forward
def _check_supported(sizes: dict) -> None:
    want = {"rotary_emb": True, "shift_tokens": False, "share_input_output_emb": False,
            "norm": "rmsnorm_zc", "layer_scale": False}
    for key, value in want.items():
        if sizes.get(key) != value:
            raise ValueError(f"the reference covers {key}={value!r} only (got {sizes.get(key)!r})")
    for t in sizes["attn_types"]:
        if t not in ("gated_delta", "gated_full"):
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


def forward_logits(params: dict, sizes: dict, text, image_codes):
    """text: (text_seq_len,) raw ids, 0 = pad; image_codes: (m,).  Returns
    (n, vocabulary) float32 logits, forbidden ids at -inf."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        ids = token_ids(sizes, text, image_codes)
        n = ids.shape[0]
        split = int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"])
        table = jnp.concatenate([_f32(params["text_emb"]["table"]),
                                 _f32(params["image_emb"]["table"])])
        x = table[ids]
        t = params["transformer"]
        eps = float(sizes["norm_eps"])
        types = list(sizes["attn_types"])
        for l in range(int(sizes["depth"])):
            wrap = t["layers"][l]
            mixer = gated_delta_net if types[l % len(types)] == "gated_delta" else gated_attention
            x = x + mixer(sizes, t["shared_attn"][str(l)], _norm(wrap["attn_norm"]["w"], x, eps))
            x = x + moe(sizes, t["shared_ff"][str(l)], _norm(wrap["ff_norm"]["w"], x, eps))
        logits = _mat(params["logits_linear"], _norm(params["logits_norm"]["w"], x, eps))
        if "b" in params["logits_linear"]:
            logits = logits + _f32(params["logits_linear"]["b"])
        row_is_text = (np.arange(n) < int(sizes["text_seq_len"]))[:, None]
        col_is_text = (np.arange(logits.shape[1]) < split)[None, :]
        return jnp.where(jnp.asarray(row_is_text == col_is_text), logits, -jnp.inf)


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
