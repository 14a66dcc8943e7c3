"""Plain float32 reference for the DALL-E token stream: forward and loss.

The yardstick `correct` is decided against.  Straightforward `jax.numpy`, one
sequence at a time, float32 with `jax.default_matmul_precision("highest")`
(on a TPU a float32 matmul otherwise runs in bf16 passes); no kernels, no KV
cache, no batching, no scan, and every mask, the token shift and the rotary
table are written out here from their definitions instead of imported from
`dalle_pytorch_tpu`.  From the program it takes only the parameter tree (the
weights' storage format) and, through `sizes`, the configuration file's numbers.

The block, as this repo's `models/transformer.py` defines it (each departure
from a published model is listed under `assumed` in that model's config file):

    x = x + scale_a * Attn(shift(LN(x)))      pre-norm, LayerScale
    x = x + scale_f * GEGLU(shift(LN(x)))     feed-forward width 4 * dim, gated

  * joint sequence [<bos>, text (text_seq_len), image raster (fmap**2)], cut to
    text_seq_len + fmap**2 positions; a pad text id 0 at position p becomes the
    per-position id num_text_tokens + p;
  * shared input/output embedding: the embedding of token t is column t of the
    logits matrix;
  * token shift: a text position takes the first half of its channels from the
    position before it; an image position takes its first quarter from the
    pixel above and its second quarter from the pixel to the left (zero at the
    borders);
  * rotary embedding on q, k AND v: dim_head // 3 channels of language rotary
    over the text index (image tokens pinned at 8192), then pixel rotary over
    the image row and the image column (text tokens pinned at -10);
  * layer l attends through pattern attn_types[l % len]: `full`, `axial_row`
    (same image row), `axial_col` (same image column), `conv_like` (a causal
    kernel_size x kernel_size window); every pattern sees all text; all causal;
  * logits: a text position may only predict text ids, an image position only
    image ids; loss = (CE_text + w * CE_image) / (w + 1).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ------------------------------------------------------------------ geometry
def _geometry(sizes: dict):
    fmap = int(sizes["image_fmap_size"])
    text_len = int(sizes["text_seq_len"]) + 1  # <bos> + text
    return fmap, text_len, text_len + fmap * fmap - 1  # last token predicts nothing


def pattern_mask(sizes: dict, attn_type: str, n: int) -> np.ndarray:
    """(n, n) bool, True = query row may attend key column (causal included)."""
    fmap, text_len, _ = _geometry(sizes)
    q = np.arange(n)[:, None]
    k = np.arange(n)[None, :]
    causal = k <= q
    if attn_type == "full":
        return causal
    k_text = k < text_len
    both_img = (q >= text_len) & (k >= text_len)
    qr, qc = np.divmod(np.maximum(q - text_len, 0), fmap)
    kr, kc = np.divmod(np.maximum(k - text_len, 0), fmap)
    if attn_type == "axial_row":
        img = qr == kr
    elif attn_type == "axial_col":
        img = qc == kc
    elif attn_type == "conv_like":
        ks = int(sizes.get("conv_kernel_size", 5))
        dil = int(sizes.get("conv_dilation", 1))
        reach = (ks - 1) * dil
        dr, dc = qr - kr, qc - kc
        img = ((dr >= 0) & (dr <= reach) & (dr % dil == 0)
               & (dc >= 0) & (dc <= reach) & (dc % dil == 0))
    else:
        raise ValueError(f"the reference has no pattern {attn_type!r}")
    return causal & (k_text | (both_img & img))


def rotary_angles(sizes: dict, n: int) -> np.ndarray:
    """(n, dim_head) rotation angle of every channel at every position; each
    frequency drives one adjacent channel pair; channels past the three
    rotary groups get angle 0 (no rotation)."""
    fmap, text_len, _ = _geometry(sizes)
    dim_head = int(sizes["dim_head"])
    rot = dim_head // 3
    lang = 1.0 / (10000.0 ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    pixel = np.linspace(1.0, 5.0, rot // 2) * math.pi
    grid = np.linspace(-1.0, 1.0, fmap)
    table = np.zeros((text_len + fmap * fmap, dim_head), np.float64)
    n_lang, n_pix = 2 * len(lang), 2 * len(pixel)
    for p in range(table.shape[0]):
        if p < text_len:
            t_pos, r_pos, c_pos = float(p), -10.0, -10.0
        else:
            r, c = divmod(p - text_len, fmap)
            t_pos, r_pos, c_pos = 8192.0, grid[r], grid[c]
        table[p, :n_lang] = np.repeat(t_pos * lang, 2)
        table[p, n_lang:n_lang + n_pix] = np.repeat(r_pos * pixel, 2)
        table[p, n_lang + n_pix:n_lang + 2 * n_pix] = np.repeat(c_pos * pixel, 2)
    assert n_lang + 2 * n_pix <= dim_head
    return table[:n].astype(np.float32)


def _rotate(x, angles):
    """x: (n, heads, dim_head).  Channel pair (a, b) -> (a cos - b sin, b cos + a sin)."""
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([-b, a], axis=-1).reshape(x.shape)
    return x * cos + turned * sin


def _token_shift(sizes: dict, x):
    """x: (n, dim)."""
    fmap, text_len, _ = _geometry(sizes)
    n, d = x.shape
    p = np.arange(n)
    is_text = p < text_len
    r, c = np.divmod(np.maximum(p - text_len, 0), fmap)
    prev = jnp.asarray(np.maximum(p - 1, 0))
    above = jnp.asarray(np.maximum(p - fmap, 0))
    x_prev = jnp.where(jnp.asarray((is_text & (p > 0)) | (~is_text & (c > 0)))[:, None],
                       x[prev], 0.0)
    x_above = jnp.where(jnp.asarray(~is_text & (r > 0))[:, None], x[above], 0.0)
    ch = np.arange(d)[None, :]
    text_row = jnp.where(jnp.asarray(ch < d // 2), x_prev, x)
    img_row = jnp.where(jnp.asarray(ch < d // 4), x_above,
                        jnp.where(jnp.asarray(ch < d // 2), x_prev, x))
    return jnp.where(jnp.asarray(is_text)[:, None], text_row, img_row)


# --------------------------------------------------------------------- layers
def _f32(a):
    return jnp.asarray(a).astype(F32)


def _layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * _f32(p["scale"]) + _f32(p["bias"])


def _linear(p, x):
    y = x @ _f32(p["w"])
    return y + _f32(p["b"]) if "b" in p else y


def _attention(sizes, p, x, mask, angles):
    n = x.shape[0]
    heads, dh = int(sizes["heads"]), int(sizes["dim_head"])
    # qkv columns are head-major: [head0: q|k|v, head1: q|k|v, ...]
    qkv = _linear(p["qkv"], x).reshape(n, heads, 3, dh)
    q, k, v = (_rotate(qkv[:, :, i], angles) for i in range(3))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
    scores = jnp.where(jnp.asarray(mask)[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(n, heads * dh)
    return _linear(p["out"], out)


def _geglu(p, x):
    return _linear(p["w2"], _linear(p["w1"], x)
                   * jax.nn.gelu(_linear(p["w1g"], x), approximate=False))


def _check_supported(sizes: dict) -> None:
    unsupported = {
        "rotary_emb": True, "shift_tokens": True, "share_input_output_emb": True,
        "sandwich_norm": False, "stable": False, "reversible": False,
    }
    for key, want in unsupported.items():
        if key in sizes and bool(sizes[key]) != want:
            raise ValueError(f"the reference covers {key}={want} only")
    if sizes.get("shared_attn_ids") or sizes.get("shared_ff_ids"):
        raise ValueError("the reference covers unshared layers only")


# -------------------------------------------------------------------- forward
def token_ids(sizes: dict, text, image_codes):
    """Joint ids [<bos>, text, image], cut to the model's sequence, as int32."""
    ts, vt = int(sizes["text_seq_len"]), int(sizes["num_text_tokens"])
    text = jnp.clip(jnp.asarray(text, jnp.int32), 0, vt - 1)
    text = jnp.where(text == 0, vt + jnp.arange(ts, dtype=jnp.int32), text)
    ids = jnp.concatenate([jnp.zeros((1,), jnp.int32), text,
                           jnp.asarray(image_codes, jnp.int32) + vt + ts])
    return ids[:_geometry(sizes)[2]]


def forward_logits(params: dict, sizes: dict, text, image_codes):
    """text: (text_seq_len,) raw ids, 0 = pad; image_codes: (m,) with
    0 <= m <= fmap**2.  Returns (n, vocabulary) float32 logits for the n =
    min(1 + text_seq_len + m, sequence) positions, forbidden ids at -inf."""
    _check_supported(sizes)
    with jax.default_matmul_precision("highest"):
        ids = token_ids(sizes, text, image_codes)
        n = ids.shape[0]
        w_out = _f32(params["logits_linear"]["w"])  # (dim, vocabulary)
        x = w_out.T[ids]
        angles = jnp.asarray(rotary_angles(sizes, n))
        t = params["transformer"]
        types = list(sizes["attn_types"])
        for l in range(int(sizes["depth"])):
            wrap = t["layers"][l]
            mask = pattern_mask(sizes, types[l % len(types)], n)
            h = _token_shift(sizes, _layer_norm(wrap["attn_norm"], x))
            x = x + _attention(sizes, t["shared_attn"][str(l)], h, mask, angles) \
                * _f32(wrap["attn_scale"]).reshape(-1)
            h = _token_shift(sizes, _layer_norm(wrap["ff_norm"], x))
            x = x + _geglu(t["shared_ff"][str(l)], h) * _f32(wrap["ff_scale"]).reshape(-1)
        logits = _linear(params["logits_linear"], _layer_norm(params["logits_norm"], x))
        split = int(sizes["num_text_tokens"]) + int(sizes["text_seq_len"])
        row_is_text = (np.arange(n) < int(sizes["text_seq_len"]))[:, None]
        col_is_text = (np.arange(logits.shape[1]) < split)[None, :]
        return jnp.where(jnp.asarray(row_is_text == col_is_text), logits, -jnp.inf)


def loss(params: dict, sizes: dict, text, image_codes):
    """The weighted cross-entropy of one full sequence (all fmap**2 codes)."""
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
