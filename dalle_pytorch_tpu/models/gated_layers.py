"""The two token mixers of a hybrid trunk: gated softmax attention with
grouped-query heads (`gated_full`) and the Gated DeltaNet linear-attention
layer (`gated_delta`), and the zero-centred RMSNorm both sit behind.

Layer equations (x a token's hidden vector, no bias anywhere):

  N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)               zero-centred RMSNorm

  gated_full:   [q, gate] = W_q x (heads x dim_head each, q first; under
                `attn_gate` 'per_head' the gate is one column a head);
                k = W_k x, v = W_v x (kv_heads x dim_head);  q, k pass a
                per-head N (plain, x / rms * w, under `norm` 'rmsnorm');
                rotary (rotate-half) on the first
                partial_rotary_factor * dim_head channels, by stream position
                (YaRN frequencies and cos, sin x its attention factor where
                `rope_yarn_factor` is set);
                causal softmax(q k^T / sqrt(dim_head)) v, each key/value head
                serving heads / kv_heads query heads;
                out = W_o (attn * sigmoid(gate)), a head's gate scalar over
                its dim_head channels under 'per_head'

  gated_window: gated_full with `window_heads` query heads, the default
                rotary of `window_rotary_theta` on the whole of dim_head, and
                a causal band: query i sees keys
                i - window + 1 .. i [`sliding_window`]

  gated_delta:  [q, k, v, z] = W_qkvz x;  [b, a] = W_ba x;
                (q, k, v) <- silu(causal depthwise conv(q, k, v));
                q, k L2-normalised per head, q scaled by dk^-0.5; each key
                head serves value_heads / key_heads value heads;
                beta = sigmoid(b), or 2 sigmoid(b) under `gdn_neg_eigval`
                [`linear_allow_neg_eigval`: the transition I - beta k k^T then
                has eigenvalues in (-1, 1]];
                alpha = exp(-exp(A_log) * softplus(a + dt_bias));
                o = the gated delta rule (ops/delta_rule.py);
                out = W_o (rms(o) * w_n * silu(z)), rms over each head

Statistics (every norm, the L2 normalisation, decay and beta, the delta
rule's state) are float32 whatever the compute type.  `gated_delta` has two
forms of one layer: the full sequence (training, and a serving prefill, which
also takes the state after the last position and the last taps - 1 inputs of
the convolution) and `gated_delta_step`, one token of every slot on that
state and those taps (the serving decode step).  `gated_full` and
`gated_window` run on the training path only (models/transformer.refuse_hybrid).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.core.module import linear, linear_init
from dalle_pytorch_tpu.core.rng import KeyChain
from dalle_pytorch_tpu.kernels import delta_step
from dalle_pytorch_tpu.ops.attention import attend
from dalle_pytorch_tpu.ops.delta_rule import gated_delta_rule, gated_delta_step as delta_rule_step

F32 = jnp.float32


# ---------------------------------------------------------------------- norms
def rms_norm_init(dim: int, zero_centered: bool = True):
    return {"w": jnp.zeros((dim,), F32) if zero_centered else jnp.ones((dim,), F32)}


def rms_norm(params, x, eps: float, zero_centered: bool = True):
    """Float32 statistics, result in x's type."""
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    w = params["w"].astype(F32)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


# --------------------------------------------------------------------- rotary
def _yarn_inv_freq(cfg, rot: int) -> np.ndarray:
    """YaRN's frequencies (arXiv:2309.00071; the `rope_type` 'yarn' of the
    published configs): theta^(-2i/rot) extrapolated below channel pair `low`,
    divided by the factor above `high`, a linear ramp between, where
    low = floor(c(beta_fast)), high = ceil(c(beta_slow)) and
    c(b) = rot ln(original_max / (2 pi b)) / (2 ln theta)."""
    theta, factor = cfg.rotary_theta, cfg.rope_yarn_factor
    extrap = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)

    def c(b):
        return rot * math.log(cfg.rope_yarn_original_max / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(cfg.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(c(cfg.rope_yarn_beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrap * (1.0 - ramp) + extrap / factor * ramp


def rotary_attention_factor(cfg, kind: str = "gated_full") -> float:
    """What cos and sin are multiplied by: YaRN's `attention_factor`, 0.1 ln
    factor + 1, on a `gated_full` layer under `rope_yarn_factor`, else 1."""
    if kind != "gated_full" or not cfg.rope_yarn_factor:
        return 1.0
    return 0.1 * math.log(cfg.rope_yarn_factor) + 1.0


def partial_rotary_angles(cfg, n: int, kind: str = "gated_full") -> np.ndarray:
    """(n, rot / 2) angles of stream positions 0..n-1; rot = the rotated share
    of dim_head.  A `gated_window` layer rotates all of it, by its own base."""
    if kind == "gated_window":
        factor, theta = 1.0, cfg.window_rotary_theta
    else:
        factor, theta = cfg.partial_rotary_factor, cfg.rotary_theta
    rot = int(cfg.dim_head * factor)
    if kind == "gated_full" and cfg.rope_yarn_factor:
        inv_freq = _yarn_inv_freq(cfg, rot)
    else:
        inv_freq = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    return (np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]).astype(np.float32)


def apply_partial_rotary(angles, t, factor: float = 1.0):
    """t: (b, n, heads, dim_head) float32; rotate-half on the first
    2 * angles.shape[-1] channels: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)
    with x1, x2 the two HALVES of the rotated channels, cos and sin times
    `factor` (`rotary_attention_factor`)."""
    half = angles.shape[-1]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2, rest = t[..., :half], t[..., half:2 * half], t[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def expand_kv_heads(t, heads: int):
    """(b, n, kv_heads, d) -> (b, n, heads, d): key/value head j serves the
    query heads j * group .. (j + 1) * group - 1."""
    return jnp.repeat(t, heads // t.shape[2], axis=2)


# ----------------------------------------------------------------- gated_full
def attn_heads(cfg, kind: str = "gated_full") -> int:
    """Query heads of a `gated_full` or `gated_window` layer."""
    if kind == "gated_window" and cfg.window_heads:
        return cfg.window_heads
    return cfg.heads


def _gate_width(cfg, heads: int) -> int:
    return heads if cfg.attn_gate == "per_head" else heads * cfg.dim_head


def init_gated_full(key, cfg, kind: str = "gated_full") -> dict:
    if cfg.attn_gate not in ("elementwise", "per_head"):
        raise ValueError(f"attn_gate {cfg.attn_gate!r} is not valid; choose 'elementwise' or 'per_head'")
    if kind == "gated_window" and cfg.window < 1:
        raise ValueError(f"a gated_window layer needs window >= 1 keys (got {cfg.window})")
    keys = KeyChain(key)
    heads = attn_heads(cfg, kind)
    inner, kv = heads * cfg.dim_head, cfg.kv_heads_resolved * cfg.dim_head
    return {
        "q": linear_init(keys.next(), cfg.dim, inner + _gate_width(cfg, heads), bias=False),
        "k": linear_init(keys.next(), cfg.dim, kv, bias=False),
        "v": linear_init(keys.next(), cfg.dim, kv, bias=False),
        "out": linear_init(keys.next(), inner, cfg.dim, bias=False),
        "q_norm": rms_norm_init(cfg.dim_head, _qk_norm_zero_centered(cfg)),
        "k_norm": rms_norm_init(cfg.dim_head, _qk_norm_zero_centered(cfg)),
    }


def _qk_norm_zero_centered(cfg) -> bool:
    """The per-head q / k norm takes the block's form: plain (weight 1 at
    init) under `norm` 'rmsnorm', zero-centred otherwise."""
    return cfg.norm != "rmsnorm"


def gated_full_attention(p, cfg, x, use_flash: bool = False, mesh=None, kind: str = "gated_full"):
    """x: (b, n, dim) -> (b, n, dim).  `use_flash`: the caller's choice of
    kernels/flash_attention.py over the dense score matrix.  `kind`
    'gated_window': the window layer's heads, rotary and band."""
    b, n, _ = x.shape
    heads, dh = attn_heads(cfg, kind), cfg.dim_head
    inner = heads * dh
    qg = linear(p["q"], x)
    q, gate = qg[..., :inner].reshape(b, n, heads, dh), qg[..., inner:]
    k = linear(p["k"], x).reshape(b, n, -1, dh)
    v = linear(p["v"], x).reshape(b, n, -1, dh)
    angles = jnp.asarray(partial_rotary_angles(cfg, n, kind))
    factor = rotary_attention_factor(cfg, kind)
    zc = _qk_norm_zero_centered(cfg)
    q = apply_partial_rotary(angles, rms_norm(p["q_norm"], q.astype(F32), cfg.norm_eps, zc), factor)
    k = apply_partial_rotary(angles, rms_norm(p["k_norm"], k.astype(F32), cfg.norm_eps, zc), factor)
    q = q.astype(x.dtype).transpose(0, 2, 1, 3)
    k = expand_kv_heads(k.astype(x.dtype), heads).transpose(0, 2, 1, 3)
    v = expand_kv_heads(v, heads).transpose(0, 2, 1, 3)
    window = cfg.window if kind == "gated_window" else 0
    band = None
    if window:
        from dalle_pytorch_tpu.ops.masks import window_mask_np

        band = window_mask_np(n, window)
    with jax.named_scope("swa_core" if window else "full_core"):
        if use_flash:
            from dalle_pytorch_tpu.kernels.flash_attention import flash_attention

            out = flash_attention(q, k, v, mask=band, causal=True, scale=dh ** -0.5, mesh=mesh,
                                  count_as="window" if window else None)
        else:
            allowed = band if window else jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
            out = attend(q * (dh ** -0.5), k, v, mask=allowed[None, None])
    out = out.transpose(0, 2, 1, 3)
    if cfg.attn_gate == "per_head":  # one scalar a head, over its dim_head channels
        with jax.named_scope("attn_gate"):
            out = out * jax.nn.sigmoid(gate.astype(F32))[..., None].astype(out.dtype)
        out = out.reshape(b, n, inner)
    else:
        out = out.reshape(b, n, inner)
        with jax.named_scope("attn_gate"):
            out = out * jax.nn.sigmoid(gate.astype(F32)).astype(out.dtype)
    return linear(p["out"], out)


# ---------------------------------------------------------------- gated_delta
def init_gated_delta(key, cfg) -> dict:
    keys = KeyChain(key)
    kd = cfg.gdn_key_heads * cfg.gdn_key_dim
    vd = cfg.gdn_value_heads * cfg.gdn_value_dim
    conv_ch = 2 * kd + vd
    kk = cfg.gdn_conv_kernel
    bound = 1.0 / math.sqrt(kk)  # a depthwise kernel's fan-in is its taps
    return {
        "qkvz": linear_init(keys.next(), cfg.dim, 2 * kd + 2 * vd, bias=False),
        "ba": linear_init(keys.next(), cfg.dim, 2 * cfg.gdn_value_heads, bias=False),
        # (tap, channel); the LAST tap multiplies the current position
        "conv": {"w": jax.random.uniform(keys.next(), (kk, conv_ch), F32, -bound, bound)},
        # decay rate A = exp(A_log) uniform in (0, 16), dt_bias 1: the
        # published module's initialisation
        "A_log": jnp.log(jax.random.uniform(keys.next(), (cfg.gdn_value_heads,), F32, 1e-3, 16.0)),
        "dt_bias": jnp.ones((cfg.gdn_value_heads,), F32),
        "norm": rms_norm_init(cfg.gdn_value_dim, zero_centered=False),
        "out": linear_init(keys.next(), vd, cfg.dim, bias=False),
    }


def causal_depthwise_conv(w, x):
    """x: (b, n, channels), w: (taps, channels); y_t = sum_j w[j] x_{t - taps + 1 + j},
    zeros before the sequence.  Float32."""
    taps = w.shape[0]
    n = x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(F32)
    return sum(xp[:, j:j + n] * w[j] for j in range(taps))


def _l2_normalize(t, eps: float = 1e-6):
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + eps)


def _heads_and_gates(cfg, conv, ba, a_log, dt_bias):
    """The elementwise chain between the convolution and the rule: silu, L2
    normalisation, key heads spread over their value heads, beta and the log
    of the decay.  conv: (b, n, 2 kd + vd) float32, ba: (b, n, 2 hv) float32;
    returns q, k, v: (b, n, hv, .) and log_decay, beta: (b, n, hv), float32."""
    b, n, _ = conv.shape
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    kd = hk * dk
    qkv = jax.nn.silu(conv)
    q = _l2_normalize(qkv[..., :kd].reshape(b, n, hk, dk)) * (dk ** -0.5)
    k = _l2_normalize(qkv[..., kd:2 * kd].reshape(b, n, hk, dk))
    v = qkv[..., 2 * kd:].reshape(b, n, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    if cfg.gdn_neg_eigval:
        beta = 2.0 * beta
    log_decay = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(ba[..., hv:] + dt_bias.astype(F32))
    q, k = (expand_kv_heads(t, hv) for t in (q, k))
    return q, k, v, log_decay, beta


def _gate_norm(p, cfg, o, z, dtype):
    """rms(o) * w_n * silu(z) per head: o (..., hv, dv) float32, z the gate's
    (..., hv * dv) columns; returns (..., hv * dv) in `dtype`."""
    z = z.reshape(o.shape)
    o = rms_norm(p["norm"], o, cfg.norm_eps, zero_centered=False)
    return (o * jax.nn.silu(z.astype(F32))).astype(dtype).reshape(*o.shape[:-2], -1)


def gated_delta_net(p, cfg, x, return_state: bool = False):
    """x: (b, n, dim) -> (b, n, dim).  `return_state` (a serving prefill):
    also {"state": the rule's state after position n - 1 (b, hv, dk, dv)
    float32, "taps": the convolution's last taps - 1 INPUTS (b, taps - 1,
    2 kd + vd), zeros where the sequence is shorter}: what
    `gated_delta_step` continues from."""
    kd, vd = cfg.gdn_key_heads * cfg.gdn_key_dim, cfg.gdn_value_heads * cfg.gdn_value_dim
    with jax.named_scope("gdn_proj"):
        qkvz = linear(p["qkvz"], x)
        ba = linear(p["ba"], x).astype(F32)
    with jax.named_scope("gdn_conv"):
        conv_in = qkvz[..., :2 * kd + vd]
        q, k, v, log_decay, beta = (jnp.moveaxis(t, 2, 1) for t in _heads_and_gates(
            cfg, causal_depthwise_conv(p["conv"]["w"], conv_in), ba, p["A_log"], p["dt_bias"]))
    with jax.named_scope("gdn_scan"):
        o, state = gated_delta_rule(q, k, v, log_decay, beta)
    with jax.named_scope("gdn_gate_norm"):
        o = _gate_norm(p, cfg, jnp.moveaxis(o, 1, 2), qkvz[..., 2 * kd + vd:], x.dtype)
    out = linear(p["out"], o)
    if not return_state:
        return out
    keep = cfg.gdn_conv_kernel - 1
    taps = jnp.pad(conv_in, ((0, 0), (keep, 0), (0, 0)))[:, -keep:]
    return out, {"state": state, "taps": taps}


def _use_delta_kernel(cfg) -> bool:
    """Whether the one-token rule takes the Pallas kernel (kernels/delta_step.py:
    one read and one write of the state, where XLA makes two reads and a
    write), from what the code can observe, as `transformer._use_flash` does:
    on a TPU, at head shapes a tile holds.  Everywhere else the definition
    (`ops/delta_rule.gated_delta_step`) runs."""
    return jax.default_backend() == "tpu" and delta_step.supports(
        cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim)


def gated_delta_step(p, cfg, x, carried):
    """One token of every slot.  x: (s, 1, dim); carried: {"state": (s, hv, dk,
    dv) float32, "taps": (s, taps - 1, 2 kd + vd)} as `gated_delta_net` (or the
    step before) left them.  Returns (out (s, 1, dim), the new carried)."""
    kd, vd = cfg.gdn_key_heads * cfg.gdn_key_dim, cfg.gdn_value_heads * cfg.gdn_value_dim
    with jax.named_scope("gdn_proj"):
        qkvz = linear(p["qkvz"], x)
        ba = linear(p["ba"], x).astype(F32)
    with jax.named_scope("gdn_conv_step"):
        taps = carried["taps"]
        window = jnp.concatenate([taps, qkvz[..., :2 * kd + vd].astype(taps.dtype)], axis=1)
        conv = jnp.sum(window.astype(F32) * p["conv"]["w"].astype(F32), axis=1, keepdims=True)
        q, k, v, log_decay, beta = (t[:, 0] for t in _heads_and_gates(
            cfg, conv, ba, p["A_log"], p["dt_bias"]))
    with jax.named_scope("gdn_step"):
        step = delta_step.gated_delta_step_kernel if _use_delta_kernel(cfg) else delta_rule_step
        o, state = step(q, k, v, log_decay, beta, carried["state"])
    with jax.named_scope("gdn_gate_norm"):
        o = _gate_norm(p, cfg, o[:, None], qkvz[..., 2 * kd + vd:], x.dtype)
    return linear(p["out"], o), {"state": state, "taps": window[:, 1:]}
