"""The two token mixers of a hybrid trunk: gated softmax attention with
grouped-query heads (`gated_full`) and the Gated DeltaNet linear-attention
layer (`gated_delta`), and the zero-centred RMSNorm both sit behind.

Layer equations (x a token's hidden vector, no bias anywhere):

  N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)               zero-centred RMSNorm

  gated_full:   [q, gate] = W_q x (heads x dim_head each, q first);
                k = W_k x, v = W_v x (kv_heads x dim_head);  q, k pass a
                per-head N;  rotary (rotate-half) on the first
                partial_rotary_factor * dim_head channels, by stream position;
                causal softmax(q k^T / sqrt(dim_head)) v, each key/value head
                serving heads / kv_heads query heads;
                out = W_o (attn * sigmoid(gate))

  gated_delta:  [q, k, v, z] = W_qkvz x;  [b, a] = W_ba x;
                (q, k, v) <- silu(causal depthwise conv(q, k, v));
                q, k L2-normalised per head, q scaled by dk^-0.5; each key
                head serves value_heads / key_heads value heads;
                beta = sigmoid(b), alpha = exp(-exp(A_log) * softplus(a + dt_bias));
                o = the gated delta rule (ops/delta_rule.py);
                out = W_o (rms(o) * w_n * silu(z)), rms over each head

Statistics (every norm, the L2 normalisation, decay and beta, the delta
rule's state) are float32 whatever the compute type.  Training path only: no
cache, no decode step (models/transformer.refuse_hybrid).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.core.module import linear, linear_init
from dalle_pytorch_tpu.core.rng import KeyChain
from dalle_pytorch_tpu.ops.attention import attend
from dalle_pytorch_tpu.ops.delta_rule import gated_delta_rule

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
def partial_rotary_angles(cfg, n: int) -> np.ndarray:
    """(n, rot / 2) angles of stream positions 0..n-1; rot = the rotated share
    of dim_head."""
    rot = int(cfg.dim_head * cfg.partial_rotary_factor)
    inv_freq = 1.0 / (cfg.rotary_theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    return (np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]).astype(np.float32)


def apply_partial_rotary(angles, t):
    """t: (b, n, heads, dim_head) float32; rotate-half on the first
    2 * angles.shape[-1] channels: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)
    with x1, x2 the two HALVES of the rotated channels."""
    half = angles.shape[-1]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2, rest = t[..., :half], t[..., half:2 * half], t[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def expand_kv_heads(t, heads: int):
    """(b, n, kv_heads, d) -> (b, n, heads, d): key/value head j serves the
    query heads j * group .. (j + 1) * group - 1."""
    return jnp.repeat(t, heads // t.shape[2], axis=2)


# ----------------------------------------------------------------- gated_full
def init_gated_full(key, cfg) -> dict:
    keys = KeyChain(key)
    inner, kv = cfg.heads * cfg.dim_head, cfg.kv_heads_resolved * cfg.dim_head
    return {
        "q": linear_init(keys.next(), cfg.dim, 2 * inner, bias=False),
        "k": linear_init(keys.next(), cfg.dim, kv, bias=False),
        "v": linear_init(keys.next(), cfg.dim, kv, bias=False),
        "out": linear_init(keys.next(), inner, cfg.dim, bias=False),
        "q_norm": rms_norm_init(cfg.dim_head),
        "k_norm": rms_norm_init(cfg.dim_head),
    }


def gated_full_attention(p, cfg, x, use_flash: bool = False, mesh=None):
    """x: (b, n, dim) -> (b, n, dim).  `use_flash`: the caller's choice of
    kernels/flash_attention.py over the dense score matrix."""
    b, n, _ = x.shape
    heads, dh = cfg.heads, cfg.dim_head
    inner = heads * dh
    qg = linear(p["q"], x)
    q, gate = qg[..., :inner].reshape(b, n, heads, dh), qg[..., inner:]
    k = linear(p["k"], x).reshape(b, n, -1, dh)
    v = linear(p["v"], x).reshape(b, n, -1, dh)
    angles = jnp.asarray(partial_rotary_angles(cfg, n))
    q = apply_partial_rotary(angles, rms_norm(p["q_norm"], q.astype(F32), cfg.norm_eps))
    k = apply_partial_rotary(angles, rms_norm(p["k_norm"], k.astype(F32), cfg.norm_eps))
    q = q.astype(x.dtype).transpose(0, 2, 1, 3)
    k = expand_kv_heads(k.astype(x.dtype), heads).transpose(0, 2, 1, 3)
    v = expand_kv_heads(v, heads).transpose(0, 2, 1, 3)
    if use_flash:
        from dalle_pytorch_tpu.kernels.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=True, scale=dh ** -0.5, mesh=mesh)
    else:
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        out = attend(q * (dh ** -0.5), k, v, mask=causal[None, None])
    out = out.transpose(0, 2, 1, 3).reshape(b, n, inner)
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


def _conv_and_gates(cfg, qkv_in, ba, conv_w, a_log, dt_bias):
    """The elementwise chain between the projections and the rule: conv, silu,
    L2 normalisation, key heads spread over their value heads, beta and the
    log of the decay; all (b, heads, n, ...) float32."""
    b, n, _ = qkv_in.shape
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    kd = hk * dk
    qkv = jax.nn.silu(causal_depthwise_conv(conv_w, qkv_in))
    q = _l2_normalize(qkv[..., :kd].reshape(b, n, hk, dk)) * (dk ** -0.5)
    k = _l2_normalize(qkv[..., kd:2 * kd].reshape(b, n, hk, dk))
    v = qkv[..., 2 * kd:].reshape(b, n, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    log_decay = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(ba[..., hv:] + dt_bias.astype(F32))
    heads_first = lambda t: jnp.moveaxis(t, 2, 1)
    q, k = (heads_first(expand_kv_heads(t, hv)) for t in (q, k))
    return q, k, heads_first(v), heads_first(log_decay), heads_first(beta)


def gated_delta_net(p, cfg, x):
    """x: (b, n, dim) -> (b, n, dim)."""
    b, n, _ = x.shape
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    kd, vd = hk * dk, hv * dv
    with jax.named_scope("gdn_proj"):
        qkvz = linear(p["qkvz"], x)
        ba = linear(p["ba"], x).astype(F32)
    with jax.named_scope("gdn_conv"):
        q, k, v, log_decay, beta = _conv_and_gates(
            cfg, qkvz[..., :2 * kd + vd], ba, p["conv"]["w"], p["A_log"], p["dt_bias"])
    with jax.named_scope("gdn_scan"):
        o = gated_delta_rule(q, k, v, log_decay, beta)
    with jax.named_scope("gdn_gate_norm"):
        z = qkvz[..., 2 * kd + vd:].reshape(b, n, hv, dv)
        o = rms_norm(p["norm"], jnp.moveaxis(o, 1, 2), cfg.norm_eps, zero_centered=False)
        o = (o * jax.nn.silu(z.astype(F32))).astype(x.dtype).reshape(b, n, vd)
    return linear(p["out"], o)
