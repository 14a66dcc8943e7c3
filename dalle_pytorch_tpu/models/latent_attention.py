"""Multi-head latent attention (`mla`), the training form.

Layer equations (x a token's hidden vector, no bias anywhere, N a plain
RMSNorm with its own weight, initialised 1):

    c_q = N(W_qa x)                         (mla_q_rank)
    [q_nope_h ; q_rope_h] = W_qb c_q        heads x (mla_nope_dim + mla_rope_dim)
    [c_kv ; k_rope] = W_kva x               (mla_kv_rank + mla_rope_dim)
    [k_nope_h ; v_h] = W_kvb N(c_kv)        heads x (mla_nope_dim + mla_v_dim)
    rotary (rotate-half over all mla_rope_dim channels, base rotary_theta, by
    stream position) on q_rope_h and on k_rope, ONE vector that every head shares
    k_h = [k_nope_h ; k_rope]
    o_h = causal softmax(q_h . k_h / sqrt(mla_nope_dim + mla_rope_dim)) v_h
    out = W_o [o_1 .. o_heads]

Training materialises k_h and v_h for every head.  The absorbed form, which
attends over the latent c_kv itself and is what a cache of c_kv and k_rope
serves, is the decode form: this module has none (training path only,
models/transformer.refuse_hybrid).  Norm statistics and the rotation are
float32 whatever the compute type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.core.module import linear, linear_init
from dalle_pytorch_tpu.core.rng import KeyChain
from dalle_pytorch_tpu.models.gated_layers import apply_partial_rotary, rms_norm, rms_norm_init
from dalle_pytorch_tpu.ops.attention import attend

F32 = jnp.float32


def init_mla(key, cfg) -> dict:
    keys = KeyChain(key)
    heads, nope, rope, vd = cfg.heads, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    return {
        "q_a": linear_init(keys.next(), cfg.dim, cfg.mla_q_rank, bias=False),
        "q_norm": rms_norm_init(cfg.mla_q_rank, zero_centered=False),
        "q_b": linear_init(keys.next(), cfg.mla_q_rank, heads * (nope + rope), bias=False),
        "kv_a": linear_init(keys.next(), cfg.dim, cfg.mla_kv_rank + rope, bias=False),
        "kv_norm": rms_norm_init(cfg.mla_kv_rank, zero_centered=False),
        "kv_b": linear_init(keys.next(), cfg.mla_kv_rank, heads * (nope + vd), bias=False),
        "out": linear_init(keys.next(), heads * vd, cfg.dim, bias=False),
    }


def rope_angles(cfg, n: int) -> np.ndarray:
    """(n, mla_rope_dim / 2) angles of stream positions 0..n-1."""
    rot = cfg.mla_rope_dim
    inv_freq = 1.0 / (cfg.rotary_theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    return (np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]).astype(np.float32)


def mla_attention(p, cfg, x, use_flash: bool = False, mesh=None):
    """x: (b, n, dim) -> (b, n, dim).  `use_flash`: the caller's choice of
    kernels/flash_attention.py over the dense score matrix (the kernel wants
    keys and values of one width: nope + rope == v, as published)."""
    b, n, _ = x.shape
    heads, nope, rope, vd = cfg.heads, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    norm = lambda w, t: rms_norm(w, t, cfg.norm_eps, zero_centered=False)
    with jax.named_scope("mla_q_proj"):
        q = linear(p["q_b"], norm(p["q_norm"], linear(p["q_a"], x))).reshape(b, n, heads, nope + rope)
    with jax.named_scope("mla_kv_proj"):
        kva = linear(p["kv_a"], x)
        k_rope = kva[..., cfg.mla_kv_rank:]
        kv = linear(p["kv_b"], norm(p["kv_norm"], kva[..., :cfg.mla_kv_rank]))
        kv = kv.reshape(b, n, heads, nope + vd)
    with jax.named_scope("mla_rope"):
        angles = jnp.asarray(rope_angles(cfg, n))
        q_rope = apply_partial_rotary(angles, q[..., nope:].astype(F32)).astype(x.dtype)
        k_rope = apply_partial_rotary(angles, k_rope[:, :, None, :].astype(F32)).astype(x.dtype)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1).transpose(0, 2, 1, 3)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (b, n, heads, rope))],
                            axis=-1).transpose(0, 2, 1, 3)
        v = kv[..., nope:].transpose(0, 2, 1, 3)
    scale = (nope + rope) ** -0.5
    with jax.named_scope("mla_core"):
        if use_flash and vd == nope + rope:
            from dalle_pytorch_tpu.kernels.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=True, scale=scale, mesh=mesh)
        else:
            causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
            out = attend(q * scale, k, v, mask=causal[None, None])
    with jax.named_scope("mla_out"):
        return linear(p["out"], out.transpose(0, 2, 1, 3).reshape(b, n, heads * vd))
