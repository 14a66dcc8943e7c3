"""Sampling helpers (explicit-key equivalents of
/root/reference/dalle_pytorch/dalle_pytorch.py:51-69)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def log_clamp(t: jnp.ndarray, eps: float = 1e-20) -> jnp.ndarray:
    return jnp.log(jnp.clip(t, min=eps))


def gumbel_noise(key: jax.Array, shape, dtype=jnp.float32) -> jnp.ndarray:
    u = jax.random.uniform(key, shape, dtype)
    return -log_clamp(-log_clamp(u))


def gumbel_sample(key: jax.Array, logits: jnp.ndarray, temperature: float = 1.0, axis: int = -1):
    """argmax(logits / temperature + G); with -inf-filtered logits the noise
    leaves masked entries at -inf, so this samples from the softmax.  The
    noise and the sum are float32 whatever the logits' type: a bfloat16
    uniform has 128 values, and an argmax over logits + that noise never
    reaches the tail of the kept logits, so it is no draw from their softmax."""
    return jnp.argmax(logits.astype(jnp.float32) / temperature
                      + gumbel_noise(key, logits.shape, jnp.float32), axis=axis)


def top_k_filter(logits: jnp.ndarray, thres: float = 0.5) -> jnp.ndarray:
    """Keep the top max(int((1-thres)*V), 1) logits, set the rest to -inf.

    Exact parity with the reference's top_k (dalle_pytorch.py:63-69,
    topk + scatter): EXACTLY k entries survive — ties at the k-th value are
    broken by top_k's ordering, not all kept (a tracked round-4 micro-delta,
    now closed).  k is static (derived from the vocab size), so this jits to
    one lax.top_k + scatter."""
    num_logits = logits.shape[-1]
    k = max(int((1.0 - thres) * num_logits), 1)
    val, ind = jax.lax.top_k(logits, k)
    probs = jnp.full_like(logits, -jnp.inf)
    return jnp.put_along_axis(probs, ind, val, axis=-1, inplace=False)


def prob_mask_like(key: jax.Array, shape, prob: float) -> jnp.ndarray:
    return jax.random.uniform(key, shape) < prob
