"""The gated delta rule, chunked.

The rule, per value head, with state S (dk, dv) zero at the start of a sequence:

    S' = alpha_t S_{t-1};   S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;   o_t = S_t^T q_t

A token-by-token `lax.scan` over thousands of positions neither fits (its
backward keeps a state per position) nor finishes (a rank-1 update a step
leaves the MXU idle).  The chunked form (Yang et al., "Gated Delta Networks",
arXiv:2412.06464, section 3.3) does the work of `chunk` positions as matrix
products: inside a chunk the rule is a unit lower-triangular system, whose
inverse the products below build, and only one state per chunk is carried by a
scan.  Everything here is float32 at full matmul precision whatever the
caller's compute type (the state integrates over the whole sequence, and a
bfloat16 pass in the triangular inverse compounds); inputs arrive already
normalised.  `jax.grad` differentiates it as written (the backward is the
transposed scan over chunks), but for the triangular inverse, which brings its
own backward.

`gated_delta_rule` also returns the state after the last position: what a
serving prefill hands to the decode step, which advances it one token at a
time with `gated_delta_step` (the three lines above as they stand, on a state
per slot).  beta ranges over (0, 2): with beta = 2 sigmoid(b) the transition
I - beta k k^T has eigenvalues in (-1, 1] (`gdn_neg_eigval`), and nothing here
relies on beta < 1 (the triangular system is unit lower-triangular whatever
beta is).

The plain recurrence both must equal is written apart from this package, in
the benchmark's references (`benchmark/reference/qwen3_next_reference.py` and
`olmo_hybrid_reference.py`, `delta_rule_recurrence`); tests/test_hybrid_trunk.py
and tests/test_olmo_hybrid_serving.py hold them together.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
_PRECISION = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.einsum, precision=_PRECISION, preferred_element_type=jnp.float32)


@jax.custom_vjp
def _unit_lower_inverse(lower: jnp.ndarray) -> jnp.ndarray:
    """(I + L)^-1 for strictly lower-triangular L (..., c, c).  With M = -L
    nilpotent (M^c = 0) the inverse is the finite series sum_p M^p, which
    factors as (I + M)(I + M^2)(I + M^4)...: log2(c) squarings, all on the MXU,
    where forward substitution would be c dependent row updates.  Its backward
    is the inverse's own derivative, -inv^T ct inv^T: differentiating the
    squarings instead would keep every power of every chunk for the backward,
    most of a layer's memory."""
    c = lower.shape[-1]
    power = -lower
    inv = jnp.eye(c, dtype=lower.dtype) + power
    span = 2  # inv holds the series up to M^(span - 1)
    while span < c:
        power = _mm("...ij,...jk->...ik", power, power)
        inv = inv + _mm("...ij,...jk->...ik", inv, power)
        span *= 2
    return inv


def _unit_lower_inverse_fwd(lower):
    inv = _unit_lower_inverse(lower)
    return inv, inv


def _unit_lower_inverse_bwd(inv, ct):
    return (-_mm("...ji,...jk->...ik", inv, _mm("...jk,...lk->...jl", ct, inv)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q, k, v, log_decay, beta, chunk: int = CHUNK):
    """q, k: (b, h, n, dk), L2-normalised per head, q scaled; v: (b, h, n, dv);
    log_decay = log(alpha) <= 0 and beta in (0, 2): (b, h, n).  Returns (the
    outputs o: (b, h, n, dv), the state after position n - 1: (b, h, dk, dv)),
    float32.  Any n: a tail that does not fill a chunk is padded with
    positions that neither write (beta 0, k 0) nor decay (log_decay 0), so the
    state after the pad is the state after position n - 1."""
    b, h, n, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, log_decay, beta))
    pad = -n % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
    nc = (n + pad) // chunk
    q, k, v = (a.reshape(b, h, nc, chunk, a.shape[-1]) for a in (q, k, v))
    g = jnp.cumsum(g.reshape(b, h, nc, chunk), axis=-1)  # decay since the chunk began
    beta = beta.reshape(b, h, nc, chunk)

    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    # decay from position j to position i >= j of one chunk; the exponent is
    # masked BEFORE exp: above the diagonal it is positive and may overflow
    span = g[..., :, None] - g[..., None, :]
    decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, span, 0.0)), 0.0)

    k_beta = k * beta[..., None]
    lower = jnp.where(row > col, _mm("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
    inv = _unit_lower_inverse(lower)
    # what each position writes if the chunk began from a zero state, and the
    # keys through which the incoming state corrects that
    writes = _mm("...ij,...jv->...iv", inv, v * beta[..., None])
    k_seen = _mm("...ij,...jk->...ik", inv, k_beta * jnp.exp(g)[..., None])
    qk = _mm("...ik,...jk->...ij", q, k) * decay

    def one_chunk(state, xs):
        q_c, k_c, writes_c, k_seen_c, qk_c, g_c = xs
        new_v = writes_c - _mm("...ck,...kv->...cv", k_seen_c, state)
        out = _mm("...ck,...kv->...cv", q_c * jnp.exp(g_c)[..., None], state) \
            + _mm("...ij,...jv->...iv", qk_c, new_v)
        g_end = g_c[..., -1]
        carried = k_c * jnp.exp(g_end[..., None] - g_c)[..., None]
        state = state * jnp.exp(g_end)[..., None, None] \
            + _mm("...ck,...cv->...kv", carried, new_v)
        return state, out

    chunks_first = lambda a: jnp.moveaxis(a, 2, 0)
    state, out = jax.lax.scan(one_chunk, jnp.zeros((b, h, dk, dv), f32),
                              tuple(map(chunks_first, (q, k, writes, k_seen, qk, g))))
    return jnp.moveaxis(out, 0, 2).reshape(b, h, nc * chunk, dv)[:, :, :n], state


def gated_delta_step(q, k, v, log_decay, beta, state):
    """The rule for ONE position of every row: q, k: (s, h, dk); v: (s, h, dv);
    log_decay, beta: (s, h); state: (s, h, dk, dv) float32.  Returns (o:
    (s, h, dv), the new state), float32.  Elementwise products and sums over
    dk, no matrix unit (a rank-1 update has nothing for it, and its float32
    products would be rounded to bfloat16 there).  S'^T k and S'^T q are read
    in ONE pass over the state (o = S^T q = S'^T q + (k . q) u with u = beta
    (v - S'^T k), so the output needs no pass over the new state), and the
    write is the second and last."""
    f32 = jnp.float32
    q, k, v, beta = (a.astype(f32) for a in (q, k, v, beta))
    decayed = state * jnp.exp(log_decay.astype(f32))[..., None, None]
    read_k = jnp.sum(decayed * k[..., None], axis=-2)
    read_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - read_k)
    out = read_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return out, decayed + k[..., None] * u[..., None, :]
