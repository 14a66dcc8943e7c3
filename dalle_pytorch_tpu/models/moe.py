"""A routed feed-forward that is told which experts it holds.

    p = softmax(W_r x) over ALL `moe_experts` (float32);  the `moe_top_k`
    largest, renormalised to sum 1;  E(x) = W_d (silu(W_g x) * (W_u x));
    MoE(x) = sum_{e in top-k, e held here} p_e E_e(x) + sigmoid(w_s . x) E_shared(x)

The layer holds experts [moe_first_expert, moe_first_expert + moe_experts_held)
of a stated expert-parallel deployment: it routes over every expert, computes
the terms of the sum that its own experts give (and the shared expert, which
every rank computes alike), and that partial result is the layer's output.
Nothing stands in for the absent ranks or their exchange; with every expert
held the output is the whole layer's.

No token is dropped, whatever the load: the (token, expert) pairs are sorted
by expert, so each held expert's rows are contiguous, and the three products
are grouped matrix multiplications over row groups of the sizes the router
produced.  The pair buffer has a row for every pair (tokens x top_k): under
jit the bound is static, and that is the one bound no routing can exceed.
Rows past the held pairs are zero and cost the grouped product nothing (on the
TPU its grid covers the occupied row tiles only).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.core.module import Initializer, linear, linear_init
from dalle_pytorch_tpu.core.rng import KeyChain

_ROW_TILE = 128  # the kernel's row tile; the pair buffer is padded to it


def init_moe(key: jax.Array, cfg) -> dict:
    keys = KeyChain(key)
    held, dim, width = cfg.moe_held, cfg.dim, cfg.moe_ff_dim
    params = {
        "router": linear_init(keys.next(), dim, cfg.moe_experts, bias=False),
        "experts": {
            "wg": Initializer.uniform_fan_in(keys.next(), (held, dim, width), dim),
            "wu": Initializer.uniform_fan_in(keys.next(), (held, dim, width), dim),
            "wd": Initializer.uniform_fan_in(keys.next(), (held, width, dim), width),
        },
    }
    if cfg.moe_shared_ff_dim:
        sw = cfg.moe_shared_ff_dim
        params["shared"] = {
            "wg": linear_init(keys.next(), dim, sw, bias=False),
            "wu": linear_init(keys.next(), dim, sw, bias=False),
            "wd": linear_init(keys.next(), sw, dim, bias=False),
            "gate": linear_init(keys.next(), dim, 1, bias=False),
        }
    return params


def route(router: dict, cfg, x2: jnp.ndarray):
    """x2: (tokens, dim).  Returns (weights (tokens, top_k) float32, expert ids
    (tokens, top_k) int32).  Float32 at full matmul precision: the margin
    between the k-th and the next expert is small next to a bfloat16 rounding
    step, and a flipped choice is a different expert's whole output."""
    logits = jnp.dot(x2.astype(jnp.float32), router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.moe_top_k)  # ties: the lower id first
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32)


def _use_gmm_kernel() -> bool:
    return jax.default_backend() == "tpu"


def _tiling(k: int, n: int):
    return (_ROW_TILE, min(k, 1024), min(n, 512))


def grouped_matmul(lhs, rhs, group_sizes, path_tally: Optional[Dict[str, int]] = None):
    """lhs (rows, k) whose first sum(group_sizes) rows lie in groups, in order;
    rhs (groups, k, n).  Row r of group g gives lhs[r] @ rhs[g]; rows past the
    groups give zero.  On the TPU: jax's own megablox grouped-matmul kernel
    (Pallas; a grid over occupied row tiles, its own backward).  Elsewhere
    `lax.ragged_dot`, its definition."""
    kernel = _use_gmm_kernel()
    if path_tally is not None:
        path_tally["kernel" if kernel else "fallback"] += 1
    if not kernel:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                                  preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
              _tiling(rhs.shape[1], rhs.shape[2]))
    # the kernel leaves rows it never visited unwritten; a select (not a
    # multiply) both zeroes them and keeps them out of every gradient
    occupied = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes)
    return jnp.where(occupied, out, jnp.zeros((), out.dtype))


def _swiglu(wg, wu, wd, x):
    return linear(wd, jax.nn.silu(linear(wg, x)) * linear(wu, x))


def _routed_terms(cfg, path_tally, x2, weights, ids, ex):
    """sum over the held experts e of a token's top-k of p_e E_e(x): (tokens,
    dim), and the held experts' row counts."""
    tokens, dim = x2.shape
    k, held, first = cfg.moe_top_k, cfg.moe_held, cfg.moe_first_expert
    with jax.named_scope("moe_dispatch"):
        local = ids - first
        here = (local >= 0) & (local < held)
        group = jnp.where(here, local, held).reshape(-1)  # `held` = not ours: sorts last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held]
        rows = tokens * k
        padded = rows + (-rows) % _ROW_TILE
        order = jnp.pad(order, (0, padded - rows))
        occupied = (jnp.arange(padded) < jnp.sum(sizes))[:, None]
        xg = jnp.where(occupied, jnp.take(x2, order // k, axis=0), jnp.zeros((), x2.dtype))

    with jax.named_scope("moe_experts"):
        gmm = functools.partial(grouped_matmul, group_sizes=sizes, path_tally=path_tally)
        hidden = jax.nn.silu(gmm(xg, ex["wg"].astype(xg.dtype))) * gmm(xg, ex["wu"].astype(xg.dtype))
        yg = gmm(hidden, ex["wd"].astype(xg.dtype))

    with jax.named_scope("moe_combine"):
        w_sorted = jnp.take(jnp.where(here, weights, 0.0).reshape(-1), order[:rows])
        yg = (yg[:rows].astype(jnp.float32) * w_sorted[:, None]).astype(x2.dtype)
        # back to pair order (token-major), then the k terms of each token add
        # up, in x's type: at most `held` of them are not zero, and a float32
        # sum would make the backward a float32 buffer of every pair
        unsort = jnp.argsort(order[:rows])
        out = jnp.take(yg, unsort, axis=0).reshape(tokens, k, dim).sum(axis=1)
    return out, sizes


def moe_feed_forward(params: dict, cfg, x: jnp.ndarray,
                     path_tally: Optional[Dict[str, int]] = None):
    """x: (batch, n, dim) -> (out (batch, n, dim), stats).  `stats` are device
    scalars: `moe_pairs_here` (pairs routed to held experts) and
    `moe_load_max_over_mean` (the busiest held expert's rows over the mean)."""
    b, n, dim = x.shape
    x2 = x.reshape(b * n, dim)
    held = cfg.moe_held

    with jax.named_scope("moe_router"):
        weights, ids = route(params["router"], cfg, x2)

    # the pair buffers (tokens * top_k rows, mostly empty) are recomputed in
    # the backward instead of kept: at the expected load the held experts'
    # products are a percent of the layer's operations, their buffers would be
    # most of its memory
    out, sizes = jax.checkpoint(functools.partial(_routed_terms, cfg, path_tally))(
        x2, weights, ids, params["experts"])
    pairs_here = jnp.sum(sizes)

    if "shared" in params:
        with jax.named_scope("shared_expert"):
            sh = params["shared"]
            gate = jax.nn.sigmoid(linear(sh["gate"], x2).astype(jnp.float32))
            out = out.astype(jnp.float32) \
                + gate * _swiglu(sh["wg"], sh["wu"], sh["wd"], x2).astype(jnp.float32)

    stats = {
        "moe_pairs_here": pairs_here.astype(jnp.float32),
        "moe_load_max_over_mean": jnp.max(sizes) / jnp.maximum(pairs_here / held, 1.0),
    }
    return out.astype(x.dtype).reshape(b, n, dim), stats
