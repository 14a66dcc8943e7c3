"""A routed feed-forward that is told which experts it holds.

    p = softmax(W_r x) over ALL `moe_experts` (float32);  the `moe_top_k`
    largest, renormalised to sum 1, times `moe_routed_scale`;
    E(x) = W_d (silu(W_g x) * (W_u x));
    MoE(x) = sum_{e in top-k, e held here} p_e E_e(x) + sigmoid(w_s . x) E_shared(x)

With `moe_router` 'sigmoid_bias': s = sigmoid(W_r x); the experts are the
top-k of s + b, b a balancing bias that no gradient trains; their weights are
s (without b) of the chosen over their sum, times `moe_routed_scale`.  After
an optimizer step b_e += `moe_bias_rate` * sign(mean(c) - c_e), c_e the step's
count of tokens that chose e (`balance_bias`; arXiv:2408.15664).  With
`moe_shared_gated` false the shared expert is added as it is.

The layer holds experts [moe_first_expert, moe_first_expert + moe_experts_held)
of a stated expert-parallel deployment: it routes over every expert, computes
the terms of the sum that its own experts give (and the shared expert, which
every rank computes alike), and that partial result is the layer's output.
Nothing stands in for the absent ranks or their exchange; with every expert
held the output is the whole layer's.

No token is dropped, whatever the load: the (token, expert) pairs are ranked
by expert, so each held expert's rows are contiguous, and the three products
are grouped matrix multiplications over row groups of the sizes the router
produced.  Every row of a pair buffer is paid for whether a pair lies in it or
not (a gather, the kernels' row tiles, the selects around them, a scatter-add;
forward, recomputed and transposed), so the buffer is sized by the load this
rank EXPECTS, not by the load no routing can exceed: `pair_rows` = tokens x
top_k x held / experts, times `_HEAD_ROOM`, in whole row tiles, and never more
than tokens x top_k.  The ranking is walked in chunks of that many rows, as
many as hold a pair (a loop whose length the device decides: one chunk at the
expected load, every chunk when every pair lies here), each chunk dispatched,
multiplied and added into its tokens' rows on its own.  So the layer costs
what its load costs, at any load, and is exact at every one;
`moe_overflow_share` says how often one chunk was not enough.  Where every
expert is held the one chunk is the whole ranking.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.core.module import Initializer, linear, linear_init
from dalle_pytorch_tpu.core.rng import KeyChain

_ROW_TILE = 128  # the kernel's row tile; a chunk of the ranking is whole tiles
# A chunk's rows over the pairs this rank expects.  Routing that sends pairs
# here independently spreads by sqrt(pairs) (random weights: 2,640 +- 50 of
# 42,240), so the first chunk holds such a load with room to spare and a
# smaller factor would walk two.  It is no bound on the load: a router trained
# with nothing to balance it across ranks came to send this rank twelve times
# its share (PERF.md section 6, PR 27), and such a call walks more chunks.
_HEAD_ROOM = 2


def init_moe(key: jax.Array, cfg) -> dict:
    keys = KeyChain(key)
    held, dim, width = cfg.moe_held, cfg.dim, cfg.moe_ff_dim
    router = linear_init(keys.next(), dim, cfg.moe_experts, bias=False)
    if cfg.moe_router == "sigmoid_bias":
        router["bias"] = jnp.zeros((cfg.moe_experts,), jnp.float32)
    elif cfg.moe_router != "softmax":
        raise ValueError(f"moe_router {cfg.moe_router!r} is not valid; choose 'softmax' or 'sigmoid_bias'")
    params = {
        "router": router,
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
        }
        if cfg.moe_shared_gated:
            params["shared"]["gate"] = linear_init(keys.next(), dim, 1, bias=False)
    return params


def route(router: dict, cfg, x2: jnp.ndarray):
    """x2: (tokens, dim).  Returns (weights (tokens, top_k) float32, expert ids
    (tokens, top_k) int32).  Float32 at full matmul precision: the margin
    between the k-th and the next expert is small next to a bfloat16 rounding
    step, and a flipped choice is a different expert's whole output."""
    logits = jnp.dot(x2.astype(jnp.float32), router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        # the bias decides WHO is chosen and never what a chosen expert weighs
        biased = scores + jax.lax.stop_gradient(router["bias"].astype(jnp.float32))
        _, ids = jax.lax.top_k(biased, cfg.moe_top_k)  # ties: the lower id first
        weights = jnp.take_along_axis(scores, ids, axis=-1)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * cfg.moe_routed_scale
        return weights, ids.astype(jnp.int32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.moe_top_k)  # ties: the lower id first
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if cfg.moe_routed_scale != 1.0:  # [`moe_routed_scaling_factor`]
        weights = weights * cfg.moe_routed_scale
    return weights, ids.astype(jnp.int32)


def balance_bias(cfg, bias, counts):
    """The bias's rule, once an optimizer step: toward the experts that fewer
    tokens than the mean chose, by `moe_bias_rate` each (arXiv:2408.15664,
    the sign form).  `counts`: (moe_experts,) choices of the step in this layer."""
    counts = counts.astype(jnp.float32)
    return bias + cfg.moe_bias_rate * jnp.sign(jnp.mean(counts) - counts).astype(bias.dtype)


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


def _whole_tiles(rows: int) -> int:
    return rows + (-rows) % _ROW_TILE


def _padded_rows(cfg, tokens: int) -> int:
    return _whole_tiles(tokens * cfg.moe_top_k)


def pair_rows(cfg, tokens: int) -> int:
    """Rows of a chunk of the ranking of `tokens` tokens' pairs (static):
    `_HEAD_ROOM` times the pairs expected at the held experts, in whole row
    tiles, and at most every pair."""
    expected = tokens * cfg.moe_top_k * cfg.moe_held / cfg.moe_experts
    return min(_whole_tiles(math.ceil(_HEAD_ROOM * expected)), _padded_rows(cfg, tokens))


def _chunk_terms(cfg, rows, path_tally, ranking, first_row, x2, weights, ex):
    """What the `rows` rows of the ranking from `first_row` on add to the
    routed terms: (tokens, dim).  `rows` is static, `first_row` is not."""
    order, sizes, here = ranking
    tokens, dim = x2.shape
    with jax.named_scope("moe_dispatch"):
        pair = jax.lax.dynamic_slice(order, (first_row,), (rows,))
        token = pair // cfg.moe_top_k
        ends = jnp.cumsum(sizes)
        last_row = jnp.minimum(ends, first_row + rows)
        sizes = jnp.maximum(last_row - jnp.maximum(ends - sizes, first_row), 0)  # of each group, here
        occupied = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        xg = jnp.where(occupied, jnp.take(x2, token, axis=0), jnp.zeros((), x2.dtype))

    with jax.named_scope("moe_experts"):
        gmm = functools.partial(grouped_matmul, group_sizes=sizes, path_tally=path_tally)
        hidden = jax.nn.silu(gmm(xg, ex["wg"])) * gmm(xg, ex["wu"])
        yg = gmm(hidden, ex["wd"])

    with jax.named_scope("moe_combine"):
        w_row = jnp.take(jnp.where(here, weights, 0.0).reshape(-1), pair)
        yg = (yg.astype(jnp.float32) * w_row[:, None]).astype(x2.dtype)
        # each row into its token's row, in x's type: at most `held` terms a
        # token are not zero, and a float32 sum would make the backward a
        # float32 buffer of every row.  Rows past the pairs are zero.
        return jnp.zeros((tokens, dim), x2.dtype).at[token].add(yg)


def _walk(cfg, rows, path_tally, x2, weights, ex, ranking):
    """The sum of `_chunk_terms` over the chunks of `rows` rows that hold a
    pair, and its gradient by the same walk."""
    def chunks(ranking):
        _, sizes, _ = ranking
        return -(-jnp.sum(sizes) // rows)

    def in_x_type(ex):
        return jax.tree.map(lambda w: w.astype(x2.dtype), ex)

    def add(total, part):  # in the combine's scope, so that the layer's scopes hold the loop's own cost
        with jax.named_scope("moe_combine"):
            return jax.tree.map(jnp.add, total, part)

    def forward(x2, weights, ex, ranking):
        term = functools.partial(_chunk_terms, cfg, rows, path_tally, ranking)
        ex = in_x_type(ex)
        return jax.lax.fori_loop(
            0, chunks(ranking), lambda j, out: add(out, term(j * rows, x2, weights, ex)),
            jnp.zeros(x2.shape, x2.dtype))

    # A loop of a length the device decides has no transpose, and the buffers
    # are to be recomputed in the backward instead of kept (they would be most
    # of the layer's memory), as jax.checkpoint would: each chunk is
    # differentiated on its own and the gradients add up, the experts' in
    # x's type (an expert's rows lie in a chunk or two).
    def backward(inputs, g):
        x2, weights, ex, ranking = inputs
        term = functools.partial(_chunk_terms, cfg, rows, None, ranking)
        narrow = in_x_type(ex)

        def add_chunk(j, grads):
            _, pull = jax.vjp(functools.partial(term, j * rows), x2, weights, narrow)
            return add(grads, pull(g))

        dx, dw, dex = jax.lax.fori_loop(
            0, chunks(ranking), add_chunk, jax.tree.map(jnp.zeros_like, (x2, weights, narrow)))
        return dx, dw, jax.tree.map(lambda d, w: d.astype(w.dtype), dex, ex), None

    terms = jax.custom_vjp(forward)
    terms.defvjp(lambda *inputs: (forward(*inputs), inputs), backward)
    return terms(x2, weights, ex, ranking)


def _routed_terms(cfg, path_tally, x2, weights, ids, ex):
    """sum over the held experts e of a token's top-k of p_e E_e(x): (tokens,
    dim), and the held experts' row counts."""
    tokens, held = x2.shape[0], cfg.moe_held
    rows = pair_rows(cfg, tokens)
    with jax.named_scope("moe_dispatch"):  # integers only: no gradient passes here
        local = ids - cfg.moe_first_expert
        here = (local >= 0) & (local < held)
        group = jnp.where(here, local, held).reshape(-1)  # `held` = not ours: ranks last
        order = jnp.argsort(group, stable=True)
        order = jnp.pad(order, (0, (-order.shape[0]) % rows))  # whole chunks
        sizes = jnp.bincount(group, length=held + 1)[:held]
    return _walk(cfg, rows, path_tally, x2, weights, ex, (order, sizes, here)), sizes


def moe_feed_forward(params: dict, cfg, x: jnp.ndarray,
                     path_tally: Optional[Dict[str, int]] = None):
    """x: (batch, n, dim) -> (out (batch, n, dim), stats).  `stats` are device
    scalars: `moe_pairs_here` (pairs routed to held experts),
    `moe_load_max_over_mean` (the busiest held expert's rows over the mean) and
    `moe_overflow_share` (1 where the pairs outgrew `pair_rows` and more than
    one chunk was walked, else 0: averaged over layer calls, a share); under
    a bias-balanced router also `moe_choice_counts`, the (moe_experts,) tokens
    that chose each expert, held here or not (the router is whole here)."""
    b, n, dim = x.shape
    x2 = x.reshape(b * n, dim)
    held = cfg.moe_held

    with jax.named_scope("moe_router"):
        weights, ids = route(params["router"], cfg, x2)

    out, sizes = _routed_terms(cfg, path_tally, x2, weights, ids, params["experts"])
    pairs_here = jnp.sum(sizes)

    if "shared" in params:
        with jax.named_scope("shared_expert"):
            sh = params["shared"]
            gate = jax.nn.sigmoid(linear(sh["gate"], x2).astype(jnp.float32)) if "gate" in sh else 1.0
            out = out.astype(jnp.float32) \
                + gate * _swiglu(sh["wg"], sh["wu"], sh["wd"], x2).astype(jnp.float32)

    stats = {
        "moe_pairs_here": pairs_here.astype(jnp.float32),
        "moe_load_max_over_mean": jnp.max(sizes) / jnp.maximum(pairs_here / held, 1.0),
        "moe_overflow_share": (pairs_here > pair_rows(cfg, b * n)).astype(jnp.float32),
    }
    if cfg.moe_router == "sigmoid_bias":
        with jax.named_scope("moe_router"):
            stats["moe_choice_counts"] = jnp.bincount(ids.reshape(-1), length=cfg.moe_experts)
    return out.astype(x.dtype).reshape(b, n, dim), stats
