"""Pallas TPU flash attention (forward + backward kernels).

The memory-linear attention path for `full` and pattern-masked attention:
blockwise online-softmax in VMEM, never materializing (n, n) scores in HBM —
forward saves only (out, logsumexp).  This replaces both the reference's
dense einsum attention and its DeepSpeed/Triton block-sparse CUDA kernels
(/root/reference/dalle_pytorch/attention.py:339-398): block sparsity appears
as *skipped tiles* — causally-dead tiles and tiles whose static pattern-mask
block is all-False are never computed, in forward and backward alike.

Backward runs as two Pallas kernels: a dq pass (grid over query tiles,
accumulating over key tiles) and a dk/dv pass (grid over key tiles,
accumulating over query tiles), both recomputing probabilities from the saved
logsumexp.

Operands: every tile product (`_dot`) takes its operands in the type q, k, v
and dO arrived in and accumulates in float32.  bfloat16 in: the tiles go to the
matrix unit as read, the softmax scale multiplies the float32 scores (and dq /
dk when they are written), and p and dS are rounded to the operand type once,
right before the products that consume them, as `ops.attention.attend` rounds
its probabilities.  float32 in: float32 operands.  Row maxima, sums, `lse`,
`delta` and every accumulator are float32 either way.  Nothing selects this
but `q.dtype`; `flash_attention` counts the calls of each kind while it is
traced (`kernels/flash_calls_16bit_operands`, `kernels/flash_calls_32bit_operands`).

Tiles: `block_q` / `block_k` are CAPS, and `resolve_block` gives each side the
largest multiple of the 128-lane width under its cap that divides the
sequence (default cap 384: 384 x 384 tiles at 1,152 and 4,224 positions,
256 x 256 at 1,280 and 4,352, 128 x 128 at 640).  The kernels' time is the
grid step's, not the matrix unit's, so the largest tile that divides the
sequence is the fastest known.  `flash_attention` counts the tile each call
resolved while it is traced (`kernels/flash_tile_<bq>x<bk>`).

Grid: `grid="auto"` compacts wherever the resolved grid has a dead step,
whether causality or the pattern kills it (`sparse_index.grid_has_dead_step`):
a dead step on the dense grid still takes its slot and fetches its K/V (and
mask) tiles.  At 384-tiles no image pattern at fmap 32 kills a tile inside
the causal triangle, but causality kills 3 of 9 steps at 1,152 positions and
55 of 121 at 4,224, so every causal training call runs the compacted grid;
a 1 x 1 grid (a 128-position prefill) and a non-causal call without a mask
(CLIP) have none and stay dense.  Counted like the tile
(`kernels/flash_grid_compact`, `kernels/flash_grid_dense`).

On CPU (tests) kernels run in interpret mode; any platform other than cpu or
tpu is an error, never an interpreter.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.observability import health as health_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics

# The default is a CAP on the tile, and `resolve_block` picks the largest
# multiple of the 128-lane width under it that divides the sequence: 384 x 384
# where 384 divides it (1,152 = 3 x 384 and 4,224 = 11 x 384: every sequence
# the benchmark's cells train on), 256 x 256 where only 256 does (1,280,
# 4,352), 128 x 128 otherwise.  The kernels are bound by the grid step, not by
# the matrix unit: a 384 x 384 x 256 forward tile takes 1.94 us where nine
# 128 x 128 ones take 6.3, so the same three kernels run x 3.2-3.5 faster (one
# v5e, PERF.md section 6).  384 is the largest tile that is known to compile
# for a v5e at head widths 128 and 256 in all seven bodies, mask tile included
# (tests/test_chip_compile.py).
DEFAULT_BLOCK_Q = 384
DEFAULT_BLOCK_K = 384
_LANES = 128  # TPU lane width; lse/delta rows are stored broadcast over lanes
_NEG = -1e30


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"flash attention kernels run on tpu (compiled) or cpu "
            f"(interpret mode, tests); backend {backend!r} is neither"
        )
    return backend == "cpu"


def resolve_block(n: int, block: int) -> int:
    """The block size actually used for sequence length n under the cap
    `block`: the LARGEST multiple of the 128-lane width that divides n and
    does not exceed the cap (1,152 and 4,224 -> 384 under the default cap,
    1,280 -> 256, 640 -> 128; a caller's `block=128` stays 128).  Only where
    there is none: the cap (at most n) halved until it divides n, and, when
    halving bottoms out below 8, plain divisors of n (largest first,
    preferring sublane-aligned multiples of 8) before raising.  The fallback
    is what lets odd-factor sequence lengths (e.g. n = 270 = 2*3^3*5 -> 135
    under a cap of 256, 129 -> 43 under one of 128) reach the kernel path at
    all; lengths with no divisor in [8, block] (e.g. the fmap-48 layout length
    2305 = 5*461) still fail loudly.  Everything that must agree with the
    kernels on the tile (the scan-layers path's liveness and compacted
    tables, the profiler's tile density) calls this with the same cap."""
    cap = min(block, n)
    for b in range(cap - cap % _LANES, 0, -_LANES):
        if n % b == 0:
            return b
    b = cap
    while b and n % b:
        b //= 2
    if b >= 8:
        return b
    for d in range(cap, 7, -1):  # aligned divisors first: full sublane tiles
        if n % d == 0 and d % 8 == 0:
            return d
    for d in range(cap, 7, -1):
        if n % d == 0:
            return d
    raise ValueError(
        f"no valid flash block size for seq len {n} (no divisor in "
        f"[8, {cap}]) — use the dense attention path"
    )


def _when_live(compute, live_ref, i, j, head, *, causal, use_mask, block_q,
               block_k, **_):
    """Run `compute` on the dense grid's tile (i, j) unless causality or the
    pattern's liveness table kills it."""
    if not (causal or use_mask):
        return compute()
    live = True
    if causal:
        live = j * block_k <= i * block_q + block_q - 1
    if use_mask:
        cell = live_ref[i, j] if head is None else live_ref[head, i, j]
        live = jnp.logical_and(live, cell > 0)
    pl.when(live)(compute)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _is_16bit(dtype) -> bool:
    return jnp.dtype(dtype).itemsize < 4


def _dot(a, b, contract):
    """One tile product on the matrix unit: operands in the type they have,
    float32 result.  A 16-bit product states its own precision: the ambient
    default may be "highest" (tests/conftest.py), which Mosaic's 16-bit matmul
    refuses, and the program must not depend on it.  float32 operands take
    the ambient precision, as they always did."""
    precision = jax.lax.Precision.DEFAULT if _is_16bit(a.dtype) else None
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _masked_scores(q, k, mask_ref, kmask_ref, i, j, *, scale, causal, block_q,
                   block_k, use_mask, use_kmask):
    """Scaled, masked float32 scores of one (block_q, block_k) tile; q and k
    as read.  Every kernel, dense and compacted, builds its scores here."""
    s = _dot(q, k, _NT) * scale
    if causal:
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)
    if use_mask:
        m = mask_ref[:]
        if m.ndim == 3:  # per-head mask block (1, bq, bk)
            m = m[0]
        s = jnp.where(m, s, _NEG)
    if use_kmask:
        # per-batch key-padding row (1, block_k) broadcast over query rows
        s = jnp.where(kmask_ref[0] > 0, s, _NEG)
    return s


def _live_tile_fraction(live, nq: int, nk: int, block_q: int, block_k: int,
                        causal: bool) -> float:
    """Fraction of the (nq, nk) tile grid the kernels compute: pattern
    liveness AND tile-granular causality.  Static python float for the
    CostEstimate; a traced liveness table (scan-selected) falls back to the
    causal-only fraction."""
    from dalle_pytorch_tpu.kernels.sparse_index import block_causal_live_np

    cmask = (
        block_causal_live_np(nq, nk, block_q, block_k)
        if causal else np.ones((nq, nk), bool)
    )
    if live is not None:
        try:
            lv = np.asarray(live) > 0  # host-sync-ok: static trace-time table
            return float((lv & cmask).mean())
        except Exception:
            pass  # traced table: price causality only
    return float(cmask.mean())


# ---------------------------------------------------------------------------
# one live tile's work, shared by the dense and the compacted kernels: the two
# grids differ in how a step finds its tile, never in what it does there (which
# is what keeps them bit-exact against each other at any operand type).
# `sk` is the static score keywords of `_masked_scores`.
# ---------------------------------------------------------------------------

def _online_softmax_tile(q_ref, k_ref, v_ref, mask_ref, kmask_ref, i, j,
                         m_scr, l_scr, acc_scr, **sk):
    s = _masked_scores(q_ref[0], k_ref[0], mask_ref, kmask_ref, i, j, **sk)
    m_prev = m_scr[:, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]
    acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
    m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _write_out(o_ref, lse_ref, row_max, l_scr, acc_scr):
    l = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(row_max + jnp.log(l), lse_ref.shape[1:])


def _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                  kmask_ref, i, j, **sk):
    """(p, dS / scale) of one tile, float32, from the saved logsumexp."""
    s = _masked_scores(q_ref[0], k_ref[0], mask_ref, kmask_ref, i, j, **sk)
    p = jnp.exp(s - lse_ref[0][:, :1])
    dp = _dot(do_ref[0], v_ref[0], _NT)
    return p, p * (dp - delta_ref[0][:, :1])


def _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
             kmask_ref, i, j, dq_scr, **sk):
    _, ds = _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          mask_ref, kmask_ref, i, j, **sk)
    k = k_ref[0]
    dq_scr[:] = dq_scr[:] + _dot(ds.astype(k.dtype), k, _NN)


def _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
              kmask_ref, i, j, dk_scr, dv_scr, **sk):
    p, ds = _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          mask_ref, kmask_ref, i, j, **sk)
    q, do = q_ref[0], do_ref[0]
    dv_scr[:] = dv_scr[:] + _dot(p.astype(do.dtype), do, _TN)
    dk_scr[:] = dk_scr[:] + _dot(ds.astype(q.dtype), q, _TN)


def _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, scale):
    dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, live_ref, kmask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, h, per_head, **sk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    head = pl.program_id(0) % h if per_head else None

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        _online_softmax_tile(q_ref, k_ref, v_ref, mask_ref, kmask_ref, i, j,
                             m_scr, l_scr, acc_scr, **sk)

    _when_live(_compute, live_ref, i, j, head, **sk)

    @pl.when(j == nk - 1)
    def _finalize():
        _write_out(o_ref, lse_ref, m_scr[:, :1], l_scr, acc_scr)


def _dummy_specs_args(use_mask, mask, live, nq, nk, block_q, block_k,
                      h=1, kv_grid=False):
    specs = []
    if use_mask:
        per_head = mask.ndim == 3
        if live is None:
            live = jnp.ones(
                (mask.shape[0], nq, nk) if per_head else (nq, nk), jnp.int32
            )
        if per_head:
            if kv_grid:
                mspec = pl.BlockSpec((1, block_q, block_k), lambda bh, j, i: (bh % h, i, j))
            else:
                mspec = pl.BlockSpec((1, block_q, block_k), lambda bh, i, j: (bh % h, i, j))
        else:
            if kv_grid:
                mspec = pl.BlockSpec((block_q, block_k), lambda b, j, i: (i, j))
            else:
                mspec = pl.BlockSpec((block_q, block_k), lambda b, i, j: (i, j))
        specs.append(mspec)
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return specs, (mask, live)
    specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return specs, (jnp.zeros((1,), jnp.int32), jnp.zeros((1, 1), jnp.int32))


def _kmask_spec_arg(use_kmask, kmask, h, block_k, kv_grid=False):
    """Per-batch key-padding row: the grid batch index is b*h-flattened, so
    the index map divides by the (static) head count.  kv_grid swaps the
    (i, j) program-id order for the dk/dv pass.  kmask is (b, 1, n): the
    chip's tiling wants a block's last two dims to be (8, 128)-multiples or
    the array's own, and a (1, block_k) block of a (b, n) array is neither —
    the unit middle axis makes it the array's own."""
    if use_kmask:
        if kv_grid:
            spec = pl.BlockSpec((1, 1, block_k), lambda bh, j, i: (bh // h, 0, j))
        else:
            spec = pl.BlockSpec((1, 1, block_k), lambda bh, i, j: (bh // h, 0, j))
        return [spec], (kmask,)
    return [pl.BlockSpec(memory_space=pltpu.SMEM)], (jnp.zeros((1,), jnp.int32),)


@jax.named_scope("flash_attn_fwd")
def _flash_fwd(q, k, v, mask, live, kmask, h, causal, scale, block_q, block_k):
    """q, k, v: (bh, n, d); kmask: optional (b, 1, n) int32 key-padding rows.
    Returns (out (bh, n, d), lse (bh, n, LANES)).  The named scope makes the
    kernel a labelled row in xprof traces (telemetry span mirroring)."""
    bh, n, d = q.shape
    assert n % block_q == 0 and n % block_k == 0, (n, block_q, block_k)
    nq, nk = n // block_q, n // block_k
    use_mask = mask is not None
    use_kmask = kmask is not None
    per_head = use_mask and mask.ndim == 3

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    mspecs, margs = _dummy_specs_args(use_mask, mask, live, nq, nk, block_q, block_k, h=h)
    in_specs += mspecs
    kspecs, kargs = _kmask_spec_arg(use_kmask, kmask, h, block_k)
    in_specs += kspecs

    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=scale, use_mask=use_mask, use_kmask=use_kmask, h=h, per_head=per_head,
    )
    # price only the tiles the kernel actually computes: XLA's cost_analysis
    # reads this estimate, and the flops crosscheck / bench MFU were
    # overstating sparse configs when every masked tile was billed dense
    flops = 2 * 2 * bh * n * n * d * _live_tile_fraction(
        live, n // block_q, n // block_k, block_q, block_k, causal
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, n, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n, _LANES), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            # static python floats from shapes — host-sync-ok
            flops=int(flops), bytes_accessed=int(3 * bh * n * d * 4),
            transcendentals=int(bh * n * n),
        ),
        name="flash_fwd",
        interpret=_interpret(),
    )(q, k, v, *margs, *kargs)
    if health_mod.taps_active():
        # the fused kernel never materializes scores; its logsumexp rows are
        # the exported logit statistic (row max <= lse <= row max + log n) —
        # the saturation signal for bf16 attention numerics without giving
        # up the O(n)-memory path
        health_mod.tap_attention("attn_flash", lse=lse[:, :, 0])
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, live_ref,
               kmask_ref, dq_ref, dq_scr, *, h, per_head, **sk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    head = pl.program_id(0) % h if per_head else None

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                 kmask_ref, i, j, dq_scr, **sk)

    _when_live(_compute, live_ref, i, j, head, **sk)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * sk["scale"]).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, live_ref,
                kmask_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, h, per_head, **sk):
    # grid: (bh, key tile j, query tile i) — accumulate over query tiles
    j = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)
    head = pl.program_id(0) % h if per_head else None

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                  kmask_ref, i, j, dk_scr, dv_scr, **sk)

    _when_live(_compute, live_ref, i, j, head, **sk)

    @pl.when(i == nq - 1)
    def _finalize():
        _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sk["scale"])


@jax.named_scope("flash_attn_bwd")
def _flash_bwd(q, k, v, do, out, lse, mask, live, kmask, h, causal, scale, block_q, block_k):
    bh, n, d = q.shape
    nq, nk = n // block_q, n // block_k
    use_mask = mask is not None
    use_kmask = kmask is not None
    per_head = use_mask and mask.ndim == 3

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (bh, n, _LANES))

    qkvdo_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),  # k
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),  # v
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # do
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),  # delta
    ]
    mspecs, margs = _dummy_specs_args(use_mask, mask, live, nq, nk, block_q, block_k, h=h)
    kspecs, kargs = _kmask_spec_arg(use_kmask, kmask, h, block_k)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, block_q=block_q, block_k=block_k,
                          scale=scale, use_mask=use_mask, use_kmask=use_kmask,
                          h=h, per_head=per_head),
        grid=(bh, nq, nk),
        in_specs=qkvdo_specs + mspecs + kspecs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta, *margs, *kargs)

    # dk/dv pass: grid over key tiles; index maps swap i/j roles
    kv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),  # q
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),  # k
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),  # v
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),  # do
        pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),  # delta
    ]
    if use_mask:
        mspecs2, _ = _dummy_specs_args(
            use_mask, mask, live, nq, nk, block_q, block_k, h=h, kv_grid=True
        )
    else:
        mspecs2 = mspecs
    kspecs2, _ = _kmask_spec_arg(use_kmask, kmask, h, block_k, kv_grid=True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, block_q=block_q, block_k=block_k,
                          scale=scale, use_mask=use_mask, use_kmask=use_kmask,
                          h=h, per_head=per_head),
        grid=(bh, nk, nq),
        in_specs=kv_specs + mspecs2 + kspecs2,
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, n, d), k.dtype),
            jax.ShapeDtypeStruct((bh, n, d), v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta, *margs, *kargs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# compacted grid (scalar-prefetch) kernels
# ---------------------------------------------------------------------------
#
# The dense grid above schedules every (i, j) tile and `pl.when`-skips the
# dead ones — dead tiles still occupy grid slots and still DMA K/V blocks.
# The kernels below instead run a flat grid (bh, T) over ONLY the live tiles
# of a static pattern: per-step tile coordinates come from int32 index tables
# (kernels/sparse_index.py) fed through `num_scalar_prefetch`, so BlockSpec
# index maps read the prefetched tables and fetch only live blocks (the
# splash-attention design).  Liveness, visit order (ascending j within each
# query row; ascending i within each key column for dk/dv) and the
# init/compute/finalize math are IDENTICAL to the dense grid, which makes the
# compacted kernels bit-exact against it — verified per pattern by
# tests/test_flash_compact.py.
#
# The optional VFA-style variant (vfa=True) exploits the static live set a
# step further: a first max-only pass computes each row's global score
# maximum, and the accumulation pass then uses that fixed maximum — no
# per-tile rescale of the running accumulator (alpha multiplies drop out).
# Same math analytically, but a different summation order: allclose, not
# bit-identical, to the online-softmax forward.  The backward is unchanged
# (it only consumes the saved logsumexp, which VFA reproduces exactly).


def _tab(ref, hid, t):
    """Scalar-prefetch table read: tables are (1, T) shared or (h, T)
    per-head; `hid` is 0 or the head id."""
    return ref[hid, t]


def _compact_in_specs(d, block_q, block_k, h, H, mask, use_kmask):
    """BlockSpecs for (q, k, v, mask, kmask) on the compacted grid.  Index
    maps receive (b, t, *scalar_refs) — the five prefetched tables — and
    look tile coordinates up in them.  Returns (q/k/v specs, mask spec,
    kmask spec)."""
    per_head_tab = H > 1

    def hid(b):
        return b % h if per_head_tab else 0

    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda b, t, qr, kc, fr, la, va: (b, qr[hid(b), t], 0))
    k_spec = pl.BlockSpec(
        (1, block_k, d), lambda b, t, qr, kc, fr, la, va: (b, kc[hid(b), t], 0))
    v_spec = pl.BlockSpec(
        (1, block_k, d), lambda b, t, qr, kc, fr, la, va: (b, kc[hid(b), t], 0))
    if mask is not None:
        if mask.ndim == 3:  # per-head mask: tables must be per-head too
            mask_spec = pl.BlockSpec(
                (1, block_q, block_k),
                lambda b, t, qr, kc, fr, la, va: (b % h, qr[b % h, t], kc[b % h, t]),
            )
        else:
            mask_spec = pl.BlockSpec(
                (block_q, block_k),
                lambda b, t, qr, kc, fr, la, va: (qr[hid(b), t], kc[hid(b), t]),
            )
    else:
        mask_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    if use_kmask:
        kmask_spec = pl.BlockSpec(
            (1, 1, block_k),
            lambda b, t, qr, kc, fr, la, va: (b // h, 0, kc[hid(b), t]))
    else:
        kmask_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return (q_spec, k_spec, v_spec), mask_spec, kmask_spec


def _compact_row_spec(block_q, d, h, H):
    """Output/row-input spec addressed by the current QUERY tile (o, lse,
    do, delta, dq, gmax)."""
    per_head_tab = H > 1

    def hid(b):
        return b % h if per_head_tab else 0

    return pl.BlockSpec(
        (1, block_q, d), lambda b, t, qr, kc, fr, la, va: (b, qr[hid(b), t], 0))


def _compact_col_spec(block_k, d, h, H):
    """Output spec addressed by the current KEY tile (dk, dv)."""
    per_head_tab = H > 1

    def hid(b):
        return b % h if per_head_tab else 0

    return pl.BlockSpec(
        (1, block_k, d), lambda b, t, qr, kc, fr, la, va: (b, kc[hid(b), t], 0))


def _mask_args(mask, use_kmask, kmask):
    margs = (mask,) if mask is not None else (jnp.zeros((1,), jnp.int32),)
    kargs = (kmask,) if use_kmask else (jnp.zeros((1,), jnp.int32),)
    return margs + kargs


def _fwd_kernel_compact(qr_ref, kc_ref, fr_ref, la_ref, va_ref,
                        q_ref, k_ref, v_ref, mask_ref, kmask_ref, o_ref, lse_ref,
                        m_scr, l_scr, acc_scr, *, h, per_head, **sk):
    t = pl.program_id(1)
    hid = pl.program_id(0) % h if per_head else 0
    i = _tab(qr_ref, hid, t)
    j = _tab(kc_ref, hid, t)

    @pl.when(_tab(fr_ref, hid, t) == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_tab(va_ref, hid, t) == 1)
    def _compute():
        _online_softmax_tile(q_ref, k_ref, v_ref, mask_ref, kmask_ref, i, j,
                             m_scr, l_scr, acc_scr, **sk)

    @pl.when(_tab(la_ref, hid, t) == 1)
    def _finalize():
        _write_out(o_ref, lse_ref, m_scr[:, :1], l_scr, acc_scr)


def _max_kernel_compact(qr_ref, kc_ref, fr_ref, la_ref, va_ref,
                        q_ref, k_ref, mask_ref, kmask_ref, gmax_ref, m_scr, *,
                        h, per_head, **sk):
    """VFA pass 1: per-row global score maxima over the live set (scores
    only — no exp, no PV matmul)."""
    t = pl.program_id(1)
    hid = pl.program_id(0) % h if per_head else 0
    i = _tab(qr_ref, hid, t)
    j = _tab(kc_ref, hid, t)

    @pl.when(_tab(fr_ref, hid, t) == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)

    @pl.when(_tab(va_ref, hid, t) == 1)
    def _compute():
        s = _masked_scores(q_ref[0], k_ref[0], mask_ref, kmask_ref, i, j, **sk)
        m_scr[:] = jnp.broadcast_to(
            jnp.maximum(m_scr[:, :1], jnp.max(s, axis=-1, keepdims=True)),
            m_scr.shape,
        )

    @pl.when(_tab(la_ref, hid, t) == 1)
    def _finalize():
        gmax_ref[0] = jnp.broadcast_to(m_scr[:, :1], gmax_ref.shape[1:])


def _fwd_kernel_compact_vfa(qr_ref, kc_ref, fr_ref, la_ref, va_ref,
                            q_ref, k_ref, v_ref, mask_ref, kmask_ref, gmax_ref,
                            o_ref, lse_ref, l_scr, acc_scr, *, h, per_head, **sk):
    """VFA pass 2: accumulation against the precomputed global maximum — the
    running max is global from the start, so the per-tile accumulator rescale
    (alpha) drops out entirely."""
    t = pl.program_id(1)
    hid = pl.program_id(0) % h if per_head else 0
    i = _tab(qr_ref, hid, t)
    j = _tab(kc_ref, hid, t)

    @pl.when(_tab(fr_ref, hid, t) == 1)
    def _init():
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_tab(va_ref, hid, t) == 1)
    def _compute():
        s = _masked_scores(q_ref[0], k_ref[0], mask_ref, kmask_ref, i, j, **sk)
        p = jnp.exp(s - gmax_ref[0][:, :1])
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
        v = v_ref[0]
        acc_scr[:] = acc_scr[:] + _dot(p.astype(v.dtype), v, _NN)

    @pl.when(_tab(la_ref, hid, t) == 1)
    def _finalize():
        _write_out(o_ref, lse_ref, gmax_ref[0][:, :1], l_scr, acc_scr)


@jax.named_scope("flash_attn_fwd_compact")
def _flash_fwd_compact(q, k, v, mask, kmask, tabs, h, causal, scale, block_q,
                       block_k, vfa):
    """Compacted-grid forward.  tabs: the 10-tuple of sparse_index tables in
    TABLE_KEYS order; the first five (row-major) drive this pass."""
    bh, n, d = q.shape
    qr, kc, fr, la, va = tabs[:5]
    H, T = qr.shape
    use_mask = mask is not None
    use_kmask = kmask is not None
    per_head = H > 1
    nq = n // block_q

    qkv_specs, mask_spec, kmask_spec = _compact_in_specs(
        d, block_q, block_k, h, H, mask, use_kmask)
    row_spec = _compact_row_spec(block_q, d, h, H)
    lse_spec = _compact_row_spec(block_q, _LANES, h, H)
    args = (qr, kc, fr, la, va, q, k, v) + _mask_args(mask, use_kmask, kmask)

    # live-tile pricing: T is the (static) compacted grid length
    cost = pl.CostEstimate(
        flops=int(2 * 2 * bh * T * block_q * block_k * d),
        bytes_accessed=int(bh * (2 * T * block_k + 2 * nq * block_q) * d * 4),
        transcendentals=int(bh * T * block_q * block_k),
    )

    gargs = ()
    gmax_spec = []
    if vfa:
        gmax = pl.pallas_call(
            functools.partial(
                _max_kernel_compact, causal=causal, block_q=block_q,
                block_k=block_k, scale=scale, use_mask=use_mask,
                use_kmask=use_kmask, h=h, per_head=per_head,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(bh, T),
                in_specs=[qkv_specs[0], qkv_specs[1], mask_spec, kmask_spec],
                out_specs=lse_spec,
                scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((bh, n, _LANES), jnp.float32),
            name="flash_compact_max",
            interpret=_interpret(),
        )(qr, kc, fr, la, va, q, k, *_mask_args(mask, use_kmask, kmask))
        gargs = (gmax,)
        gmax_spec = [lse_spec]
        kernel = functools.partial(
            _fwd_kernel_compact_vfa, causal=causal, block_q=block_q,
            block_k=block_k, scale=scale, use_mask=use_mask,
            use_kmask=use_kmask, h=h, per_head=per_head,
        )
        scratch = [
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ]
    else:
        kernel = functools.partial(
            _fwd_kernel_compact, causal=causal, block_q=block_q,
            block_k=block_k, scale=scale, use_mask=use_mask,
            use_kmask=use_kmask, h=h, per_head=per_head,
        )
        scratch = [
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ]

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bh, T),
            in_specs=list(qkv_specs) + [mask_spec, kmask_spec] + gmax_spec,
            out_specs=(row_spec, lse_spec),
            scratch_shapes=scratch,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, n, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n, _LANES), jnp.float32),
        ),
        cost_estimate=cost,
        name="flash_compact_fwd",
        interpret=_interpret(),
    )(*args, *gargs)
    if health_mod.taps_active():
        health_mod.tap_attention("attn_flash", lse=lse[:, :, 0])
    return out, lse


def _dq_kernel_compact(qr_ref, kc_ref, fr_ref, la_ref, va_ref,
                       q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       mask_ref, kmask_ref, dq_ref, dq_scr, *, h, per_head, **sk):
    t = pl.program_id(1)
    hid = pl.program_id(0) % h if per_head else 0
    i = _tab(qr_ref, hid, t)
    j = _tab(kc_ref, hid, t)

    @pl.when(_tab(fr_ref, hid, t) == 1)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_tab(va_ref, hid, t) == 1)
    def _compute():
        _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                 kmask_ref, i, j, dq_scr, **sk)

    @pl.when(_tab(la_ref, hid, t) == 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * sk["scale"]).astype(dq_ref.dtype)


def _dkv_kernel_compact(qr_ref, kc_ref, fr_ref, la_ref, va_ref,
                        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        mask_ref, kmask_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                        h, per_head, **sk):
    """Column-major traversal: the scalars are the TRANSPOSED tables
    (qrowT..validT) — first/last mark a key column's first/last live query
    tile, and dk/dv accumulate per key tile exactly like the dense kernel."""
    t = pl.program_id(1)
    hid = pl.program_id(0) % h if per_head else 0
    i = _tab(qr_ref, hid, t)
    j = _tab(kc_ref, hid, t)

    @pl.when(_tab(fr_ref, hid, t) == 1)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_tab(va_ref, hid, t) == 1)
    def _compute():
        _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                  kmask_ref, i, j, dk_scr, dv_scr, **sk)

    @pl.when(_tab(la_ref, hid, t) == 1)
    def _finalize():
        _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sk["scale"])


@jax.named_scope("flash_attn_bwd_compact")
def _flash_bwd_compact(q, k, v, do, out, lse, mask, kmask, tabs, h, causal,
                       scale, block_q, block_k):
    bh, n, d = q.shape
    use_mask = mask is not None
    use_kmask = kmask is not None

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (bh, n, _LANES))

    qr, kc, fr, la, va = tabs[:5]
    H, T = qr.shape
    per_head = H > 1

    qkv_specs, mask_spec, kmask_spec = _compact_in_specs(
        d, block_q, block_k, h, H, mask, use_kmask)
    row_spec = _compact_row_spec(block_q, d, h, H)
    lse_spec = _compact_row_spec(block_q, _LANES, h, H)
    margs = _mask_args(mask, use_kmask, kmask)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel_compact, causal=causal, block_q=block_q,
            block_k=block_k, scale=scale, use_mask=use_mask,
            use_kmask=use_kmask, h=h, per_head=per_head,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bh, T),
            in_specs=list(qkv_specs) + [row_spec, lse_spec, lse_spec,
                                        mask_spec, kmask_spec],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), q.dtype),
        name="flash_compact_dq",
        interpret=_interpret(),
    )(qr, kc, fr, la, va, q, k, v, do, lse, delta, *margs)

    # dk/dv: the transposed tables drive a column-major traversal
    qrT, kcT, frT, laT, vaT = tabs[5:]
    H2, T2 = qrT.shape
    assert H2 == H, (H2, H)
    col_spec = _compact_col_spec(block_k, d, h, H)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel_compact, causal=causal, block_q=block_q,
            block_k=block_k, scale=scale, use_mask=use_mask,
            use_kmask=use_kmask, h=h, per_head=per_head,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bh, T2),
            in_specs=list(qkv_specs) + [row_spec, lse_spec, lse_spec,
                                        mask_spec, kmask_spec],
            out_specs=(col_spec, col_spec),
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, n, d), k.dtype),
            jax.ShapeDtypeStruct((bh, n, d), v.dtype),
        ),
        name="flash_compact_dkv",
        interpret=_interpret(),
    )(qrT, kcT, frT, laT, vaT, q, k, v, do, lse, delta, *margs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------

@jax.named_scope("flash_attn_bwd_xla")
def _dense_recompute_grads(q, k, v, mask, kmask, h, causal, scale, lse, do):
    """Backward in XLA ops with exact probabilities from the saved logsumexp.
    Materializes (bh, n, n) transients (fused/streamed by XLA).  At 128x128
    tiles this beat the Pallas backward at seq ~1280 on v5e; at the 256- and
    384-tiles `resolve_block` gives now the Pallas backward is both faster
    and O(n) memory, so this path is the fallback ('xla')."""
    f32 = jnp.float32
    s = jnp.einsum("bid,bjd->bij", q.astype(f32) * scale, k.astype(f32))
    n = q.shape[1]
    if causal:
        i_pos = jnp.arange(n)[:, None]
        j_pos = jnp.arange(n)[None, :]
        s = jnp.where(j_pos <= i_pos, s, _NEG)
    if mask is not None:
        if mask.ndim == 3:  # (h, n, n) per-head: tile over the batch dim
            b = q.shape[0] // mask.shape[0]
            s = jnp.where(jnp.tile(mask, (b, 1, 1)), s, _NEG)
        else:
            s = jnp.where(mask[None], s, _NEG)
    if kmask is not None:
        s = jnp.where(jnp.repeat(kmask > 0, h, axis=0), s, _NEG)  # (bh, 1, n)
    p = jnp.exp(s - lse[:, :, :1])
    do32 = do.astype(f32)
    dv = jnp.einsum("bij,bid->bjd", p, do32)
    dp = jnp.einsum("bid,bjd->bij", do32, v.astype(f32))
    out = jnp.einsum("bij,bjd->bid", p, v.astype(f32))
    delta = jnp.sum(do32 * out, axis=-1, keepdims=True)
    ds = p * (dp - delta)
    dq = jnp.einsum("bij,bjd->bid", ds, k.astype(f32)) * scale
    dk = jnp.einsum("bij,bid->bjd", ds, q.astype(f32)) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, mask, live, kmask, tabs, h, causal, scale, block_q, block_k,
           bwd_impl, vfa):
    """tabs: None (dense grid) or the 10-tuple of compacted index tables in
    sparse_index.TABLE_KEYS order (compacted grid)."""
    if tabs is not None:
        out, _ = _flash_fwd_compact(
            q, k, v, mask, kmask, tabs, h, causal, scale, block_q, block_k, vfa)
    else:
        out, _ = _flash_fwd(q, k, v, mask, live, kmask, h, causal, scale, block_q, block_k)
    return out


def _flash_vjp_fwd(q, k, v, mask, live, kmask, tabs, h, causal, scale, block_q,
                   block_k, bwd_impl, vfa):
    if tabs is not None:
        out, lse = _flash_fwd_compact(
            q, k, v, mask, kmask, tabs, h, causal, scale, block_q, block_k, vfa)
    else:
        out, lse = _flash_fwd(q, k, v, mask, live, kmask, h, causal, scale, block_q, block_k)
    # Residuals carry checkpoint names so a selective remat policy
    # (save_only_these_names('flash_out', 'flash_lse')) can keep them across a
    # jax.checkpoint boundary — the backward then never re-runs the forward
    # kernel (whole-layer remat would).  lse rows are broadcast over the lane
    # dim; save one lane and re-broadcast in the backward.
    out = checkpoint_name(out, "flash_out")
    lse1 = checkpoint_name(lse[:, :, :1], "flash_lse")
    return out, (q, k, v, mask, live, kmask, tabs, out, lse1)


def _flash_vjp_bwd(h, causal, scale, block_q, block_k, bwd_impl, vfa, res, do):
    q, k, v, mask, live, kmask, tabs, out, lse1 = res
    if bwd_impl == "pallas":
        lse = jnp.broadcast_to(lse1, (*lse1.shape[:2], _LANES))
        if tabs is not None:
            dq, dk, dv = _flash_bwd_compact(
                q, k, v, do, out, lse, mask, kmask, tabs, h, causal, scale,
                block_q, block_k)
        else:
            dq, dk, dv = _flash_bwd(q, k, v, do, out, lse, mask, live, kmask, h, causal, scale, block_q, block_k)
    else:
        dq, dk, dv = _dense_recompute_grads(q, k, v, mask, kmask, h, causal, scale, lse1, do)
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    # 'pallas' (two-pass kernels, O(n) memory; what every cell runs, and the
    # faster the larger the resolved tile) | 'xla' (dense recompute from the
    # saved logsumexp: (n, n) scores in HBM; no tile, kept as a reference)
    bwd_impl: str = "pallas",
    live: Optional[jnp.ndarray] = None,
    key_mask: Optional[jnp.ndarray] = None,
    grid: str = "auto",
    tables=None,
    vfa: bool = False,
    mesh=None,
) -> jnp.ndarray:
    """(b, h, n, d) attention.  `mask`: optional static (n, n) — or
    per-head (h, n, n) — bool pattern (True = may attend), combined with
    causality inside the kernel; a
    tile-liveness table is derived from it at trace time so fully-masked
    tiles cost nothing.  Pass `live` ((n/block_q, n/block_k) int32) explicitly
    when the mask is traced (e.g. selected per-layer inside lax.scan).
    `key_mask`: optional (b, n) per-batch key-padding rows (True/nonzero =
    attend) — traced, applied inside the kernels, so padded text (CLIP
    encoding, masked prefill) keeps the O(n)-memory path instead of falling
    back to dense XLA attention.  q is expected UNSCALED
    (scale defaults to d^-1/2), unlike ops.attention.attend.

    `grid`: 'dense' schedules the full (bh, nq, nk) tile grid and
    `pl.when`-skips dead tiles; 'compact' runs the compacted (bh, T) grid over
    live tiles only, driven by scalar-prefetched index tables (bit-exact vs
    'dense'); 'auto' picks 'compact' wherever the resolved grid has a dead
    step, killed by causality or by a static mask
    (`sparse_index.grid_has_dead_step`), and 'dense' where every step is live
    or the mask is traced and no `tables` came with it.  `tables`: explicit
    sparse_index.build_compacted_tables output (dict, or tuple in TABLE_KEYS
    order) — REQUIRED for the compacted grid when the mask is traced
    (scan-selected); must be built at resolve_block() granularity.  `vfa`:
    on the compacted grid, precompute global row maxima in a first max-only
    pass and skip the per-tile accumulator rescale (allclose, not
    bit-identical, to the online-softmax forward); ignored on the dense
    grid.  `mesh`: the multi-device mesh the enclosing jit is partitioned
    over, if any — the kernels then run under `jax.shard_map`, batch split
    over the data axes and heads over `tp` (see `_shard_over_mesh`)."""
    b, h, n, d = q.shape
    if scale is None:
        scale = d ** -0.5
    # which operands the kernels' products take is decided here, by the input
    # alone, while the program is traced: one count a call
    bits = 16 if _is_16bit(q.dtype) else 32
    obs_metrics.counter(f"kernels/flash_calls_{bits}bit_operands").inc()
    block_q = resolve_block(n, block_q)
    block_k = resolve_block(n, block_k)
    # ... and which tile the sequence's divisors gave it, counted the same way
    obs_metrics.counter(f"kernels/flash_tile_{block_q}x{block_k}").inc()
    if grid not in ("auto", "dense", "compact"):
        raise ValueError(f"grid must be auto|dense|compact, got {grid!r}")
    if live is not None:
        # a caller-supplied liveness table must match the RESOLVED grid, not
        # the requested blocks (silent mismatch = out-of-bounds tile skipping)
        tiles = (n // block_q, n // block_k)
        want = (mask.shape[0], *tiles) if (mask is not None and mask.ndim == 3) else tiles
        assert live.shape == want, (
            f"live table {live.shape} != grid {want}; "
            f"build it at resolve_block() granularity"
        )

    if mask is not None and live is None:
        try:  # static masks (the normal case) yield a tile-liveness table
            mask_np = np.asarray(mask)  # host-sync-ok: traced masks raise into the except
            if mask_np.ndim == 3:  # per-head (h, n, n)
                live = jnp.asarray(
                    mask_np.reshape(mask_np.shape[0], n // block_q, block_q,
                                    n // block_k, block_k)
                    .any(axis=(2, 4))
                    .astype(np.int32)
                )
            else:
                live = jnp.asarray(
                    mask_np.reshape(n // block_q, block_q, n // block_k, block_k)
                    .any(axis=(1, 3))
                    .astype(np.int32)
                )
        except Exception:
            live = None  # traced mask without explicit live: no tile skipping

    tabs = _resolve_tables(grid, tables, mask, h, n, causal, block_q, block_k)
    # ... and which grid it takes, counted the same way
    obs_metrics.counter(f"kernels/flash_grid_{'dense' if tabs is None else 'compact'}").inc()
    km = None if key_mask is None else key_mask.astype(jnp.int32)[:, None, :]

    def run(q, k, v, mask, live, km, tabs):  # local (b, h) under shard_map
        b, h = q.shape[:2]
        out = _flash(q.reshape(b * h, n, d), k.reshape(b * h, n, d),
                     v.reshape(b * h, n, d), mask, live, km, tabs, h, causal,
                     scale, block_q, block_k, bwd_impl, vfa)
        return out.reshape(b, h, n, d)

    if mesh is not None and mesh.size > 1:
        run = _shard_over_mesh(run, mesh, b, h, mask, live, km, tabs)
    return run(q, k, v, mask, live, km, tabs)


def _shard_over_mesh(run, mesh, b, h, mask, live, km, tabs):
    """`run` under `jax.shard_map` over `mesh`.  The chip's compiler refuses
    a Mosaic kernel inside a GSPMD-partitioned program ("cannot be
    automatically partitioned"), so on a multi-device mesh the kernel call
    is made manual: batch over the data axes, heads over `tp` (attention is
    independent per batch row and head, so no collective is needed), each
    left whole where it does not divide.  Per-head masks/tables follow the
    heads; shared ones are replicated.  The same wrap runs in interpret mode
    on a CPU mesh, so tests cover the path the chip takes."""
    from jax.sharding import PartitionSpec as P

    from dalle_pytorch_tpu.parallel.mesh import AXIS_TP, BATCH_AXES

    data = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if b % int(np.prod([mesh.shape[a] for a in data])):
        data = ()
    tp = AXIS_TP if mesh.shape.get(AXIS_TP, 1) > 1 and h % mesh.shape[AXIS_TP] == 0 else None

    def by_head(x):  # (h, ...) per-head operands follow the heads
        return P(tp) if x.shape[0] == h and x.ndim == 3 else P()

    qkv = P(data or None, tp)
    specs = (
        qkv, qkv, qkv,
        None if mask is None else by_head(mask),
        None if live is None else by_head(live),
        None if km is None else P(data or None),
        None if tabs is None else tuple(
            P(tp) if t.shape[0] == h and h > 1 else P() for t in tabs),
    )
    return jax.shard_map(run, mesh=mesh, in_specs=specs, out_specs=qkv,
                         check_vma=False)


def _resolve_tables(grid, tables, mask, h, n, causal, block_q, block_k):
    """The compacted-grid index tables `_flash` will run with, or None for
    the dense grid.  Validates explicit tables against the resolved grid;
    builds tables from a static mask (or none) at trace time; under 'auto',
    compacts wherever the grid has a dead step, killed by causality or by
    the pattern (`sparse_index.grid_has_dead_step`), and keeps the dense grid
    where every step is live (a 1 x 1 grid, a non-causal call without a
    mask) or the mask is traced and no tables came with it."""
    from dalle_pytorch_tpu.kernels import sparse_index as si

    nq, nk = n // block_q, n // block_k
    if tables is not None:
        if grid == "dense":
            raise ValueError("grid='dense' with explicit compacted tables")
        if isinstance(tables, dict):
            tables = tuple(tables[key] for key in si.TABLE_KEYS)
        tabs = tuple(jnp.asarray(t, jnp.int32) for t in tables)
        H = tabs[0].shape[0]
        if H not in (1, h):
            raise ValueError(f"tables head dim {H} incompatible with h={h}")
        if mask is not None and getattr(mask, "ndim", 2) == 3 and H != h:
            # shared tables would schedule per-head-DEAD tiles, whose
            # uninitialized-max exp(0)=1 rows break bit-exactness
            raise ValueError("per-head mask requires per-head compacted tables")
        for t in tabs[:5]:
            assert t.shape == tabs[0].shape, (t.shape, tabs[0].shape)
        for t in tabs[5:]:
            assert t.shape == tabs[5].shape, (t.shape, tabs[5].shape)
        return tabs
    if grid == "dense":
        return None

    if mask is None:
        bl = np.ones((nq, nk), bool)
    else:
        try:
            mask_np = np.asarray(mask) != 0  # host-sync-ok: traced masks raise into the except
        except Exception:
            if grid == "compact":
                raise ValueError(
                    "grid='compact' with a traced mask needs explicit tables "
                    "(sparse_index.build_compacted_tables at resolve_block "
                    "granularity)"
                )
            return None  # auto + traced mask: dense grid
        from dalle_pytorch_tpu.ops.masks import block_live_np

        bl = block_live_np(mask_np, block_q, block_k)
    if grid == "auto" and not si.grid_has_dead_step(bl, block_q, block_k, causal=causal):
        return None  # every step of the grid is live: nothing to compact away
    tables = si.build_compacted_tables(bl, block_q, block_k, causal=causal)
    return tuple(jnp.asarray(tables[key]) for key in si.TABLE_KEYS)
