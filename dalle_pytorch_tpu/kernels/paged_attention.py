"""Pallas TPU paged-attention decode kernel: one query token per slot attends
over the slot's K/V blocks WHERE THEY LIE in the serving pool.

The pool is per layer `(num_blocks, heads, block_size, dim_head)`; a slot's
sequence is the blocks its row of `block_tables` names, in order.  The grid
walks (slot, logical block); the K/V BlockSpec index maps look the physical
block up in the scalar-prefetched table, so no per-slot dense view is ever
built and no XLA operation touches the pool.  A slot fetches only its LIVE
blocks: those in which its mask row permits a key (none past its offset, and
under `axial_row` or `conv_like` 3-5 of up to 18) plus the block its new
column goes into.  The live list is compacted outside (a sort of S x
num_blocks flags) and prefetched beside the table; entries past its end
repeat the last live block, so the tile stays resident, and `pl.when` turns
the compute off (the compacted flash kernels' rule for padding entries).

The new column is attended from the operand (substituted into the current
block's tile) and the current block is written back through
`input_output_aliases` on the pool operands, so a donated pool is updated
in place and there is no read-after-write inside the kernel.  Inactive
slots (all-zero table rows) write the trash block 0, as the XLA path does.

Numerics: the same mathematics as `ops.attention.attend` in another order of
summation — blockwise online softmax in float32.  The two contractions have
ONE query row per head, so they run on the VPU (multiply + reduce) in exact
float32 rather than as M=1 matmuls; masked keys are filled with `finfo.min`
before the softmax and their probability is forced to 0, so stale bytes in
a block count for nothing.

On CPU (tests) the kernel runs in interpret mode, by
`flash_attention._interpret`'s rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.kernels import flash_attention

_SUBLANES = 8  # float32 rows of one vreg; a packed dtype holds 32 // bits times as many


def _current_block(offset, block_size: int, n_blocks: int):
    """The logical block a slot's new column goes into."""
    return jnp.minimum(offset // block_size, n_blocks - 1)


def supports(dim_head: int, block_size: int, pool_dtype) -> bool:
    """Shapes one K/V block tile can hold without padding: `dim_head` fills
    whole lanes and `block_size` whole sublane tiles of the pool's dtype."""
    dt = jnp.dtype(pool_dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return False
    rows = _SUBLANES * max(1, 4 // dt.itemsize)
    return dim_head % flash_attention._LANES == 0 and block_size % rows == 0


def _kernel(bt_ref, off_ref, live_ref, nlive_ref, q_ref, nk_ref, nv_ref,
            mask_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, m_scr, l_scr,
            acc_scr, *, block_size, n_blocks):
    s, j = pl.program_id(0), pl.program_id(1)
    off = off_ref[s]
    cur = _current_block(off, block_size, n_blocks)
    n_live = nlive_ref[s]
    blk = live_ref[s, jnp.minimum(j, n_live - 1)]  # the logical block of this step
    neg = jnp.finfo(jnp.float32).min

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, neg)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j < n_live)
    def _compute():
        # (1, bs, 1): the sequence position of each row of this block
        pos = blk * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size, 1), 1)
        is_new = pos == off
        k = jnp.where(is_new, nk_ref[0], k_ref[0])  # (h, bs, dh), pool dtype
        v = jnp.where(is_new, nv_ref[0], v_ref[0])

        @pl.when(blk == cur)
        def _write():
            ko_ref[0] = k
            vo_ref[0] = v

        # the slot's mask row lies along lanes; scores lie along sublanes
        # (keys are rows of the tile), so turn this block's (1, bs) slice
        # into a (bs, 1) column through the identity
        mrow = mask_ref[0, pl.ds(blk, 1), :]  # (1, bs) float32 0/1
        eye = (jax.lax.broadcasted_iota(jnp.int32, (block_size, block_size), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (block_size, block_size), 1))
        mcol = jnp.sum(jnp.where(eye, mrow, 0.0), axis=1, keepdims=True)
        allowed = (mcol > 0.5)[None]  # (1, bs, 1)

        q = q_ref[0].astype(jnp.float32)  # (h, 1, dh), already scaled
        sc = jnp.sum(q * k.astype(jnp.float32), axis=-1, keepdims=True)  # (h, bs, 1)
        sc = jnp.where(allowed, sc, neg)
        m_prev = m_scr[:]  # (h, 1, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(allowed, jnp.exp(sc - m_cur), 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.sum(
            p * v.astype(jnp.float32), axis=1, keepdims=True)  # (h, 1, dh)
        m_scr[:] = m_cur

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


def paged_decode_attention(q, new_k, new_v, k_pool, v_pool, block_tables,
                           offsets, mask_rows):
    """One decode step's attention for S slots over a paged pool.

    q: (S, h, dh) queries, already scaled.  new_k / new_v: (S, h, dh), the
    column of the token each slot is at.  k_pool / v_pool: (num_blocks, h,
    block_size, dh).  block_tables: (S, max_blocks) int32 physical block
    ids; offsets: (S,) int32, the position each slot's token occupies.
    mask_rows: (S, seq_len) bool, the keys each slot's query may attend
    (causality and the layer's pattern folded in; the slot's own position
    included where the pattern permits it).

    Returns (out (S, h, dh) in q's dtype, new k_pool, new v_pool): the pools
    with each slot's column written at block `table[s, off // bs]`, row
    `off % bs`, every other byte untouched (aliased to the operands)."""
    S, h, dh = q.shape
    _, _, bs, _ = k_pool.shape
    seq_len = mask_rows.shape[1]
    n_blocks = -(-seq_len // bs)
    assert block_tables.shape[1] >= n_blocks, (block_tables.shape, n_blocks)

    mask = mask_rows.astype(jnp.float32)
    if n_blocks * bs != seq_len:
        mask = jnp.pad(mask, ((0, 0), (0, n_blocks * bs - seq_len)))
    mask = mask.reshape(S, n_blocks, bs)
    block_tables = block_tables.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)

    # each slot's live logical blocks, ascending, then the dead ones
    cur = _current_block(offsets, bs, n_blocks)
    is_live = (mask > 0).any(axis=-1) | (jnp.arange(n_blocks)[None, :] == cur[:, None])
    live = jnp.argsort(~is_live, axis=1, stable=True).astype(jnp.int32)
    n_live = is_live.sum(axis=1).astype(jnp.int32)

    row = pl.BlockSpec((1, h, 1, dh), lambda s, j, bt, off, lv, nl: (s, 0, 0, 0))
    fetch = pl.BlockSpec(
        (1, h, bs, dh),
        lambda s, j, bt, off, lv, nl: (bt[s, lv[s, jnp.minimum(j, nl[s] - 1)]], 0, 0, 0))
    write = pl.BlockSpec(
        (1, h, bs, dh), lambda s, j, bt, off, lv, nl: (
            bt[s, _current_block(off[s], bs, n_blocks)], 0, 0, 0))
    mask_spec = pl.BlockSpec((1, n_blocks, bs), lambda s, j, bt, off, lv, nl: (s, 0, 0))

    out, k_new, v_new = pl.pallas_call(
        functools.partial(_kernel, block_size=bs, n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S, n_blocks),
            in_specs=[row, row, row, mask_spec, fetch, fetch],
            out_specs=(row, write, write),
            scratch_shapes=[
                pltpu.VMEM((h, 1, 1), jnp.float32),
                pltpu.VMEM((h, 1, 1), jnp.float32),
                pltpu.VMEM((h, 1, dh), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((S, h, 1, dh), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ),
        # operands count the four prefetched tables: k_pool is 8, v_pool 9
        input_output_aliases={8: 1, 9: 2},
        # NO cost_estimate, on purpose.  Told how many bytes the call reads,
        # XLA's memory-space assignment takes it for memory-bound and stages
        # whole pool arrays through VMEM around it (76 MB in, 76 MB out, 7 of
        # 16 arrays at DALL-E width); pinning the operands to HBM instead
        # (out_shape=pltpu.HBM(...)) stops that but aborts the compiler where
        # the pool is not donated and the operand is XLA's own copy.
        # tests/test_chip_compile.py holds both programs to this.
        name="paged_decode_attn",
        interpret=flash_attention._interpret(),
    )(
        block_tables, offsets, live, n_live,
        q[:, :, None, :], new_k.astype(k_pool.dtype)[:, :, None, :],
        new_v.astype(v_pool.dtype)[:, :, None, :], mask, k_pool, v_pool,
    )
    return out[:, :, 0, :], k_new, v_new
