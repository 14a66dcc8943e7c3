"""The one-token gated delta rule as a Pallas kernel: every slot's state read
ONCE and written ONCE.

`ops/delta_rule.gated_delta_step` is the operation's meaning and what runs
everywhere else.  XLA makes it two reads and a write of the state (a reduce
pass for S'^T k and S'^T q, then the write pass: the write needs the whole of
S'^T k first, and no XLA fusion holds a (dk, dv) tile across the two).  Here a
grid step holds `heads_per_step` heads' states of one slot in VMEM (96 x 192
float32 is 72 KB a head, no tiling needed), reads both products off the tile,
writes the updated tile back over the one it read (the state is aliased in to
out) and moves on: 2 x the state's bytes a layer, the least there is.

Everything is elementwise float32 on the vector unit, as the definition says
(a rank-1 update has nothing for the matrix unit, whose float32 products are
rounded to bfloat16); interpreted on the CPU by `flash_attention._interpret`'s
rule.  The per-head columns k and q have to lie along the
state's ROWS (sublanes); they arrive as one (dk, 2 heads) tile a slot, heads
along the lanes, and a head's column is taken out of it by a lane mask and a
lane reduction.  decay and beta are scalars a head: SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.kernels import flash_attention

F32 = jnp.float32
_VMEM_BUDGET = 8 * 2 ** 20  # in + out tiles, double-buffered, of the scoped 16 MiB


def _padded_head_bytes(dk: int, dv: int) -> int:
    return -(-dk // 8) * 8 * -(-dv // 128) * 128 * 4


def heads_per_step(heads: int, dk: int, dv: int) -> int:
    """The most heads a grid step holds: a divisor of `heads` whose tiles (in
    and out, double-buffered) fit the budget."""
    fits = [hb for hb in range(1, heads + 1)
            if heads % hb == 0 and 4 * hb * _padded_head_bytes(dk, dv) <= _VMEM_BUDGET]
    return max(fits) if fits else 0


def supports(heads: int, dk: int, dv: int) -> bool:
    """A state tile's rows fill sublanes (dk a multiple of 8; any dv: the block
    spans the array's last two dimensions) and one head's tiles fit."""
    return dk % 8 == 0 and heads_per_step(heads, dk, dv) > 0


def _kernel(decay_ref, beta_ref, kq_ref, v_ref, s_ref, o_ref, s_out_ref, *, heads: int, hb: int):
    slot, group = pl.program_id(0), pl.program_id(1)
    kq = kq_ref[0]  # (dk, lanes): lane h holds head h's k, lane heads + h its q
    lane = jax.lax.broadcasted_iota(jnp.int32, kq.shape, 1)
    for j in range(hb):
        h = group * hb + j
        k_col = jnp.sum(jnp.where(lane == h, kq, 0.0), axis=1, keepdims=True)          # (dk, 1)
        q_col = jnp.sum(jnp.where(lane == heads + h, kq, 0.0), axis=1, keepdims=True)
        decayed = s_ref[0, j] * decay_ref[slot, h]                                      # (dk, dv)
        read_k = jnp.sum(decayed * k_col, axis=0, keepdims=True)                        # (1, dv)
        read_q = jnp.sum(decayed * q_col, axis=0, keepdims=True)
        u = beta_ref[slot, h] * (v_ref[0, pl.ds(h, 1), :] - read_k)
        # o = S^T q = S'^T q + (k . q) u: no pass over the new state
        o_ref[0, pl.ds(h, 1), :] = read_q + jnp.sum(k_col * q_col, axis=0, keepdims=True) * u
        s_out_ref[0, j] = decayed + k_col * u


def gated_delta_step_kernel(q, k, v, log_decay, beta, state):
    """`ops/delta_rule.gated_delta_step`'s arguments and results: q, k (s, h,
    dk); v (s, h, dv); log_decay, beta (s, h); state (s, h, dk, dv) float32,
    which the new state overwrites where the caller donates it."""
    s, h, dk = k.shape
    dv = v.shape[-1]
    hb = heads_per_step(h, dk, dv)
    lanes = -(-2 * h // 128) * 128
    kq = jnp.concatenate([jnp.moveaxis(k, 1, 2), jnp.moveaxis(q, 1, 2)], axis=-1).astype(F32)
    kq = jnp.pad(kq, ((0, 0), (0, 0), (0, lanes - 2 * h)))  # (s, dk, lanes)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    whole_heads = pl.BlockSpec((1, h, dv), lambda i, g: (i, 0, 0))  # a step's heads: rows h of it
    tiles = pl.BlockSpec((1, hb, dk, dv), lambda i, g: (i, g, 0, 0))
    out, new_state = pl.pallas_call(
        functools.partial(_kernel, heads=h, hb=hb),
        grid=(s, h // hb),
        in_specs=[smem, smem, pl.BlockSpec((1, dk, lanes), lambda i, g: (i, 0, 0)), whole_heads, tiles],
        out_specs=[whole_heads, tiles],
        out_shape=[jax.ShapeDtypeStruct((s, h, dv), F32), jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={4: 1},
        interpret=flash_attention._interpret(),
        name="gdn_step",
    )(jnp.exp(log_decay.astype(F32)), beta.astype(F32), kq, v.astype(F32), state)
    return out, new_state
