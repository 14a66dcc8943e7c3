"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

The reference has no sequence parallelism (SURVEY.md §2.3) — it attacks long
sequences with sparse patterns instead.  For a first-class long-context story
on TPU we shard the sequence over devices and rotate K/V blocks around the
ring with ppermute while accumulating attention with an online (flash-style)
softmax: memory per device is O(n/P), communication overlaps with the block
matmuls, and the collectives ride ICI neighbour links.

The math is the standard blockwise-softmax recurrence (m, l, acc carried per
query), computed in f32 regardless of input dtype.

Training memory is ALSO O(n/P): a custom VJP re-rotates blocks through the
ring in the backward pass (flash-style recompute from the saved per-query
logsumexp), so no step's (n_loc x n_loc) score block is ever saved.  The
backward ring rotates a (q, do, lse, delta, dq) packet while each device's
K/V stay put — dk/dv accumulate locally, and each packet arrives back home
after a full cycle carrying its finished dq."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from dalle_pytorch_tpu.parallel.mesh import AXIS_SP

P = PartitionSpec
_NEG = -1e30


def _causal_block_mask(s, my, src, n):
    """Mask scores for query block owned by `my` against key block owned by
    `src` (global positions owner*n + local index)."""
    i_loc = jnp.arange(n)
    q_pos = my * n + i_loc[:, None]
    k_pos = src * n + i_loc[None, :]
    return jnp.where(k_pos <= q_pos, s, _NEG)


def _pattern_block(mask_rows, col_owner, nk):
    """(n_rows_local, nk) sub-block of a row-sharded global pattern: the
    columns owned by `col_owner` (traced)."""
    return jax.lax.dynamic_slice(
        mask_rows, (0, col_owner * nk), (mask_rows.shape[0], nk)
    )


def _ring_fwd_pass(q, k, v, mask_rows, axis_name: str, causal: bool, scale: float):
    """Online-softmax ring.  Returns (out, lse) with lse: (b, h, n, 1).
    mask_rows: optional (n_loc, n_glob) — this device's query rows of a
    global static pattern (True = may attend)."""
    n_dev = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, n, d = q.shape

    q32 = q.astype(jnp.float32) * scale
    m = jnp.full((b, h, n, 1), _NEG, jnp.float32)
    l = jnp.zeros((b, h, n, 1), jnp.float32)
    acc = jnp.zeros((b, h, n, d), jnp.float32)

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    k_cur, v_cur = k, v
    for step in range(n_dev):
        src = jnp.mod(my - step, n_dev)  # device whose block we currently hold
        s = jnp.einsum("bhid,bhjd->bhij", q32, k_cur.astype(jnp.float32))
        if causal:
            s = _causal_block_mask(s, my, src, n)
        if mask_rows is not None:
            s = jnp.where(_pattern_block(mask_rows, src, n), s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_exp = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p_exp, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhij,bhjd->bhid", p_exp, v_cur.astype(jnp.float32))
        m = m_new
        if step < n_dev - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    l = jnp.maximum(l, 1e-30)
    out = acc / l
    lse = m + jnp.log(l)
    return out.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ring_attention_local(q, k, v, mask_rows, mask_cols,
                          axis_name: str, causal: bool, scale: float):
    """q, k, v: (b, h, n_loc, d) — the local sequence shard.  Runs the full
    ring inside shard_map.  mask_rows/(cols): the global pattern sharded by
    query rows (forward) and by key columns (backward — the packet carries
    other devices' QUERIES past our keys, so we need our key-columns against
    every query row)."""
    out, _ = _ring_fwd_pass(q, k, v, mask_rows, axis_name, causal, scale)
    return out


def _ring_vjp_fwd(q, k, v, mask_rows, mask_cols, axis_name, causal, scale):
    out, lse = _ring_fwd_pass(q, k, v, mask_rows, axis_name, causal, scale)
    # mask_rows' SHAPE rides the residuals so its float0 cotangent can be
    # built correctly ((n_loc, n_glob) != mask_cols' (n_glob, n_loc))
    rows_shape = None if mask_rows is None else mask_rows.shape
    return out, (q, k, v, mask_cols, rows_shape, out, lse)


def _ring_vjp_bwd(axis_name, causal, scale, res, do):
    """Ring-recompute backward: probabilities are rebuilt per block from the
    saved logsumexp (never materialized across steps), K/V never move — the
    (q, do, lse, delta, dq) packet rotates instead and is home after n_dev
    hops with its dq complete."""
    q, k, v, mask_cols, rows_shape, out, lse = res
    n_dev = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    n = q.shape[2]

    f32 = jnp.float32
    k32 = k.astype(f32)
    v32 = v.astype(f32)
    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1, keepdims=True)

    dk = jnp.zeros_like(k32)
    dv = jnp.zeros_like(v32)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    # the rotating packet; q/do ride the ring in their input dtype (like the
    # forward's k/v — half the ICI bytes under bf16) and are cast per step;
    # lse/delta/dq genuinely need f32.  q stays raw (scale enters via ds,
    # matching s = (q*scale)·k so dq = scale * ds·k and dk = scale * ds^T·q)
    packet = (q, do, lse, delta, jnp.zeros(q.shape, f32))
    for step in range(n_dev):
        q_raw, do_raw, lse_cur, delta_cur, dq_cur = packet
        q_cur = q_raw.astype(f32)
        do_cur = do_raw.astype(f32)
        owner = jnp.mod(my - step, n_dev)  # whose queries we currently hold
        s = jnp.einsum("bhid,bhjd->bhij", q_cur * scale, k32)
        if causal:
            s = _causal_block_mask(s, owner, my, n)
        if mask_cols is not None:
            # mask_cols: (n_glob, n_loc) — our key columns; take the rows of
            # the queries we currently hold (owner's block)
            sub = jax.lax.dynamic_slice(
                mask_cols, (owner * n, 0), (n, mask_cols.shape[1])
            )
            s = jnp.where(sub, s, _NEG)
        p = jnp.exp(s - lse_cur)  # masked entries: exp(_NEG - lse) == 0
        dp = jnp.einsum("bhid,bhjd->bhij", do_cur, v32)
        ds = p * (dp - delta_cur)
        dq_cur = dq_cur + jnp.einsum("bhij,bhjd->bhid", ds, k32) * scale
        dk = dk + jnp.einsum("bhij,bhid->bhjd", ds, q_cur) * scale
        dv = dv + jnp.einsum("bhij,bhid->bhjd", p, do_cur)
        # rotate after EVERY step (incl. the last) so each packet ends at its
        # owner with dq finished
        packet = jax.lax.ppermute(
            (q_raw, do_raw, lse_cur, delta_cur, dq_cur), axis_name, perm
        )

    dq = packet[4]
    # cotangents for the two (boolean) mask views are float0 zeros, each in
    # its OWN local shape (row-sharded vs column-sharded views differ)
    drows = None if rows_shape is None else jnp.zeros(rows_shape, jax.dtypes.float0)
    dcols = None if mask_cols is None else jnp.zeros(
        mask_cols.shape, jax.dtypes.float0
    )
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            drows, dcols)


_ring_attention_local.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_comm_bytes(batch: int, heads: int, seq_shard: int, dim_head: int,
                    n_dev: int, itemsize: int = 4,
                    include_backward: bool = True) -> float:
    """Per-device wire bytes for ONE ring_attention call over an `n_dev` ring.

    Forward: K and V blocks ((b, h, n_loc, d) each, in the input dtype) hop
    n_dev - 1 times.  Backward: the (q, do, lse, delta, dq) packet rotates a
    full cycle (n_dev hops — see _ring_vjp_bwd); q/do ride in the input
    dtype, lse/delta/dq in f32.  This is the accounting the comms ledger
    (observability/comms.py) prices sp traffic with — keep it in lockstep
    with the schedules above."""
    kv_block = float(batch * heads * seq_shard * dim_head * itemsize)
    fwd = (n_dev - 1) * 2.0 * kv_block
    if not include_backward:
        return fwd
    f32_block = float(batch * heads * seq_shard * dim_head * 4)
    scalar_block = float(batch * heads * seq_shard * 4)  # (..., 1) f32
    packet = 2.0 * kv_block + f32_block + 2.0 * scalar_block
    return fwd + n_dev * packet


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = True,
    axis_name: str = AXIS_SP,
    scale: float | None = None,
    mask: jnp.ndarray | None = None,
):
    """Global (b, h, n, d) attention with n sharded over `axis_name`.

    Equivalent to dense softmax attention (ops/attention.py) with a causal
    mask; n must divide evenly by the axis size.  `mask`: optional static
    (n, n) bool pattern (True = may attend) — axial/conv/block-sparse layers
    keep the O(n/P)-memory ring under sequence parallelism instead of
    falling back to dense GSPMD attention.  Each device holds only its
    row-block (fwd) and column-block (bwd) of the pattern: O(n^2/P) bool."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(None, None, axis_name, None)
    if mask is None:
        fn = jax.shard_map(
            partial(_ring_attention_local, mask_rows=None, mask_cols=None,
                    axis_name=axis_name, causal=causal, scale=scale),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
        return fn(q, k, v)
    mask = jnp.asarray(mask, bool)
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=axis_name, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(axis_name, None), P(None, axis_name)),
        out_specs=spec,
    )
    return fn(q, k, v, mask, mask)
