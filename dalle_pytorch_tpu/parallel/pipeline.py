"""Pipeline parallelism over a `pp` mesh axis with a memory-lean explicit
backward schedule.

The reference has no pipeline engine (DeepSpeed's existed but DALLE-pytorch
never wired it up); for the depth-64 flagship geometry pipeline stages are the
natural TPU scale-out axis once tensor parallelism saturates a slice.  Design:

- The transformer's scan-layers execution already stacks per-layer params
  along a leading depth axis; pipelining shards THAT axis over `pp` — each
  stage holds depth/P contiguous layers and runs them with the same
  (rematted) per-layer body the single-chip path uses.
- Forward schedule: M microbatches over P stages, T = M+P-1 ticks inside one
  `lax.scan` (T = v*M+P-1 chunk-sized ticks under interleave=v); activations
  hop stages with a single `ppermute` per tick.  Bubble fraction (P-1)/T of
  the tick count — and ticks are v x shorter when interleaved.
- Backward schedule: NOT autodiff through the tick scan.  `pipeline_scan` is
  a `jax.custom_vjp`: the forward saves ONLY each microbatch's stage-input
  boundary activation (M boundary tensors per stage — v*M under
  interleave=v, since every ring loop has its own boundary — megabytes at
  flagship scale either way), and the backward runs the explicit reverse
  pipeline: the last
  stage starts first, cotangents hop stages with the inverse ppermute, and
  each stage recomputes its forward from the saved boundary before applying
  the vjp (the 1F1B backward phase, expressed as its own tick scan).  This
  replaces AD-through-scan residuals — every tick's carried activations plus
  every tick's rematted layer boundaries, O((M+P)·(depth/P)) tensors — with
  the information-theoretic floor for an outside-the-pipeline loss: O(M)
  boundary tensors + one stage of transient recompute.
- Why not loss-inside 1F1B interleaving (activation residency ∝ P·mb): with
  the loss outside the pipeline (the `jax.value_and_grad` contract the rest
  of the framework — and the grads-bit-match regression harness — relies
  on), the first cotangent exists only after ALL microbatches have finished
  the forward, so fwd/bwd of different microbatches cannot overlap in time.
  What CAN be bounded is what this does bound: saved state shrinks to the M
  stage-input boundaries (≈ M·mb·n·dim, e.g. 8×1×1280×1152 bf16 ≈ 23 MB at
  the flagship geometry), which is noise next to weights; this is the same
  tradeoff praxis'/GSPMD's TPU pipelines make.
- Composition: `jax.shard_map(..., axis_names={'pp'})` is manual ONLY over
  `pp`; dp/fsdp/tp/sp stay automatic, so GSPMD still emits gradient
  all-reduces, ZeRO-3 gathers, and Megatron TP collectives inside each stage
  — in the forward AND in the hand-written backward (it is ordinary traced
  code).

Bubble ticks are skipped with `lax.cond` in both directions (a stage holding
no valid microbatch does no layer compute) — EXCEPT when the stage body
itself contains global collectives (sequence sharding's halo permutes),
where skipping would leave live stages waiting in a collective the bubble
stages never enter; `skip_bubble=False` then runs-and-discards bubble ticks
(see the pipeline_scan docstring).  Param/optimizer memory scaling over pp
comes from the sharding rules (parallel/sharding.py folds `pp` into the
data-sharding axes), not from this schedule.

Known costs (documented, not hidden): inputs/outputs are materialized on all
stages (the batch is small relative to weights and shards over dp/fsdp), and
everything outside the layer stack (embeddings, head, loss) computes
redundantly on every stage — a few percent of depth-64 FLOPs, and free in
wall-clock terms because SPMD stages would otherwise idle in the bubble.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from dalle_pytorch_tpu.parallel.mesh import AXIS_PP

P = PartitionSpec


def default_num_micro(batch: int, stages: int) -> int:
    """The divisor of `batch` that is >= stages (keeps every stage busy) and
    closest to 2*stages (the bubble/activation-memory sweet spot); if no
    divisor reaches `stages`, the largest divisor — never a silent M=1 when
    a better split exists."""
    divs = [m for m in range(1, batch + 1) if batch % m == 0]
    cands = [m for m in divs if m >= stages]
    if cands:
        return min(cands, key=lambda m: (abs(m - 2 * stages), m))
    return max(divs)


def _is_float(leaf) -> bool:
    return jnp.issubdtype(jnp.result_type(leaf), jnp.inexact)


def pipeline_comm_bytes(batch: int, seq: int, dim: int, stages: int,
                        num_micro: Optional[int] = None, itemsize: int = 4,
                        interleave: int = 1,
                        include_backward: bool = True) -> float:
    """Per-device wire bytes for one pipeline_scan call: every tick moves one
    microbatch-chunk activation ((batch/M, seq, dim)) through the stage-hop
    ppermute, in the forward (T = v*M + P - 1 ticks) and again in the
    explicit-backward tick scan.  The comms ledger (observability/comms.py)
    prices pp traffic with this — keep it in lockstep with the schedule."""
    if num_micro is None:
        num_micro = default_num_micro(batch, stages)
    ticks = interleave * num_micro + stages - 1
    hop = float(batch // num_micro) * seq * dim * itemsize
    return ticks * hop * (2.0 if include_backward else 1.0)


def pipeline_scan(
    body: Callable,  # (h, xs_i) -> (h, ignored) — one layer, as lax.scan body
    x: jnp.ndarray,  # (batch, ...) activations
    xs: Any,  # pytree, leaves stacked over a leading depth axis
    mesh: Mesh,
    axis: str = AXIS_PP,
    num_micro: Optional[int] = None,
    fold_micro: Optional[Callable] = None,  # (xs_local, micro_id) -> xs_local
    skip_bubble: bool = True,
    interleave: int = 1,
) -> jnp.ndarray:
    """Drop-in replacement for `lax.scan(body, x, xs)[0]` over stacked layers,
    with the depth axis sharded over `axis` and the batch microbatched.

    `fold_micro` lets the caller derive per-microbatch values from the
    per-layer xs before the stage applies them — e.g. folding the microbatch
    index into dropout keys so microbatches don't share masks (a single-stage
    scan draws one mask for the whole batch; a pipeline processes microbatches
    separately and must not reuse the identical mask for each).

    `interleave` (v): the circular/looped schedule — the depth splits into
    v*P chunks and each device holds every P-th chunk, so a microbatch loops
    the ring v times.  Ticks shrink to chunk-granularity: T = v*M + P - 1
    ticks of depth/(v*P) layers each, vs GPipe's (M + P - 1) ticks of
    depth/P layers — bubble time drops ~v-fold ((P-1) chunk-ticks instead of
    (P-1) stage-ticks).  Wrap-around activations ride the same ppermute ring
    into a per-microbatch holding buffer on stage 0 (and its mirror on the
    last stage in the backward).  Requires num_micro >= P.

    `skip_bubble`: bubble ticks skip the stage compute entirely via lax.cond.
    This is only sound when the stage body contains no GLOBAL collectives:
    the cond predicate is pp-varying, so a full-clique collective inside it
    (e.g. the halo permutes sequence sharding lowers token shifts to) would
    be entered by live stages but skipped by bubble stages — a distributed
    deadlock on any backend.  Callers running with seq_shard_axis MUST pass
    skip_bubble=False; bubble ticks then compute-and-discard ((P-1)/T wasted
    stage compute, the plain GPipe cost)."""
    stages = mesh.shape[axis]
    depth = jax.tree_util.tree_leaves(xs)[0].shape[0]
    batch = x.shape[0]
    v = int(interleave)
    assert v >= 1, f"interleave must be >= 1, got {interleave}"
    assert depth % (stages * v) == 0, (
        f"depth {depth} % (pp {stages} * interleave {v}) != 0"
    )
    if num_micro is None:
        num_micro = default_num_micro(batch, stages)
    assert batch % num_micro == 0, f"batch {batch} % num_micro {num_micro} != 0"
    M = num_micro
    if v > 1:
        assert M >= stages, (
            f"interleave needs num_micro ({M}) >= pp stages ({stages}): the "
            "wrap-around buffer must be written before it is read"
        )
        # cyclic chunk assignment: device s holds chunks {s, s+P, ...} — a
        # plain transpose on the stacked depth axis, differentiated through
        # normally (it sits OUTSIDE the custom_vjp boundary)
        cl = depth // (stages * v)
        xs = jax.tree_util.tree_map(
            lambda l: l.reshape(v, stages, cl, *l.shape[1:])
            .swapaxes(0, 1)
            .reshape(depth, *l.shape[1:]),
            xs,
        )
    VM = v * M
    ticks = VM + stages - 1
    xm = x.reshape(M, batch // M, *x.shape[1:])

    # Split xs into differentiable (float) and non-differentiable (mask
    # indices, dropout keys) leaves: custom_vjp cotangents for the latter are
    # float0 by convention, and jax.vjp is only taken over the float part.
    leaves, treedef = jax.tree_util.tree_flatten(xs)
    fmask = tuple(_is_float(l) for l in leaves)
    fl = tuple(l for l, m in zip(leaves, fmask) if m)
    il = tuple(l for l, m in zip(leaves, fmask) if not m)

    def rebuild(fl_, il_):
        fi, ii, out = 0, 0, []
        for m in fmask:
            if m:
                out.append(fl_[fi])
                fi += 1
            else:
                out.append(il_[ii])
                ii += 1
        return jax.tree_util.tree_unflatten(treedef, out)

    def stage_fn(fl_local, il_local, h, micro_id, chunk=None):
        """This stage's layers (one chunk of them under interleave) on one
        microbatch's activations."""
        if v > 1:
            cl_ = jax.tree_util.tree_leaves(fl_local)[0].shape[0] // v
            pick = lambda l: jax.lax.dynamic_index_in_dim(
                l.reshape(v, cl_, *l.shape[1:]), chunk, 0, keepdims=False
            )
            fl_local = jax.tree_util.tree_map(pick, fl_local)
            il_local = jax.tree_util.tree_map(pick, il_local)
        ws = rebuild(fl_local, il_local)
        if fold_micro is not None:
            ws = fold_micro(ws, micro_id)
        # named per-stage region: xprof traces show the stage compute as its
        # own labelled row, separating it from the ppermute hops and bubbles
        with jax.named_scope("pp_stage_layers"):
            h, _ = jax.lax.scan(lambda hh, w: (body(hh, w)[0], None), h, ws)
        return h

    fwd_perm = [(i, (i + 1) % stages) for i in range(stages)]
    bwd_perm = [(i, (i - 1) % stages) for i in range(stages)]
    specs_like = lambda tree: jax.tree_util.tree_map(lambda _: P(axis), tree)

    def per_stage_fwd(fl_local, il_local, xm_in, with_saved: bool):
        s = jax.lax.axis_index(axis)

        @jax.named_scope("pp_fwd_tick")
        def tick(carry, t):
            h, outs, saved, ring = carry
            if v > 1:
                # the rotated-in h is the last stage's output of virtual
                # micro t - P: stage 0 banks it for the next ring loop
                # BEFORE ingestion overwrites h (write-then-read also makes
                # the M == P same-tick handoff correct)
                slot_w = (t - stages) % M
                prev_r = jax.lax.dynamic_index_in_dim(ring, slot_w, 0, keepdims=False)
                ring = jax.lax.dynamic_update_index_in_dim(
                    ring, jnp.where((s == 0) & (t >= stages), h, prev_r), slot_w, 0
                )
                x_fresh = jax.lax.dynamic_index_in_dim(
                    xm_in, jnp.clip(t, 0, M - 1), 0, keepdims=False
                )
                x_wrap = jax.lax.dynamic_index_in_dim(ring, t % M, 0, keepdims=False)
                x_in = jnp.where(t < M, x_fresh, x_wrap)
            else:
                x_in = jax.lax.dynamic_index_in_dim(
                    xm_in, jnp.clip(t, 0, M - 1), 0, keepdims=False
                )
            h = jnp.where(s == 0, x_in, h)  # first stage ingests
            j = t - s  # virtual micro = (round, micro) flattened
            valid = (j >= 0) & (j < VM)
            jc = jnp.clip(j, 0, VM - 1)
            mc = jc % M
            chunk = jnp.clip(jc // M, 0, v - 1)
            if with_saved:
                # the boundary activation entering this stage for virtual
                # micro jc — the ONLY tensor the backward keeps per micro
                saved = jax.lax.cond(
                    valid,
                    lambda sv: jax.lax.dynamic_update_index_in_dim(sv, h, jc, 0),
                    lambda sv: sv,
                    saved,
                )
            if skip_bubble:
                h = jax.lax.cond(
                    valid,
                    lambda hh: stage_fn(fl_local, il_local, hh, mc, chunk),
                    lambda hh: hh,
                    h,
                )
            else:
                # every device must reach the stage body's collectives on
                # every tick; bubble output is discarded by the select
                h = jnp.where(valid, stage_fn(fl_local, il_local, h, mc, chunk), h)
            # last stage records each LAST-round microbatch as it finishes
            om = t - (stages - 1) - (v - 1) * M
            oc = jnp.clip(om, 0, M - 1)
            write = (s == stages - 1) & (om >= 0)
            prev = jax.lax.dynamic_index_in_dim(outs, oc, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, h, prev), oc, 0
            )
            with jax.named_scope("pp_ppermute_fwd"):
                h = jax.lax.ppermute(h, axis, fwd_perm)
            return (h, outs, saved, ring), None

        var = lambda z: jax.lax.pcast(z, (axis,), to="varying")
        h0 = var(jnp.zeros_like(xm_in[0]))
        outs0 = var(jnp.zeros_like(xm_in))
        ring0 = outs0 if v > 1 else h0  # dummy when not interleaved
        saved0 = (
            var(jnp.zeros((VM, *xm_in.shape[1:]), xm_in.dtype))
            if with_saved else h0  # dummy
        )
        (_, outs, saved, _), _ = jax.lax.scan(
            tick, (h0, outs0, saved0, ring0), jnp.arange(ticks)
        )
        # only the last stage's buffer holds real outputs; psum-select makes
        # the result replicated over `axis` (out_specs P())
        out = jax.lax.psum(jnp.where(s == stages - 1, outs, jnp.zeros_like(outs)), axis)
        if with_saved:
            return out, jax.tree_util.tree_map(lambda l: l[None], (saved,))[0]
        return out

    def fwd_only(fl_, il_, xm_):
        fn = jax.shard_map(
            lambda a, b, c: per_stage_fwd(a, b, c, with_saved=False),
            mesh=mesh,
            in_specs=(specs_like(fl_), specs_like(il_), P()),
            out_specs=P(),
            axis_names={axis},
        )
        return fn(fl_, il_, xm_)

    def fwd_saving(fl_, il_, xm_):
        fn = jax.shard_map(
            lambda a, b, c: per_stage_fwd(a, b, c, with_saved=True),
            mesh=mesh,
            in_specs=(specs_like(fl_), specs_like(il_), P()),
            out_specs=(P(), P(axis)),
            axis_names={axis},
        )
        return fn(fl_, il_, xm_)

    def per_stage_bwd(fl_local, il_local, saved_local, g):
        """Reverse pipeline: the last stage starts at tick 0 with the LAST
        virtual micro, injects the loss cotangent (final round) or the
        wrap-around cotangent banked from stage 0's rotations (earlier
        rounds), recomputes its forward from the saved boundary, applies the
        vjp, and sends the input-cotangent backwards via the inverse
        rotation."""
        s = jax.lax.axis_index(axis)
        saved_local = saved_local[0]  # drop the (1,) stage-stacking dim

        @jax.named_scope("pp_bwd_tick")
        def tick(carry, u):
            dh, dfl, dx, dring = carry
            # virtual micro handled this tick, in REVERSE order
            j_lin = u - (stages - 1 - s)
            valid = (j_lin >= 0) & (j_lin < VM)
            jj = jnp.clip(VM - 1 - j_lin, 0, VM - 1)
            mc = jj % M
            chunk = jnp.clip(jj // M, 0, v - 1)
            if v > 1:
                # bank the rotated-in dh: it is stage 0's input-cotangent for
                # virtual micro VM+P-1-u, i.e. the wrap cotangent the last
                # stage will need for that micro minus one round (write
                # before read — the M == P same-tick handoff again)
                jj_src = VM + stages - 1 - u
                slot_w = jj_src % M
                prev_r = jax.lax.dynamic_index_in_dim(dring, slot_w, 0, keepdims=False)
                dring = jax.lax.dynamic_update_index_in_dim(
                    dring,
                    jnp.where((s == stages - 1) & (u >= stages), dh, prev_r),
                    slot_w, 0,
                )
                g_hi = jax.lax.dynamic_index_in_dim(
                    g, jnp.clip(jj - (v - 1) * M, 0, M - 1), 0, keepdims=False
                )
                g_lo = jax.lax.dynamic_index_in_dim(dring, mc, 0, keepdims=False)
                g_in = jnp.where(jj >= (v - 1) * M, g_hi, g_lo)
            else:
                g_in = jax.lax.dynamic_index_in_dim(g, mc, 0, keepdims=False)
            # injection replaces whatever rotated in (mirrors the forward's
            # stage-0 ingestion overwrite, which makes the rotated
            # wrap-around value's cotangent exactly zero)
            dh = jnp.where(s == stages - 1, g_in, dh)

            def do(dh_):
                h_in = jax.lax.dynamic_index_in_dim(saved_local, jj, 0, keepdims=False)
                _, vjp_fn = jax.vjp(
                    lambda fl_, hh: stage_fn(fl_, il_local, hh, mc, chunk),
                    fl_local, h_in,
                )
                dfl_i, dh_in = vjp_fn(dh_)
                return dfl_i, dh_in

            if skip_bubble:
                dfl_add, dh = jax.lax.cond(
                    valid,
                    do,
                    lambda dh_: (jax.tree_util.tree_map(jnp.zeros_like, fl_local), dh_),
                    dh,
                )
            else:
                dfl_run, dh_run = do(dh)
                dfl_add = jax.tree_util.tree_map(
                    lambda g: jnp.where(valid, g, jnp.zeros_like(g)), dfl_run
                )
                dh = jnp.where(valid, dh_run, dh)
            dfl = jax.tree_util.tree_map(jnp.add, dfl, dfl_add)
            # the cotangent leaving stage 0 on the FIRST round is d x_in
            dx = jax.lax.cond(
                valid & (s == 0) & (jj < M),
                lambda d: jax.lax.dynamic_update_index_in_dim(d, dh, mc, 0),
                lambda d: d,
                dx,
            )
            with jax.named_scope("pp_ppermute_bwd"):
                dh = jax.lax.ppermute(dh, axis, bwd_perm)
            return (dh, dfl, dx, dring), None

        var = lambda z: jax.lax.pcast(z, (axis,), to="varying")
        dh0 = var(jnp.zeros_like(g[0]))
        # fl_local arrives P(axis)-sharded, i.e. already pp-varying — its
        # zeros need no pcast (g is replicated, so its derivatives do)
        dfl0 = jax.tree_util.tree_map(jnp.zeros_like, fl_local)
        dx0 = var(jnp.zeros_like(g))
        dring0 = dx0 if v > 1 else dh0  # dummy when not interleaved
        (_, dfl, dx, _), _ = jax.lax.scan(
            tick, (dh0, dfl0, dx0, dring0), jnp.arange(ticks)
        )
        dx = jax.lax.psum(jnp.where(s == 0, dx, jnp.zeros_like(dx)), axis)
        # dfl leaves are local (depth/P, ...) blocks — out_specs P(axis)
        # concatenates them straight back to the global (depth, ...) layout
        return dfl, dx

    @jax.custom_vjp
    def run(fl_, il_, xm_):
        return fwd_only(fl_, il_, xm_)

    def run_fwd(fl_, il_, xm_):
        out, saved = fwd_saving(fl_, il_, xm_)
        return out, (fl_, il_, saved)

    def run_bwd(res, g):
        fl_, il_, saved = res
        fn = jax.shard_map(
            per_stage_bwd,
            mesh=mesh,
            in_specs=(specs_like(fl_), specs_like(il_), P(axis), P()),
            out_specs=(specs_like(fl_), P()),
            axis_names={axis},
        )
        dfl, dxm = fn(fl_, il_, saved, g)
        dil = tuple(np.zeros(np.shape(l), jax.dtypes.float0) for l in il_)
        return dfl, dil, dxm

    run.defvjp(run_fwd, run_bwd)
    out = run(fl, il, xm)
    return out.reshape(batch, *x.shape[1:])
