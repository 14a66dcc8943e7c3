"""Analytic inter-chip communication accounting.

FlashAttention's lesson is that the ledger of DATA MOVEMENT — not FLOPs —
is what explains (and fixes) a memory-bound kernel; this module keeps the
same ledger for inter-chip movement.  Training never calls a collective
explicitly (XLA emits them from sharding annotations, plus the pipeline's
manual ppermute), so the bytes a mesh moves per step are *derivable* from
the mesh shape + the sharding/settings that produced those annotations:

  dp    one ring all-reduce of the gradient buffer per step
  fsdp  ZeRO-1/2: grad all-reduce + updated-shard all-gather;
        ZeRO-3: param all-gather per use (fwd + bwd, per microbatch)
        + one gradient reduce-scatter
  tp    one activation all-reduce per residual branch per direction
        (the Megatron pattern: 2 branches x fwd+bwd per layer)
  sp    ring attention K/V rotation (fwd) + the (q, do, lse, delta, dq)
        backward packet — priced by parallel/ring.ring_comm_bytes, the
        same source of truth as the schedule itself
  pp    one stage-hop ppermute per tick, forward and explicit backward —
        parallel/pipeline.pipeline_comm_bytes

All figures are per-chip WIRE bytes per optimizer step (the ring all-reduce
costs 2·(n-1)/n of the payload on the wire, an all-gather/reduce-scatter
(n-1)/n).  The ledger is cross-checked against XLA's own `cost_analysis`
bytes-accessed: the two measure different things (bytes-accessed is HBM
traffic, dominated by local reads/writes), so — exactly like
`FlopsCrosscheck` — the alarm fires on persistent DRIFT of the ratio from
its first observed value, which catches a silently changed collective
footprint (a lost sharding annotation, an accidental full-replication)
without pretending the two numbers should ever be equal.

Everything here is host-side arithmetic on static shapes — no device values
are touched, so the module is lint-clean under tools/lint_host_sync.py by
construction.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Union

from dalle_pytorch_tpu.observability import metrics as metrics_mod
from dalle_pytorch_tpu.observability.xla import FlopsCrosscheck

# ---------------------------------------------------------------------------
# collective wire-cost primitives (per-chip bytes, ring algorithms)
# ---------------------------------------------------------------------------

def ring_all_reduce_bytes(payload: float, n: int) -> float:
    """Per-chip wire bytes to all-reduce a `payload`-byte tensor over n
    chips: reduce-scatter + all-gather, each (n-1)/n of the payload."""
    return 2.0 * payload * (n - 1) / n if n > 1 else 0.0


def all_gather_bytes(payload: float, n: int) -> float:
    """Per-chip wire bytes to all-gather a tensor whose GLOBAL size is
    `payload` bytes from n shards."""
    return payload * (n - 1) / n if n > 1 else 0.0


def reduce_scatter_bytes(payload: float, n: int) -> float:
    return payload * (n - 1) / n if n > 1 else 0.0


# ---------------------------------------------------------------------------
# tree sizing
# ---------------------------------------------------------------------------

def tree_float_bytes(tree: Any, itemsize: Optional[int] = None) -> float:
    """Total bytes of the floating leaves of `tree` — in their storage dtype,
    or repriced at `itemsize` (e.g. a grad_dtype override).  Pure shape/dtype
    arithmetic; never reads device values."""
    import jax
    import jax.numpy as jnp

    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = jnp.result_type(leaf)
        if not jnp.issubdtype(dt, jnp.floating):
            continue
        size = getattr(leaf, "size", None)
        if size is None:
            continue
        total += size * (itemsize if itemsize is not None else jnp.dtype(dt).itemsize)
    return total


def _itemsize(dtype) -> int:
    import jax.numpy as jnp

    return jnp.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def step_comms_ledger(
    axes: Mapping[str, int],
    *,
    param_bytes: float,
    grad_bytes: float,
    batch: int,
    seq_len: int,
    dim: int,
    depth: int,
    heads: int,
    dim_head: int,
    compute_itemsize: int = 4,
    zero_stage: int = 0,
    grad_accum: int = 1,
    pp_num_micro: Optional[int] = None,
    pp_interleave: int = 1,
    param_shard_fraction: Optional[float] = None,
) -> Dict[str, Any]:
    """Per-chip wire bytes per optimizer step for each active mesh axis.

    `axes` is {axis: size} (see parallel/mesh.axis_sizes — a plain dict works
    too, so hypothetical meshes can be priced without devices).  `batch` is
    the GLOBAL per-step batch; activations are sharded over (dp, fsdp), so
    activation collectives are priced at the local batch.

    `param_shard_fraction` overrides the 1/(tp·pp) every-leaf-shards
    approximation with the EXACT at-rest fraction from the partitioning
    registry (dalle_step_comms computes it when handed the registry) — the
    dp/fsdp collectives move each chip's OWN shard, so their payloads are
    priced at that fraction."""
    d = int(axes.get("dp", 1))
    f = int(axes.get("fsdp", 1))
    t = int(axes.get("tp", 1))
    s = int(axes.get("sp", 1))
    p = int(axes.get("pp", 1))

    data_shards = max(d * f, 1)
    batch_local = max(batch // data_shards, 1)
    # params (and so gradients) are sharded over tp at rest (Megatron
    # column/row specs) and over pp (the registry folds pp into the
    # data-sharding axes), so the dp/fsdp collectives each chip runs move
    # only its OWN shard of the tree.  Default approximation: every leaf is
    # treated as tp/pp-shardable — matmul weights (the tree's mass) are; the
    # small non-TP-ruled leaves (norms, biases without a rule) are
    # over-divided.  With param_shard_fraction the exact registry figure
    # replaces it.
    param_shard = (param_shard_fraction if param_shard_fraction is not None
                   else 1.0 / max(t * p, 1))
    grad_local = grad_bytes * param_shard
    param_local = param_bytes * param_shard
    per_axis: List[Dict[str, Any]] = []

    if d > 1:
        per_axis.append({
            "axis": "dp", "size": d, "op": "all_reduce",
            "bytes_per_step": ring_all_reduce_bytes(grad_local, d),
            "payload_bytes": grad_local,
        })

    if f > 1:
        if zero_stage >= 3:
            # params gathered around each use — forward and backward of every
            # microbatch — plus one gradient reduce-scatter per step
            gathers = 2.0 * max(grad_accum, 1)
            per_axis.append({
                "axis": "fsdp", "size": f,
                "op": "all_gather+reduce_scatter", "zero_stage": zero_stage,
                "bytes_per_step": (gathers * all_gather_bytes(param_local, f)
                                   + reduce_scatter_bytes(grad_local, f)),
                "payload_bytes": param_local,
            })
        elif zero_stage >= 1:
            # params replicated (plain grad all-reduce), moments sharded:
            # each chip updates its shard and all-gathers the result
            per_axis.append({
                "axis": "fsdp", "size": f,
                "op": "all_reduce+all_gather", "zero_stage": zero_stage,
                "bytes_per_step": (ring_all_reduce_bytes(grad_local, f)
                                   + all_gather_bytes(param_local, f)),
                "payload_bytes": grad_local,
            })
        else:
            per_axis.append({
                "axis": "fsdp", "size": f, "op": "all_reduce",
                "zero_stage": zero_stage,
                "bytes_per_step": ring_all_reduce_bytes(grad_local, f),
                "payload_bytes": grad_local,
            })

    if t > 1:
        # Megatron pattern: one activation all-reduce per residual branch
        # (attention out-proj + ff down-proj) per direction
        act = 1.0 * batch_local * seq_len * dim * compute_itemsize
        per_axis.append({
            "axis": "tp", "size": t, "op": "all_reduce",
            "bytes_per_step": depth * 2 * 2 * ring_all_reduce_bytes(act, t),
            "payload_bytes": act,
            "collectives": depth * 4,
        })

    if s > 1:
        from dalle_pytorch_tpu.parallel.ring import ring_comm_bytes

        per_layer = ring_comm_bytes(
            batch_local, heads, max(seq_len // s, 1), dim_head, s,
            itemsize=compute_itemsize,
        )
        per_axis.append({
            "axis": "sp", "size": s, "op": "ppermute_ring",
            "bytes_per_step": depth * per_layer,
            "payload_bytes": per_layer,
        })

    if p > 1:
        from dalle_pytorch_tpu.parallel.pipeline import (
            default_num_micro,
            pipeline_comm_bytes,
        )

        num_micro = pp_num_micro or default_num_micro(batch_local, p)
        per_axis.append({
            "axis": "pp", "size": p, "op": "ppermute",
            "bytes_per_step": pipeline_comm_bytes(
                batch_local, seq_len, dim, p, num_micro=num_micro,
                itemsize=compute_itemsize, interleave=max(pp_interleave, 1),
            ),
            "num_micro": num_micro,
        })

    total = sum(row["bytes_per_step"] for row in per_axis)
    return {
        "mesh": dict(axes),
        "batch": batch,
        "batch_local": batch_local,
        "per_axis": per_axis,
        "total_bytes_per_step": total + 0.0,
    }


def dalle_step_comms(mesh: Union[Mapping[str, int], Any, None], params: Any,
                     cfg: Any, batch: int,
                     settings: Any = None,
                     registry: Any = None) -> Optional[Dict[str, Any]]:
    """The ledger for a live DALLE training step: sizes from the mesh (a
    `jax.sharding.Mesh` or a plain {axis: size} mapping), payload bytes from
    the param tree, dtypes and ZeRO stage from the StepSettings, geometry
    from the DALLEConfig.  Returns None without a mesh (single-chip: no
    inter-chip traffic to account).

    `registry` (parallel/registry.PartitionRegistry — pass the step_fn's)
    prices the at-rest param/grad shard each dp/fsdp collective moves at
    its EXACT per-leaf fraction instead of the 1/(tp·pp) approximation —
    the same rules the cross-check audits."""
    if mesh is None:
        return None
    from dalle_pytorch_tpu.parallel.mesh import axis_sizes

    axes = axis_sizes(mesh)
    shard_fraction = None
    if registry is not None:
        # zero_stage 0 here deliberately: this fraction is the tp/pp at-rest
        # division only — the fsdp sharding is what the fsdp ROW prices
        shard_fraction = registry.shard_fraction(params, axes, 0)
    param_bytes = tree_float_bytes(params)
    if settings is not None and getattr(settings, "grad_dtype", None) is not None:
        grad_bytes = tree_float_bytes(params, itemsize=_itemsize(settings.grad_dtype))
    else:
        grad_bytes = tree_float_bytes(params, itemsize=4)
    compute_itemsize = 4
    if settings is not None and getattr(settings, "compute_dtype", None) is not None:
        compute_itemsize = _itemsize(settings.compute_dtype)
    return step_comms_ledger(
        axes,
        param_bytes=param_bytes,
        grad_bytes=grad_bytes,
        batch=batch,
        seq_len=cfg.total_seq_len,
        dim=cfg.dim,
        depth=cfg.depth,
        heads=cfg.heads,
        dim_head=cfg.dim_head,
        compute_itemsize=compute_itemsize,
        zero_stage=int(getattr(settings, "zero_stage", 0) or 0) if settings is not None else 0,
        grad_accum=int(getattr(settings, "grad_accum", 1) or 1) if settings is not None else 1,
        pp_num_micro=getattr(cfg, "pp_num_micro", None),
        pp_interleave=int(getattr(cfg, "pp_interleave", 1) or 1),
        param_shard_fraction=shard_fraction,
    )


def prefill_handoff_bytes(tcfg: Any, n_pre: int, lanes: int = 1,
                          itemsize: int = 4,
                          kv_quant: Optional[str] = None) -> float:
    """Bytes of the prefill→decode KV handoff for ONE admission: the k + v
    prefix every layer carries, `lanes` sequences deep (a CFG-guided request
    hands over its [cond] and [null] prefixes).  This is the dense cache
    `write_prefill_to_pool` scatters — priced analytically so tests can
    cross-check the figure against the actual handoff arrays' nbytes.  With
    `kv_quant` the worker ships int8 payloads + per-token scales; the price
    comes from the SAME `kv_bytes_per_elem` formula the memory ledger uses."""
    from dalle_pytorch_tpu.quantization import kv_bytes_per_elem

    return (2.0 * tcfg.depth * lanes * tcfg.heads * n_pre * tcfg.dim_head
            * kv_bytes_per_elem(kv_quant, itemsize, tcfg.dim_head))


def prefill_handoff_row(tcfg: Any, n_pre: int, lanes: int = 1,
                        itemsize: int = 4, ring_bytes: float = 0.0,
                        admissions_per_step: float = 1.0,
                        kv_quant: Optional[str] = None) -> Dict[str, Any]:
    """The comms-ledger row for prefill/decode disaggregation: the wire
    bytes a prefill mesh ships to a decode replica per admission (KV prefix
    + the token-shift ring tails when shift_tokens is on).  Shaped like
    `step_comms_ledger`'s per_axis rows so fleet reports and
    `publish_gauges` treat it uniformly."""
    payload = prefill_handoff_bytes(tcfg, n_pre, lanes, itemsize,
                                    kv_quant=kv_quant)
    row = {
        "axis": "handoff", "size": 2, "op": "prefill_to_decode",
        "bytes_per_step": (payload + ring_bytes) * admissions_per_step,
        "payload_bytes": payload,
        "ring_bytes": ring_bytes,
        "n_pre": n_pre,
        "lanes": lanes,
    }
    if kv_quant:
        row["kv_quant"] = kv_quant
    return row


def publish_gauges(ledger: Mapping[str, Any], registry=None) -> None:
    """Mirror the ledger into the metrics registry: one gauge per axis plus
    the total — the numbers the fleet report and bench rows read back."""
    reg = registry if registry is not None else metrics_mod.REGISTRY
    for row in ledger.get("per_axis", []):
        reg.gauge(f"comms/{row['axis']}_bytes_per_step").set(row["bytes_per_step"])
    reg.gauge("comms/total_bytes_per_step").set(ledger["total_bytes_per_step"])


def comms_roofline(total_bytes: float, step_flops: float,
                   peak_flops: Optional[float] = None,
                   ici_bytes_per_s: Optional[float] = None,
                   n_chips: int = 1) -> Optional[Dict[str, Any]]:
    """Comms-vs-compute roofline for one step: time each side would take at
    its peak, and which one bounds the step.  Overlap is the best case —
    `bound` says which resource the step CANNOT go faster than.  Peaks not
    passed in come from the chip table (core/chips.py); on CPU there are
    none and the roofline is None — nothing is priced against a guess.

    BOTH sides are per-chip: `total_bytes` is the ledger's per-chip wire
    bytes, so `step_flops` (the analytic WHOLE-step model, all chips) is
    divided by `n_chips` — comparing fleet FLOPs against one chip's traffic
    would bias every verdict toward compute-bound."""
    if peak_flops is None or ici_bytes_per_s is None:
        from dalle_pytorch_tpu.core.chips import chip_spec

        spec = chip_spec()
        if spec is None:
            return None
        peak_flops = peak_flops if peak_flops is not None else spec.bf16_flops
        ici_bytes_per_s = (ici_bytes_per_s if ici_bytes_per_s is not None
                           else spec.ici_bytes_per_s)
    flops_per_chip = step_flops / max(n_chips, 1)
    compute_s = flops_per_chip / peak_flops
    comms_s = total_bytes / ici_bytes_per_s
    return {
        "comms_s_at_peak": comms_s + 0.0,
        "compute_s_at_peak": compute_s + 0.0,
        "comms_over_compute": (comms_s / compute_s) if compute_s > 0 else None,
        "bound": "comms" if comms_s > compute_s else "compute",
        "n_chips": max(n_chips, 1),
        "ici_bytes_per_s": ici_bytes_per_s + 0.0,
        "peak_flops": peak_flops + 0.0,
    }


class CommsCrosscheck(FlopsCrosscheck):
    """Analytic-comms vs cost_analysis bytes-accessed, with the same
    drift-from-first-ratio persistence alarm as the FLOPs cross-check.  The
    measured side is HBM traffic, not wire traffic — the RATIO is the
    invariant: when it moves, either the collective footprint changed (a
    dropped sharding annotation replicates a tensor XLA used to shard) or
    the analytic model no longer matches the program."""

    RATIO_GAUGE = "xla_bytes_over_analytic_comms"
    ALARM_COUNTER = "comms_divergence_alarms"
