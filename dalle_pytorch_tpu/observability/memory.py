"""Analytic HBM accounting, XLA memory-analysis cross-checking, donation
auditing, live headroom tracking, and OOM forensics.

DALL-E-scale training is memory-bound before it is compute-bound: the
reference's reversible blocks exist to fit HBM, FlashAttention's whole point
is the memory hierarchy, and the failure that actually kills runs is
`RESOURCE_EXHAUSTED` — usually at step 0, after a ten-minute compile.  The
repo already prices FLOPs (training/profiling.py) and wire bytes
(observability/comms.py) analytically and cross-checks both against XLA;
this module closes the triangle for the resource with the hardest failure
mode.  Four cooperating pieces:

* **Analytic ledger** (`step_memory_ledger` / `dalle_step_memory`) — per-chip
  resident HBM priced from the mesh shape + StepSettings + model geometry:
  param storage (tp/pp-sharded at rest, fsdp-sharded under ZeRO-3 — the same
  shard-pricing rules as the comms ledger), optimizer state by ZeRO stage,
  gradient + f32-accumulator buffers, and the activation working set per
  execution/remat policy (scan_layers x microbatch), with a fits /
  doesn't-fit verdict against the per-device HBM capacity.
* **XLA cross-check** (`step_memory_analysis` + `MemoryCrosscheck`) — the
  compiled executable's own `memory_analysis()` (argument / output / temp /
  generated-code sizes), compared against the ledger through the SAME
  drift-from-first-ratio persistence alarm as the FLOPs/comms cross-checks:
  the two models measure different things (XLA sees fusion, rematerialized
  buffers, layout padding), so the RATIO is the invariant.  The same
  analysis drives the **donation audit**: `donate_argnums=0` silently
  dropping (a dtype/sharding mismatch, an aliasing-unsupported backend)
  doubles the train-state footprint without any error — `audit_donation`
  alarms when the aliased bytes fall short of the donated argument.
* **Live headroom** (`HbmMonitor`) — `peak_bytes_in_use` deltas per flush
  window plus a usage-fraction alarm (once per episode, hysteresis re-arm)
  that routes through the telemetry alarm hub into the on-alarm
  TraceTrigger capture.
* **OOM forensics** (`is_oom_error` / `write_oom_report`) — when a CLI
  catches RESOURCE_EXHAUSTED at compile or step time it writes
  `oom_report_*.txt`: the ledger breakdown, the memory_analysis dump, live
  allocator stats, and `oom_suggestions`' ranked actionable changes (raise
  the ZeRO stage, enable remat, shrink the microbatch) derived from which
  ledger row dominates — then exits `resilience.EXIT_OOM`.

Everything here is host-side arithmetic on static shapes and host dicts —
no traced value is ever read, so the module is covered by
tools/lint_host_sync.py (pure by construction)."""
from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Optional, Union

from dalle_pytorch_tpu.observability import metrics as metrics_mod
from dalle_pytorch_tpu.observability.comms import tree_float_bytes
from dalle_pytorch_tpu.observability.xla import FlopsCrosscheck

def device_hbm_capacity(device=None) -> Optional[float]:
    """Per-device HBM capacity in bytes: the allocator's own `bytes_limit`
    when exposed, else the chip table (core/chips.py).  None on CPU — there
    is no meaningful capacity to verdict against; an accelerator the table
    does not know raises."""
    import jax

    from dalle_pytorch_tpu.core.chips import chip_spec

    device = device if device is not None else jax.local_devices()[0]
    stats = device.memory_stats()
    if stats and stats.get("bytes_limit"):
        return stats["bytes_limit"] * 1.0
    spec = chip_spec(device)
    return None if spec is None else spec.hbm_bytes


# ---------------------------------------------------------------------------
# the analytic ledger
# ---------------------------------------------------------------------------

def rest_shard_fraction(axes: Mapping[str, int], zero_stage: int = 0,
                        moments: bool = False) -> float:
    """Fraction of a param-shaped tree each chip holds AT REST — the comms
    ledger's shard-pricing rules (params are tp/pp-sharded at rest;
    fsdp-sharded under ZeRO-3, moments already under ZeRO-1).

    This is the every-leaf-shards APPROXIMATION for pricing hypothetical
    meshes without a tree in hand.  When the live trees exist, the ledgers
    price the EXACT fraction from the partitioning registry instead
    (`PartitionRegistry.shard_fraction` — the same rule table that placed
    the state), so ledger and reality cannot drift apart silently."""
    t = int(axes.get("tp", 1))
    p = int(axes.get("pp", 1))
    f = int(axes.get("fsdp", 1))
    stage_floor = 1 if moments else 3
    fsdp_div = f if (zero_stage >= stage_floor and f > 1) else 1
    return 1.0 / max(t * p * fsdp_div, 1)


def activation_bytes(
    axes: Mapping[str, int],
    *,
    batch: int,
    seq_len: int,
    dim: int,
    depth: int,
    heads: int,
    dim_head: int,
    compute_itemsize: int = 4,
    grad_accum: int = 1,
    execution: str = "sequential",
    remat_policy: str = "full",
    ff_mult: int = 4,
    flash_attention: bool = False,
    pp_num_micro: Optional[int] = None,
) -> Dict[str, float]:
    """Per-chip activation working set of one training step.

    The model: the peak is (saved-for-backward bytes) + (one layer's live
    recompute working set).  What is *saved* depends on the execution
    engine:

      sequential         every layer's boundary AND internals stay live
      remat 'full'       only the per-layer residual boundaries
      remat 'flash'      + flash_out and the f32 lse rows per layer
      remat 'flash_qkv'  + the qkv projections per layer
      remat 'flash_qkv_ff' + the (GEGLU a, gates) ff pre-activation per layer
      reversible         two residual streams, depth-independent

    Microbatching (lax.scan over grad_accum) means only ONE microbatch's
    saved set is live at a time; sp shards the sequence; tp shards the
    per-branch internals (qkv, ff hidden) but not the residual stream; pp
    divides depth across stages but keeps ~pp microbatches' boundaries in
    flight (the GPipe stash).  Dense-XLA attention materializes the (s, s)
    score matrix; the flash kernel never does."""
    d_ax = int(axes.get("dp", 1))
    f_ax = int(axes.get("fsdp", 1))
    t = int(axes.get("tp", 1))
    s_ax = int(axes.get("sp", 1))
    p = int(axes.get("pp", 1))

    batch_local = max(batch // max(d_ax * f_ax, 1), 1)
    micro = max(batch_local // max(grad_accum, 1), 1)
    s_loc = max(seq_len // s_ax, 1)
    depth_local = max(depth // p, 1)
    bsd = 1.0 * micro * s_loc * dim * compute_itemsize
    # attention internals live at the INNER width (heads x dim_head), which
    # is wider than the residual stream whenever heads*dim_head != dim
    bsi = 1.0 * micro * s_loc * heads * dim_head * compute_itemsize

    qkv = 3.0 * bsi / t
    attn_out = bsi  # pre-out-projection attention context
    ff_hidden = 2.0 * ff_mult * bsd / t  # GEGLU: a + gates, each b.s.(mult*d)/tp
    misc = 2.0 * bsd  # norms / token-shift copies
    scores = 0.0 if flash_attention else (
        1.0 * micro * (heads / t) * s_loc * s_loc * compute_itemsize
    )
    layer_ws = qkv + attn_out + ff_hidden + misc + scores

    lse = 1.0 * micro * (heads / t) * s_loc * 4  # f32, flash kernels only
    if execution == "reversible":
        saved_per_layer = 0.0
        boundaries = 2.0 * bsd
    elif execution == "remat":
        extras = {
            "full": 0.0,
            "flash": bsi + lse,  # flash_out is (b, h, s, dh)
            "flash_qkv": bsi + lse + qkv,
            "flash_qkv_ff": bsi + lse + qkv + ff_hidden,
        }.get(remat_policy, 0.0)
        saved_per_layer = extras
        boundaries = depth_local * bsd
    else:  # sequential: everything stays live for backward
        saved_per_layer = layer_ws
        boundaries = depth_local * bsd
    saved = boundaries + depth_local * saved_per_layer

    in_flight = 1
    if p > 1:
        from dalle_pytorch_tpu.parallel.pipeline import default_num_micro

        num_micro = pp_num_micro or default_num_micro(batch_local, p)
        in_flight = max(min(num_micro, p), 1)

    total = saved * in_flight + layer_ws
    return {
        "bytes": total,
        "saved_bytes": saved,
        "layer_working_set_bytes": layer_ws,
        "microbatch": micro,
        "in_flight_microbatches": in_flight,
    }


def step_memory_ledger(
    axes: Mapping[str, int],
    *,
    param_bytes: float,
    grad_bytes: float,
    opt_bytes: float,
    batch: int,
    seq_len: int,
    dim: int,
    depth: int,
    heads: int,
    dim_head: int,
    compute_itemsize: int = 4,
    zero_stage: int = 0,
    grad_accum: int = 1,
    accum_bytes: Optional[float] = None,
    execution: str = "sequential",
    remat_policy: str = "full",
    ff_mult: int = 4,
    flash_attention: bool = False,
    pp_num_micro: Optional[int] = None,
    input_bytes: float = 0.0,
    capacity_bytes: Optional[float] = None,
    param_shard_fraction: Optional[float] = None,
    moment_shard_fraction: Optional[float] = None,
) -> Dict[str, Any]:
    """Per-chip resident HBM of one optimizer step, row by row.

    `axes` is {axis: size} (a plain dict works — hypothetical meshes are
    priced without devices; {} is a single chip).  `param_bytes` /
    `grad_bytes` / `opt_bytes` are WHOLE-tree bytes in their storage dtypes;
    the rows apply the at-rest shard fractions — the scalar
    `rest_shard_fraction` model by default, or the EXACT registry-priced
    `param_shard_fraction` / `moment_shard_fraction` when the caller has
    the live trees (dalle_step_memory passes them).  `accum_bytes` is the
    f32 microbatch accumulator (defaults to grad_bytes repriced at 4 bytes
    is the caller's job — pass it explicitly); `input_bytes` is the
    on-device batch (text ids + pixels, including prefetch depth)."""
    # host-sync-ok: mesh-axis sizes are static python ints
    axes = {k: int(v) for k, v in dict(axes).items()}
    p_frac = (param_shard_fraction if param_shard_fraction is not None
              else rest_shard_fraction(axes, zero_stage, moments=False))
    m_frac = (moment_shard_fraction if moment_shard_fraction is not None
              else rest_shard_fraction(axes, zero_stage, moments=True))

    rows: List[Dict[str, Any]] = [
        {"name": "params", "bytes": param_bytes * p_frac,
         "detail": f"storage x {p_frac:.4g} at-rest shard"},
        {"name": "grads", "bytes": grad_bytes * p_frac,
         "detail": f"grad_dtype buffer x {p_frac:.4g}"},
    ]
    if grad_accum > 1 and accum_bytes:
        rows.append({"name": "grad_accum", "bytes": accum_bytes * p_frac,
                     "detail": "f32 microbatch accumulator"})
    rows.append({"name": "opt_state", "bytes": opt_bytes * m_frac,
                 "detail": f"zero_stage {zero_stage} x {m_frac:.4g}"})
    act = activation_bytes(
        axes, batch=batch, seq_len=seq_len, dim=dim, depth=depth,
        heads=heads, dim_head=dim_head, compute_itemsize=compute_itemsize,
        grad_accum=grad_accum, execution=execution, remat_policy=remat_policy,
        ff_mult=ff_mult, flash_attention=flash_attention,
        pp_num_micro=pp_num_micro,
    )
    rows.append({"name": "activations", "bytes": act["bytes"],
                 "detail": (f"{execution}/{remat_policy} micro={act['microbatch']}"
                            f" in_flight={act['in_flight_microbatches']}")})
    if input_bytes:
        rows.append({"name": "inputs", "bytes": input_bytes * 1.0,
                     "detail": "device batch (+prefetch)"})

    return _finish_ledger(rows, axes=axes, batch=batch,
                          capacity_bytes=capacity_bytes,
                          activations=act)


def _finish_ledger(rows, *, axes=None, batch=None, capacity_bytes=None,
                   **extra) -> Dict[str, Any]:
    total = sum(r["bytes"] for r in rows)
    dominant = max(rows, key=lambda r: r["bytes"])["name"] if rows else None
    if capacity_bytes is None:
        capacity_bytes = device_hbm_capacity()
    ledger: Dict[str, Any] = {
        "rows": rows,
        "total_bytes": total + 0.0,
        "dominant": dominant,
        "capacity_bytes": capacity_bytes,
        "fits": (total <= capacity_bytes) if capacity_bytes else None,
        "headroom_frac": (1.0 - total / capacity_bytes) if capacity_bytes else None,
    }
    if axes is not None:
        ledger["mesh"] = dict(axes)
    if batch is not None:
        ledger["batch"] = batch
    ledger.update(extra)
    return ledger


def _itemsize(dtype) -> int:
    import jax.numpy as jnp

    return jnp.dtype(dtype).itemsize


def dalle_step_memory(
    mesh: Union[Mapping[str, int], Any, None],
    params: Any,
    opt_state: Any,
    cfg: Any,
    batch: int,
    settings: Any = None,
    input_bytes: float = 0.0,
    capacity_bytes: Optional[float] = None,
    registry: Any = None,
) -> Dict[str, Any]:
    """The HBM ledger for a live DALLE training step: payload bytes from the
    actual param/optimizer trees (their storage dtypes — a bf16-stored run
    prices at 2 bytes), dtypes and ZeRO stage from the StepSettings, geometry
    and execution policy from the DALLEConfig.  Unlike the comms ledger, a
    missing mesh is NOT a no-op — single-chip runs OOM too ({} = one chip).

    `registry` (parallel/registry.PartitionRegistry — pass the step_fn's)
    replaces the scalar at-rest shard fractions with the EXACT per-leaf
    fractions the placement rules produce, so the ledger is priced from the
    same table that sharded the state it audits."""
    if mesh is None:
        axes: Mapping[str, int] = {}
    else:
        from dalle_pytorch_tpu.parallel.mesh import axis_sizes

        axes = axis_sizes(mesh)
    # price params at the RUN's storage dtype: before distribution the tree
    # is still the caller's f32 init, but settings.param_dtype is what
    # init_fn will cast it to (a --param_dtype bfloat16 run halves this row)
    if settings is not None and getattr(settings, "param_dtype", None) is not None:
        param_bytes = tree_float_bytes(
            params, itemsize=_itemsize(settings.param_dtype))
    else:
        param_bytes = tree_float_bytes(params)
    grad_itemsize = 4
    if settings is not None and getattr(settings, "grad_dtype", None) is not None:
        grad_itemsize = _itemsize(settings.grad_dtype)
    grad_bytes = tree_float_bytes(params, itemsize=grad_itemsize)
    # a missing opt_state is priced as adam: two f32 moments per param
    opt_bytes = (tree_float_bytes(opt_state) if opt_state is not None
                 else 2.0 * tree_float_bytes(params, itemsize=4))
    compute_itemsize = 4
    if settings is not None and getattr(settings, "compute_dtype", None) is not None:
        compute_itemsize = _itemsize(settings.compute_dtype)
    grad_accum = int(getattr(settings, "grad_accum", 1) or 1) if settings is not None else 1

    zero_stage = int(getattr(settings, "zero_stage", 0) or 0) if settings is not None else 0
    p_frac = m_frac = None
    if registry is not None:
        p_frac = registry.shard_fraction(params, axes, zero_stage)
        # moments mirror the param tree's paths when no live opt tree exists
        m_frac = registry.shard_fraction(
            opt_state if opt_state is not None else params, axes,
            zero_stage, moments=True,
            itemsize=None if opt_state is not None else 4,
        )
    execution = getattr(cfg, "resolved_execution", None) or "sequential"
    flash = _resolves_to_flash(getattr(cfg, "attn_kernel", "auto"))
    return step_memory_ledger(
        axes,
        param_bytes=param_bytes,
        grad_bytes=grad_bytes,
        opt_bytes=opt_bytes,
        batch=batch,
        seq_len=cfg.total_seq_len,
        dim=cfg.dim,
        depth=cfg.depth,
        heads=cfg.heads,
        dim_head=cfg.dim_head,
        compute_itemsize=compute_itemsize,
        zero_stage=zero_stage,
        grad_accum=grad_accum,
        accum_bytes=tree_float_bytes(params, itemsize=4) if grad_accum > 1 else None,
        execution=execution,
        remat_policy=getattr(cfg, "remat_policy", "full") or "full",
        flash_attention=flash,
        pp_num_micro=getattr(cfg, "pp_num_micro", None),
        input_bytes=input_bytes,
        capacity_bytes=capacity_bytes,
        param_shard_fraction=p_frac,
        moment_shard_fraction=m_frac,
    )


def _resolves_to_flash(attn_kernel: str) -> bool:
    """Mirror transformer._use_flash's config half: 'auto' is flash on TPU
    backends only (the Pallas kernel never materializes the score matrix)."""
    if attn_kernel == "flash":
        return True
    if attn_kernel in ("xla", "ring"):
        return False
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:
        return False


def generic_memory_ledger(params: Any, opt_state: Any = None,
                          input_bytes: float = 0.0,
                          capacity_bytes: Optional[float] = None) -> Dict[str, Any]:
    """Tree-only ledger for models without a priced geometry (train_vae):
    params + f32 grads + optimizer moments + the device batch.  Activations
    are deliberately absent — a conv working-set model would be guesswork —
    so the verdict is a LOWER bound (stated in the report)."""
    param_bytes = tree_float_bytes(params)
    rows = [
        {"name": "params", "bytes": param_bytes, "detail": "storage dtypes"},
        {"name": "grads", "bytes": tree_float_bytes(params, itemsize=4),
         "detail": "f32 gradient buffer"},
        {"name": "opt_state",
         "bytes": (tree_float_bytes(opt_state) if opt_state is not None
                   else 2.0 * tree_float_bytes(params, itemsize=4)),
         "detail": "optimizer moments"},
    ]
    if input_bytes:
        rows.append({"name": "inputs", "bytes": input_bytes * 1.0,
                     "detail": "device batch"})
    ledger = _finish_ledger(rows, capacity_bytes=capacity_bytes)
    ledger["lower_bound"] = True  # no activation row
    return ledger


def sampling_memory_ledger(cfg: Any, batch: int, params: Any = None,
                           itemsize: Optional[int] = None,
                           capacity_bytes: Optional[float] = None,
                           paged_pool: Optional[Mapping[str, Any]] = None,
                           ) -> Dict[str, Any]:
    """The generation path's ledger: params + the KV cache the cached decode
    loop carries (2 x depth x b x seq x heads x dim_head in the param dtype,
    models/sampling.init_cache) + the per-position logits buffer.

    `paged_pool` ({num_blocks, block_size, num_slots, itemsize?} — see
    serving/kv_pool.paged_ledger_entry) switches the KV row to the serving
    engine's shape: the shared block pool at rest plus the transient
    one-layer dense gather the paged decode step materializes (`batch` then
    counts decode SLOTS, not a dense request batch)."""
    if itemsize is None:
        itemsize = 4
        if params is not None:
            import jax
            import jax.numpy as jnp

            leaves = [x for x in jax.tree_util.tree_leaves(params)
                      if hasattr(x, "dtype")
                      and jnp.issubdtype(jnp.result_type(x), jnp.floating)]
            if leaves:
                itemsize = _itemsize(leaves[0].dtype)
    rows = []
    if params is not None:
        from dalle_pytorch_tpu.quantization import (
            tree_is_quantized,
            tree_weight_bytes,
        )

        if tree_is_quantized(params):
            rows.append({"name": "params", "bytes": tree_weight_bytes(params),
                         "detail": "int8 matmul blocks + float scales/rest"})
        else:
            rows.append({"name": "params", "bytes": tree_float_bytes(params),
                         "detail": "storage dtypes"})
    if paged_pool is not None:
        nb = int(paged_pool["num_blocks"])  # host-sync-ok: static pool geometry
        bs = int(paged_pool["block_size"])  # host-sync-ok: static pool geometry
        slots = int(paged_pool.get("num_slots", batch))
        isz = int(paged_pool.get("itemsize", itemsize))
        kv_quant = paged_pool.get("kv_quant")
        if kv_quant:
            from dalle_pytorch_tpu.quantization import kv_bytes_per_elem

            bpe = kv_bytes_per_elem(kv_quant, isz, cfg.dim_head)
            pool_bytes = 2.0 * cfg.depth * nb * cfg.heads * bs * cfg.dim_head * bpe
            detail = (f"{nb} blocks x {bs} tok x 2 x depth x h x dh, "
                      f"{kv_quant} + per-token scales (shared, at rest)")
        else:
            pool_bytes = 2.0 * cfg.depth * nb * cfg.heads * bs * cfg.dim_head * isz
            detail = (f"{nb} blocks x {bs} tok x 2 x depth x h x dh "
                      "(shared, at rest)")
        rows.append({"name": "paged_kv_pool", "bytes": pool_bytes,
                     "detail": detail})
        # the paged decode gathers ONE layer's dense view per slot at a time
        gather = 2.0 * slots * cfg.heads * cfg.total_seq_len * cfg.dim_head * isz
        rows.append({"name": "paged_gather", "bytes": gather,
                     "detail": f"one layer's dense view x {slots} slots (transient)"})
    else:
        kv = 2.0 * cfg.depth * batch * cfg.total_seq_len * cfg.heads * cfg.dim_head * itemsize
        rows.append({"name": "kv_cache", "bytes": kv,
                     "detail": f"2 x depth x b{batch} x s{cfg.total_seq_len} x h x dh"})
    rows.append({"name": "logits", "bytes": 1.0 * batch * cfg.total_tokens * 4,
                 "detail": "per-position vocab logits (f32)"})
    extra = _decode_read_accounting(cfg, batch, itemsize)
    if extra is not None:
        gather_row, read_bytes = extra
        rows.append(gather_row)
        return _finish_ledger(rows, batch=batch, capacity_bytes=capacity_bytes,
                              decode_kv_read_bytes_per_step=read_bytes)
    return _finish_ledger(rows, batch=batch, capacity_bytes=capacity_bytes)


def _decode_read_accounting(cfg: Any, batch: int, itemsize: int):
    """Pattern-limited decode-read pricing for the sparse-aware decode
    (models/transformer._attention_cached with decode tables): per step each
    pattern layer gathers only its Kmax permitted keys instead of reading the
    full seq_len cache row.  Returns (transient gather row, per-step KV read
    bytes summed over layers) — the row is the (b, h, Kmax, dh) K/V transient
    (one layer live at a time, so max over layers), the read total is what
    the decode step actually moves, shared by construction with
    sparse_index.decode_kv_span.  None when the config has no transformer
    view or sparse decode is off (full-cache reads are already priced by the
    kv_cache row's width)."""
    if not hasattr(cfg, "transformer_config"):
        return None
    try:
        tcfg = cfg.transformer_config()
    except Exception:
        return None
    if not getattr(tcfg, "sparse_decode", False):
        return None
    from dalle_pytorch_tpu.kernels.sparse_index import decode_kv_span
    from dalle_pytorch_tpu.models.transformer import (
        _pattern_for, _pattern_key, derive_layer_specs,
    )

    n = tcfg.seq_len
    spans = {}
    read_bytes = 0.0
    kmax = 0
    any_pattern = False
    for spec in derive_layer_specs(tcfg):
        key = _pattern_key(spec)
        if key not in spans:
            pm = _pattern_for(tcfg, key[0], key[1])
            spans[key] = decode_kv_span(pm, n)
            any_pattern |= pm is not None
        span = spans[key]
        read_bytes += 2.0 * batch * tcfg.heads * span * tcfg.dim_head * itemsize
        if span < n:  # full layers read the cache in place, no gather
            kmax = max(kmax, span)
    if not any_pattern:
        return None
    row = {
        "name": "decode_gather",
        "bytes": 2.0 * batch * tcfg.heads * kmax * tcfg.dim_head * itemsize,
        "detail": (f"sparse decode K/V gather, Kmax {kmax} of s{n} "
                   "(transient, one layer)"),
    }
    return row, read_bytes


def publish_gauges(ledger: Mapping[str, Any], registry=None) -> None:
    """Mirror the ledger into `mem/*` gauges — one per row plus the total,
    the verdict, and the capacity the verdict was priced against."""
    reg = registry if registry is not None else metrics_mod.REGISTRY
    for row in ledger.get("rows", []):
        reg.gauge(f"mem/{row['name']}_bytes").set(row["bytes"])
    reg.gauge("mem/total_bytes").set(ledger["total_bytes"])
    if ledger.get("capacity_bytes"):
        reg.gauge("mem/capacity_bytes").set(ledger["capacity_bytes"])
        reg.gauge("mem/headroom_frac").set(ledger["headroom_frac"])
        reg.gauge("mem/fits").set(1.0 if ledger["fits"] else 0.0)


# ---------------------------------------------------------------------------
# XLA memory-analysis cross-check + donation audit
# ---------------------------------------------------------------------------

def step_memory_analysis(step_fn: Callable, *args) -> Optional[Dict[str, float]]:
    """The compiled executable's own memory accounting:
    {argument_bytes, output_bytes, temp_bytes, alias_bytes,
    generated_code_bytes, total_bytes} per device, or None where the
    backend/compiler doesn't expose `memory_analysis()`.

    Accepts the same shapes as xla.step_cost_analysis (a jitted function or
    a wrapper with `.jitted`/`.mesh`).  NOTE: this compiles via
    `.lower(...).compile()` — a real backend compile, not just a trace —
    so callers shield it behind `CompileWatcher.suspended()` and run it
    sparingly (the Telemetry facade does both)."""
    target = getattr(step_fn, "jitted", step_fn)
    if not hasattr(target, "lower"):
        return None
    import contextlib

    mesh = getattr(step_fn, "mesh", None)
    ctx = contextlib.nullcontext()
    if mesh is not None:
        from dalle_pytorch_tpu.parallel.mesh import mesh_context

        ctx = mesh_context(mesh)
    try:
        with ctx:
            ma = target.lower(*args).compile().memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0) * 1.0,
        "output_bytes": getattr(ma, "output_size_in_bytes", 0) * 1.0,
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0) * 1.0,
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0) * 1.0,
        "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", 0) * 1.0,
    }
    # live peak model: arguments + scratch + program text + whatever output
    # is NOT aliased back onto a donated argument
    out["total_bytes"] = (
        out["argument_bytes"] + out["temp_bytes"] + out["generated_code_bytes"]
        + max(out["output_bytes"] - out["alias_bytes"], 0.0)
    )
    return out


def audit_donation(analysis: Mapping[str, float], expected_bytes: float,
                   min_frac: float = 0.5) -> Dict[str, Any]:
    """Did `donate_argnums` actually alias the train state?  `expected_bytes`
    is the per-chip at-rest bytes of the donated argument (the ledger's
    params + opt_state rows); XLA reports what it aliased as
    `alias_size_in_bytes`.  Donation silently dropping (dtype mismatch
    between argument and result, an aliasing-unsupported backend, a wrapper
    re-jitting without the donation) shows up as aliased << expected —
    doubled train-state residency with no error anywhere else."""
    donated = analysis.get("alias_bytes") or 0.0
    frac = donated / expected_bytes if expected_bytes > 0 else None
    ok = frac is not None and frac >= min_frac
    metrics_mod.gauge("mem/donated_bytes").set(donated)
    if not ok:
        metrics_mod.counter("donation_dropped_alarms").inc()
    return {"donated_bytes": donated, "expected_bytes": expected_bytes + 0.0,
            "donated_frac": frac, "ok": ok}


class MemoryCrosscheck(FlopsCrosscheck):
    """Analytic HBM ledger vs `memory_analysis()` total, with the same
    drift-from-first-ratio persistence alarm as the FLOPs/comms checks.  The
    two will never be equal (XLA sees layout padding, fusion scratch, and
    rematerialization the analytic model prices coarsely) — the RATIO moving
    is what says a config change invalidated the ledger (or a lost donation
    / sharding annotation doubled a buffer XLA used to alias)."""

    RATIO_GAUGE = "xla_mem_over_analytic_bytes"
    ALARM_COUNTER = "mem_divergence_alarms"


# ---------------------------------------------------------------------------
# live headroom
# ---------------------------------------------------------------------------

class HbmMonitor:
    """Live allocator tracking at the telemetry flush cadence.

    `observe(step, stats)` takes the {key: max-across-devices} dict
    `xla.record_memory_gauges` returns, publishes the per-window
    `peak_bytes_in_use` delta, and fires ONE `hbm_headroom` alarm per
    episode when bytes_in_use crosses `headroom_frac` x capacity (re-armed
    with hysteresis when usage recedes below `rearm_frac`).  The alarm
    routes through the telemetry hub, so the on-alarm TraceTrigger captures
    the steps where the allocator is thrashing — while it still is.
    Episode state rides checkpoint meta (`state_dict`/`load_state_dict`,
    the DivergenceMonitor discipline) so a resumed run does not re-fire
    mid-episode."""

    def __init__(self, capacity_bytes: Optional[float] = None,
                 headroom_frac: float = 0.9,
                 rearm_frac: Optional[float] = None,
                 on_alarm: Optional[Callable[[Dict[str, Any]], None]] = None,
                 registry=None):
        self.capacity_bytes = (capacity_bytes if capacity_bytes is not None
                               else device_hbm_capacity())
        self.headroom_frac = headroom_frac
        self.rearm_frac = rearm_frac if rearm_frac is not None else headroom_frac * 0.95
        self.on_alarm = on_alarm
        self.registry = registry if registry is not None else metrics_mod.REGISTRY
        self.alarmed = False
        self.last_peak: Optional[float] = None
        self.alarms = 0

    def observe(self, step: Optional[int], stats: Optional[Mapping[str, float]]
                ) -> Optional[Dict[str, Any]]:
        if not stats:
            return None  # CPU: no allocator stats — degrade silently
        in_use = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        rec: Dict[str, Any] = {"step": step}
        if peak is not None:
            delta = peak - self.last_peak if self.last_peak is not None else 0.0
            self.last_peak = peak
            rec["peak_bytes_in_use"] = peak
            rec["peak_window_delta_bytes"] = delta
            self.registry.gauge("mem/peak_window_delta_bytes").set(delta)
        if in_use is not None:
            rec["bytes_in_use"] = in_use
        usage = None
        basis = in_use if in_use is not None else peak
        if self.capacity_bytes and basis is not None:
            usage = basis / self.capacity_bytes
            rec["usage_frac"] = usage
            self.registry.gauge("mem/usage_frac").set(usage)
        if usage is not None and self.headroom_frac:
            if usage >= self.headroom_frac and not self.alarmed:
                self.alarmed = True
                self.alarms += 1
                self.registry.counter("hbm_headroom_alarms").inc()
                if self.on_alarm is not None:
                    self.on_alarm({
                        "type": "hbm_headroom", "step": step,
                        "usage_frac": usage, "threshold": self.headroom_frac,
                        "bytes_in_use": basis,
                        "capacity_bytes": self.capacity_bytes,
                    })
            elif usage < self.rearm_frac:
                self.alarmed = False  # episode over — the next crossing fires
        rec["alarmed"] = self.alarmed
        return rec

    def state_dict(self) -> Dict[str, Any]:
        return {"alarmed": self.alarmed, "last_peak": self.last_peak,
                "alarms": self.alarms}

    def load_state_dict(self, state: Optional[Mapping[str, Any]]) -> None:
        if not state:
            return
        self.alarmed = bool(state.get("alarmed", False))
        self.last_peak = state.get("last_peak")
        self.alarms = state.get("alarms", 0) or 0


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("resource_exhausted", "resource exhausted", "out of memory",
                "ran out of memory", "oom while")


def is_oom_error(exc: BaseException) -> bool:
    """True when `exc` (or anything on its cause/context chain) is an XLA
    RESOURCE_EXHAUSTED / out-of-memory failure — the compile-time and
    step-time shapes both match."""
    seen = 0
    while exc is not None and seen < 8:
        msg = str(exc).lower()
        if any(m in msg for m in _OOM_MARKERS):
            return True
        exc = exc.__cause__ or exc.__context__
        seen += 1
    return False


def oom_suggestions(ledger: Optional[Mapping[str, Any]],
                    axes: Optional[Mapping[str, int]] = None,
                    settings: Any = None) -> List[str]:
    """Ranked, actionable config changes derived from which ledger row
    dominates.  Suggestions already in effect (remat already on, ZeRO
    already 3) are skipped, so the list stays applicable."""
    out: List[str] = []
    dominant = (ledger or {}).get("dominant")
    zero = int(getattr(settings, "zero_stage", 0) or 0) if settings is not None else 0
    accum = int(getattr(settings, "grad_accum", 1) or 1) if settings is not None else 1
    fsdp = int((axes or {}).get("fsdp", 1))

    def lowp(dtype_attr):
        dt = getattr(settings, dtype_attr, None) if settings is not None else None
        if dt is None:
            return False
        try:
            return _itemsize(dt) < 4
        except Exception:
            return False

    if dominant == "opt_state":
        if zero < 1:
            out.append("raise --zero_stage to 1 (shard optimizer moments over fsdp"
                       + ("; add --mesh_fsdp > 1 first" if fsdp <= 1 else "") + ")")
        elif zero < 3:
            out.append("raise --zero_stage to 3 (shard params + moments over fsdp)")
        out.append("switch the optimizer to adafactor (factored f32 stats are "
                   "O(rows+cols) instead of 2x params)")
    if dominant == "params":
        if not lowp("param_dtype"):
            out.append("--param_dtype bfloat16 (halves resident param storage; "
                       "stochastic-rounded updates)")
        if zero < 3:
            out.append("raise --zero_stage to 3 (params sharded over fsdp at rest)")
        out.append("add tensor/pipeline parallelism (--mesh_tp / --mesh_pp shard "
                   "params at rest)")
    if dominant in ("grads", "grad_accum"):
        if not lowp("grad_dtype"):
            out.append("set grad_dtype=bfloat16 in StepSettings (halves the "
                       "gradient buffer; sound with scale-invariant optimizers)")
        if zero < 2:
            out.append("raise --zero_stage to 2")
    if dominant == "activations":
        execution = ""
        for row in (ledger or {}).get("rows", []):
            if row["name"] == "activations":
                execution = row.get("detail", "")
        if execution.startswith("sequential"):
            out.append("--execution remat (recompute activations in backward "
                       "instead of keeping every layer live)")
        elif execution.startswith("remat/") and not execution.startswith("remat/full"):
            out.append("weaken --remat_policy toward 'full' (save fewer "
                       "per-layer tensors)")
        # already at remat/full (or reversible): the remat lever is spent
        out.append(f"raise --ga_steps (e.g. {max(accum * 2, 2)}) to shrink the "
                   "microbatch the activations are priced at")
        out.append("--scan_layers (stacked layers share one layer's buffers "
                   "under lax.scan)")
    if dominant == "kv_cache":
        out.append("shrink the generation --batch_size (the KV cache is linear "
                   "in it)")
        out.append("cast params (and so the cache) to bfloat16 for sampling")
    if dominant == "paged_kv_pool":
        out.append("shrink the serving pool (--num_blocks) or --block_size — "
                   "admission control will queue instead")
        out.append("cast params (and so the pool) to bfloat16 for serving")
    if dominant == "paged_gather":
        out.append("shrink --slots (the transient gather is linear in decode "
                   "slots)")
    out.append("shrink --batch_size (or shard it further with --mesh_dp/--mesh_fsdp)")
    return out


def format_ledger(ledger: Optional[Mapping[str, Any]]) -> str:
    """Human-readable ledger table (shared by the OOM report and
    tools/memory_report.py)."""
    if not ledger:
        return "  (no analytic ledger available)"
    lines = []
    total = ledger.get("total_bytes") or 0.0
    for row in ledger.get("rows", []):
        pct = 100.0 * row["bytes"] / total if total > 0 else 0.0
        mark = "  <-- dominant" if row["name"] == ledger.get("dominant") else ""
        lines.append(f"  {row['name']:<14} {row['bytes'] / 1e9:>9.3f} GB "
                     f"{pct:>5.1f}%  {row.get('detail', '')}{mark}")
    lines.append(f"  {'TOTAL':<14} {total / 1e9:>9.3f} GB")
    cap = ledger.get("capacity_bytes")
    if cap:
        verdict = "FITS" if ledger.get("fits") else "DOES NOT FIT"
        lines.append(f"  capacity       {cap / 1e9:>9.3f} GB per chip -> {verdict} "
                     f"(headroom {100.0 * (ledger.get('headroom_frac') or 0):.1f}%)")
    if ledger.get("lower_bound"):
        lines.append("  (activations not modeled for this architecture — "
                     "the total is a LOWER bound)")
    return "\n".join(lines)


def write_oom_report(dir: str, *, error: BaseException, phase: str,
                     ledger: Optional[Mapping[str, Any]] = None,
                     analysis: Optional[Mapping[str, float]] = None,
                     live_stats: Optional[Mapping[str, float]] = None,
                     context: Optional[Mapping[str, Any]] = None,
                     settings: Any = None,
                     process_index: int = 0) -> str:
    """Write `oom_report_<phase>[_pN]_<ts>.txt` under `dir`: what was
    resident (the ledger), what XLA planned (memory_analysis), what the
    allocator saw (live stats), and what to change (ranked suggestions).
    Returns the path.  Never raises — forensics must not mask the OOM."""
    try:
        d = Path(dir)
        d.mkdir(parents=True, exist_ok=True)
        ptag = f"_p{process_index}" if process_index else ""
        path = d / f"oom_report_{phase}{ptag}_{int(time.time())}.txt"
        lines = [
            "=" * 72,
            f"OUT OF MEMORY during {phase}",
            "=" * 72,
            "",
            "error:",
            "  " + "\n  ".join(str(error).splitlines()[:12] or ["<empty>"]),
            "",
        ]
        if context:
            lines.append("context:")
            for k, v in context.items():
                lines.append(f"  {k}: {v}")
            lines.append("")
        lines.append("analytic HBM ledger (per chip):")
        lines.append(format_ledger(ledger))
        lines.append("")
        if analysis:
            lines.append("XLA memory_analysis (per device):")
            for k, v in analysis.items():
                lines.append(f"  {k:<22} {v / 1e9:>9.3f} GB")
            lines.append("")
        if live_stats:
            lines.append("live allocator stats (max across local devices):")
            for k, v in sorted(live_stats.items()):
                lines.append(f"  {k:<28} {v / 1e9:>9.3f} GB")
            lines.append("")
        axes = (ledger or {}).get("mesh")
        lines.append("suggestions (ranked by the dominant ledger row):")
        for i, s in enumerate(oom_suggestions(ledger, axes, settings), 1):
            lines.append(f"  {i}. {s}")
        lines.append("")
        path.write_text("\n".join(lines))
        metrics_mod.counter("oom_reports_written").inc()
        return str(path)
    except Exception:  # pragma: no cover - forensics must never mask the OOM
        return ""


def provoke_oom(simulate_reason: str = "injected") -> None:
    """The `--inject_fault oom@STEP` payload: on TPU, allocate device
    buffers until the backend raises a REAL RESOURCE_EXHAUSTED; elsewhere
    (CPU — exhausting host RAM would take the machine down) raise a
    faithfully-shaped simulated error.  Either way the exception propagates
    into the CLI's forensic handler."""
    import jax

    if jax.default_backend() == "tpu":
        hold = []
        try:
            import jax.numpy as jnp

            cap = device_hbm_capacity()
            chunk = int(cap // 8 // 4)  # f32 elements, 1/8th of HBM per grab
            for _ in range(64):
                hold.append(jax.block_until_ready(  # host-sync-ok: chaos hook
                    jax.device_put(jnp.ones((chunk,), jnp.float32))
                ))
        finally:
            del hold
        # the allocator somehow satisfied 8x HBM — fall through to simulate
    raise jax.errors.JaxRuntimeError(
        f"RESOURCE_EXHAUSTED: [chaos] {simulate_reason} OOM: simulated "
        "out-of-memory while allocating device buffer"
    )
