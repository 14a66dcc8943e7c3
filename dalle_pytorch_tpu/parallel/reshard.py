"""Elastic resharding: move a TrainState between mesh topologies.

A preemption that gives back fewer (or differently-arranged) chips used to
end the run — `--resume auto` on sharded/multi-host configs failed loudly
(PR 3's documented restriction).  With the partitioning registry as the one
source of truth for placement, moving state between topologies is
mechanical: re-resolve every leaf's PartitionSpec against the TARGET mesh
and `device_put` it there.  XLA handles the data movement (a host round
trip at worst on CPU, resharding collectives on TPU); numerics are
untouched — tests/test_resharding.py proves a round trip dp8 → tp4×dp2 →
dp8 is bit-identical.

Before any device is touched, `reshard_preflight_ledger` prices the
at-rest per-chip footprint (params + gradient buffer + optimizer state, at
their exact registry shard fractions) on the target topology against the
per-device HBM capacity, and `reshard_state` REFUSES a reshard that cannot
fit (`ReshardPreflightError`) — a dp8 → dp2 shrink of a model that only
fit because it was 8-way sharded must fail with a ledger, not with a
RESOURCE_EXHAUSTED after minutes of compilation.

Everything here is `device_put` + the registry's host-side rule table — no
shard_map.

Host-side by design (this module runs BETWEEN steps, never inside a jit
trace); covered by tools/lint_host_sync.py with the deliberate host work
waived line-by-line."""
from __future__ import annotations

from typing import Any, Mapping, Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dalle_pytorch_tpu.parallel.registry import (
    PartitionRegistry,
    default_registry,
    normalize_mesh_axes,
)

P = PartitionSpec

__all__ = [
    "ReshardPreflightError",
    "reshard_preflight_ledger",
    "reshard_state",
    "reshard_tree",
]


class ReshardPreflightError(RuntimeError):
    """The target topology cannot hold the state at rest — refused BEFORE
    touching devices.  Carries the offending ledger as `.ledger`."""

    def __init__(self, message: str, ledger: Optional[dict] = None):
        super().__init__(message)
        self.ledger = ledger


def reshard_preflight_ledger(
    params: Any,
    opt_state: Any,
    mesh_or_axes: Union[Mesh, Mapping[str, int], None],
    *,
    zero_stage: int = 0,
    tensor_parallel: Optional[bool] = None,
    registry: Optional[PartitionRegistry] = None,
    grad_itemsize: Optional[int] = 4,
    capacity_bytes: Optional[float] = None,
) -> dict:
    """Per-chip AT-REST bytes of (params, gradient buffer, optimizer state)
    on the target topology, each row priced at its EXACT registry shard
    fraction — the PR 5 ledger's verdict machinery (`fits`, `dominant`,
    `headroom_frac`) applied to the resharding decision.  Activations are
    deliberately absent: this is the floor the state needs before a single
    step runs, i.e. a lower bound (stated in the row details).

    `grad_itemsize=None` skips the gradient row (offline checkpoint
    rewrites don't hold one)."""
    from dalle_pytorch_tpu.observability.memory import (
        _finish_ledger,
        tree_float_bytes,
    )
    from dalle_pytorch_tpu.quantization import tree_is_quantized, tree_weight_bytes

    reg = registry if registry is not None else default_registry()
    axes = normalize_mesh_axes(mesh_or_axes)
    p_frac = reg.shard_fraction(
        params, axes, zero_stage, tensor_parallel=tensor_parallel)
    quantized = tree_is_quantized(params)
    rows = [
        {"name": "params",
         "bytes": (tree_weight_bytes(params) if quantized
                   else tree_float_bytes(params)) * p_frac,
         "detail": (f"int8 blocks + scales x {p_frac:.4g} registry at-rest shard"
                    if quantized else
                    f"storage x {p_frac:.4g} registry at-rest shard")},
    ]
    if grad_itemsize is not None:
        rows.append(
            {"name": "grads",
             "bytes": tree_float_bytes(params, itemsize=grad_itemsize) * p_frac,
             "detail": f"grad buffer x {p_frac:.4g}"})
    if opt_state is not None:
        m_frac = reg.shard_fraction(
            opt_state, axes, zero_stage, tensor_parallel=tensor_parallel,
            moments=True)
        opt_bytes = tree_float_bytes(opt_state)
    else:
        # no live tree: estimate adam (two f32 moments per param), sharded
        # like params-shaped moments
        m_frac = reg.shard_fraction(
            params, axes, zero_stage, tensor_parallel=tensor_parallel,
            moments=True, itemsize=4)
        opt_bytes = 2.0 * tree_float_bytes(params, itemsize=4)
    rows.append({"name": "opt_state", "bytes": opt_bytes * m_frac,
                 "detail": f"zero_stage {zero_stage} x {m_frac:.4g}"})
    ledger = _finish_ledger(rows, axes=axes, capacity_bytes=capacity_bytes)
    ledger["lower_bound"] = True  # no activation row — at-rest floor only
    ledger["registry_fingerprint"] = reg.fingerprint()
    return ledger


def _place(tree: Any, specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda spec, leaf: jax.device_put(leaf, NamedSharding(mesh, spec)),
        specs,
        tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def reshard_tree(
    tree: Any,
    new_mesh: Mesh,
    *,
    registry: Optional[PartitionRegistry] = None,
    zero_stage: int = 0,
    tensor_parallel: Optional[bool] = None,
    moments: bool = False,
) -> Any:
    """Re-place one pytree (live or host-restored) onto `new_mesh` under the
    registry rules."""
    reg = registry if registry is not None else default_registry()
    specs = reg.tree_specs(tree, new_mesh, zero_stage,
                           tensor_parallel=tensor_parallel, moments=moments)
    return _place(tree, specs, new_mesh)


def reshard_state(
    state: Any,
    old_mesh: Union[Mesh, Mapping[str, int], None],
    new_mesh: Mesh,
    *,
    registry: Optional[PartitionRegistry] = None,
    zero_stage: int = 0,
    tensor_parallel: Optional[bool] = None,
    preflight: bool = True,
    capacity_bytes: Optional[float] = None,
    grad_itemsize: Optional[int] = 4,
) -> Any:
    """Move a live TrainState from `old_mesh`'s topology onto `new_mesh`
    (dp8 → tp4×dp2, a pp2 shrink, ...): every param and optimizer leaf is
    re-resolved against the TARGET mesh through the registry and device_put
    there; the step counter is replicated.  `old_mesh` identifies where the
    state came from — it is reported in errors and lets callers log the
    transition; the placement itself needs only the target.

    With `preflight` (default), the at-rest memory ledger for the target
    topology is checked FIRST and a reshard that cannot fit raises
    ReshardPreflightError without touching a device."""
    from dalle_pytorch_tpu.parallel.train_step import TrainState

    reg = registry if registry is not None else default_registry()
    if preflight:
        ledger = reshard_preflight_ledger(
            state.params, state.opt_state, new_mesh,
            zero_stage=zero_stage, tensor_parallel=tensor_parallel,
            registry=reg, grad_itemsize=grad_itemsize,
            capacity_bytes=capacity_bytes,
        )
        if ledger["fits"] is False:
            raise ReshardPreflightError(
                "reshard refused: moving this state from "
                f"{normalize_mesh_axes(old_mesh) or 'single-chip'} to "
                f"{normalize_mesh_axes(new_mesh) or 'single-chip'} needs "
                f"{ledger['total_bytes'] / 1e9:.2f}GB per chip at rest "
                f"(dominant: {ledger['dominant']}) but only "
                f"{ledger['capacity_bytes'] / 1e9:.2f}GB is available — "
                "the target topology cannot hold it before a single step "
                "runs.  Use more chips, a higher --zero_stage, or bf16 "
                "param storage.",
                ledger=ledger,
            )
    params = reshard_tree(
        state.params, new_mesh, registry=reg, zero_stage=zero_stage,
        tensor_parallel=tensor_parallel)
    opt_state = reshard_tree(
        state.opt_state, new_mesh, registry=reg, zero_stage=zero_stage,
        tensor_parallel=tensor_parallel, moments=True)
    step = jax.device_put(state.step, NamedSharding(new_mesh, P()))
    return TrainState(step, params, opt_state)
