"""Device-mesh construction.

The reference scales out through NCCL process groups managed by DeepSpeed /
Horovod launchers (SURVEY.md §2 rows 15-19).  The TPU-native replacement is a
single logical `jax.sharding.Mesh` over all devices with four named axes:

  dp    pure data parallelism (gradients all-reduced by XLA over ICI)
  fsdp  data parallelism + parameter/optimizer sharding (ZeRO-3 style)
  tp    tensor parallelism (attention heads / ff hidden sharded)
  sp    sequence/context parallelism (ring attention, parallel/ring.py)
  pp    pipeline parallelism (GPipe stage schedule, parallel/pipeline.py)

Collectives are never called explicitly for training — XLA emits them from
sharding annotations, riding ICI within a slice and DCN across slices (the
one exception: the pipeline's stage-hop ppermute, which is manual by nature).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_PP = "pp"
MESH_AXES = (AXIS_DP, AXIS_FSDP, AXIS_TP, AXIS_SP, AXIS_PP)

# batch is sharded over every data-like axis
BATCH_AXES = (AXIS_DP, AXIS_FSDP)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp: int = -1  # -1: absorb all remaining devices
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        fixed = self.fsdp * self.tp * self.sp * self.pp
        dp = self.dp
        if dp == -1:
            assert n_devices % fixed == 0, (n_devices, fixed)
            dp = n_devices // fixed
        assert dp * fixed == n_devices, (
            f"mesh {dp}x{self.fsdp}x{self.tp}x{self.sp}x{self.pp} != {n_devices} devices"
        )
        return MeshConfig(dp, self.fsdp, self.tp, self.sp, self.pp)


# Framework-owned record of the innermost `with mesh:` block.  jax keeps its
# context mesh in private thread-resources state; rather than reaching into
# it, every mesh built here is a ContextMesh that also registers itself on
# enter (contextvar → survives threads spawned per context, unlike a plain
# global).
_ACTIVE_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "dalle_tpu_active_mesh", default=None
)
# Mesh forbids setattr (immutable), so enter/exit tokens live in a
# context-local stack beside the contextvar rather than on the instance.
_MESH_TOKENS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "dalle_tpu_mesh_tokens", default=()
)


class ContextMesh(Mesh):
    """`jax.sharding.Mesh` that additionally publishes itself to
    `active_mesh()` while entered, so model code can discover the ambient
    mesh through a public, framework-owned channel."""

    def __enter__(self):
        token = _ACTIVE_MESH.set(self)
        _MESH_TOKENS.set(_MESH_TOKENS.get() + (token,))
        return super().__enter__()

    def __exit__(self, *exc):
        tokens = _MESH_TOKENS.get()
        if not tokens:
            raise RuntimeError(
                "ContextMesh.__exit__ called with no matching __enter__ on "
                "this context: the enter/exit token stack is empty.  This "
                "happens when __exit__ runs in a different thread/context "
                "than __enter__ (contextvars don't propagate backwards into "
                "threads started before the enter), or when exits are "
                "unbalanced (e.g. calling __exit__ twice).  Enter and exit "
                "the mesh from the same thread, or use "
                "dalle_pytorch_tpu.parallel.mesh.mesh_context()."
            )
        _MESH_TOKENS.set(tokens[:-1])
        try:
            _ACTIVE_MESH.reset(tokens[-1])
        except ValueError as e:
            raise RuntimeError(
                "ContextMesh.__exit__: the innermost enter token is not "
                "valid in this context — mesh enters/exits are interleaved "
                "across threads or out of order (exit meshes in LIFO order, "
                "from the thread that entered them)."
            ) from e
        return super().__exit__(*exc)


def active_mesh() -> Optional[Mesh]:
    """The innermost entered ContextMesh, or — for users driving jax's own
    mesh plumbing — the (abstract) mesh installed via `jax.sharding.set_mesh`;
    both answer inside a jit trace, where model code asks.  A plain
    `jax.sharding.Mesh` entered with a bare `with mesh:` publishes itself to
    neither; enter it through `mesh_context()` instead."""
    mesh = _ACTIVE_MESH.get()
    if mesh is not None:
        return mesh
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    """Enter `mesh` AND publish it to `active_mesh()`.  Use this (not a bare
    `with mesh:`) when the mesh may be a plain `jax.sharding.Mesh` a user
    built themselves — a ContextMesh publishes itself, a plain Mesh does
    not, and model code (ring attention, pipeline engagement) discovers the
    ambient mesh through `active_mesh()`."""
    token = _ACTIVE_MESH.set(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def axis_sizes(mesh) -> dict:
    """{axis: size} for a Mesh — or a plain mapping passed through (the comms
    model prices hypothetical meshes from their shape alone, no devices
    needed).  Unnamed axes default to 1 on lookup, so callers can ask for any
    of MESH_AXES regardless of how the mesh was built."""
    if isinstance(mesh, Mesh):
        return dict(mesh.shape)
    return dict(mesh)


def make_mesh(cfg: MeshConfig = MeshConfig(), devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    cfg = cfg.resolve(len(devices))
    arr = np.asarray(devices).reshape(cfg.dp, cfg.fsdp, cfg.tp, cfg.sp, cfg.pp)
    return ContextMesh(arr, MESH_AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(BATCH_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
