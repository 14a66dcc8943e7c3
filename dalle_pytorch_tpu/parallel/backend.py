"""Distributed-backend facade.

Mirrors the reference's pluggable backend abstraction
(/root/reference/dalle_pytorch/distributed_utils.py and
distributed_backends/distributed_backend.py:12-178) — the same registry,
arg-parser wrapping, and worker-topology queries — with the DeepSpeed and
Horovod engines replaced by ONE JaxBackend: `initialize` joins the multi-host
world (jax.distributed), `distribute` builds a mesh-sharded train step
(parallel/train_step.py), and `average_all` is a cross-process mean.  The
DummyBackend keeps every code path runnable single-process, like the
reference's dummy backend."""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.parallel.mesh import MeshConfig, make_mesh
from dalle_pytorch_tpu.parallel.train_step import StepSettings, make_train_step


class DistributedBackend:
    """Template-method base class (parity with distributed_backend.py)."""

    BACKEND_NAME = "None"
    ROOT_RANK = 0

    def __init__(self):
        self.is_initialized = False

    # -- lifecycle ---------------------------------------------------------
    def has_backend(self) -> bool:
        return True

    def initialize(self):
        self._initialize()
        self.is_initialized = True

    def _initialize(self):
        raise NotImplementedError

    def require_init(self):
        assert self.is_initialized, (
            f"{self.BACKEND_NAME} backend not initialized; call initialize() first"
        )

    # -- argparse ----------------------------------------------------------
    def wrap_arg_parser(self, parser):
        return parser

    # -- topology ----------------------------------------------------------
    def get_world_size(self) -> int:
        self.require_init()
        return self._get_world_size()

    def get_rank(self) -> int:
        self.require_init()
        return self._get_rank()

    def get_local_rank(self) -> int:
        self.require_init()
        return self._get_local_rank()

    def is_root_worker(self) -> bool:
        return self.get_rank() == self.ROOT_RANK

    def is_local_root_worker(self) -> bool:
        return self.get_local_rank() == self.ROOT_RANK

    def local_barrier(self):
        self.require_init()
        self._local_barrier()

    # -- work distribution -------------------------------------------------
    def check_batch_size(self, batch_size: int):
        assert batch_size >= self.get_world_size(), (
            f"batch size can't be smaller than number of processes "
            f"({batch_size} < {self.get_world_size()})"
        )

    def distribute(
        self,
        loss_fn=None,
        params: Any = None,
        optimizer: Any = None,
        training_data: Any = None,
        lr_scheduler: Any = None,
        mesh_config: Optional[MeshConfig] = None,
        settings: StepSettings = StepSettings(),
        **kwargs,
    ):
        """Build the distributed training artifacts.  Returns
        (state, step_fn, training_data, lr_scheduler) — the 4-tuple shape of
        the reference's `distribute`, with the wrapped model/optimizer pair
        replaced by (sharded TrainState, jitted step_fn)."""
        self.require_init()
        return self._distribute(
            loss_fn, params, optimizer, training_data, lr_scheduler, mesh_config, settings, **kwargs
        )

    def average_all(self, value):
        self.require_init()
        return self._average_all(value)


class DummyBackend(DistributedBackend):
    """Single-process no-op backend (parity with dummy_backend.py)."""

    BACKEND_NAME = "Dummy"

    def _initialize(self):
        pass

    def _get_world_size(self) -> int:
        return 1

    def _get_rank(self) -> int:
        return self.ROOT_RANK

    def _get_local_rank(self) -> int:
        return self.ROOT_RANK

    def _local_barrier(self):
        pass

    def _distribute(self, loss_fn, params, optimizer, training_data, lr_scheduler,
                    mesh_config, settings, use_mesh: bool = True,
                    registry=None, param_rule=None, **kwargs):
        mesh = make_mesh(mesh_config or MeshConfig()) if use_mesh else None
        init_fn, step_fn = make_train_step(
            loss_fn, optimizer, mesh=mesh, settings=settings, registry=registry,
            param_rule=param_rule)
        return init_fn(params), step_fn, training_data, lr_scheduler

    def _average_all(self, value):
        return value


class JaxBackend(DistributedBackend):
    """Multi-host TPU backend: one process per host, XLA collectives over
    ICI/DCN, mesh sharding instead of NCCL all-reduce."""

    BACKEND_NAME = "Jax"

    def wrap_arg_parser(self, parser):
        parser.add_argument(
            "--coordinator_address", type=str, default=None,
            help="host:port of process 0 for jax.distributed.initialize",
        )
        parser.add_argument("--num_processes", type=int, default=None)
        parser.add_argument("--process_id", type=int, default=None)
        return parser

    def __init__(self, coordinator_address=None, num_processes=None, process_id=None):
        super().__init__()
        self._coord = (coordinator_address, num_processes, process_id)

    def _initialize(self):
        coord, num, pid = self._coord
        if coord is not None:
            if (num is None) != (pid is None):
                raise ValueError(
                    "--num_processes and --process_id must be given together "
                    "(or both omitted for TPU-pod auto-detection)"
                )
            jax.distributed.initialize(coord, num, pid)
        elif _tpu_pod_env() and not jax.distributed.is_initialized():
            # NB: nothing here may touch a device or ask jax.process_count()
            # first — that initialises the backend, after which
            # jax.distributed.initialize() refuses to run
            jax.distributed.initialize()

    def _get_world_size(self) -> int:
        return jax.process_count()

    def _get_rank(self) -> int:
        return jax.process_index()

    def _get_local_rank(self) -> int:
        return 0  # one process per host on TPU

    def _local_barrier(self):
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("dalle_pytorch_tpu.barrier")

    def _distribute(self, loss_fn, params, optimizer, training_data, lr_scheduler,
                    mesh_config, settings, registry=None, param_rule=None, **kwargs):
        mesh = make_mesh(mesh_config or MeshConfig())
        init_fn, step_fn = make_train_step(
            loss_fn, optimizer, mesh=mesh, settings=settings, registry=registry,
            param_rule=param_rule)
        return init_fn(params), step_fn, training_data, lr_scheduler

    def _average_all(self, value):
        if jax.process_count() == 1:
            return value
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(jnp.asarray(value))
        return np.mean(np.asarray(gathered), axis=0)


def _tpu_pod_env() -> bool:
    import os

    return any(k in os.environ for k in ("TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS"))


# --- registry (parity with distributed_utils.py) ---------------------------

_DEFAULT = "none"
BACKENDS = {
    "none": DummyBackend,
    "dummy": DummyBackend,
    "jax": JaxBackend,
}

is_distributed: Optional[bool] = None
backend: Optional[DistributedBackend] = None


def wrap_arg_parser(parser):
    parser.add_argument(
        "--distributed_backend",
        "--distr_backend",
        type=str,
        default=_DEFAULT,
        help="which distributed backend to use (none | jax)",
    )
    for b in set(BACKENDS.values()):
        parser = b().wrap_arg_parser(parser)
    return parser


def set_backend_from_args(args):
    """Select and return the backend module-level singleton."""
    global is_distributed, backend
    name = getattr(args, "distributed_backend", _DEFAULT).lower()
    if name not in BACKENDS:
        raise ValueError(f"unknown distributed backend: {name!r} (choose from {sorted(BACKENDS)})")
    if name == "jax":
        backend = JaxBackend(
            getattr(args, "coordinator_address", None),
            getattr(args, "num_processes", None),
            getattr(args, "process_id", None),
        )
        is_distributed = True
    else:
        backend = DummyBackend()
        is_distributed = False
    return backend


def using_backend(test_backend) -> bool:
    global backend
    if isinstance(test_backend, str):
        return backend is not None and backend.BACKEND_NAME.lower() == test_backend.lower()
    return isinstance(backend, test_backend)
