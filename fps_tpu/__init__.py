"""fps_tpu — a TPU-native parameter-server framework.

A ground-up rebuild of the capabilities of ``lucaRadicalbit/flink-parameter-server-1``
(Scala on Apache Flink DataStream) as an idiomatic JAX/XLA framework for TPU:

* parameters live in **sharded jax arrays in HBM** (the reference's server shards —
  ``ParameterServerLogic`` instances holding hash partitions of the id space;
  expected upstream path ``src/main/scala/hu/sztaki/ilab/ps/``),
* **pull** is a collective gather (``all_gather`` + ``psum_scatter`` over the ICI
  mesh) instead of a Flink record routed by ``partitionCustom(hash(paramId))``,
* **push** is a collective scatter-add instead of a ``Push(id, delta)`` envelope,
* the training loop is a ``jax.lax.scan`` / ``while_loop`` step driver instead of
  Flink's ``ConnectedIterativeStreams`` feedback edge,
* async/SSP bounded staleness is a snapshot-refresh schedule inside the compiled
  loop instead of the reference's free-running operator asynchrony.

The user contract mirrors the reference's two-trait API (``WorkerLogic`` /
``ParameterServerLogic``) in functional form — see :mod:`fps_tpu.core.api`.
"""

from fps_tpu.core.api import ServerLogic, WorkerLogic, StepOutput
from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
from fps_tpu.core.driver import Trainer, TrainerConfig, num_workers_of
from fps_tpu.core.resilience import (
    GuardConfig,
    PoisonedStreamError,
    RollbackPolicy,
    SnapshotCorruptionError,
)
from fps_tpu.core.checkpoint import AsyncCheckpointer, Checkpointer
from fps_tpu.core.store import TableSpec, ParamStore
from fps_tpu.parallel.mesh import init_distributed, make_ps_mesh
from fps_tpu import obs
from fps_tpu import serve
from fps_tpu import supervise

__version__ = "0.1.0"

__all__ = [
    "ServerLogic",
    "WorkerLogic",
    "StepOutput",
    "TableSpec",
    "ParamStore",
    "Trainer",
    "TrainerConfig",
    "num_workers_of",
    "DeviceDataset",
    "DeviceEpochPlan",
    "make_ps_mesh",
    "init_distributed",
    "GuardConfig",
    "RollbackPolicy",
    "SnapshotCorruptionError",
    "PoisonedStreamError",
    "Checkpointer",
    "AsyncCheckpointer",
    "obs",
    "serve",
    "supervise",
    "__version__",
]
