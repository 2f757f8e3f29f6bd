"""Device-mesh construction for the parameter-server layout.

The reference runs ``workerParallelism`` worker subtasks and ``psParallelism``
server subtasks as separate Flink operators connected by a network shuffle
(``FlinkParameterServer.transform``, expected upstream path
``src/main/scala/hu/sztaki/ilab/ps/FlinkParameterServer.scala``).

On TPU we use an SPMD layout instead: every chip is *both* a worker and a
parameter shard. The mesh has two named axes:

* ``data``  — pure data parallelism: parameter tables are **replicated** along
  it, the example stream is split across it.
* ``shard`` — the parameter-server axis: tables are **row-sharded** along it
  (the analog of ``psParallelism``), and the example stream is split across it
  too (workers = all devices).

So ``workerParallelism == data * shard`` and ``psParallelism == shard``.
A plain single-axis PS is ``data=1``.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names used throughout the framework.
DATA_AXIS = "data"
SHARD_AXIS = "shard"


def make_ps_mesh(
    num_shards: int | None = None,
    num_data: int = 1,
    *,
    devices=None,
) -> Mesh:
    """Build a ``(data, shard)`` mesh over the available devices.

    Args:
      num_shards: size of the parameter-shard axis (the reference's
        ``psParallelism``). Defaults to ``len(devices) // num_data``.
      num_data: size of the replicated data-parallel axis.
      devices: optional explicit device list (defaults to ``jax.devices()``).

    Returns:
      A ``jax.sharding.Mesh`` with axes ``('data', 'shard')``.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if num_shards is None:
        if n % num_data != 0:
            raise ValueError(f"{n} devices not divisible by num_data={num_data}")
        num_shards = n // num_data
    if num_data * num_shards != n:
        raise ValueError(
            f"mesh {num_data}x{num_shards} does not cover {n} devices"
        )
    dev_grid = np.asarray(devices).reshape(num_data, num_shards)
    return Mesh(dev_grid, (DATA_AXIS, SHARD_AXIS))


def default_mesh_shape(n_devices: int) -> tuple[int, int]:
    """Factor ``n_devices`` into a (data, shard) shape.

    Prefers a square-ish split with shard >= data so that parameter sharding
    (the scarce resource: HBM) gets the larger axis.
    """
    best = (1, n_devices)
    d = int(math.isqrt(n_devices))
    while d >= 1:
        if n_devices % d == 0 and n_devices // d >= d:
            best = (d, n_devices // d)
            break
        d -= 1
    return best


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     local_device_ids=None) -> None:
    """Initialize multi-host JAX (call once per process, before any jax use).

    Thin wrapper over ``jax.distributed.initialize``: on TPU pods the
    arguments are auto-detected from the environment; on CPU/GPU fleets pass
    the coordinator address and process topology explicitly. After this,
    ``jax.devices()`` spans every host and :func:`make_ps_mesh` builds a
    global mesh — the framework's collectives then ride ICI within a slice
    and DCN across hosts, replacing the reference's Netty/Akka fabric for
    the multi-node case.
    """
    # CPU fleets (and the multi-process test harness): cross-process
    # collectives need the gloo transport; without it the CPU backend
    # refuses multiprocess computations outright. TPU ignores it.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


def host_to_replicated(x, mesh: Mesh):
    """Place a host array replicated over ``mesh``, multi-controller safe.

    Single-process: a plain ``device_put``. Multi-process (mesh spans
    non-addressable devices): every process supplies its identical local
    copy via ``make_array_from_process_local_data``.
    """
    sh = NamedSharding(mesh, PartitionSpec())
    if sh.is_fully_addressable:
        return jax.device_put(x, sh)
    return jax.make_array_from_process_local_data(sh, np.asarray(x))


def host_to_sharded(x, sharding: NamedSharding):
    """Place a GLOBAL host array onto a (possibly multi-process) sharding.

    Single-process: a plain ``device_put``. Multi-process: every process
    passes the identical full array and
    ``make_array_from_process_local_data`` slices out each process's
    addressable portion (the documented ``global_shape == data.shape``
    mode, which requires the data to be identical across hosts — exactly
    the host-ingest contract: every process runs the same deterministic
    chunk iterator).
    """
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    x = np.asarray(x)
    return jax.make_array_from_process_local_data(sharding, x, x.shape)


_KEY_PUT_CACHE: dict = {}


def key_to_replicated(key, mesh: Mesh):
    """Place a PRNG key replicated over ``mesh``, multi-controller safe.

    Key arrays have an extended dtype numpy can't hold, so the key *data*
    (identical in every process) rides through a jitted re-wrap with
    replicated output sharding.
    """
    sh = NamedSharding(mesh, PartitionSpec())
    if sh.is_fully_addressable:
        return jax.device_put(key, sh)
    fn = _KEY_PUT_CACHE.get(mesh)
    if fn is None:
        fn = _KEY_PUT_CACHE[mesh] = jax.jit(
            jax.random.wrap_key_data, out_shardings=sh
        )
    return fn(np.asarray(jax.random.key_data(key)))


_REPLICATE_CACHE: dict = {}


def replicate_to_mesh(x, mesh: Mesh):
    """Replicate a (possibly sharded) device array over ``mesh`` through a
    per-mesh cached jitted identity.

    NOTE: in multi-controller runs this is a COLLECTIVE — every process of
    the mesh must call it (a lone process blocks forever waiting for the
    others' shards). Host-read helpers built on it (``ParamStore``'s
    ``lookup_host``/``dump_model``) inherit that contract.
    """
    fn = _REPLICATE_CACHE.get(mesh)
    if fn is None:
        fn = _REPLICATE_CACHE[mesh] = jax.jit(
            lambda a: a, out_shardings=NamedSharding(mesh, PartitionSpec())
        )
    return fn(x)
