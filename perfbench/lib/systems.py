"""The system under test, as the benchmark drives it.

:class:`System` is the driving surface every model kind's adapter
(``perfbench/models/<kind>.py``, found by ``resolve.py``) stands on. An
adapter builds the program's own objects through the API a user calls
(``make_ps_mesh`` -> model factory -> ``DeviceDataset`` ->
``DeviceEpochPlan``), places seeded initial tables that the BENCHMARK
made (so the reference can start from the same ones without taking
anything from the program), and reads tables back in logical id order.
The base drives ``Trainer.run_indexed``; an adapter of another entry
(``perfbench/entries/<kind>/<entry>.py``) overrides ``call`` and, where
the batches it feeds are other ones, ``fed_chunks``. Nothing here
computes a metric.
"""

from __future__ import annotations


def to_physical(logical, num_shards: int, like):
    """Logical ``(ids, dim)`` rows -> the program's owner-major table,
    placed like ``like`` (an array of the program's own making, for its
    shape and sharding). Layout helpers are the program's."""
    import jax
    import jax.numpy as jnp

    from fps_tpu.core.store import id_to_phys, rows_per_shard

    n = logical.shape[0]
    rps = rows_per_shard(n, num_shards)
    phys = id_to_phys(jnp.arange(n, dtype=jnp.int32), num_shards, rps)
    if logical.ndim == 1:
        logical = logical[:, None]
    out = jnp.zeros(like.shape, like.dtype).at[phys].set(
        logical.astype(like.dtype))
    return jax.device_put(out, like.sharding)


class System:
    """Common driving surface; subclasses build ``trainer``, ``store``,
    ``plan`` and say how tables map to the reference's names."""

    entry = "run_indexed"   # the entry of the program that ``call`` drives
    loss_key = "loss"       # the per-step metric the reference's loss mirrors

    def __init__(self, cfg: dict, traffic: dict, data: dict, seed: int):
        import jax

        from fps_tpu import DeviceDataset, make_ps_mesh, num_workers_of

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.mesh = make_ps_mesh()
        self.W = num_workers_of(self.mesh)
        self.key = jax.random.key(seed & 0xFFFFFFFF)
        self.examples_per_epoch = len(next(iter(data.values())))
        self.build(data, DeviceDataset(self.mesh, data))
        self.epochs_per_call = int(traffic["epochs_per_call"])
        self.calls = 0

    # -- what a subclass provides -----------------------------------------
    def build(self, data, dataset):
        raise NotImplementedError

    def place(self, init: dict):
        """Reference-named logical init tables -> (tables, local_state)."""
        raise NotImplementedError

    def _shells(self):
        """The program's own freshly initialised state, kept only for its
        shapes and shardings. Its key is FIXED: ``init_state`` closes over
        the key, which makes it a constant of the compiled initialiser, so
        a key from the seed would compile anew for every seed."""
        import jax

        return self.trainer.init_state(jax.random.key(0))

    def export(self, tables, local_state) -> dict:
        """(tables, local_state) -> reference-named logical host arrays."""
        raise NotImplementedError

    # -- driving ------------------------------------------------------------
    def _plan(self, dataset, local_batch: int, route_key):
        from fps_tpu import DeviceEpochPlan

        return DeviceEpochPlan(dataset, num_workers=self.W,
                               local_batch=local_batch, route_key=route_key,
                               seed=self.seed & 0x7FFFFFFF)

    def call(self, tables, local_state):
        """Queue the next ``epochs_per_call`` epochs; returns at once with
        device metrics (one dict of per-step arrays per epoch)."""
        E = self.epochs_per_call
        out = self.trainer.run_indexed(
            tables, local_state, self.plan, self.key, epochs=E,
            start_epoch=self.calls * E, as_numpy=False)
        self.calls += 1
        return out

    @property
    def examples_per_call(self) -> int:
        return self.examples_per_epoch * self.epochs_per_call

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        """The global batches call ``call_index`` consumed, in step order,
        as ``(steps, W * local_batch)`` device chunks materialised by the
        plan's own traced batch function (the one ``run_indexed`` scans).
        Yields ``(chunk, live_steps)``: trailing chunks are padded with
        weight-0 steps past ``steps_per_epoch``."""
        from fps_tpu.core.device_ingest import device_epoch_chunks

        E, T = self.epochs_per_call, int(self.plan.steps_per_epoch)
        for e in range(call_index * E, (call_index + 1) * E):
            done = 0
            for chunk in device_epoch_chunks(
                    self.plan.dataset, num_workers=self.W,
                    local_batch=self.plan.local_batch,
                    steps_per_chunk=steps_per_chunk, plan=self.plan,
                    start_epoch=e):
                yield chunk, min(steps_per_chunk, T - done)
                done += steps_per_chunk
