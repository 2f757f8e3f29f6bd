"""Subprocess body for the multi-process distributed tests.

Usage: python _mp_worker.py <process_id> <num_processes> <port> <out_npz>
                            [scenario]

Initializes multi-controller JAX over a local gloo coordinator and trains
the standard tiny MF workload through the full framework path on a (2, 4)
global mesh. Scenarios:

* ``indexed``  (default) — device-resident ingest, fused indexed epochs,
  synchronous.
* ``host_sync`` — HOST ingest (`fit_stream` over numpy chunks placed via
  ``make_array_from_process_local_data``), synchronous.
* ``host_ssp``  — host ingest, SSP bounded staleness (sync_every=2).
* ``indexed_mean`` / ``indexed_shard8_mean`` — the two indexed scenarios
  under ``combine="mean"``: the item table is small, so its push fills the
  ``(rps, dim + 1)`` accumulator by the DENSE exchange (``push.dense_acc``),
  whose ``all_gather`` over the data axis (the former) and ``all_to_all``
  over the shard axis (the latter) cross the process boundary; their
  fixed-order in-program sums must give one process's bits.
* ``indexed_shard8`` — indexed ingest on a ``(data=1, shard=8)`` mesh, so
  the SHARD axis spans the process boundary: every pull's all_gather /
  psum_scatter, every push's shard-axis all_gather, ``dump_model``'s
  replication, and the checkpoint save's host transfer all move shard ROWS
  between the two OS processes (round-2 verdict: the one untested
  collective topology — every other scenario keeps shards process-local).

Every rank calls `dump_model` (a collective); rank 0 writes the table for
the parent test to compare against a single-process run. The shard8
scenario also checkpoints (every rank — the save's table dump is itself
a collective) and re-reads the snapshot to prove the cross-process
checkpoint path agrees with ``dump_model``.
"""

import sys


def main() -> int:
    pid, nproc, port, out = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    scenario = sys.argv[5] if len(sys.argv) > 5 else "indexed"

    from fps_tpu.parallel.mesh import init_distributed

    init_distributed(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )

    import numpy as np

    import jax

    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    combine = "mean" if scenario.endswith("_mean") else "sum"
    scenario = scenario.removesuffix("_mean")
    if scenario == "indexed_shard8":
        mesh = make_ps_mesh(num_shards=8, num_data=1)
    else:
        mesh = make_ps_mesh(num_shards=4, num_data=2)
    W = num_workers_of(mesh)
    data = synthetic_ratings(57, 31, 2000, seed=0)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)
    sync_every = 2 if scenario == "host_ssp" else None
    trainer, store = online_mf(mesh, cfg, sync_every=sync_every,
                               combine=combine)
    tables, ls = trainer.init_state(jax.random.key(0))

    if scenario in ("indexed", "indexed_shard8"):
        ds = DeviceDataset(mesh, data)
        plan = DeviceEpochPlan(
            ds, num_workers=W, local_batch=32, route_key="user", seed=5
        )
        tables, ls, metrics = trainer.run_indexed(
            tables, ls, plan, jax.random.key(1), epochs=2
        )
        n = sum(float(m["n"].sum()) for m in metrics)
    elif scenario in ("host_sync", "host_ssp"):
        # Host ingest: every process runs the identical deterministic chunk
        # iterator; run_chunk places the numpy leaves onto the global mesh.
        chunks = multi_epoch_chunks(
            data, 2, num_workers=W, local_batch=32, steps_per_chunk=4,
            route_key="user", sync_every=sync_every, seed=5,
        )
        tables, ls, metrics = trainer.fit_stream(
            tables, ls, chunks, jax.random.key(1)
        )
        n = sum(float(np.asarray(m["n"]).sum()) for m in metrics)
    else:
        raise SystemExit(f"unknown scenario {scenario!r}")
    assert n == 2 * 2000, n

    # dump_model replicates cross-host shards through a jitted identity — a
    # COLLECTIVE, so EVERY process must call it (on a topology where the
    # shard axis spans processes, a rank-0-only call deadlocks waiting for
    # the other processes' shards). Rank 0 alone writes the file.
    ids, values = store.dump_model("item_factors")

    if scenario == "indexed_shard8":
        # Cross-process checkpoint: every rank runs the collective table
        # dump inside save (atomic same-path writes race benignly), then
        # the re-read snapshot must agree with dump_model's host view.
        import os

        from fps_tpu.core.checkpoint import Checkpointer

        ck = Checkpointer(os.path.join(os.path.dirname(out), "ck_shard8"),
                          keep=1)
        ck.save(1, store, ls)
        _, snap_tables, _, _ = ck.read_snapshot(1)
        got = snap_tables["item_factors"]  # logical order, padding stripped
        host = store.lookup_host("item_factors", np.arange(31))
        assert np.array_equal(got, host), "checkpoint != dump view"

    if pid == 0:
        np.savez(out, item_factors=values)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
