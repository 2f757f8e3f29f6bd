"""Relative weak-scaling curve for passive-aggressive on the virtual mesh.

The single-chip PA-I headline now beats the measured native `ps` baseline
(BENCH r4), but the framework's structural case for PA on TPU has always
been data-parallel scale-out (BASELINE.md): per-example closed-form steps
with a tiny L2-resident model are the sequential loop's best case, while
the PS path amortizes per-row transactions across workers. This tool
MEASURES that claim's shape: examples/s vs W ∈ {1, 2, 4, 8} workers at a
FIXED per-worker batch (weak scaling — total work grows with W) on the
8-virtual-CPU-device mesh (the same fabric the test suite and the
multichip dryrun use; absolute CPU numbers are meaningless, the RELATIVE
curve is the artifact).

Run from /root/repo:  python tools/pa_scaling.py
Re-execs itself into a cleaned 8-device CPU subprocess when needed.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

# `python tools/pa_scaling.py` puts tools/ (not the repo root) on sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PER_WORKER_EX = 65_536
LOCAL_BATCH = 4_096
NF, NNZ = 47_236, 64


def run_curve(route: str):
    import dataclasses

    import jax

    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.passive_aggressive import (
        PAConfig, WEIGHT_TABLE, passive_aggressive,
    )
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    devs = jax.devices()
    results = []
    print(f"--- route: {route} ---", flush=True)
    for W in (1, 2, 4, 8):
        if W > len(devs):
            break
        mesh = make_ps_mesh(num_shards=W, num_data=1, devices=devs[:W])
        assert num_workers_of(mesh) == W
        nex = PER_WORKER_EX * W
        data = synthetic_sparse_classification(nex, NF, NNZ, seed=3,
                                               noise=0.05)
        cfg = PAConfig(num_features=NF, variant="PA-I", C=1.0)
        trainer, store = passive_aggressive(mesh, cfg,
                                            max_steps_per_call=8)
        if route != "auto":
            store.specs[WEIGHT_TABLE] = dataclasses.replace(
                store.specs[WEIGHT_TABLE],
                dense_collectives=(route == "dense"),
            )
        tables, ls = trainer.init_state(jax.random.key(0))
        ds = DeviceDataset(mesh, data)
        plan = DeviceEpochPlan(ds, num_workers=W, local_batch=LOCAL_BATCH,
                               seed=1)
        # warm (compile), then best-of-3 timed epochs
        tables, ls, _ = trainer.run_indexed(tables, ls, plan,
                                            jax.random.key(9))
        best = 1e9
        for r in range(3):
            t0 = time.perf_counter()
            tables, ls, m = trainer.run_indexed(tables, ls, plan,
                                                jax.random.key(1 + r))
            best = min(best, time.perf_counter() - t0)
        ex_s = nex / best
        results.append((W, ex_s))
        base = results[0][1]
        # All W virtual devices share the same host cores, so aggregate
        # ex/s CANNOT rise with W here; what the curve measures is TOTAL
        # WORK PER EXAMPLE (= base_rate / rate): flat aggregate rate at
        # W-fold work means per-example work is constant in W — the
        # property that turns into linear scale-out on physical chips.
        print(
            f"W={W}: {ex_s:12.0f} ex/s aggregate  "
            f"(x{ex_s / base:4.2f} of W=1)  "
            f"work/example x{base / ex_s:5.2f}",
            flush=True,
        )
    return results


def main():
    import jax

    from fps_tpu.utils.hostenv import cpu_mesh_env, reexec_count

    routes = sys.argv[1:] or ["dense", "gathered"]
    bad = [r for r in routes if r not in ("auto", "dense", "gathered")]
    if bad:
        raise SystemExit(f"unknown route(s) {bad!r} — choose from "
                         "auto / dense / gathered")
    if len(jax.devices()) >= 8:
        for route in routes:
            run_curve(route)
        return
    if reexec_count() >= 8:
        raise RuntimeError("re-exec failed to provide 8 devices")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_mesh_env(8)
    subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
        env=env, cwd=root, check=True,
    )


if __name__ == "__main__":
    main()
