"""End-to-end online MF: convergence, determinism, sync vs SSP.

Mirrors the reference's algorithm tests (SURVEY.md §4): stream a small
dataset through the full pipeline and assert convergence-style properties,
not exact values — plus a determinism test the asynchronous reference could
never have.
"""

import jax
import numpy as np
import pytest

from fps_tpu.core.driver import num_workers_of
from fps_tpu.core.ingest import epoch_chunks, multi_epoch_chunks
from fps_tpu.models.matrix_factorization import (
    MFConfig,
    online_mf,
    predict_host,
    rmse,
)
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.utils.datasets import synthetic_ratings, train_test_split

NU, NI, NR, RANK = 96, 64, 6000, 4


def run_mf(mesh, sync_every=None, epochs=3, seed=3):
    cfg = MFConfig(
        num_users=NU, num_items=NI, rank=RANK, learning_rate=0.08, reg=0.005
    )
    trainer, store = online_mf(mesh, cfg, sync_every=sync_every)
    data = synthetic_ratings(NU, NI, NR, rank=3, noise=0.05, seed=seed)
    train, test = train_test_split(data)

    tables, local_state = trainer.init_state(jax.random.key(0))
    W = num_workers_of(mesh)
    chunks = multi_epoch_chunks(
        train,
        epochs,
        num_workers=W,
        local_batch=32,
        steps_per_chunk=8,
        route_key="user",
        sync_every=sync_every,
        seed=11,
    )
    tables, local_state, metrics = trainer.fit_stream(
        tables, local_state, chunks, jax.random.key(1)
    )

    se = np.concatenate([m["se"] for m in metrics])
    n = np.concatenate([m["n"] for m in metrics])
    train_rmse_curve = np.sqrt(se.sum() / n.sum())

    pred = predict_host(
        store, np.asarray(local_state), W, test["user"], test["item"]
    )
    return float(train_rmse_curve), rmse(pred, test["rating"]), n


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_mf_converges_sync(devices8, mesh_shape):
    mesh = make_ps_mesh(num_shards=mesh_shape[1], num_data=mesh_shape[0])
    _, test_rmse, n = run_mf(mesh)
    # Planted rank-3 structure with sigma=0.05 noise; untrained predicts ~0
    # giving RMSE near the rating std (~0.6). Learning must beat 0.35.
    assert test_rmse < 0.35, f"test RMSE {test_rmse}"
    # Every real example was processed exactly once per epoch.
    assert int(np.sum(n)) == 3 * int(0.9 * NR)


def test_mf_converges_ssp(devices8):
    mesh = make_ps_mesh(num_shards=8, num_data=1)
    _, test_rmse, _ = run_mf(mesh, sync_every=4)
    assert test_rmse < 0.4, f"SSP test RMSE {test_rmse}"


def test_mf_sync_deterministic(devices8):
    mesh = make_ps_mesh(num_shards=8, num_data=1)
    r1 = run_mf(mesh, epochs=1)
    r2 = run_mf(mesh, epochs=1)
    assert r1[0] == r2[0]
    assert r1[1] == r2[1]


@pytest.mark.parametrize("num_workers,negatives", [(1, 0), (4, 0), (4, 2)])
def test_worker_step_through_push_local_equals_at_add(monkeypatch,
                                                      num_workers,
                                                      negatives):
    """The worker's local scatter-add runs through the routing layer
    (``store.push_local`` -> ``ops.scatter_add``): bit for bit the
    ``.at[u // W].add`` it replaced, and one ``scatter_add.*`` route in the
    log beside the pull's ``gather.*``."""
    import jax.numpy as jnp

    import fps_tpu.models.matrix_factorization as mfm
    import fps_tpu.ops as ops

    cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                   negative_samples=negatives)
    worker = mfm.MatrixFactorizationWorker(cfg, num_workers)
    rng = np.random.default_rng(5)
    B, rows = 256, -(-NU // num_workers)
    batch = {
        # This worker's users (u % W == 0), many of them more than once.
        "user": jnp.asarray(rng.integers(0, rows, B) * num_workers,
                            jnp.int32),
        "item": jnp.asarray(rng.integers(0, NI, B), jnp.int32),
        "rating": jnp.asarray(rng.normal(0, 1, B), jnp.float32),
        "weight": jnp.asarray(rng.random(B) < 0.9, jnp.float32),
    }
    batch = worker.prepare(batch, jax.random.key(2))
    pulled = {mfm.ITEM_TABLE: jnp.asarray(
        rng.normal(0, 0.1, (B * (1 + negatives), RANK)), jnp.float32)}
    local = jnp.asarray(rng.normal(0, 0.1, (rows, RANK)), jnp.float32)

    ops.clear_routes()
    got = worker.step(batch, pulled, local, jax.random.key(3))
    routes = [r.route for r in ops.routes_traced()]
    assert routes == ["gather.xla", "scatter_add.xla"]

    monkeypatch.setattr(
        mfm, "push_local",
        lambda t, ids, d, *, num_shards: t.at[ids // num_shards].add(d))
    want = worker.step(batch, pulled, local, jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(got.local_state),
                                  np.asarray(want.local_state))
    assert not np.array_equal(np.asarray(got.local_state), np.asarray(local))
    for a, b in zip(jax.tree.leaves((got.pushes, got.out)),
                    jax.tree.leaves((want.pushes, want.out))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
