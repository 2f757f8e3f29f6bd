"""Device-resident ingest: coverage/routing invariants + indexed-epoch parity.

Mirrors the reference's test approach (assert invariants, not bitwise
outputs — SURVEY.md §4) on the 8-virtual-device CPU mesh: every example is
visited exactly once per epoch, keyed routing pins examples to the owning
worker, padding rows carry weight 0, and the fused index-fed epoch runner
(`Trainer.run_indexed`) produces the same tables as the chunked driver.
"""

import numpy as np
import pytest

import jax

from fps_tpu import ops
from fps_tpu.core import device_ingest
from fps_tpu.core.device_ingest import (
    DeviceDataset,
    DeviceEpochPlan,
    columns_take_slices,
    device_epoch_chunks,
    unkeyed_queue_rows,
)
from fps_tpu.core.driver import num_workers_of
from fps_tpu.models.matrix_factorization import MFConfig, online_mf
from fps_tpu.models.passive_aggressive import PAConfig, passive_aggressive
from fps_tpu.parallel.mesh import key_to_replicated, make_ps_mesh
from fps_tpu.utils.datasets import (
    synthetic_ratings,
    synthetic_sparse_classification,
)


@pytest.fixture(scope="module")
def mesh(devices8):
    return make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])


@pytest.fixture(scope="module")
def data():
    d = synthetic_ratings(57, 31, 1003, seed=0)
    # distinct ratings so multiset comparison detects duplicates/misses
    d["rating"] = (np.arange(1003) * 0.001).astype(np.float32)
    return d


@pytest.fixture(scope="module")
def dataset(mesh, data):
    return DeviceDataset(mesh, data)


SPARSE_NF, SPARSE_N = 211, 1003     # 1003 % 8 == 3: ragged queues
SPARSE_CFG = PAConfig(num_features=SPARSE_NF, variant="PA-I", C=1.0)


@pytest.fixture(scope="module")
def sparse_data():
    """2-D columns, and ``stream``: a keyed plan on it has the unkeyed
    plan's queue matrix (row ``i`` goes to worker ``i % W``, in order)."""
    d = synthetic_sparse_classification(SPARSE_N, SPARSE_NF, 6, seed=5)
    # Rows told apart by their values (PA's labels must stay +-1).
    d["feat_vals"] = (d["feat_vals"]
                      + np.arange(SPARSE_N, dtype=np.float32)[:, None] * 1e-3)
    return d


@pytest.fixture(scope="module")
def sparse_dataset(mesh, sparse_data):
    return DeviceDataset(mesh, sparse_data)


def _keyed_twin(mesh, sparse_data, **plan_kwargs):
    """A KEYED plan over the same rows whose queue matrix is the unkeyed
    one (key ``i`` of row ``i``; the stable host argsort keeps stream
    order): it reads its rows from the uploaded host matrix, as every
    plan did before PR 46."""
    ds = DeviceDataset(mesh, dict(
        sparse_data, stream=np.arange(SPARSE_N, dtype=np.int32)))
    return DeviceEpochPlan(ds, route_key="stream", **plan_kwargs)


LOCAL_BATCH = 16


def _collect(chunks, W, route):
    """Gather (example ratings, routing violations) across all chunks."""
    seen = []
    for c in chunks:
        c = {k: np.asarray(v) for k, v in c.items()}
        wt = c["weight"].reshape(-1, W * LOCAL_BATCH)
        u = c["user"].reshape(-1, W * LOCAL_BATCH)
        r = c["rating"].reshape(-1, W * LOCAL_BATCH)
        mask = wt > 0
        seen.append(r[mask])
        if route:
            worker_of_slot = np.arange(W * LOCAL_BATCH) // LOCAL_BATCH
            assert (u[mask] % W == np.broadcast_to(
                worker_of_slot, u.shape)[mask]).all()
    return np.concatenate(seen)


@pytest.mark.parametrize("shuffle", [None, "interleave", "sort"])
@pytest.mark.parametrize("route", [None, "user"])
@pytest.mark.parametrize("sync_every", [None, 2])
def test_chunks_cover_every_example_once(dataset, data, shuffle, route,
                                         sync_every):
    W = 8
    chunks = device_epoch_chunks(
        dataset, num_workers=W, local_batch=LOCAL_BATCH, steps_per_chunk=4,
        route_key=route, sync_every=sync_every, seed=3, shuffle=shuffle,
    )
    seen = _collect(chunks, W, route)
    assert len(seen) == len(data["rating"])
    np.testing.assert_allclose(np.sort(seen), np.sort(data["rating"]))


def test_interleave_differs_by_epoch_and_mixes(dataset, data):
    W = 8
    orders = []
    for seed in (0, 1):
        chunks = device_epoch_chunks(
            dataset, num_workers=W, local_batch=LOCAL_BATCH,
            steps_per_chunk=4, route_key=None, seed=seed,
            shuffle="interleave",
        )
        orders.append(_collect(chunks, W, None))
    # same multiset, different order across epochs/seeds
    np.testing.assert_allclose(np.sort(orders[0]), np.sort(orders[1]))
    assert not np.array_equal(orders[0], orders[1])
    # and not stream order either
    stream = device_epoch_chunks(
        dataset, num_workers=W, local_batch=LOCAL_BATCH, steps_per_chunk=4,
        route_key=None, seed=0, shuffle=None,
    )
    assert not np.array_equal(orders[0], _collect(stream, W, None))


@pytest.mark.parametrize("sync_every", [None, 2])
def test_indexed_epoch_matches_chunked(mesh, dataset, data, sync_every):
    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=57, num_items=31, rank=4)

    tr1, _ = online_mf(mesh, cfg, sync_every=sync_every)
    t1, l1 = tr1.init_state(jax.random.key(0))
    chunks = device_epoch_chunks(
        dataset, num_workers=W, local_batch=64, steps_per_chunk=4,
        route_key="user", seed=7, sync_every=sync_every, shuffle="interleave",
    )
    t1, l1, m1 = tr1.fit_stream(t1, l1, chunks, jax.random.key(1))

    tr2, _ = online_mf(mesh, cfg, sync_every=sync_every)
    t2, l2 = tr2.init_state(jax.random.key(0))
    plan = DeviceEpochPlan(
        dataset, num_workers=W, local_batch=64, route_key="user",
        shuffle="interleave", seed=7, sync_every=sync_every,
    )
    t2, l2, m2 = tr2.run_indexed(t2, l2, plan, jax.random.key(1))

    n1 = sum(float(m["n"].sum()) for m in m1)
    n2 = sum(float(m["n"].sum()) for m in m2)
    assert n1 == n2 == len(data["rating"])
    np.testing.assert_allclose(
        np.asarray(t1["item_factors"]), np.asarray(t2["item_factors"]),
        atol=1e-5,
    )


@pytest.mark.parametrize("shuffle", [None, "interleave", "sort"])
def test_indexed_epoch_matches_chunked_2d_columns(mesh, sparse_data,
                                                  sparse_dataset, shuffle):
    """The same parity for an UNKEYED data set with 2-D columns, whose
    steps both drivers slice from the columns' transposed buffers
    (``ingest.rows_sliced``; under ``sort`` they compute and gather,
    ``ingest.rows_computed``). Two programs round differently on this
    backend (the two drivers' 6e-8 on the tree before PR 46 too; the
    sliced step against the gathered one 3e-8 on batches that are the
    same to the bit), so bits are held where the programs differ in
    nothing but the rows' origin: ``run_indexed`` through the closed form
    (``pack=False``) against ``run_indexed`` reading the same queue
    matrix (``_keyed_twin``)."""
    W = num_workers_of(mesh)
    kw = dict(num_workers=W, local_batch=16, shuffle=shuffle, seed=7)
    plan = DeviceEpochPlan(sparse_dataset, **kw)
    assert plan.sliced == (shuffle != "sort")

    def run(drive):
        trainer, _ = passive_aggressive(mesh, SPARSE_CFG)
        tables, ls = trainer.init_state(jax.random.key(0))
        tables, ls, m = drive(trainer, tables, ls)
        assert sum(float(x["n"].sum()) for x in m) == SPARSE_N
        return np.asarray(tables["weights"])

    chunked = run(lambda tr, t, l: tr.fit_stream(
        t, l, device_epoch_chunks(sparse_dataset, num_workers=W,
                                  local_batch=16, steps_per_chunk=4,
                                  plan=plan), jax.random.key(1)))
    indexed = run(lambda tr, t, l: tr.run_indexed(
        t, l, plan, jax.random.key(1)))
    computed = run(lambda tr, t, l: tr.run_indexed(
        t, l, DeviceEpochPlan(sparse_dataset, pack=False, **kw),
        jax.random.key(1)))
    queued = run(lambda tr, t, l: tr.run_indexed(
        t, l, _keyed_twin(mesh, sparse_data, **kw), jax.random.key(1)))
    np.testing.assert_allclose(chunked, indexed, atol=1e-6)
    np.testing.assert_allclose(indexed, computed, atol=1e-6)
    np.testing.assert_array_equal(computed, queued)
    assert np.abs(indexed).max() > 0.01


def test_indexed_multi_epoch_converges(mesh, dataset):
    """Loss falls over epochs through the fused runner (sanity: training
    actually happens, per-epoch shuffles differ)."""
    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)
    tr, _ = online_mf(mesh, cfg)
    t, l = tr.init_state(jax.random.key(0))
    plan = DeviceEpochPlan(
        dataset, num_workers=W, local_batch=32, route_key="user", seed=5,
    )
    t, l, metrics = tr.run_indexed(t, l, plan, jax.random.key(1), epochs=4)
    rmse = [float(np.sqrt(m["se"].sum() / m["n"].sum())) for m in metrics]
    assert rmse[-1] < rmse[0] * 0.9, rmse


def test_indexed_sparse_workload_ssp(mesh):
    """DeviceEpochPlan handles 2-D columns (sparse feat_ids/feat_vals) and
    the SSP indexed runner: Criteo-style logreg trains through run_indexed
    with multi-call epochs."""
    from fps_tpu.models.logistic_regression import (
        LogRegConfig,
        logistic_regression,
        predict_proba_host,
    )
    from fps_tpu.utils.datasets import (
        synthetic_sparse_classification,
        train_test_split,
    )

    NF = 400
    W = num_workers_of(mesh)
    d = synthetic_sparse_classification(6000, NF, 8, seed=7, noise=0.05)
    d = dict(d, label=(d["label"] > 0).astype(np.float32))
    train, test = train_test_split(d)
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, store = logistic_regression(
        mesh, cfg, sync_every=4, max_steps_per_call=8
    )
    tables, ls = trainer.init_state(jax.random.key(0))
    ds = DeviceDataset(mesh, train)
    plan = DeviceEpochPlan(
        ds, num_workers=W, local_batch=32, sync_every=4, seed=3
    )
    assert plan.steps_per_epoch > 8  # multi-call epochs exercised
    tables, ls, m = trainer.run_indexed(
        tables, ls, plan, jax.random.key(1), epochs=6
    )
    # metrics sized exactly to the epoch, no phantom padded-call rows
    assert m[0]["n"].shape[0] == plan.steps_per_epoch
    p = predict_proba_host(store, test["feat_ids"], test["feat_vals"])
    acc = float(np.mean((p > 0.5) == (test["label"] > 0.5)))
    assert acc > 0.78, acc


def test_run_indexed_checkpoint_resume_bit_exact(mesh, dataset, tmp_path):
    """interrupt-at-epoch-2 + restore + continue == straight 4-epoch run,
    bit for bit (epoch shuffles and PRNG streams keyed by absolute epoch)."""
    from fps_tpu.core.checkpoint import Checkpointer

    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)

    def fresh():
        tr, store = online_mf(mesh, cfg)
        t, l = tr.init_state(jax.random.key(0))
        plan = DeviceEpochPlan(
            dataset, num_workers=W, local_batch=32, route_key="user", seed=5
        )
        return tr, store, t, l, plan

    # straight run
    tr_a, store_a, t, l, plan = fresh()
    t_full, l_full, _ = tr_a.run_indexed(t, l, plan, jax.random.key(1),
                                         epochs=4)

    # interrupted run: 2 epochs + snapshot
    tr, store, t, l, plan = fresh()
    ck = Checkpointer(str(tmp_path))
    t2, l2, _ = tr.run_indexed(
        t, l, plan, jax.random.key(1), epochs=2,
        checkpointer=ck, checkpoint_every=2,
    )
    # resume from the snapshot in a fresh trainer (different init — the
    # restore must fully overwrite it)
    tr3, store3, t3, l3, plan3 = fresh()
    store3.tables = t3
    t3, l3, step = tr3.restore_checkpoint(ck, l3)
    assert step == 2
    t4, l4, _ = tr3.run_indexed(
        t3, l3, plan3, jax.random.key(1), epochs=2, start_epoch=2
    )
    # Compare real rows via dump_model / logical user order — restore
    # zero-fills padding rows (unreachable by any valid id), so raw
    # physical arrays may differ there.
    from fps_tpu.models.recommendation import mf_user_vectors

    _, v_full = store_a.dump_model("item_factors")
    _, v_resumed = store3.dump_model("item_factors")
    np.testing.assert_array_equal(v_full, v_resumed)
    users = np.arange(57)
    np.testing.assert_array_equal(
        mf_user_vectors(np.asarray(l_full), W, users),
        mf_user_vectors(np.asarray(l4), W, users),
    )


@pytest.mark.parametrize("shuffle", [None, "interleave"])
@pytest.mark.parametrize("route", [None, "user"])
def test_transposed_buffer_matches_gather_path(mesh, dataset, shuffle, route):
    """The transposed-epoch fast path (contiguous slices of a per-epoch
    relayout) must produce bit-identical batches to the gather path."""
    W = 8
    fast = DeviceEpochPlan(
        dataset, num_workers=W, local_batch=LOCAL_BATCH, route_key=route,
        shuffle=shuffle, seed=3, pack=True,
    )
    slow = DeviceEpochPlan(
        dataset, num_workers=W, local_batch=LOCAL_BATCH, route_key=route,
        shuffle=shuffle, seed=3, pack=False,
    )
    assert fast._tbuf_jit is not None  # fast path actually engaged
    assert fast.steps_per_epoch == slow.steps_per_epoch
    fast_at = jax.jit(fast.local_batch_at)
    slow_at = jax.jit(slow.local_batch_at)
    for epoch in (0, 1):
        fa, sa = fast.epoch_args(epoch), slow.epoch_args(epoch)
        assert "tbuf" in fa and "tbuf" not in sa
        for t in range(fast.steps_per_epoch):
            for w in range(W):
                bf = fast_at(fa, np.int32(w), np.int32(t))
                bs = slow_at(sa, np.int32(w), np.int32(t))
                assert set(bf) == set(bs)
                wf = np.asarray(bf["weight"])
                np.testing.assert_array_equal(wf, np.asarray(bs["weight"]))
                for k in bf:
                    if k == "weight":
                        continue
                    # padding slots may differ (zeros vs clamped reads);
                    # only real rows must agree
                    np.testing.assert_array_equal(
                        np.asarray(bf[k])[wf > 0], np.asarray(bs[k])[wf > 0]
                    )


def test_explicit_plan_kwarg_mismatch_raises(dataset):
    """Passing a plan plus disagreeing geometry kwargs must raise, not
    silently use the plan's geometry."""
    W = 8
    plan = DeviceEpochPlan(
        dataset, num_workers=W, local_batch=LOCAL_BATCH, route_key="user",
        seed=3,
    )
    # Validation is eager — it must fire at call time, not at first next().
    with pytest.raises(ValueError, match="local_batch"):
        device_epoch_chunks(
            dataset, num_workers=W, local_batch=LOCAL_BATCH * 2,
            steps_per_chunk=4, route_key="user", seed=3, plan=plan,
        )
    with pytest.raises(ValueError, match="route_key"):
        device_epoch_chunks(
            dataset, num_workers=W, local_batch=LOCAL_BATCH,
            steps_per_chunk=4, route_key=None, seed=3, plan=plan,
        )


def test_on_epoch_sees_live_store(mesh, dataset):
    """Under donate=True the pre-call table buffers are invalidated; the
    store must be repointed at the live arrays before on_epoch runs so
    per-epoch validation via store.lookup_host works."""
    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)
    tr, store = online_mf(mesh, cfg)  # donate=True default
    t, l = tr.init_state(jax.random.key(0))
    plan = DeviceEpochPlan(
        dataset, num_workers=W, local_batch=32, route_key="user", seed=5
    )
    seen = []

    def on_epoch(e, metrics):
        # The natural per-epoch validation pattern: host read of the live
        # tables. Raises "array deleted" if the store still points at the
        # donated pre-call buffers.
        vals = store.lookup_host("item_factors", np.arange(5))
        assert np.isfinite(vals).all()
        seen.append(e)

    tr.run_indexed(t, l, plan, jax.random.key(1), epochs=2,
                   on_epoch=on_epoch)
    assert seen == [0, 1]


def test_packed_blowup_guard_falls_back(mesh):
    """Extreme routing skew (every example keyed to one worker) must skip
    the packed fast path (HBM blowup) and still train correctly."""
    W = num_workers_of(mesh)
    n = 257
    d = {"user": np.full(n, 0, np.int32),  # all route to worker 0
         "item": np.arange(n, dtype=np.int32) % 31,
         "rating": np.linspace(0, 1, n).astype(np.float32)}
    ds = DeviceDataset(mesh, d)
    assert ds.packed("user", W) is None  # blowup W*maxq/n = W > 2
    plan = DeviceEpochPlan(ds, num_workers=W, local_batch=16,
                           route_key="user", seed=0)
    assert "packed" not in plan.epoch_args(0)
    cfg = MFConfig(num_users=1, num_items=31, rank=4)
    tr, _ = online_mf(mesh, cfg)
    t, l = tr.init_state(jax.random.key(0))
    t, l, m = tr.run_indexed(t, l, plan, jax.random.key(1))
    assert sum(float(x["n"].sum()) for x in m) == n


def test_negative_seed_and_sort_key_shape(devices8):
    """epoch_args' host-side rng must accept negative seeds (SeedSequence
    rejects negative entropy) and fabricate sort key data sized for the
    active prng impl."""
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=4, num_data=2)
    ds = DeviceDataset(mesh, synthetic_ratings(32, 24, 512, seed=0))
    for shuffle in ("interleave", "sort"):
        plan = DeviceEpochPlan(ds, num_workers=8, local_batch=8,
                               shuffle=shuffle, seed=-3)
        args = plan.epoch_args(0)
        assert args is not None
        # deterministic per (seed, epoch)
        a0 = jax.tree.map(lambda x: np.asarray(x), plan.epoch_args(1))
        a1 = jax.tree.map(lambda x: np.asarray(x), plan.epoch_args(1))
        for x, y in zip(jax.tree.leaves(a0), jax.tree.leaves(a1)):
            np.testing.assert_array_equal(x, y)


def test_run_indexed_as_numpy_false_matches(mesh, dataset):
    """as_numpy=False returns DEVICE metrics (no blocking conversion) that
    are value-identical to the default host metrics of the same run."""
    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)

    def run(as_numpy):
        tr, _ = online_mf(mesh, cfg, donate=False)
        t, l = tr.init_state(jax.random.key(0))
        plan = DeviceEpochPlan(
            dataset, num_workers=W, local_batch=32, route_key="user", seed=5,
        )
        return tr.run_indexed(t, l, plan, jax.random.key(1), epochs=2,
                              as_numpy=as_numpy)[2]

    host = run(True)
    dev = run(False)
    assert all(isinstance(x, np.ndarray)
               for m in host for x in jax.tree.leaves(m))
    assert all(isinstance(x, jax.Array)
               for m in dev for x in jax.tree.leaves(m))
    for mh, md in zip(host, dev):
        for kh, kd in zip(jax.tree.leaves(mh), jax.tree.leaves(md)):
            np.testing.assert_array_equal(kh, np.asarray(kd))


# -- the rows of an unkeyed plan are computed, not read (PR 46) -------------


@pytest.mark.parametrize("W", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [1, 3, 7, 8, 9, 64, 1003])
def test_closed_form_is_the_unkeyed_queue_matrix(mesh, n, W):
    """``unkeyed_queue_rows`` against ``DeviceDataset.queues(None, W)``,
    entry for entry: ragged ``n % W``, ``n < W`` (empty queues), the
    padding zeros behind every count."""
    ds = DeviceDataset(mesh, {"x": np.arange(n, dtype=np.int32)})
    q, counts = ds.queues(None, W)
    q = np.asarray(q)
    assert q.shape == (W, max(-(-n // W), 1)) and counts.sum() == n
    got = np.stack([
        np.asarray(unkeyed_queue_rows(
            np.int32(w), np.arange(q.shape[1], dtype=np.int32),
            np.int32(counts[w]), W))
        for w in range(W)])
    assert got.dtype == q.dtype
    np.testing.assert_array_equal(got, q)


@pytest.mark.parametrize("shuffle", [None, "interleave", "sort"])
def test_computed_rows_give_the_queued_batches(mesh, sparse_data,
                                               sparse_dataset, shuffle):
    """Every step of an epoch, every worker, padding rows included: the
    batch through the closed form is the batch read through the host
    queue matrix."""
    W = 8
    kw = dict(num_workers=W, local_batch=LOCAL_BATCH, shuffle=shuffle,
              seed=3)
    # pack=False: the row gather, not the slices these shapes take (PR 50).
    plan = DeviceEpochPlan(sparse_dataset, pack=False, **kw)
    twin = _keyed_twin(mesh, sparse_data, **kw)
    q = np.asarray(twin._queues)
    np.testing.assert_array_equal(
        q, np.asarray(sparse_dataset.queues(None, W)[0]))
    assert plan.steps_per_epoch == twin.steps_per_epoch
    at, twin_at = jax.jit(plan.local_batch_at), jax.jit(twin.local_batch_at)
    padded = 0
    for epoch in (0, 1):
        a, ta = plan.epoch_args(epoch), twin.epoch_args(epoch)
        assert "queues" not in a and "queues" in ta
        for t in range(plan.steps_per_epoch):
            for w in range(W):
                got = at(a, np.int32(w), np.int32(t))
                want = twin_at(ta, np.int32(w), np.int32(t))
                want.pop("stream")
                assert set(got) == set(want)
                for k in got:
                    np.testing.assert_array_equal(
                        np.asarray(got[k]), np.asarray(want[k]))
                if shuffle is None:
                    # ...and, in stream order, straight from the matrix.
                    pos = t * LOCAL_BATCH + np.arange(LOCAL_BATCH)
                    rows = q[w, np.minimum(pos, q.shape[1] - 1)]
                    np.testing.assert_array_equal(
                        np.asarray(got["feat_ids"]),
                        sparse_data["feat_ids"][rows])
                padded += int((np.asarray(got["weight"]) == 0).sum())
    assert padded      # the weight-0 rows were compared too


def _pa_step_text(mesh, plan):
    trainer, _ = passive_aggressive(mesh, SPARSE_CFG)
    tables, ls = trainer.init_state(jax.random.key(0))
    args = plan.epoch_args(0)
    return args, trainer._get_indexed_fn(plan, "sync").lower(
        tables, ls, args, np.int32(0),
        key_to_replicated(jax.random.key(1), mesh)).as_text()


def test_unkeyed_step_has_no_queue_parameter(mesh, sparse_data,
                                             sparse_dataset):
    """That no path reads the queue is a property of the program's text:
    the unkeyed plan's operands have no ``queues`` and its step no
    parameter of the matrix's shape; a keyed plan's have both."""
    W = num_workers_of(mesh)
    kw = dict(num_workers=W, local_batch=LOCAL_BATCH, seed=3)
    plan = DeviceEpochPlan(sparse_dataset, **kw)
    twin = _keyed_twin(mesh, sparse_data, **kw)
    assert twin.maxq == plan.maxq == 126
    queue = f"tensor<{W}x{plan.maxq}xi32>"
    args, text = _pa_step_text(mesh, plan)
    assert "queues" not in args and queue not in text
    args, text = _pa_step_text(mesh, twin)
    assert args["queues"].shape == (W, plan.maxq) and queue in text


@pytest.mark.parametrize("case, want", [
    ("unkeyed-2d", "ingest.rows_sliced"),
    ("unkeyed-2d-sort", "ingest.rows_computed"),
    ("unkeyed-2d-unpacked", "ingest.rows_computed"),
    ("keyed-2d", "ingest.rows_queued"),
    ("tbuf", None),
    ("packed", None),
])
def test_route_log_names_the_ingest_branch(mesh, dataset, sparse_data,
                                           sparse_dataset, case, want):
    """Where the columns do not pack, the step logs where its rows come
    from, once: sliced from the columns' transposed buffers (PR 50),
    computed and gathered, or read from the queue and gathered; the packed
    rows' transposed buffer and the packed matrix log nothing."""
    kw = dict(num_workers=8, local_batch=LOCAL_BATCH, seed=3)
    plan = {
        "unkeyed-2d": lambda: DeviceEpochPlan(sparse_dataset, **kw),
        "unkeyed-2d-sort": lambda: DeviceEpochPlan(
            sparse_dataset, shuffle="sort", **kw),
        "unkeyed-2d-unpacked": lambda: DeviceEpochPlan(
            sparse_dataset, pack=False, **kw),
        "keyed-2d": lambda: _keyed_twin(mesh, sparse_data, **kw),
        "tbuf": lambda: DeviceEpochPlan(dataset, **kw),
        "packed": lambda: DeviceEpochPlan(dataset, shuffle="sort", **kw),
    }[case]()
    args = plan.epoch_args(0)
    # The last two cases are named after the operand their branch reads;
    # the sliced plan's operand is a buffer a column under the same name.
    assert (case in args) == (want is None)
    assert plan.sliced == (want == "ingest.rows_sliced") == isinstance(
        args.get("tbuf"), dict)
    ops.clear_routes()
    jax.jit(plan.local_batch_at).lower(args, np.int32(0), np.int32(0))
    got = [(r.route, r.rows, r.dim, r.ids) for r in ops.routes_traced()]
    cols = len(plan.dataset.columns)
    assert got == ([(want, SPARSE_N, cols, LOCAL_BATCH)] if want else []), got
    assert not {r[0] for r in got} & ops.PALLAS_ROUTES


# -- an unkeyed plan's 2-D columns read as slices (PR 50) -------------------

SLICED_N = 1003     # 1003 % 4 == 3; neither 1003 nor 251 a multiple of the grid


@pytest.fixture(scope="module")
def wide_data():
    rng = np.random.default_rng(7)
    return {"ids": rng.integers(0, 1 << 20, (SLICED_N, 5)).astype(np.int32),
            "vals": rng.normal(size=(SLICED_N, 3)).astype(np.float32),
            # Rows told apart, none of them zero: a padding row reads 0.
            "row": np.arange(1, SLICED_N + 1, dtype=np.float32)}


@pytest.fixture(scope="module")
def wide_dataset(mesh, wide_data):
    return DeviceDataset(mesh, wide_data)


@pytest.mark.parametrize("sync_every", [None, 8])
@pytest.mark.parametrize("shuffle", [None, "interleave"])
@pytest.mark.parametrize("W", [1, 4])
def test_sliced_batches_are_the_gathered_and_the_bijections(
        wide_data, wide_dataset, W, shuffle, sync_every):
    """Two epochs, every step and worker: the batch sliced from the
    columns' transposed buffers is (i) the ``rows_computed`` branch's
    (the same plan with ``pack=False``) and (ii) the rows a numpy
    computation of the bijection names, bit for bit; every row once an
    epoch; a position that holds no row reads zeros at weight 0."""
    kw = dict(num_workers=W, local_batch=LOCAL_BATCH, shuffle=shuffle,
              seed=3, sync_every=sync_every)
    plan = DeviceEpochPlan(wide_dataset, **kw)
    gathered = DeviceEpochPlan(wide_dataset, pack=False, **kw)
    assert plan.sliced and not gathered.sliced
    assert (W == 1 or SLICED_N % W) and (plan.counts % plan.grid_r).all()
    steps = plan.steps_per_epoch
    assert steps == gathered.steps_per_epoch
    assert not sync_every or steps % sync_every == 0
    at, gathered_at = (jax.jit(plan.local_batch_at),
                       jax.jit(gathered.local_batch_at))
    offsets = set()
    for epoch in (0, 1):
        a, ga = plan.epoch_args(epoch), gathered.epoch_args(epoch)
        assert set(a["tbuf"]) == set(wide_data) and "tbuf" not in ga
        for k, buf in a["tbuf"].items():
            assert buf.dtype == wide_data[k].dtype
            assert buf.shape == ((W * steps * LOCAL_BATCH,)
                                 + wide_data[k].shape[1:])
        off_w = np.asarray(a["off_w"])
        offsets.add(int(off_w[0]))
        seen = []
        for w in range(W):
            r, c, m, cnt = (plan.grid_r, int(plan.grid_c[w]),
                            int(plan.grid_m[w]), int(plan.counts[w]))
            for t in range(steps):
                got = at(a, np.int32(w), np.int32(t))
                want = gathered_at(ga, np.int32(w), np.int32(t))
                got, want = ({k: np.asarray(v) for k, v in b.items()}
                             for b in (got, want))
                # (ii) the bijection, on the host.
                pos = t * LOCAL_BATCH + np.arange(LOCAL_BATCH)
                if shuffle == "interleave":
                    qpos = ((pos % r) * c + pos // r + off_w[w]) % m
                    live = (pos < m) & (qpos < cnt)
                else:
                    qpos, live = pos, pos < cnt
                rows = (w + W * qpos)[live]
                assert set(got) == set(want) == set(wide_data) | {"weight"}
                np.testing.assert_array_equal(got["weight"], live)
                np.testing.assert_array_equal(want["weight"], live)
                for k, col in wide_data.items():
                    assert got[k].dtype == col.dtype
                    np.testing.assert_array_equal(got[k][live], col[rows])
                    np.testing.assert_array_equal(got[k][live],
                                                  want[k][live])      # (i)
                    assert not got[k][~live].any()
                seen.append(rows)
        np.testing.assert_array_equal(np.sort(np.concatenate(seen)),
                                      np.arange(SLICED_N))
    assert shuffle is None or len(offsets) == 2     # a fresh roll an epoch


def _columns(n, *slots):
    """A cell's resident columns by shape: two wide ones and the label."""
    wide = {f"c{i}": jax.ShapeDtypeStruct((n, s), d) for i, (s, d) in
            enumerate(zip(slots, (np.int32, np.float32)))}
    return {**wide, "label": jax.ShapeDtypeStruct((n,), np.float32)}


V5E_HBM = 16_909_336_064        # one v5e chip's ``bytes_limit``
B_CELL = 16_384


CELLS = {   # the cell's resident columns, the steps of its call
    "lr-criteo": (_columns(1 << 23, 39, 39), 520),
    "dlrm-criteo": (_columns(1 << 20, 26, 13), 65),
    "pa-rcv1": (_columns(9_652_968, 64, 64), 590),
}


@pytest.mark.parametrize("cell, pays, fits", [
    ("lr-criteo", True, True),
    ("dlrm-criteo", True, True),
    # Out on both grounds: the copy of a 64-slot row costs more than its
    # gather, and three calls' copies beside the columns are 118 % of
    # the chip.
    ("pa-rcv1", False, False),
])
def test_the_predicate_at_the_three_cells_shapes(cell, pays, fits):
    columns, steps = CELLS[cell]
    rows = steps * B_CELL
    assert device_ingest._slices_pay(columns) == pays
    assert device_ingest._slices_fit(columns, 1, rows, V5E_HBM) == fits
    assert columns_take_slices(columns, 1, rows, V5E_HBM) == (pays and fits)
    # A backend that reports no limit (the CPU's): the bytes decide
    # nothing, the widths still do.
    assert columns_take_slices(columns, 1, rows, None) == pays


def test_the_fit_keeps_a_margin(monkeypatch):
    """What no ``memory_stats`` shows has room: PA's columns stay out
    with two copies alive (its step program re-tiles 9.9 GB besides),
    lr's go out on a chip of three quarters the memory, dlrm's 0.2 GB
    fit either way."""
    def fit(cell, hbm):
        columns, steps = CELLS[cell]
        return device_ingest._slices_fit(columns, 1, steps * B_CELL, hbm)

    assert not fit("lr-criteo", V5E_HBM * 3 // 4)
    assert fit("dlrm-criteo", V5E_HBM * 3 // 4)
    monkeypatch.setattr(device_ingest, "_SLICED_CALLS_ALIVE", 2)
    assert not fit("pa-rcv1", V5E_HBM)
    assert fit("lr-criteo", V5E_HBM)


@pytest.mark.parametrize("case", ["keyed", "packable", "sort", "unpacked"])
def test_the_other_plans_keep_their_branches(mesh, dataset, sparse_data,
                                             sparse_dataset, case):
    """A keyed plan, a data set that packs, ``shuffle="sort"`` and
    ``pack=False`` are never sliced: their operands are what they were."""
    kw = dict(num_workers=8, local_batch=LOCAL_BATCH, seed=3)
    plan = {
        "keyed": lambda: _keyed_twin(mesh, sparse_data, **kw),
        "packable": lambda: DeviceEpochPlan(dataset, **kw),
        "sort": lambda: DeviceEpochPlan(sparse_dataset, shuffle="sort",
                                        **kw),
        "unpacked": lambda: DeviceEpochPlan(sparse_dataset, pack=False,
                                            **kw),
    }[case]()
    args = plan.epoch_args(0)
    assert not plan.sliced
    assert set(args) == {
        "keyed": {"columns", "off_w", "perm", "queues"},
        "packable": {"columns", "off_w", "perm", "tbuf"},
        "sort": {"columns", "off_w", "perm"},
        "unpacked": {"columns", "off_w", "perm"},
    }[case]
    if case == "packable":
        assert args["tbuf"].shape == (
            8, plan.steps_per_epoch * LOCAL_BATCH, len(plan.dataset.columns))
