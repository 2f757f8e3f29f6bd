"""iALS tests: oracle equivalence of the sharded half-epoch solve, objective
descent, and ranking quality on planted-structure implicit data.

iALS is the BASELINE.json extension workload ("Implicit-feedback iALS
(MovieLens-20M)"); SURVEY.md §7 calls for a per-epoch sharded
normal-equation driver distinct from the streaming PS loop.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def mods():
    import jax

    from fps_tpu.models import ials
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_implicit

    return dict(jax=jax, ials=ials, make_ps_mesh=make_ps_mesh,
                synthetic_implicit=synthetic_implicit)


def _solver(mods, num_shards, nu, ni, rank, **cfg_kw):
    jax, ials = mods["jax"], mods["ials"]
    mesh = mods["make_ps_mesh"](num_shards=num_shards, num_data=1,
                                devices=jax.devices()[:num_shards])
    cfg = ials.IALSConfig(num_users=nu, num_items=ni, rank=rank, **cfg_kw)
    solver = ials.IALSSolver(mesh, cfg)
    solver.init(jax.random.key(0))
    return solver


def _numpy_half_epoch(U, V, users, items, ratings, alpha, reg, num_solve):
    """Dense-numpy oracle for one ALS half-step solving the U side."""
    k = V.shape[1]
    G = V.T @ V
    A = np.zeros((num_solve, k, k))
    b = np.zeros((num_solve, k))
    for u, i, r in zip(users, items, ratings):
        y = V[i]
        A[u] += alpha * r * np.outer(y, y)
        b[u] += (1.0 + alpha * r) * y
    out = np.zeros((num_solve, k))
    for u in range(num_solve):
        out[u] = np.linalg.solve(G + A[u] + reg * np.eye(k), b[u])
    return out


def test_half_epoch_matches_numpy_oracle(mods, devices8):
    """The sharded gram + accumulate + solve pipeline must equal dense ALS."""
    ials = mods["ials"]
    nu, ni, rank = 13, 9, 3  # deliberately not multiples of the shard count
    solver = _solver(mods, 4, nu, ni, rank, alpha=5.0, reg=0.3)
    data = mods["synthetic_implicit"](nu, ni, 7, rank=2, seed=1)

    U0, V0 = solver.factors()
    expected = _numpy_half_epoch(
        U0.astype(np.float64), V0.astype(np.float64),
        data["user"], data["item"], data["rating"],
        alpha=5.0, reg=0.3, num_solve=nu,
    )

    solver.half_epoch(
        "user",
        ials.interaction_chunks(data, num_workers=4, local_batch=4,
                                steps_per_chunk=2, seed=None),
    )
    U1, _ = solver.factors()
    np.testing.assert_allclose(U1, expected, rtol=2e-3, atol=2e-4)


def test_item_half_epoch_matches_numpy_oracle(mods, devices8):
    ials = mods["ials"]
    nu, ni, rank = 9, 14, 3
    solver = _solver(mods, 4, nu, ni, rank, alpha=3.0, reg=0.5)
    data = mods["synthetic_implicit"](nu, ni, 6, rank=2, seed=2)

    U0, V0 = solver.factors()
    expected = _numpy_half_epoch(
        V0.astype(np.float64), U0.astype(np.float64),
        data["item"], data["user"], data["rating"],
        alpha=3.0, reg=0.5, num_solve=ni,
    )
    solver.half_epoch(
        "item",
        ials.interaction_chunks(data, num_workers=4, local_batch=4,
                                steps_per_chunk=2, seed=None),
    )
    _, V1 = solver.factors()
    np.testing.assert_allclose(V1, expected, rtol=2e-3, atol=2e-4)


def test_objective_decreases_over_epochs(mods, devices8):
    ials = mods["ials"]
    nu, ni = 48, 32
    solver = _solver(mods, 8, nu, ni, rank=8, alpha=10.0, reg=0.5)
    data = mods["synthetic_implicit"](nu, ni, 12, rank=3, seed=3)

    def chunks():
        return ials.interaction_chunks(data, num_workers=8, local_batch=8,
                                       steps_per_chunk=2, seed=0)

    losses = [solver.weighted_loss(data["user"], data["item"], data["rating"])]
    for _ in range(3):
        solver.epoch(chunks)
        losses.append(
            solver.weighted_loss(data["user"], data["item"], data["rating"])
        )
    # ALS descends monotonically on the full objective; on the observed-term
    # estimate we still demand a big first drop and no blow-up after.
    assert losses[1] < 0.5 * losses[0], losses
    assert losses[-1] <= losses[1] * 1.05, losses


def test_recall_beats_random(mods, devices8):
    ials = mods["ials"]
    nu, ni = 40, 60
    data = mods["synthetic_implicit"](nu, ni, 20, rank=3, seed=4)
    # Hold out each user's last interaction.
    last = np.full(nu, -1)
    for idx, u in enumerate(data["user"]):
        last[u] = idx
    mask = np.zeros(len(data["user"]), bool)
    mask[last[last >= 0]] = True
    train = {k: v[~mask] for k, v in data.items()}
    hu, hi = data["user"][mask], data["item"][mask]

    solver = _solver(mods, 8, nu, ni, rank=8, alpha=10.0, reg=0.5)

    def chunks():
        return ials.interaction_chunks(train, num_workers=8, local_batch=8,
                                       steps_per_chunk=2, seed=0)

    for _ in range(3):
        solver.epoch(chunks)
    rec = ials.recall_at_k(solver, hu, hi, k=10,
                           exclude=(train["user"], train["item"]))
    # Random top-10 of 60 items ≈ 0.167; planted structure must beat it well.
    assert rec > 0.35, rec


def test_full_mesh_matches_shard_only_mesh(mods, devices8):
    """iALS over a (2, 4) data x shard mesh (stream split over ALL devices,
    pushes psum'd across the data axis) must solve the same factors as the
    1 x 8 shard-only mesh — closing the round-1 restriction that refused
    data-parallel meshes."""
    jax, ials = mods["jax"], mods["ials"]
    nu, ni, rank = 24, 18, 4
    data = mods["synthetic_implicit"](nu, ni, 9, rank=2, seed=6)

    def run(num_data, num_shards):
        mesh = mods["make_ps_mesh"](
            num_shards=num_shards, num_data=num_data,
            devices=jax.devices()[: num_data * num_shards],
        )
        cfg = ials.IALSConfig(num_users=nu, num_items=ni, rank=rank,
                              alpha=5.0, reg=0.3)
        solver = ials.IALSSolver(mesh, cfg)
        solver.init(jax.random.key(0))
        assert solver.num_workers == num_data * num_shards

        def chunks():
            return ials.interaction_chunks(
                data, num_workers=solver.num_workers, local_batch=4,
                steps_per_chunk=2, seed=0,
            )

        for _ in range(2):
            solver.epoch(chunks)
        return solver.factors()

    U_a, V_a = run(1, 8)
    U_b, V_b = run(2, 4)
    # Same normal equations accumulated in a different order: equal up to
    # float32 reassociation.
    np.testing.assert_allclose(U_a, U_b, rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(V_a, V_b, rtol=5e-4, atol=5e-5)


def test_half_epoch_returns_the_sweeps_own_count_and_loss(mods, devices8):
    """A sweep returns per-step ``n`` and ``loss`` (device arrays): their
    sums are the ratings fed and the host ``weighted_loss``'s observed
    term on the tables the sweep READ, not on the one it leaves."""
    ials = mods["ials"]
    nu, ni = 48, 32
    solver = _solver(mods, 4, nu, ni, rank=8, alpha=10.0, reg=0.5)
    data = mods["synthetic_implicit"](nu, ni, 12, rank=3, seed=3)
    n_rows = len(data["user"])

    def chunks():
        return ials.interaction_chunks(data, num_workers=4, local_batch=8,
                                       steps_per_chunk=4, seed=0)

    def observed():
        U, V = solver.factors()
        reg = solver.cfg.reg * float(np.sum(U * U) + np.sum(V * V))
        return solver.weighted_loss(data["user"], data["item"],
                                    data["rating"]) - reg

    for side in ("user", "item", "user"):
        before = observed()
        m = solver.half_epoch(side, chunks())
        assert set(m) == {"n", "loss"} and m["n"].shape == m["loss"].shape
        assert m["n"].shape[0] % 4 == 0  # whole chunks, padding included
        assert float(np.sum(m["n"])) == n_rows
        np.testing.assert_allclose(float(np.sum(np.asarray(m["loss"]), dtype=np.float64)),
                                   before, rtol=1e-5)
        assert observed() < before
    both = solver.epoch(chunks)
    assert [float(np.sum(m["n"])) for m in both] == [n_rows, n_rows]


@pytest.mark.parametrize("block", [5, 7, 64])
def test_cholesky_solve_matches_numpy_in_every_block(mods, devices8,
                                                     monkeypatch, block):
    """``_solve_fn`` (Cholesky and two triangular solves, a block of ids
    at a time) against ``np.linalg.solve`` in float64: a block count that
    divides the shard's rows, one that does not (the last block starts
    early and solves some ids twice), and one block for all."""
    jax, ials = mods["jax"], mods["ials"]
    monkeypatch.setattr(ials, "SOLVE_BLOCK_IDS", block)
    nu, k = 35, 6
    solver = _solver(mods, 1, nu, 9, k, reg=0.3)
    rng = np.random.default_rng(block)
    Y = rng.normal(size=(nu, 4, k))
    A = np.einsum("nri,nrj->nij", Y, Y)
    gram = rng.normal(size=(12, k))
    gram = gram.T @ gram
    b = rng.normal(size=(nu, k))
    got = solver._solve_fn(nu, nu)(
        jax.numpy.asarray(gram, np.float32),
        jax.numpy.asarray(A.reshape(nu, k * k), np.float32),
        jax.numpy.asarray(b, np.float32))
    want = np.linalg.solve(gram[None] + A + 0.3 * np.eye(k)[None],
                           b[:, :, None])[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def _grouped_draw(L, nu, ni, seed):
    """A chunk's worth of ratings skewed so that user 0 has ``2L + 1``
    (its run of the sorted order crosses two blocks of ``L`` at least),
    user 1 exactly ``L``, user 2 none and user 3 one, the rest drawn;
    padding slots of weight 0 (carrying ids of their own, which must not
    count) scattered through."""
    rng = np.random.default_rng(seed)
    users = np.concatenate([
        np.zeros(2 * L + 1, np.int32), np.ones(L, np.int32),
        np.full(1, 3, np.int32), rng.integers(4, nu, 11).astype(np.int32)])
    n = len(users)
    B = 8
    T = -(-(n + 5) // B)
    live = np.zeros(T * B, bool)
    live[rng.permutation(T * B)[:n]] = True
    cols = {"user": np.full(T * B, 2, np.int32),   # padding names user 2
            "item": rng.integers(0, ni, T * B).astype(np.int32),
            "rating": rng.uniform(0.5, 5.0, T * B).astype(np.float32),
            "weight": live.astype(np.float32)}
    cols["user"][live] = rng.permutation(users)
    return {k: v.reshape(T, B) for k, v in cols.items()}


@pytest.mark.parametrize("L,push,shards",
                         [(8, 2, 1), (16, 1, 1), (16, 128, 1), (8, 2, 4)])
def test_grouped_sums_are_an_ids_ratings_added_in_the_plans_order(
        mods, devices8, monkeypatch, L, push, shards):
    """The accumulate program's sums, an id at a time: on one worker the
    float32 additions of the id's addends ``(alpha r y) y^T`` and
    ``(1 + alpha r) y`` ONE AFTER ANOTHER in the order the chunk holds
    them, bit for bit (the order the benchmark's reference adds them in);
    on four the same sums to float32's rounding. An id whose run crosses
    blocks of ``L`` ratings (tiles of 8), one of exactly a block, one
    absent, padding slots, and more runs in a block than one push takes."""
    jax, ials = mods["jax"], mods["ials"]
    from fps_tpu.core.store import phys_to_id, rows_per_shard

    monkeypatch.setattr(ials, "RUN_BLOCK", L)
    monkeypatch.setattr(ials, "RUN_TILE_BYTES", 1)
    monkeypatch.setattr(ials, "RUN_PUSH", push)
    nu, ni, k, alpha = 11, 7, 5, 7.0
    solver = _solver(mods, shards, nu, ni, k, alpha=alpha)
    chunk = _grouped_draw(L, nu, ni, seed=L)
    _, V = solver.factors()
    f32 = np.float32
    want_A = np.zeros((nu, k, k), f32)
    want_b = np.zeros((nu, k), f32)
    for u, i, r, w in zip(*(chunk[c].ravel() for c in
                            ("user", "item", "rating", "weight"))):
        if w > 0:
            want_A[u] += ((f32(alpha) * r * w) * V[i])[:, None] * V[i][None]
            want_b[u] += ((f32(1.0) + f32(alpha) * r) * w) * V[i]
    assert want_A[2].any() == 0 and np.count_nonzero(
        chunk["weight"] == 0) >= 5

    rps = rows_per_shard(nu, shards)
    tables = solver.store.tables
    A, b, m = solver._accumulate_fn("user")(
        tables[ials.ITEM_TABLE], tables[ials.USER_TABLE],
        solver._zeros_acc(rps * shards, k * k),
        solver._zeros_acc(rps * shards, k),
        {"solve_ids": chunk["user"], "fixed_ids": chunk["item"],
         "rating": chunk["rating"], "weight": chunk["weight"]})
    ids = np.asarray(phys_to_id(np.arange(rps * shards), shards, rps))
    real = ids < nu
    got_A = np.zeros((nu, k, k), f32)
    got_b = np.zeros((nu, k), f32)
    got_A[ids[real]] = np.asarray(A)[real].reshape(-1, k, k)
    got_b[ids[real]] = np.asarray(b)[real]
    if shards == 1:
        np.testing.assert_array_equal(got_A, want_A)
        np.testing.assert_array_equal(got_b, want_b)
    else:
        np.testing.assert_allclose(got_A, want_A, rtol=1e-5, atol=1e-9)
        np.testing.assert_allclose(got_b, want_b, rtol=1e-5, atol=1e-9)
    assert not np.asarray(A)[~real].any()
    np.testing.assert_array_equal(np.asarray(m["n"]),
                                  chunk["weight"].sum(axis=1))


@pytest.mark.parametrize("k", [1, 3, 8, 10, 16, 64, 128, 130])
def test_addends_lie_as_the_sum_layout_says(mods, k):
    """A rating's addend in rows of 128 lanes: the ``kp x kp`` left side
    row-major from the first lane, the right side from row ``g``, zeros
    elsewhere, at ranks that divide a lane row, that do not, and that
    are wider than one."""
    ials = mods["ials"]
    rng = np.random.default_rng(k)
    ya, y, by = (rng.normal(size=(5, k)).astype(np.float32)
                 for _ in range(3))
    kp, g, rows = ials._sum_layout(k)
    assert kp >= k and rows % 8 == 0 and g * 128 >= kp * kp
    out = np.asarray(ials._addends(*map(
        mods["jax"].numpy.asarray, (ya, y, by))))
    assert out.shape == (5, rows, 128)
    flat = out.reshape(5, -1)
    left = flat[:, :kp * kp].reshape(5, kp, kp)
    np.testing.assert_array_equal(left[:, :k, :k],
                                  ya[:, :, None] * y[:, None, :])
    np.testing.assert_array_equal(flat[:, g * 128:g * 128 + k], by)
    assert np.count_nonzero(flat) == np.count_nonzero(
        left[:, :k, :k]) + np.count_nonzero(by)


def test_one_chunk_and_several_chunks_solve_the_same_table(mods, devices8,
                                                           monkeypatch):
    """A sweep fed as ONE chunk and as several (a busy id's ratings then
    recur from chunk to chunk, each chunk pushing its own sum of them)
    forms the same equations: the accumulator is a table being pushed
    to, whatever the chunking."""
    ials = mods["ials"]
    monkeypatch.setattr(ials, "RUN_BLOCK", 16)
    monkeypatch.setattr(ials, "RUN_TILE_BYTES", 1)
    monkeypatch.setattr(ials, "RUN_PUSH", 4)
    nu, ni = 40, 12
    data = mods["synthetic_implicit"](nu, ni, 9, rank=2, seed=4)
    assert np.bincount(data["item"]).max() > 3 * 4

    def run(steps_per_chunk):
        solver = _solver(mods, 4, nu, ni, 6, alpha=8.0, reg=0.4)
        for side in ("item", "user"):
            m = solver.half_epoch(side, ials.interaction_chunks(
                data, num_workers=4, local_batch=8,
                steps_per_chunk=steps_per_chunk, seed=5))
        return solver.factors(), m

    (U_one, V_one), m_one = run(64)
    (U_many, V_many), m_many = run(2)
    assert m_one["n"].shape[0] == 64 and m_many["n"].shape[0] < 64
    # The same equations summed in another order: float32 reassociation.
    np.testing.assert_allclose(V_many, V_one, rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(U_many, U_one, rtol=5e-4, atol=5e-5)


def test_per_step_count_and_loss_follow_the_plans_order(mods, devices8):
    """``n`` and ``loss`` step by step, in the order the chunks were fed,
    under the tables the sweep found: the grouped sums move neither."""
    ials = mods["ials"]
    nu, ni, alpha = 30, 20, 6.0
    solver = _solver(mods, 4, nu, ni, 4, alpha=alpha, reg=0.2)
    data = mods["synthetic_implicit"](nu, ni, 10, rank=2, seed=8)

    def chunks():
        return ials.interaction_chunks(data, num_workers=4, local_batch=8,
                                       steps_per_chunk=3, seed=2)

    for side in ("user", "item"):
        U, V = (t.astype(np.float64) for t in solver.factors())
        n, loss = [], []
        for c in chunks():
            xy = np.sum(U[c["user"]] * V[c["item"]], axis=-1)
            conf = 1.0 + alpha * c["rating"].astype(np.float64)
            n.extend(c["weight"].sum(axis=1))
            loss.extend((c["weight"] * conf * (1.0 - xy) ** 2).sum(axis=1))
        m = solver.half_epoch(side, chunks())
        np.testing.assert_array_equal(np.asarray(m["n"]), n)
        np.testing.assert_allclose(np.asarray(m["loss"]), loss, rtol=2e-5)
        assert min(n) == 0 < max(n)  # padding steps among them
