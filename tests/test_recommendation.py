"""Top-K recommendation tests (the reference's ...AndTopK MF variant).

Correctness oracle: brute-force numpy ranking over the logical table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from fps_tpu.core.store import ParamStore, TableSpec
from fps_tpu.models.recommendation import (
    build_topk_fn,
    mf_user_vectors,
    recommend_topk,
)
from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh


def _store(mesh, num_ids, dim, seed=0):
    rng = np.random.default_rng(seed)
    logical = rng.normal(0, 1, (num_ids, dim)).astype(np.float32)

    def init(key, ids):
        safe = jnp.minimum(ids, num_ids - 1)
        return jnp.take(jnp.asarray(logical), safe, axis=0)

    store = ParamStore(mesh, [TableSpec("items", num_ids, dim, init)])
    store.init(jax.random.key(0))
    return store, logical


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4), (1, 3)])
def test_topk_matches_bruteforce(devices8, mesh_shape):
    nd, ns = mesh_shape
    mesh = make_ps_mesh(num_shards=ns, num_data=nd, devices=devices8[: nd * ns])
    num_ids, dim, B, k = 57, 6, 9, 5
    store, logical = _store(mesh, num_ids, dim)

    rng = np.random.default_rng(1)
    q = rng.normal(0, 1, (B, dim)).astype(np.float32)
    ids, scores = recommend_topk(store, "items", q, k)

    want = np.argsort(-(q @ logical.T), axis=1)[:, :k]
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(
        scores, np.take_along_axis(q @ logical.T, want, 1), rtol=1e-5
    )


def test_topk_with_exclusions(devices8):
    mesh = make_ps_mesh(num_shards=8, num_data=1, devices=devices8)
    num_ids, dim, B, k, E = 40, 4, 6, 4, 3
    store, logical = _store(mesh, num_ids, dim, seed=2)

    rng = np.random.default_rng(3)
    q = rng.normal(0, 1, (B, dim)).astype(np.float32)
    full = q @ logical.T
    # Exclude each query's true top-E items: results must be ranks E..E+k-1.
    order = np.argsort(-full, axis=1)
    exclude = order[:, :E].astype(np.int32)
    ids, _ = recommend_topk(store, "items", q, k, exclude=exclude)
    np.testing.assert_array_equal(ids, order[:, E : E + k])

    # -1 slots are ignored.
    none = np.full((B, E), -1, np.int32)
    ids2, _ = recommend_topk(store, "items", q, k, exclude=none)
    np.testing.assert_array_equal(ids2, order[:, :k])


def test_topk_fn_is_jittable_and_reusable(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8)
    store, logical = _store(mesh, 33, 5, seed=4)
    fn = build_topk_fn(store, "items", k=3, exclude_capacity=0)
    repl = NamedSharding(mesh, P())
    for seed in (5, 6):
        q = np.random.default_rng(seed).normal(0, 1, (4, 5)).astype(np.float32)
        ex = jax.device_put(jnp.full((4, 1), -1, jnp.int32), repl)
        ids, _ = fn(store.tables, jax.device_put(jnp.asarray(q), repl), ex)
        want = np.argsort(-(q @ logical.T), axis=1)[:, :3]
        np.testing.assert_array_equal(np.asarray(ids), want)


def test_mf_user_vectors_layout():
    W = 4
    num_users, rank = 10, 3
    rps = -(-num_users // W)
    table = np.zeros((rps * W, rank), np.float32)
    for u in range(num_users):
        table[(u % W) * rps + u // W] = u
    users = np.array([0, 3, 7, 9])
    got = mf_user_vectors(table, W, users)
    np.testing.assert_array_equal(got, np.repeat(users[:, None], rank, 1))


# ---------------------------------------------------------------------------
# Online (in-loop) top-K emission — the streaming AndTopK shape.
# ---------------------------------------------------------------------------

def test_online_topk_tap_interleaves_and_matches_bruteforce(devices8):
    """Top-K events ride the metrics stream interleaved with training, per
    worker, on the tap cadence; with lr=0 (frozen tables) the emitted
    ranking must equal the brute-force oracle over each worker's users."""
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.models.recommendation import (
        make_online_topk_tap,
        mf_topk_query_fn,
    )
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    W = num_workers_of(mesh)
    NU, NI, K, Q, EVERY = 40, 29, 5, 3, 2
    cfg = MFConfig(num_users=NU, num_items=NI, rank=4, learning_rate=0.0,
                   reg=0.0)
    trainer, store = online_mf(mesh, cfg, donate=False)
    trainer.config = __import__("dataclasses").replace(
        trainer.config,
        step_tap=make_online_topk_tap(
            store, "item_factors", K, every=EVERY,
            query_fn=mf_topk_query_fn(W, Q),
        ),
    )
    tables, ls = trainer.init_state(jax.random.key(0))
    data = synthetic_ratings(NU, NI, 8 * 8 * W, seed=0)
    chunk = next(epoch_chunks(data, num_workers=W, local_batch=8,
                              steps_per_chunk=8, route_key="user"))
    tables, ls, m = trainer.run_chunk(tables, ls, chunk, jax.random.key(1))

    tap = {k2: np.asarray(v) for k2, v in m["tap"].items()}
    assert tap["topk_ids"].shape == (8, W, Q, K)
    # Off-cadence steps are filled; on-cadence steps carry real emissions.
    assert (tap["topk_ids"][1] == -1).all()
    assert (tap["topk_ids"][0] >= 0).all()
    assert (tap["topk_query"][1] == -1).all()

    # Oracle: lr=0 so tables never moved — rank initial factors directly.
    items = store.lookup_host("item_factors", np.arange(NI))
    ls_host = np.asarray(ls)
    checked = 0
    for t in range(0, 8, EVERY):
        for w in range(W):
            users = tap["topk_query"][t, w]
            valid = users >= 0  # padded batch slots emit query id -1
            if not valid.any():
                continue
            qvecs = mf_user_vectors(ls_host, W, users[valid])
            want = np.argsort(-(qvecs @ items.T), axis=1)[:, :K]
            np.testing.assert_array_equal(tap["topk_ids"][t, w][valid], want)
            checked += int(valid.sum())
    assert checked > 0


def test_mf_negative_sampling_improves_implicit_ranking(devices8):
    """On positive-only (implicit) feedback every observed target is 1.0,
    so plain MF barely separates unseen-good from unseen-bad items.
    Sampling unrated items as weighted pseudo-negatives (the reference
    MF's optional knob) must improve held-out ranking (AUC of held-out
    positives vs never-interacted items) and widen the score margin
    between interacted and never-interacted items."""
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.models.recommendation import mf_user_vectors
    from fps_tpu.utils.datasets import synthetic_implicit

    mesh = make_ps_mesh(num_shards=8, num_data=1, devices=devices8[:8])
    W = num_workers_of(mesh)
    NU, NI, HELD = 48, 96, 4
    data = synthetic_implicit(NU, NI, 28, rank=3, seed=5)
    data["rating"] = np.ones_like(data["rating"])  # pure implicit

    # Hold out each user's last interactions; novel ones score the model.
    train_mask = np.ones(len(data["user"]), bool)
    held = {}
    for u in range(NU):
        rows = np.flatnonzero(data["user"] == u)
        held[u] = set(int(i) for i in data["item"][rows[-HELD:]])
        train_mask[rows[-HELD:]] = False
    train = {k2: v[train_mask] for k2, v in data.items()}
    seen = {
        u: set(map(int, np.unique(train["item"][train["user"] == u])))
        for u in range(NU)
    }
    held_eff = {u: held[u] - seen[u] for u in range(NU)}

    def run(negatives):
        cfg = MFConfig(num_users=NU, num_items=NI, rank=8,
                       learning_rate=0.08, reg=0.01,
                       negative_samples=negatives, negative_weight=0.5)
        trainer, store = online_mf(mesh, cfg)
        tables, ls = trainer.init_state(jax.random.key(0))
        chunks = multi_epoch_chunks(
            train, 12, num_workers=W, local_batch=16, steps_per_chunk=8,
            route_key="user", seed=2,
        )
        tables, ls, _ = trainer.fit_stream(tables, ls, chunks,
                                           jax.random.key(1))
        P = mf_user_vectors(np.asarray(ls), W, np.arange(NU))
        Q = store.lookup_host("item_factors", np.arange(NI))
        S = P @ Q.T
        aucs, margins = [], []
        for u in range(NU):
            pos = list(held_eff[u])
            neg = [i for i in range(NI)
                   if i not in seen[u] and i not in held[u]]
            if not pos:
                continue
            ns = S[u, neg]
            aucs.append(np.mean([np.mean(p > ns) for p in S[u, pos]]))
            margins.append(S[u, list(seen[u])].mean() - ns.mean())
        return float(np.mean(aucs)), float(np.mean(margins))

    auc0, margin0 = run(0)
    auc4, margin4 = run(4)
    assert auc4 > auc0 + 0.02, (auc0, auc4)
    assert margin4 > margin0 * 1.5, (margin0, margin4)
    assert auc4 > 0.6, auc4


def test_online_topk_tap_k_exceeds_candidates(devices8):
    """k larger than the merged candidate pool (S * min(k, rows_per_shard))
    must not fail at trace time; emitted slots beyond the real item count
    are -1 ids / NEG_INF scores and the real prefix matches brute force.
    Regression for the unclamped final lax.top_k (round-2 advice)."""
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.models.recommendation import (
        NEG_INF,
        make_online_topk_tap,
        mf_topk_query_fn,
        mf_user_vectors,
    )
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    W = num_workers_of(mesh)
    # NI=6 over 4 shards -> rows_per_shard=2 -> merged pool 4*2=8 < K=10.
    NU, NI, K, Q = 16, 6, 10, 2
    cfg = MFConfig(num_users=NU, num_items=NI, rank=4, learning_rate=0.0,
                   reg=0.0)
    trainer, store = online_mf(mesh, cfg, donate=False)
    trainer.config = __import__("dataclasses").replace(
        trainer.config,
        step_tap=make_online_topk_tap(
            store, "item_factors", K, every=1,
            query_fn=mf_topk_query_fn(W, Q),
        ),
    )
    tables, ls = trainer.init_state(jax.random.key(0))
    data = synthetic_ratings(NU, NI, 4 * 4 * W, seed=0)
    chunk = next(epoch_chunks(data, num_workers=W, local_batch=4,
                              steps_per_chunk=4, route_key="user"))
    tables, ls, m = trainer.run_chunk(tables, ls, chunk, jax.random.key(1))

    tap = {k2: np.asarray(v) for k2, v in m["tap"].items()}
    assert tap["topk_ids"].shape == (4, W, Q, K)

    items = store.lookup_host("item_factors", np.arange(NI))
    ls_host = np.asarray(ls)
    checked = 0
    for t in range(4):
        for w in range(W):
            users = tap["topk_query"][t, w]
            valid = users >= 0
            if not valid.any():
                continue
            ids_tw = tap["topk_ids"][t, w][valid]
            scores_tw = tap["topk_scores"][t, w][valid]
            # Real prefix: all NI items ranked exactly as brute force.
            qvecs = mf_user_vectors(ls_host, W, users[valid])
            want = np.argsort(-(qvecs @ items.T), axis=1)[:, :NI]
            np.testing.assert_array_equal(ids_tw[:, :NI], want)
            # Beyond the pool: sentinel slots only.
            assert (scores_tw[:, NI:] <= float(NEG_INF)).all()
            checked += int(valid.sum())
    assert checked > 0


# ---------------------------------------------------------------------------
# The selection that prunes by chunk maxima, against a full sort.
# ---------------------------------------------------------------------------

# Rows a shard at which pruning engages at every n up to 128: c 8, C 384.
_PRUNED_RPS, _PRUNED_C = 3001, 384


def _grid(rng, shape):
    """Multiples of 1/64 in [-8, 8]: with small-integer queries every
    product and every sum is exact in float32 in any order, so the
    program's scores equal numpy's to the bit and ties are plentiful."""
    return (rng.integers(-512, 513, shape) / 64.0).astype(np.float32)


def _planted(num_ids, best_ids, tied_ids=()):
    """A one-column table scored by the query [1]: ``best_ids`` get the
    distinct largest scores, ``tied_ids`` all the next one, the rest a
    pattern far below."""
    col = -1.0 - (np.arange(num_ids) % 7)
    col[np.asarray(tied_ids, int)] = 50.0
    col[np.asarray(best_ids, int)] = 100.0 + np.arange(len(best_ids))
    return col.astype(np.float32)[:, None], np.ones((3, 1), np.float32)


def _selection_case(name):
    """-> (shards, num_ids, k, exclude capacity, table, queries, the C of
    the chunking ``C x 8`` it prunes by or None where it stays direct)"""
    rng = np.random.default_rng(sum(map(ord, name)))
    R, C = _PRUNED_RPS, _PRUNED_C

    def random(num_ids, q=5, dim=4):
        return (_grid(rng, (num_ids, dim)),
                rng.integers(-2, 3, (q, dim)).astype(np.float32))

    if name == "rows_not_a_multiple_of_c":
        return 1, R, 100, 0, *random(R), C
    if name == "best_all_in_one_chunk":  # chunk 7: rows 7, 7 + C, ...
        return 1, R, 5, 0, *_planted(R, 7 + C * np.arange(5)), C
    if name == "best_in_distinct_chunks":
        return 1, R, 100, 0, *_planted(R, 3 * np.arange(100)), C
    if name == "every_score_equal":
        return 1, R, 100, 0, np.ones((R, 2), np.float32), \
            np.ones((4, 2), np.float32), C
    if name == "ties_across_a_chunk_boundary_at_rank_n":
        # Four clear winners; rank 5 tied between neighbouring chunks, a
        # winner's own chunk (row 7 + C) and the last, half-filled chunk.
        return 1, R, 5, 0, *_planted(
            R, [7, 8, 900, 2999],
            [7 + C, 8 + C, 9, 10, 11, C - 1, 2 * C - 1, 3000]), C
    if name == "n_exceeds_rows":
        return 1, 6, 10, 0, *random(6), None
    if name == "too_small_to_prune":
        return 1, 2000, 100, 0, *random(2000), None
    if name == "n_too_near_rows_to_prune":
        return 1, R, 1500, 0, *random(R), None
    if name == "one_shard_masked_padding":  # columns filling the last chunk
        return 1, R + 5, 128, 0, *random(R + 5), C
    if name == "two_shards_masked_padding":
        return 2, 2 * R - 1, 100, 0, *random(2 * R - 1), C
    if name == "eight_shards_masked_padding":
        return 8, 8 * R - 5, 100, 0, *random(8 * R - 5), C
    if name == "exclusions_through_build_topk_fn":
        return 2, 2 * R, 100, 3, *random(2 * R), C
    if name == "fetch_in_runs_of_lane_tiles":  # the kernel's second axis
        return 1, 2 * R, 130, 0, *random(2 * R, q=16), 2 * C
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "rows_not_a_multiple_of_c", "best_all_in_one_chunk",
    "best_in_distinct_chunks", "every_score_equal",
    "ties_across_a_chunk_boundary_at_rank_n", "n_exceeds_rows",
    "too_small_to_prune", "n_too_near_rows_to_prune",
    "one_shard_masked_padding", "two_shards_masked_padding",
    "eight_shards_masked_padding", "exclusions_through_build_topk_fn",
    "fetch_in_runs_of_lane_tiles",
])
def test_selection_equals_a_full_sort(devices8, monkeypatch, name):
    """The selection, pruned or direct, against a full sort of the same
    scores: the score at every rank is the reference's exactly, no id is
    answered twice or out of range, every id's score is the product's own
    (so a tie may fall either way and nothing else may differ); slots
    past the table's rows carry the ``_pad_to_k`` sentinels; the route
    log says whether the shape pruned."""
    import fps_tpu.ops as ops
    from fps_tpu.models import recommendation
    from fps_tpu.models.recommendation import NEG_INF

    shards, num_ids, k, E, logical, q, chunks = _selection_case(name)
    if name == "fetch_in_runs_of_lane_tiles":
        # One tile of 128 chunks a step of the kernel: six runs a query,
        # each over the two lane tiles of its 130 candidates.
        monkeypatch.setattr(recommendation, "_FETCH_BLOCK_BYTES",
                            4 * 8 * 8 * 128)
    mesh = make_ps_mesh(num_shards=shards, num_data=1,
                        devices=devices8[:shards])

    def init(key, ids):
        return jnp.take(jnp.asarray(logical),
                        jnp.minimum(ids, num_ids - 1), axis=0)

    store = ParamStore(mesh, [TableSpec("items", num_ids, logical.shape[1],
                                        init)])
    store.init(jax.random.key(0))
    full = q @ logical.T  # exact: see _grid
    order = np.argsort(-full, axis=1, kind="stable")
    exclude = order[:, :E].astype(np.int32) if E else None
    if E:
        full = full.copy()
        np.put_along_axis(full, exclude, -np.inf, axis=1)

    ops.clear_routes()
    ids, scores = recommend_topk(store, "items", q, k, exclude=exclude)
    routes = [r for r in ops.routes_traced() if r.route == "tap.topk_pruned"]
    assert [r.reason for r in routes] == (
        [f"chunks={chunks}x8"] if chunks else [])

    real = min(k, num_ids)
    want = -np.sort(-full, axis=1)[:, :real]
    np.testing.assert_array_equal(scores[:, :real], want)
    for row_ids, row_scores, row_full in zip(ids, scores, full):
        live = row_ids[:real]
        assert len(set(live.tolist())) == real
        assert live.min() >= 0 and live.max() < num_ids
        np.testing.assert_array_equal(row_full[live], row_scores[:real])
    assert (ids[:, real:] == -1).all()
    assert (scores[:, real:] <= float(NEG_INF)).all()
