"""Core store tests: pull answers every request, pushes accumulate.

Mirrors the reference's core test intent (SURVEY.md §4: "a core test driving
FlinkParameterServer.transform with trivial logic asserting every pull gets
answered and pushes accumulate"), on a real 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import fps_tpu.core.store as store_mod
import fps_tpu.ops as ops
from fps_tpu.core.store import (
    ParamStore,
    TableSpec,
    id_to_phys,
    phys_to_id,
    pull,
    pull_local,
    push,
    rows_per_shard,
)
from fps_tpu.models.logistic_regression import adagrad_fold
from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS, make_ps_mesh


def reference_table(num_ids, dim, num_shards):
    """Dense global table in owner-major physical layout + the id->row map."""
    rps = rows_per_shard(num_ids, num_shards)
    total = rps * num_shards
    phys = np.arange(total)
    ids = phys_to_id(phys, num_shards, rps)
    vals = (ids[:, None] * 10.0 + np.arange(dim)[None, :]).astype(np.float32)
    return vals, rps


def test_phys_id_roundtrip():
    for num_shards in (1, 3, 8):
        ids = np.arange(100)
        rps = rows_per_shard(100, num_shards)
        phys = id_to_phys(ids, num_shards, rps)
        back = phys_to_id(phys, num_shards, rps)
        np.testing.assert_array_equal(back, ids)
        assert len(np.unique(np.asarray(phys))) == 100


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_pull_returns_requested_rows(devices8, mesh_shape):
    mesh = make_ps_mesh(num_shards=mesh_shape[1], num_data=mesh_shape[0])
    S = mesh_shape[1]
    num_ids, dim, B = 103, 7, 16
    table, rps = reference_table(num_ids, dim, S)
    table_dev = jax.device_put(
        jnp.asarray(table), NamedSharding(mesh, P(SHARD_AXIS, None))
    )
    W = mesh_shape[0] * mesh_shape[1]
    rng = np.random.default_rng(0)
    ids = rng.integers(0, num_ids, (W * B,)).astype(np.int32)
    ids_dev = jax.device_put(
        jnp.asarray(ids), NamedSharding(mesh, P((DATA_AXIS, SHARD_AXIS)))
    )

    out = jax.jit(
        jax.shard_map(
            lambda t, i: pull(t, i, num_shards=S),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS))),
            out_specs=P((DATA_AXIS, SHARD_AXIS)),
            check_vma=False,
        )
    )(table_dev, ids_dev)

    expected = (ids[:, None] * 10.0 + np.arange(dim)[None, :]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_push_accumulates_including_duplicates(devices8, mesh_shape):
    mesh = make_ps_mesh(num_shards=mesh_shape[1], num_data=mesh_shape[0])
    D, S = mesh_shape
    W = D * S
    num_ids, dim, B = 50, 4, 12
    rps = rows_per_shard(num_ids, S)
    table = np.zeros((rps * S, dim), np.float32)
    table_dev = jax.device_put(
        jnp.asarray(table), NamedSharding(mesh, P(SHARD_AXIS, None))
    )
    rng = np.random.default_rng(1)
    ids = rng.integers(0, num_ids, (W * B,)).astype(np.int32)
    deltas = rng.normal(0, 1, (W * B, dim)).astype(np.float32)

    out = jax.jit(
        jax.shard_map(
            lambda t, i, d: push(
                t, i, d, num_shards=S,
                data_axis=DATA_AXIS if D > 1 else None,
            ),
            mesh=mesh,
            in_specs=(
                P(SHARD_AXIS, None),
                P((DATA_AXIS, SHARD_AXIS)),
                P((DATA_AXIS, SHARD_AXIS), None),
            ),
            out_specs=P(SHARD_AXIS, None),
            check_vma=False,
        )
    )(table_dev, jnp.asarray(ids), jnp.asarray(deltas))

    expected = np.zeros((rps * S, dim), np.float32)
    phys = np.asarray(id_to_phys(ids, S, rps))
    np.testing.assert_array_equal(
        np.asarray(phys_to_id(np.arange(rps * S), S, rps))[phys], ids
    )
    np.add.at(expected, phys, deltas)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5, atol=1e-5)


def test_push_general_apply_fn_sees_combined_delta(devices8):
    """Non-additive folds get the batch-summed delta once per id, and
    padding pushes (id -1) are dropped entirely."""
    mesh = make_ps_mesh(num_shards=8, num_data=1)
    S, num_ids, dim = 8, 24, 3
    rps = rows_per_shard(num_ids, S)
    base = np.ones((rps * S, dim), np.float32)
    ids = np.array([5] * 8 + list(range(7)) + [-1], np.int32)  # dup-heavy + pad
    deltas = np.ones((16, dim), np.float32)

    # apply_fn: param * 2 + delta  (checks it runs once per touched row).
    out = jax.jit(
        jax.shard_map(
            lambda t, i, d: push(
                t, i, d, num_shards=S, data_axis=None,
                apply_fn=lambda rows, delta: rows * 2 + delta,
            ),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                      P((DATA_AXIS, SHARD_AXIS), None)),
            out_specs=P(SHARD_AXIS, None),
            check_vma=False,
        )
    )(
        jax.device_put(jnp.asarray(base), NamedSharding(mesh, P(SHARD_AXIS, None))),
        jnp.asarray(ids),
        jnp.asarray(deltas),
    )
    out = np.asarray(out)
    phys5 = int(id_to_phys(np.int32(5), S, rps))
    # id 5: touched, combined delta = 8 (+1 from the range part? id 5 also in range)
    total5 = 8.0 + 1.0
    assert out[phys5] == pytest.approx(np.full(dim, 1 * 2 + total5))
    phys3 = int(id_to_phys(np.int32(3), S, rps))
    assert out[phys3] == pytest.approx(np.full(dim, 1 * 2 + 1.0))
    # Untouched id stays exactly as it was.
    phys20 = int(id_to_phys(np.int32(20), S, rps))
    assert out[phys20] == pytest.approx(np.ones(dim))


def test_pull_local_reads_own_rows(devices8):
    mesh = make_ps_mesh(num_shards=8, num_data=1)
    W = 8
    num_ids, dim = 40, 5
    rps = rows_per_shard(num_ids, W)
    table, _ = reference_table(num_ids, dim, W)
    # Each worker asks only for ids it owns (id % W == worker).
    ids = np.stack([np.arange(w, w + 2 * W, W) for w in range(W)]).astype(np.int32)
    ids_flat = ids.reshape(-1)

    out = jax.jit(
        jax.shard_map(
            lambda t, i: pull_local(t, i, num_shards=W),
            mesh=mesh,
            in_specs=(P((DATA_AXIS, SHARD_AXIS), None), P((DATA_AXIS, SHARD_AXIS))),
            out_specs=P((DATA_AXIS, SHARD_AXIS)),
            check_vma=False,
        )
    )(
        jax.device_put(
            jnp.asarray(table),
            NamedSharding(mesh, P((DATA_AXIS, SHARD_AXIS), None)),
        ),
        jnp.asarray(ids_flat),
    )
    expected = (ids_flat[:, None] * 10.0 + np.arange(dim)[None, :]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_param_store_init_deterministic_across_shardings(devices8):
    """Same key -> same per-id values regardless of shard count (the
    reference's id-seeded reproducible initialization)."""
    spec = TableSpec(name="t", num_ids=37, dim=4)
    vals = {}
    for S in (1, 2, 8):
        mesh = make_ps_mesh(num_shards=S, num_data=8 // S if S < 8 else 1)
        store = ParamStore(mesh, [spec])
        store.init(jax.random.key(7))
        ids = np.arange(37)
        vals[S] = store.lookup_host("t", ids)
    np.testing.assert_allclose(vals[1], vals[2], rtol=1e-6)
    np.testing.assert_allclose(vals[1], vals[8], rtol=1e-6)


@pytest.mark.parametrize("trial", range(6))
def test_pull_push_matches_numpy_model_randomized(devices8, trial):
    """Property test: for random table/mesh/batch geometries (duplicates,
    padding ids, both combine modes), a pull followed by a push through the
    collective path matches a pure-numpy model of the PS semantics."""
    rng = np.random.default_rng(100 + trial)
    nd, ns = [(1, 8), (2, 4), (4, 2), (1, 4), (2, 2), (8, 1)][trial]
    devs = jax.devices()[: nd * ns]
    mesh = make_ps_mesh(num_shards=ns, num_data=nd, devices=devs)
    num_ids = int(rng.integers(3, 200))
    dim = int(rng.integers(1, 17))
    B_local = int(rng.integers(1, 33))
    combine = ["sum", "mean"][trial % 2]
    W = nd * ns

    rps = rows_per_shard(num_ids, ns)
    vals, _ = reference_table(num_ids, dim, ns)
    # ~20% padding ids (-1) for the push; pulls use valid ids only.
    pull_ids_h = rng.integers(0, num_ids, (W, B_local)).astype(np.int32)
    push_ids_h = pull_ids_h.copy()
    drop = rng.random((W, B_local)) < 0.2
    push_ids_h[drop] = -1
    deltas_h = rng.normal(0, 1, (W, B_local, dim)).astype(np.float32)

    table = jax.device_put(
        jnp.asarray(vals), NamedSharding(mesh, P(SHARD_AXIS, None))
    )
    bsh = NamedSharding(mesh, P((DATA_AXIS, SHARD_AXIS)))
    pids = jax.device_put(pull_ids_h.reshape(-1), bsh)
    qids = jax.device_put(push_ids_h.reshape(-1), bsh)
    dls = jax.device_put(
        deltas_h.reshape(-1, dim),
        NamedSharding(mesh, P((DATA_AXIS, SHARD_AXIS), None)),
    )

    def dev(table, pids, qids, dls):
        got = pull(table, pids, num_shards=ns)
        new = push(table, qids, dls, num_shards=ns,
                   data_axis=DATA_AXIS if nd > 1 else None,
                   combine=combine,
                   apply_fn=None if combine == "sum" else lambda r, d: r + d)
        return got, new

    got, new = jax.jit(jax.shard_map(
        dev, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                  P((DATA_AXIS, SHARD_AXIS)),
                  P((DATA_AXIS, SHARD_AXIS), None)),
        out_specs=(P((DATA_AXIS, SHARD_AXIS), None), P(SHARD_AXIS, None)),
        check_vma=False,
    ))(table, pids, qids, dls)

    # numpy model: pull = row lookup; push = per-id combined fold.
    phys = np.asarray(id_to_phys(pull_ids_h.reshape(-1), ns, rps))
    np.testing.assert_allclose(np.asarray(got), vals[phys], atol=1e-5)

    expect = vals.copy()
    flat_ids = push_ids_h.reshape(-1)
    flat_d = deltas_h.reshape(-1, dim)
    for i in np.unique(flat_ids):
        if i < 0:
            continue
        sel = flat_ids == i
        agg = flat_d[sel].sum(0)
        if combine == "mean":
            agg = agg / sel.sum()
        expect[np.asarray(id_to_phys(np.int64(i), ns, rps))] += agg
    np.testing.assert_allclose(np.asarray(new), expect, atol=1e-4)


# ---------------------------------------------------------------------------
# User-pluggable push-combine strategies (the reference's combining senders).
# ---------------------------------------------------------------------------

def test_push_combine_strategies_through_trainer(devices8):
    """"max" and a user-supplied callable combine run through the FULL
    Trainer path (shard_map + scan + collectives) and match a numpy oracle
    applied per step over the global batch."""
    import jax.numpy as jnp

    from fps_tpu.core.api import ServerLogic, StepOutput, WorkerLogic
    from fps_tpu.core.driver import Trainer, TrainerConfig, num_workers_of
    from fps_tpu.core.ingest import epoch_chunks

    class Pusher(WorkerLogic):
        def pull_ids(self, batch):
            return {"t": batch["id"].astype(jnp.int32)}

        def step(self, batch, pulled, local_state, key):
            ids = jnp.where(batch["weight"] > 0,
                            batch["id"].astype(jnp.int32), -1)
            deltas = batch["val"][:, None].astype(jnp.float32)
            return StepOutput(pushes={"t": (ids, deltas)},
                              local_state=local_state,
                              out={"n": jnp.sum(batch["weight"])})

    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    W = num_workers_of(mesh)
    R = 23
    rng = np.random.default_rng(4)
    n = 768
    data = {
        "id": rng.integers(0, R, n).astype(np.int32),  # heavy duplication
        "val": rng.normal(0, 1, n).astype(np.float32),
    }

    def clipped_mean(summed, counts):
        # custom strategy: count-normalized step, clipped to [-0.5, 0.5]
        return jnp.clip(summed / jnp.maximum(counts, 1.0)[:, None],
                        -0.5, 0.5)

    def np_combine(mode, vals):
        if mode == "max":
            return vals.max()
        return np.clip(vals.mean(), -0.5, 0.5)

    for mode, combine in [("max", "max"), ("clip", clipped_mean)]:
        store = ParamStore(mesh, [TableSpec("t", R, 1).zeros_init()])
        trainer = Trainer(mesh, store, Pusher(),
                          server_logic=ServerLogic(combine=combine),
                          config=TrainerConfig(donate=False))
        tables, ls = trainer.init_state(jax.random.key(0))
        chunks = list(epoch_chunks(data, num_workers=W, local_batch=16,
                                   steps_per_chunk=4, seed=7))
        # Oracle: per global step, fold each id's pushes with the strategy,
        # then add (the default apply).
        want = np.zeros(R, np.float64)
        for c in chunks:
            ids_c = np.asarray(c["id"]).reshape(-1, W * 16)
            val_c = np.asarray(c["val"]).reshape(-1, W * 16)
            wt_c = np.asarray(c["weight"]).reshape(-1, W * 16)
            for t in range(ids_c.shape[0]):
                m = wt_c[t] > 0
                for i in np.unique(ids_c[t][m]):
                    vals = val_c[t][m][ids_c[t][m] == i]
                    want[i] += np_combine(mode, vals.astype(np.float64))
        tables, ls, _ = trainer.fit_stream(tables, ls, iter(chunks),
                                           jax.random.key(1))
        got = store.dump_model("t")[1][:, 0]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=mode)


def test_push_combine_min_and_validation(devices8):
    """"min" fold matches its oracle; unknown modes raise at trace time."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from fps_tpu.core.store import push
    from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS

    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    R = 13
    rng = np.random.default_rng(5)
    B = 32  # per worker
    ids = rng.integers(-1, R, (8, B)).astype(np.int32)  # some dropped
    deltas = rng.normal(0, 1, (8, B, 2)).astype(np.float32)

    store = ParamStore(mesh, [TableSpec("t", R, 2).zeros_init()])
    tables = store.init(jax.random.key(0))

    def dev(tab, i, d):
        return push(tab, i, d, num_shards=4, combine="min")

    f = jax.jit(jax.shard_map(
        dev, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                  P((DATA_AXIS, SHARD_AXIS))),
        out_specs=P(SHARD_AXIS, None), check_vma=False,
    ))
    got = np.asarray(f(tables["t"], jnp.asarray(ids.reshape(-1)),
                       jnp.asarray(deltas.reshape(-1, 2))))
    want = np.zeros((R, 2))
    flat_i, flat_d = ids.reshape(-1), deltas.reshape(-1, 2)
    for i in range(R):
        m = flat_i == i
        if m.any():
            want[i] = flat_d[m].min(axis=0)
    # physical rows: owner-major cyclic over 4 shards
    from fps_tpu.core.store import id_to_phys, rows_per_shard
    rps = rows_per_shard(R, 4)
    phys = np.asarray(id_to_phys(np.arange(R), 4, rps))
    np.testing.assert_allclose(got[phys], want, rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="combine"):
        jax.shard_map(
            lambda t, i, d: push(t, i, d, num_shards=4, combine="median"),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                      P((DATA_AXIS, SHARD_AXIS))),
            out_specs=P(SHARD_AXIS, None), check_vma=False,
        )(tables["t"], jnp.asarray(ids.reshape(-1)),
          jnp.asarray(deltas.reshape(-1, 2)))


@pytest.mark.parametrize("R,route", [(4, "push.mean_dense"),
                                     (8192, "push.mean_rows")])
def test_push_combine_mean_float64_precision(devices8, R, route):
    """A float64 table must fold duplicate pushes in float64: deltas that
    differ only below f32 precision (2^-40) must survive a mean-combine,
    on the accumulator branch and on the row branch (a table large against
    its payload) alike.
    Regression for the hard-coded f32 accumulator (round-2 advice)."""
    import contextlib

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from fps_tpu.core.store import id_to_phys, push, rows_per_shard
    from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS

    @contextlib.contextmanager
    def x64():
        old = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", True)
        try:
            yield
        finally:
            jax.config.update("jax_enable_x64", old)

    with x64():
        mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
        eps = 2.0 ** -40  # representable in f64, vanishes in f32 (1+eps==1)
        ids = np.array([1, 1, 1, 1], np.int32)
        deltas = np.array(
            [[1.0], [1.0 + eps], [1.0 + 2 * eps], [1.0 + 3 * eps]],
            np.float64,
        )
        store = ParamStore(
            mesh, [TableSpec("t", R, 1, dtype=jnp.float64).zeros_init()]
        )
        tables = store.init(jax.random.key(0))

        f = jax.jit(jax.shard_map(
            lambda t, i, d: push(t, i, d, num_shards=2, combine="mean"),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                      P((DATA_AXIS, SHARD_AXIS))),
            out_specs=P(SHARD_AXIS, None), check_vma=False,
        ))
        ops.clear_routes()
        got = np.asarray(f(tables["t"], jnp.asarray(ids),
                           jnp.asarray(deltas)))
        assert [r.route for r in ops.routes_traced()] == [
            route, "scatter_add.xla"]
        assert got.dtype == np.float64
        rps = rows_per_shard(R, 2)
        phys = int(np.asarray(id_to_phys(np.array([1]), 2, rps))[0])
        want = 1.0 + 1.5 * eps  # exact f64 mean of the four deltas
        # An f32 accumulator would return exactly 1.0 here.
        assert got[phys, 0] == pytest.approx(want, abs=eps / 8)
        assert got[phys, 0] != 1.0

        # Extremum fold sentinel must sit beyond the ACCUMULATOR dtype's
        # range: an f32-range fill (-3e38) would swallow an f64 delta of
        # -1e39 (max(-3e38, -1e39) = -3e38 — wrong value committed).
        g = jax.jit(jax.shard_map(
            lambda t, i, d: push(t, i, d, num_shards=2, combine="max"),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                      P((DATA_AXIS, SHARD_AXIS))),
            out_specs=P(SHARD_AXIS, None), check_vma=False,
        ))
        big = np.array([[-1.0e39], [-2.0e39], [0.0], [0.0]], np.float64)
        ids2 = np.array([1, 1, -1, -1], np.int32)  # two dropped slots
        got2 = np.asarray(g(tables["t"], jnp.asarray(ids2),
                            jnp.asarray(big)))
        assert got2[phys, 0] == pytest.approx(-1.0e39, rel=1e-12)


_SUM_RUNS_CASES = {
    # name -> (ids of the 96 a worker drawn how, the sorted route's block
    # while the push is traced, the table's dtype, does the route engage)
    "repeats": ("skewed", 32, np.float32, True),
    "no_repeats": ("distinct", 32, np.float32, True),
    "few_repeats": ("mostly_distinct", 32, np.float32, True),
    "one_id": ("one", 32, np.float32, True),
    "long_runs": ("two_hot", 32, np.float32, True),
    "padding_mixed_in": ("skewed_padded", 32, np.float32, True),
    "all_dropped": ("dropped", 32, np.float32, True),
    "ragged_last_block": ("skewed_padded", 80, np.float32, True),
    "under_one_block": ("skewed", 1_024, np.float32, False),
    "bf16_table": ("skewed", 32, jnp.bfloat16, False),
    "f64_table": ("skewed", 32, np.float64, False),
}


def _sum_runs_ids(how, n, num_ids, rng):
    if how == "distinct":
        return rng.choice(num_ids, n, replace=False)
    if how == "mostly_distinct":  # 3 in 4 distinct: past the share
        ids = rng.choice(num_ids, n, replace=False)
        ids[::4] = ids[1::4]
        return ids
    if how == "one":
        return np.full(n, 77)
    if how == "two_hot":  # two ids take 40 pushes a worker each
        ids = rng.integers(0, num_ids, n)
        ids[rng.permutation(n)[:n * 5 // 6]] = np.tile([5, 1_234], n)[
            :n * 5 // 6]
        return ids
    if how == "dropped":
        return np.full(n, -1)
    hot = rng.integers(0, num_ids, 12)
    ids = np.where(rng.random(n) < 0.7, hot[rng.integers(0, 12, n)],
                   rng.integers(0, num_ids, n))
    if how == "skewed_padded":
        ids[rng.random(n) < 0.125] = -1
    return ids


@pytest.mark.parametrize("values", ["integers", "floats"])
@pytest.mark.parametrize("case", list(_SUM_RUNS_CASES))
@pytest.mark.parametrize("S", [1, 4])
def test_push_sum_runs_equals_the_plain_scatter(devices8, monkeypatch, S,
                                                case, values):
    """The additive push through ``push.sum_runs`` (a step's rows summed
    by id, the scatter into the shard handed each distinct id once,
    sorted, the dropped last; the route's constants patched so that a
    tiny shard engages it, the ops layer routing as on the chip: the
    scatter is the sorted route's block loop) is
    ``table.at[ids].add(deltas)``: exactly on
    integer-valued rows, to float32 rounding otherwise; on one shard and
    on what the gathered exchange hands each of four; whatever the
    batch's repeats, padding and length against the block; a long run
    chained as the plain scatter chains it, bit for bit; and what it
    counts (``watch_sum_runs``) is the pushes kept and the distinct ids.
    A batch under one block, a bf16 and an f64 table stay on the plain
    scatter."""
    how, block, dtype, engages = _SUM_RUNS_CASES[case]
    R, dim, n = 3_000 * S, 16, 96 * S
    rng = np.random.default_rng(49)
    ids = _sum_runs_ids(how, n, R, rng).astype(np.int32)
    if values == "integers":
        deltas = rng.integers(-3, 4, (n, dim)).astype(np.float32)
        table = rng.integers(-50, 50, (R, dim)).astype(np.float32)
    else:
        deltas = rng.normal(0, 1, (n, dim)).astype(np.float32)
        table = rng.normal(0, 1, (R, dim)).astype(np.float32)
        if how in ("one", "two_hot"):
            # Small steps on rows away from zero, as training's are: what a
            # long run changes its row by is then an exact difference.
            deltas, table = deltas * 1e-3, 1 + np.abs(table)
    rps, seen, plain_scatter = rows_per_shard(R, S), [], ops.scatter_add

    def spy(t, i, d, **kw):
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), i)
        assert kw == {"ids_sorted": True}
        return plain_scatter(t, i, d, **kw)

    def counted_push(t, i, d):
        with store_mod.watch_sum_runs() as noted:
            out = push(t, i, d, num_shards=S, data_axis=None, table="emb")
        counts = noted.get("emb", {"pushed_ids": 0, "live_ids": 0})
        assert set(noted) <= {"emb"}
        return out, jnp.stack([jnp.asarray(counts[k], jnp.int32) for k in (
            "pushed_ids", "live_ids")])[None]

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    monkeypatch.setattr(ops, "XLA_TRANSPOSED_HBM_ROWS", 1_000)
    monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", block)
    monkeypatch.setattr(store_mod, "_routes_to_owner", lambda *a: False)
    if engages:
        monkeypatch.setattr(ops, "scatter_add", spy)
    mesh = make_ps_mesh(num_shards=S, devices=devices8[:S])
    f = jax.jit(jax.shard_map(
        counted_push, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS, None)),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
        check_vma=False))
    ops.clear_routes()
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", dtype is np.float64)
    try:
        got, counts = f(
            jax.device_put(jnp.asarray(table, dtype),
                           NamedSharding(mesh, P(SHARD_AXIS, None))),
            jnp.asarray(ids), jnp.asarray(deltas))
        jax.effects_barrier()
        assert got.dtype == dtype
    finally:
        jax.config.update("jax_enable_x64", x64)
    log = [(r.route, r.rows, r.dim, r.ids) for r in ops.routes_traced()]
    live = np.unique(ids[ids >= 0])
    if not engages:
        assert log == [("scatter_add.xla", rps, dim, n)]
        assert not np.asarray(counts).any()
    else:
        assert log == [("push.sum_runs", rps, dim, n),
                       ("scatter_add.xla_sorted", rps, dim, n)]
        assert ops.routes_traced()[0].reason == "xla_transposed_hbm"
        # What the scatter was handed on each shard: non-decreasing, none
        # negative, everything to drop last; where the batch repeats
        # itself, each of the shard's distinct ids ONCE.
        assert len(seen) == S
        for i in seen:
            assert i.shape == (n,) and i.min() >= 0
            assert (np.diff(i) >= 0).all()
            if how != "mostly_distinct":
                assert (np.diff(i[i < rps]) > 0).all()
        handed = sum((i < rps).sum() for i in seen)
        assert handed == ((ids >= 0).sum() if how == "mostly_distinct"
                          else len(live))
        assert np.asarray(counts).sum(axis=0).tolist() == [
            (ids >= 0).sum(), len(live)]
    want = table.astype(np.float64)
    phys = np.asarray(id_to_phys(ids[ids >= 0], S, rps))
    np.add.at(want, phys, deltas[ids >= 0].astype(np.float64))
    got = np.asarray(got.astype(jnp.float32) if dtype is jnp.bfloat16
                     else got)
    touched = np.zeros(R, bool)
    touched[phys] = True
    stored = (np.asarray(jnp.asarray(table, dtype).astype(jnp.float32))
              if dtype is jnp.bfloat16 else table)
    np.testing.assert_array_equal(got[~touched], stored[~touched])
    if engages and values == "floats":
        # A LONG run (over ops.SUM_RUNS_TREE_MAX_RUN rows) is chained at
        # its row's value in the batch's order, the plain scatter's own
        # float32 arithmetic, bit for bit; a short one is a tree's sum.
        chain = table.copy()
        for row, delta in zip(phys, deltas[ids >= 0]):
            chain[row] += delta
        rows, counts = np.unique(phys, return_counts=True)
        long = rows[counts > ops.SUM_RUNS_TREE_MAX_RUN]
        assert len(long) == {"one": 1, "two_hot": 2}.get(how, 0)
        np.testing.assert_array_equal(got[long], chain[long])
    if values == "integers" and dtype is not jnp.bfloat16:
        np.testing.assert_array_equal(got, want)
    elif dtype is jnp.bfloat16:
        np.testing.assert_allclose(got, want, rtol=0.05,
                                   atol=0.05 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("cell,rps,dim,num_ids,dtype,engages", [
    ("mf-netflix.epochs", 17_770, 10, 32_768, jnp.float32, False),
    ("pa-rcv1.epochs", 47_236, 1, 1_048_576, jnp.float32, False),
    ("mf-netflix.x4", 4_443, 10, 131_072, jnp.float32, False),
    ("w2v-1bw.epochs", 1_115_011, 300, 49_182, jnp.float32, False),
    ("lr-criteo.epochs", 1_000_000, 2, 425_997, jnp.float32, False),
    ("ials-ml20m.sweeps", 138_493, 4_096, 138_493, jnp.float32, False),
    ("mf-netflix-topk.epochs", 17_770, 10, 32_768, jnp.float32, False),
    ("w2v-1bw-hot.x4", 278_753, 300, 61_504, jnp.float32, False),
    ("dlrm-criteo.epochs", 33_762_577, 16, 425_984, jnp.float32, True),
    # The same table narrower or wider than float32 (the scatter adds in
    # the table's dtype), under one block of ids, as a shard of four (what
    # the queued ``dlrm-criteo.x4``'s lanes hand it), and with half a
    # million rows (between the lane-packed route's reach and the fewest
    # rows measured: it stays out).
    ("dlrm bf16", 33_762_577, 16, 425_984, jnp.bfloat16, False),
    ("dlrm f64", 33_762_577, 16, 425_984, jnp.float64, False),
    ("dlrm one block", 33_762_577, 16, 1_024, jnp.float32, False),
    ("dlrm-criteo.x4", 8_440_645, 16, 4 * 133_120, jnp.float32, True),
    ("unmeasured band", 786_432, 16, 425_984, jnp.float32, False),
])
def test_sum_runs_route_from_shapes_alone(monkeypatch, cell, rps, dim,
                                          num_ids, dtype, engages):
    """``push.sum_runs`` engages by ``push``'s own shapes, with the sorted
    scatter's third regime and nowhere without it: of the nine cells'
    pushed tables only ``dlrm-criteo.epochs``' (narrow float32 rows, so
    many that XLA keeps the table transposed in HBM, under more ids than
    a block); off the TPU, or under the ``"xla"`` backend, nothing."""
    from fps_tpu.core.store import _sum_runs_route

    assert not _sum_runs_route(rps, dim, num_ids, dtype)  # the CPU's routing
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    assert _sum_runs_route(rps, dim, num_ids, dtype) is engages
    assert ops._route_xla_sorted(rps, dim, num_ids, dtype, True) is (
        engages or cell in ("w2v-1bw.epochs", "dlrm bf16"))


@pytest.mark.parametrize("idx", [
    [7], [3, 3, 3, 3], [5, 1, 4, 2, 3], [9, 0, 9, 9, 0, 4, 9, 100, 100],
    list(np.random.default_rng(3).integers(0, 40, 1000)),
])
def test_id_runs_count_and_slot_every_occurrence(idx):
    """The row branch's bookkeeping, from sorts of the batch alone: for
    every position how often its index occurs (the sentinel row of
    dropped pushes is one more index), one slot an index, and the slots'
    own indices: the distinct indices sorted, at the front, ``drop``
    after them."""
    from fps_tpu.core.store import _id_runs

    idx = np.asarray(idx, np.int32)
    n, slot, slot_idx = map(np.asarray,
                            jax.jit(lambda i: _id_runs(i, 10_000))(
                                jnp.asarray(idx)))
    np.testing.assert_array_equal(n, [(idx == i).sum() for i in idx])
    np.testing.assert_array_equal(slot_idx[slot], idx)
    distinct = np.unique(idx)
    np.testing.assert_array_equal(slot_idx[:len(distinct)], distinct)
    assert (slot_idx[len(distinct):] == 10_000).all()


def _mean_push_case(D, S, num_ids, dim, seed=11):
    """A dup-heavy mean push on a ``D x S`` mesh: 96 ids a worker drawn
    from 12 hot ids and the whole id space (so every shard sees ids of the
    others), an eighth of them negative (dropped), onto a non-zero
    table."""
    rng = np.random.default_rng(seed)
    W, B = D * S, 96
    hot = rng.integers(0, num_ids, 12)
    ids = np.where(rng.random(W * B) < 0.7, hot[rng.integers(0, 12, W * B)],
                   rng.integers(0, num_ids, W * B)).astype(np.int32)
    ids[rng.random(W * B) < 0.125] = -1
    deltas = rng.normal(0, 1, (W * B, dim)).astype(np.float32)
    rps = rows_per_shard(num_ids, S)
    table = rng.normal(0, 1, (rps * S, dim)).astype(np.float32)
    want = table.astype(np.float64)
    for i in np.unique(ids[ids >= 0]):
        want[int(id_to_phys(np.int32(i), S, rps))] += (
            deltas[ids == i].astype(np.float64).mean(axis=0))
    return table, ids, deltas, want


def _push_on_mesh(devices, D, S, table, ids, deltas, **kw):
    """``push`` under ``shard_map`` on a ``D x S`` mesh by the GATHERED
    exchange (the owner-routed one, which 96 ids a worker would take
    without a data axis, is ruled out for the trace: what follows the
    exchange is what these tests pin, on every pushed row handed to every
    shard; ``test_routed_push_*`` run the same branches on lanes); the
    table and the route log of its one trace."""
    mesh = make_ps_mesh(num_shards=S, num_data=D, devices=devices[:D * S])
    f = jax.jit(jax.shard_map(
        lambda t, i, d: push(t, i, d, num_shards=S,
                             data_axis=DATA_AXIS if D > 1 else None, **kw),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                  P((DATA_AXIS, SHARD_AXIS), None)),
        out_specs=P(SHARD_AXIS, None), check_vma=False))
    ops.clear_routes()
    rule = store_mod._routes_to_owner
    store_mod._routes_to_owner = lambda *a: False
    try:
        out = f(jax.device_put(jnp.asarray(table),
                               NamedSharding(mesh, P(SHARD_AXIS, None))),
                jnp.asarray(ids), jnp.asarray(deltas))
    finally:
        store_mod._routes_to_owner = rule
    return np.asarray(out), ops.routes_traced()


MEAN_MESHES = [(1, 1), (1, 2), (2, 2)]  # one shard, two, two x data axis


@pytest.mark.parametrize("D,S", MEAN_MESHES)
def test_push_mean_large_table_takes_the_row_branch(devices8, monkeypatch,
                                                    D, S):
    """A table large against its payload (``[65536, 64]``, 96 ids a
    worker): the per-id mean by the row branch, chosen by shape alone, is
    the accumulator branch's table to 1e-6 and the float64 oracle's."""
    R, dim = 65_536, 64
    table, ids, deltas, want = _mean_push_case(D, S, R, dim)
    got, log = _push_on_mesh(devices8, D, S, table, ids, deltas,
                             combine="mean")
    rps, B = rows_per_shard(R, S), ids.shape[0]
    assert log[0] == ops.Route("push", "push.mean_rows", rps, dim, B,
                               False, "")
    assert [r.route for r in log[1:]] == ["scatter_add.xla"]
    assert log[1].dim == dim  # the table itself, no count column
    monkeypatch.setattr(ops, "MEAN_ROWS_TABLE_RATIO", float("inf"))
    dense, log = _push_on_mesh(devices8, D, S, table, ids, deltas,
                               combine="mean")
    assert [(r.route, r.dim, r.reason) for r in log] == [
        ("push.mean_dense", dim, "small_table"),
        ("scatter_add.xla", dim + 1, log[1].reason)]
    scale = np.abs(want).max()
    assert np.abs(got - dense).max() <= 1e-6 * scale
    assert np.abs(got - want).max() <= 1e-6 * scale
    assert np.abs(got - table).max() > 0.1  # something was pushed


@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("D,S", MEAN_MESHES)
def test_push_mean_rows_hands_its_ids_over_sorted(devices8, monkeypatch, D,
                                                  S, seed):
    """What ``push.mean_rows`` promises ``ops.scatter_add`` with
    ``ids_sorted=True`` it keeps, on every shard, over hot, unowned and
    negative ids: the ids it hands over are non-decreasing, none negative,
    the dropped (``rps``) last; the live ones are the shard's distinct
    ids, once each; the rows beside the dropped are exact zeros; and the
    pushed table is the float64 per-id mean."""
    R, dim = 65_536, 64
    table, ids, deltas, want = _mean_push_case(D, S, R, dim, seed=seed)
    rps, seen, plain = rows_per_shard(R, S), [], ops.scatter_add

    def spy(t, i, d, **kw):
        assert kw == {"ids_sorted": True}
        jax.debug.callback(lambda i, d: seen.append(
            (np.asarray(i), np.asarray(d))), i, d)
        return plain(t, i, d, **kw)

    monkeypatch.setattr(ops, "scatter_add", spy)
    got, log = _push_on_mesh(devices8, D, S, table, ids, deltas,
                             combine="mean")
    jax.effects_barrier()
    assert [r.route for r in log] == ["push.mean_rows", "scatter_add.xla"]
    assert len(seen) == D * S  # every device of the mesh runs the push
    for i, d in seen:
        assert i.shape == (ids.shape[0],) and i.min() >= 0
        assert (np.diff(i) >= 0).all() and i.max() == rps  # some dropped
        assert (np.diff(i[i < rps]) > 0).all() and not d[i == rps].any()
    # Every pushed id is live on the one shard that owns it (and on each
    # of that shard's replicas along the data axis).
    assert sum((i < rps).sum() for i, _ in seen) == D * len(
        np.unique(ids[ids >= 0]))
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


def test_push_mean_rows_sum_a_hot_id_from_zero(devices8):
    """The row branch adds ONE combined row a touched id: 976 pushes of
    1e-3 onto a table value near 1 land within an ulp or two of the
    float64 mean, as on the accumulator branch. Scattering the scaled rows
    straight into the table rounds each addend at the table's magnitude
    (ten times this gap here; 12-20x the gap on the chip, PR 28)."""
    R, dim, B = 131_072, 8, 1_024
    rng = np.random.default_rng(4)
    table = (1.0 + rng.random((R, dim))).astype(np.float32)
    ids = np.full(B, 77, np.int32)
    ids[:48] = rng.integers(0, R, 48)
    deltas = rng.normal(1e-3, 1e-3, (B, dim)).astype(np.float32)
    got, log = _push_on_mesh(devices8, 1, 1, table, ids, deltas,
                             combine="mean")
    assert log[0].route == "push.mean_rows"
    want = table[77] + deltas[ids == 77].astype(np.float64).mean(axis=0)
    assert np.abs(got[77] - want).max() <= 2.0 ** -22  # two ulps at 1-2


@pytest.mark.parametrize("D,S", MEAN_MESHES)
def test_push_mean_small_table_keeps_the_accumulator(devices8, D, S):
    """The same push into a table the payload dwarfs (MF's movie table
    under a step's ratings): ``push.mean_dense`` / ``small_table``, the
    count riding the one scatter as a column."""
    R, dim = 64, 64
    table, ids, deltas, want = _mean_push_case(D, S, R, dim)
    got, log = _push_on_mesh(devices8, D, S, table, ids, deltas,
                             combine="mean")
    assert log[0] == ops.Route("push", "push.mean_dense",
                               rows_per_shard(R, S), dim, ids.shape[0],
                               False, "small_table")
    assert [(r.route, r.dim) for r in log[1:]] == [
        ("scatter_add.xla", dim + 1)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case,logged", [
    ("apply_fn", [("push.mean_dense", "fold")]),
    ("bf16", [("push.mean_dense", "dtype")]),
    ("callable", []),
    ("max", []),
])
def test_push_mean_row_branch_is_for_the_additive_mean_alone(devices8, case,
                                                             logged):
    """On a table past the constant: a non-additive fold, a table narrower
    than the accumulate dtype, a callable combine and an extremum keep the
    accumulator (no ``push.mean_rows`` in the log), with their answers."""
    R, dim, S = 65_536, 8, 2
    table, ids, deltas, want = _mean_push_case(1, S, R, dim)
    kw = {"apply_fn": {"combine": "mean",
                       "apply_fn": lambda rows, delta: rows + delta},
          "bf16": {"combine": "mean"},
          "callable": {"combine": lambda s, c: s / jnp.maximum(c, 1)[:, None]},
          "max": {"combine": "max"}}[case]
    if case == "bf16":
        table = np.asarray(jnp.asarray(table, jnp.bfloat16))
    got, log = _push_on_mesh(devices8, 1, S, table, ids, deltas, **kw)
    assert [(r.route, r.reason) for r in log if r.op == "push"] == logged
    assert "push.mean_rows" not in [r.route for r in log]
    if case == "max":
        return
    if case == "bf16":
        # Duplicates summed in f32, ONE rounding into the bf16 row.
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(np.float64), want,
                                   rtol=0, atol=2.0 ** -7 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _acc_runs_case(case, S, num_ids, dim, seed=21):
    """A push of 96 ids a worker on ``S`` shards for ``push.acc_runs``'
    cases, onto a non-zero table whose second column is positive (an
    AdaGrad accumulator)."""
    rng = np.random.default_rng(seed)
    n = S * 96
    if case == "heavy_repeats":
        hot = rng.integers(0, num_ids, 12)
        ids = np.where(rng.random(n) < 0.7, hot[rng.integers(0, 12, n)],
                       rng.integers(0, num_ids, n))
        ids[rng.random(n) < 0.125] = -1
    elif case == "no_repeats":
        ids = rng.choice(num_ids, n, replace=False)
    elif case == "all_dropped":
        ids = np.full(n, -1)
    elif case == "one_id":
        ids = np.full(n, 77)
    else:  # "another_shard": every id is shard 1 % S's, none of the rest
        ids = rng.integers(0, num_ids // S, n) * S + 1 % S
    deltas = rng.normal(0, 1, (n, dim)).astype(np.float32)
    table = np.abs(rng.normal(0, 1, (num_ids, dim))).astype(np.float32) + 0.5
    return table, ids.astype(np.int32), deltas


_ACC_RUNS_KINDS = {
    # kw of push; the reason "push.acc_runs" logs; the push entry before it
    "fold": (lambda: {"apply_fn": adagrad_fold(0.1, 1e-6)}, "fold",
             ("push.fold", "apply_fn")),
    "mean_fold": (lambda: {"combine": "mean",
                           "apply_fn": lambda rows, delta: rows - delta},
                  "mean_dense", ("push.mean_dense", "fold")),
    # Column 0 of the combined delta is the id's COUNT, the rest its sums.
    "callable": (lambda: {"combine": lambda s, c: s.at[:, 0].set(c)},
                 "callable", None),
}


@pytest.mark.parametrize("case", ["heavy_repeats", "no_repeats",
                                  "all_dropped", "one_id", "another_shard"])
@pytest.mark.parametrize("kind", list(_ACC_RUNS_KINDS))
@pytest.mark.parametrize("S", [1, 4])
def test_push_acc_runs_equals_the_plain_accumulator(devices8, monkeypatch,
                                                    S, kind, case):
    """``push`` through ``push.acc_runs`` (the pushed rows summed by id
    run, the accumulator's scatter handed each distinct id once, sorted,
    the dropped last) is ``push`` through the plain accumulator, the
    predicate's constant patched both ways on a shard whose accumulator
    XLA keeps transposed (300,000 rows: 153.6 MB of row-major tiles): the
    touched mask and the counts bit for bit, the sums within float32
    reassociation."""
    R, dim = 300_000 * S, 2
    make_kw, reason, before = _ACC_RUNS_KINDS[kind]
    table, ids, deltas = _acc_runs_case(case, S, R, dim)
    rps, seen, plain_scatter = rows_per_shard(R, S), [], ops.scatter_add

    def spy(t, i, d, **kw):
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), i)
        assert kw == {"ids_sorted": True}
        return plain_scatter(t, i, d, **kw)

    monkeypatch.setattr(ops, "ACC_RUNS_MIN_IDS_PER_ROW", float("inf"))
    want, log = _push_on_mesh(devices8, 1, S, table, ids, deltas,
                              **make_kw())
    assert "push.acc_runs" not in [r.route for r in log]
    monkeypatch.setattr(ops, "ACC_RUNS_MIN_IDS_PER_ROW", 0.0)
    monkeypatch.setattr(ops, "scatter_add", spy)
    got, log = _push_on_mesh(devices8, 1, S, table, ids, deltas, **make_kw())
    jax.effects_barrier()
    assert [(r.route, r.rows, r.dim, r.ids, r.reason) for r in log] == (
        [(before[0], rps, dim, ids.shape[0], before[1])] if before else []
    ) + [("push.acc_runs", rps, dim, ids.shape[0], reason),
         ("scatter_add.xla", rps, dim + 1, ids.shape[0], log[-1].reason)]
    # What the scatter was handed on each shard: non-decreasing, none
    # negative, the shard's distinct ids once each, then the sentinel.
    assert len(seen) == S
    for i in seen:
        assert i.shape == ids.shape and i.min() >= 0
        assert (np.diff(i) >= 0).all() and (np.diff(i[i < rps]) > 0).all()
    assert sum((i < rps).sum() for i in seen) == len(
        np.unique(ids[ids >= 0]))
    touched = np.zeros(R, bool)
    live = np.unique(ids[ids >= 0])
    touched[np.asarray(id_to_phys(live, S, rps))] = True
    np.testing.assert_array_equal(got[~touched], table[~touched])
    np.testing.assert_array_equal((got != table).any(axis=1), touched)
    np.testing.assert_array_equal((want != table).any(axis=1), touched)
    if kind == "callable":  # column 0: the table's plus the id's count
        np.testing.assert_array_equal(got[:, 0], want[:, 0])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("rps,dim,num_ids,engages", [
    (1_000_000, 2, 425_997, True),      # lr-criteo.epochs
    (300_000, 2, 1_703_988, True),
    # The same job on four shards: 128 MB of row-major tiles, which XLA
    # keeps row-major in HBM; the plain scatter of many ids is fast there
    # and the summed runs lose without skew (tools/bench_scatter.py fold).
    (250_000, 2, 1_703_988, False),
    (17_770, 10, 32_768, False),        # mf-netflix.epochs
    (17_770, 10, 131_072, False),
    (4_443, 10, 32_768, False),
    (4_443, 10, 131_072, False),        # mf-netflix.x4 until PR 36
    (17_772, 10, 32_768, False),        # since: the dense exchange's buffer
    (1_000_000, 2, 32_768, False),      # too few ids for certain repeats
    (1_000_000, 1, 425_997, False),     # accumulator widths not swept:
    (1_000_000, 4, 425_997, False),     # the scatter would not stop early
])
def test_acc_runs_route_from_shapes_alone(rps, dim, num_ids, engages):
    """``push.acc_runs`` engages by ``push``'s own shapes: an accumulator
    whose row-major tiles are so far past XLA's VMEM regime that XLA keeps
    it transposed, under enough ids a row; both MF cells' accumulators are
    inside the regime and keep the plain scatter whatever their ids."""
    from fps_tpu.core.store import _acc_runs_route

    assert _acc_runs_route(rps, dim, num_ids, jnp.float32) is engages


@pytest.mark.parametrize("idx", [
    [7], [3, 3, 3, 3], [5, 1, 4, 2, 3], [9, 0, 9, 9, 0, 4, 9, 100, 100],
    list(np.random.default_rng(5).integers(0, 40, 1000)),
])
def test_sum_id_runs_sums_each_index_once(idx):
    """The accumulator's pre-combine from sorts of the batch alone: the
    distinct indices sorted at the front, each with the sum of its rows
    and, last column, their number EXACTLY; ``drop`` after them."""
    from fps_tpu.core.store import _sum_id_runs

    idx = np.asarray(idx, np.int32)
    rows = np.random.default_rng(6).normal(0, 1, (len(idx), 2))
    ids, sums = map(np.asarray, jax.jit(
        lambda i, r: _sum_id_runs(i, r, 10_000))(
            jnp.asarray(idx), jnp.asarray(rows, jnp.float32)))
    distinct = np.unique(idx)
    np.testing.assert_array_equal(ids[:len(distinct)], distinct)
    assert (ids[len(distinct):] == 10_000).all()
    np.testing.assert_array_equal(sums[:len(distinct), 2],
                                  [(idx == i).sum() for i in distinct])
    np.testing.assert_allclose(
        sums[:len(distinct), :2],
        [rows[idx == i].sum(axis=0) for i in distinct],
        rtol=1e-5, atol=1e-5)


def test_server_logic_swap_recompiles(devices8):
    """Swapping trainer.server_logic after a compile must MISS the compile
    cache (combine is baked into the program as a constant): the next
    chunk must fold with the new strategy, not the shadowed old one."""
    import jax.numpy as jnp

    from fps_tpu.core.api import ServerLogic, StepOutput, WorkerLogic
    from fps_tpu.core.driver import Trainer, TrainerConfig, num_workers_of
    from fps_tpu.core.ingest import epoch_chunks

    class Pusher(WorkerLogic):
        def pull_ids(self, batch):
            return {"t": batch["id"].astype(jnp.int32)}

        def step(self, batch, pulled, local_state, key):
            ids = jnp.where(batch["weight"] > 0,
                            batch["id"].astype(jnp.int32), -1)
            return StepOutput(pushes={"t": (ids, batch["val"][:, None])},
                              local_state=local_state, out={})

    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    W = num_workers_of(mesh)
    rng = np.random.default_rng(0)
    n = 128
    data = {"id": rng.integers(0, 7, n).astype(np.int32),
            "val": rng.normal(0, 1, n).astype(np.float32)}
    chunk = next(epoch_chunks(data, num_workers=W, local_batch=16,
                              steps_per_chunk=1, seed=3))

    def fold(combine):
        store = ParamStore(mesh, [TableSpec("t", 7, 1).zeros_init()])
        tr = Trainer(mesh, store, Pusher(),
                     server_logic=ServerLogic(combine=combine),
                     config=TrainerConfig(donate=False))
        t, ls = tr.init_state(jax.random.key(0))
        return tr, store, t, ls

    tr, store, t, ls = fold("sum")
    t, ls, _ = tr.run_chunk(t, ls, chunk, jax.random.key(1))
    got_sum = store.dump_model("t")[1].copy()

    # Swap the logic on the SAME trainer; rerun the same chunk on fresh
    # state. Without server_logic in the cache key this silently reuses
    # the sum program.
    from fps_tpu.core.api import ServerLogic as SL
    tr.server_logic = {"t": SL(combine="mean")}
    t2, ls2 = tr.init_state(jax.random.key(0))
    t2, ls2, _ = tr.run_chunk(t2, ls2, chunk, jax.random.key(1))
    got_swapped = store.dump_model("t")[1]

    # Oracle: a trainer built with mean from the start.
    tr3, store3, t3, ls3 = fold("mean")
    t3, ls3, _ = tr3.run_chunk(t3, ls3, chunk, jax.random.key(1))
    got_mean = store3.dump_model("t")[1]

    np.testing.assert_array_equal(got_swapped, got_mean)
    assert not np.array_equal(got_sum, got_mean)  # the swap matters


# ---------------------------------------------------------------------------
# Dense collective route (replicate-on-read / dense-reduce-on-write): the
# small-table path where per-worker row transactions are O(B) instead of
# the gathered route's O(W*B) per shard. Same results, different comms.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4), (8, 1)])
def test_pull_dense_matches_gathered(devices8, mesh_shape):
    mesh = make_ps_mesh(num_shards=mesh_shape[1], num_data=mesh_shape[0])
    S = mesh_shape[1]
    num_ids, dim, B = 103, 7, 16
    table, rps = reference_table(num_ids, dim, S)
    table_dev = jax.device_put(
        jnp.asarray(table), NamedSharding(mesh, P(SHARD_AXIS, None))
    )
    W = mesh_shape[0] * mesh_shape[1]
    rng = np.random.default_rng(3)
    # include -1 drop slots: both routes must read them as zero rows
    ids = rng.integers(0, num_ids, (W * B,)).astype(np.int32)
    ids[:: 7] = -1
    ids_dev = jax.device_put(
        jnp.asarray(ids), NamedSharding(mesh, P((DATA_AXIS, SHARD_AXIS)))
    )

    def run(dense):
        return jax.jit(
            jax.shard_map(
                lambda t, i: pull(t, i, num_shards=S, dense=dense),
                mesh=mesh,
                in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS))),
                out_specs=P((DATA_AXIS, SHARD_AXIS)),
                check_vma=False,
            )
        )(table_dev, ids_dev)

    expected = np.where(
        (ids >= 0)[:, None],
        (ids[:, None] * 10.0 + np.arange(dim)[None, :]),
        0.0,
    ).astype(np.float32)
    np.testing.assert_allclose(np.asarray(run(True)), expected, rtol=1e-6)
    # both routes read -1 slots as zero rows (gather_rows drop contract)
    np.testing.assert_allclose(np.asarray(run(False)), expected, rtol=1e-6)


def _push_on(mesh, table, ids, deltas, **kw):
    """``push`` of ``ids`` / ``deltas`` (the workers' batches end to end)
    into ``table`` (physical layout) on ``mesh``, jitted, not yet called."""
    D, S = mesh.devices.shape
    fn = jax.jit(jax.shard_map(
        lambda t, i, d: push(t, i, d, num_shards=S,
                             data_axis=DATA_AXIS if D > 1 else None, **kw),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P((DATA_AXIS, SHARD_AXIS)),
                  P((DATA_AXIS, SHARD_AXIS), None)),
        out_specs=P(SHARD_AXIS, None), check_vma=False))
    args = (jax.device_put(jnp.asarray(table),
                           NamedSharding(mesh, P(SHARD_AXIS, None))),
            jnp.asarray(ids), jnp.asarray(deltas))
    return fn, args


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4), (8, 1)])
def test_push_dense_matches_gathered(devices8, mesh_shape):
    mesh = make_ps_mesh(num_shards=mesh_shape[1], num_data=mesh_shape[0])
    D, S = mesh_shape
    W = D * S
    num_ids, dim, B = 50, 4, 12
    rps = rows_per_shard(num_ids, S)
    table = np.zeros((rps * S, dim), np.float32)
    rng = np.random.default_rng(4)
    ids = rng.integers(0, num_ids, (W * B,)).astype(np.int32)
    ids[::5] = -1  # dropped pushes
    deltas = rng.normal(0, 1, (W * B, dim)).astype(np.float32)

    def run(dense):
        fn, args = _push_on(mesh, table, ids, deltas, dense=dense)
        return fn(*args)

    expected = np.zeros((rps * S, dim), np.float32)
    keep = ids >= 0
    phys = np.asarray(id_to_phys(ids[keep], S, rps))
    np.add.at(expected, phys, deltas[keep])
    np.testing.assert_allclose(np.asarray(run(True)), expected,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(run(False)), expected,
                               rtol=1e-5, atol=1e-5)


def _pushes_logged():
    return [(r.route, r.reason) for r in ops.routes_traced()
            if r.op == "push"]


_ACC_FOLDS = {
    # name -> (push kwargs, reason logged, float64 oracle of a touched row
    # from its current value, the sum of its pushes and their number)
    "mean": (dict(combine="mean"), "mean_dense",
             lambda cur, s, n: cur + s / n),
    "callable": (dict(combine=lambda s, c: s / jnp.sqrt(
        jnp.maximum(c, 1.0))[:, None]), "callable",
                 lambda cur, s, n: cur + s / np.sqrt(n)),
    "apply_fn": (dict(apply_fn=lambda cur, d: 0.5 * cur + d), "fold",
                 lambda cur, s, n: 0.5 * cur + s),
}


@pytest.mark.parametrize("fold", list(_ACC_FOLDS))
@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4), (8, 1)])
def test_push_dense_accumulator_matches_gathered(devices8, mesh_shape, fold):
    """The ``(rps, dim + 1)`` accumulator filled by the dense exchange
    (each worker scatters its OWN rows, the shards' windows exchanged and
    summed in a fixed order) holds the gathered exchange's sums and
    counts: under the per-id mean, a callable combine and a stateful fold
    the table equals the gathered route's and a float64 oracle's, rows
    nobody pushed (dropped ids, ids no worker of the shard holds) keep
    their bits, and the route log says which exchange ran, once."""
    kw, reason, oracle = _ACC_FOLDS[fold]
    D, S = mesh_shape
    mesh = make_ps_mesh(num_shards=S, num_data=D)
    W = D * S
    num_ids, dim, B = 50, 4, 12
    rps = rows_per_shard(num_ids, S)
    rng = np.random.default_rng(8)
    table = rng.normal(0, 1, (rps * S, dim)).astype(np.float32)
    # ids 30.. are never pushed: whole rows of every shard stay untouched
    ids = rng.integers(0, 30, (W * B,)).astype(np.int32)
    ids[::5] = -1  # dropped pushes
    deltas = rng.normal(0, 1, (W * B, dim)).astype(np.float32)

    ops.clear_routes()
    fn, args = _push_on(mesh, table, ids, deltas, dense=True, **kw)
    dense = np.asarray(fn(*args))
    logged = _pushes_logged()
    assert logged.count(("push.dense_acc", reason)) == 1, logged
    assert [r for r in ops.routes_traced()
            if r.route == "scatter_add.xla"][-1][2:5] == (
                rps * S, dim + 1, B)  # the worker's own ids, all the rows
    ops.clear_routes()
    fn, args = _push_on(mesh, table, ids, deltas, dense=False, **kw)
    gathered = np.asarray(fn(*args))
    assert "push.dense_acc" not in [r for r, _ in _pushes_logged()]

    want = table.astype(np.float64)
    keep = ids >= 0
    phys = np.asarray(id_to_phys(ids[keep], S, rps))
    touched = np.unique(phys)
    for row in touched:
        mine = deltas[keep][phys == row].astype(np.float64)
        want[row] = oracle(want[row], mine.sum(axis=0), len(mine))
    scale = np.abs(want).max()
    assert np.abs(dense - gathered).max() <= 1e-6 * scale
    assert np.abs(dense - want).max() <= 1e-6 * scale
    untouched = np.setdiff1d(np.arange(rps * S), touched)
    assert len(untouched) >= 20
    np.testing.assert_array_equal(dense[untouched], table[untouched])


@pytest.mark.parametrize("combine", ["max", "min", "mean"])
def test_push_dense_keeps_gathered_for_extrema_and_mean_rows(devices8,
                                                             combine):
    """What the dense exchange does not serve keeps the gathered one under
    ``dense=True``, program for program: the extrema (no sum to exchange)
    and a mean push whose table is large against its payload
    (``push.mean_rows``: the pushed rows go straight into the shard)."""
    mesh = make_ps_mesh(num_shards=4, num_data=2)
    num_ids, dim, B = 16_000, 4, 2
    rps = rows_per_shard(num_ids, 4)
    rng = np.random.default_rng(9)
    table = rng.normal(0, 1, (rps * 4, dim)).astype(np.float32)
    ids = rng.integers(0, 40, (8 * B,)).astype(np.int32)
    ids[3] = -1
    deltas = rng.normal(0, 1, (8 * B, dim)).astype(np.float32)

    out, logs, texts = {}, {}, {}
    for dense in (True, False):
        ops.clear_routes()
        fn, args = _push_on(mesh, table, ids, deltas, dense=dense,
                            combine=combine)
        texts[dense] = fn.lower(*args).as_text()
        logs[dense] = _pushes_logged()
        out[dense] = np.asarray(fn(*args))
    assert logs[True] == logs[False] == (
        [("push.mean_rows", "")] if combine == "mean" else [])
    assert texts[True] == texts[False]
    np.testing.assert_array_equal(out[True], out[False])


@pytest.mark.parametrize("fold", ["sum", *_ACC_FOLDS])
def test_push_on_one_device_lowers_one_program_dense_or_not(devices8, fold):
    """On one device there is nobody to exchange with: ``dense=True``
    lowers the program ``dense=False`` lowers, and logs no exchange."""
    mesh = make_ps_mesh(num_shards=1, num_data=1, devices=devices8[:1])
    kw = _ACC_FOLDS[fold][0] if fold != "sum" else {}
    rng = np.random.default_rng(10)
    table = rng.normal(0, 1, (50, 4)).astype(np.float32)
    ids = rng.integers(-1, 50, (12,)).astype(np.int32)
    deltas = rng.normal(0, 1, (12, 4)).astype(np.float32)
    texts = {}
    for dense in (True, False):
        ops.clear_routes()
        fn, args = _push_on(mesh, table, ids, deltas, dense=dense, **kw)
        texts[dense] = fn.lower(*args).as_text()
        assert "push.dense_acc" not in [r for r, _ in _pushes_logged()]
    assert texts[True] == texts[False]


def test_dense_route_trains_pa_equivalently(devices8):
    """End-to-end: a PA run with forced dense collectives matches the
    gathered route to f32 reassociation tolerance, on a mesh with both a
    data axis and a shard axis."""
    import dataclasses as _dc

    import importlib

    # the models package re-exports a same-named factory FUNCTION that
    # shadows the submodule attribute `import ... as` resolves through
    pa_mod = importlib.import_module("fps_tpu.models.passive_aggressive")
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import Trainer, TrainerConfig, num_workers_of
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    mesh = make_ps_mesh(num_shards=4, num_data=2)
    W = num_workers_of(mesh)
    data = synthetic_sparse_classification(W * 64 * 4, 300, 10, seed=6)

    def run(dense):
        cfg = pa_mod.PAConfig(num_features=300, variant="PA-I", C=1.0)
        store = pa_mod.make_store(mesh, cfg)
        store.specs[pa_mod.WEIGHT_TABLE] = _dc.replace(
            store.specs[pa_mod.WEIGHT_TABLE], dense_collectives=dense
        )
        trainer = Trainer(mesh, store, pa_mod.PassiveAggressiveWorker(cfg),
                          config=TrainerConfig(donate=False))
        tables, ls = trainer.init_state(jax.random.key(0))
        ds = DeviceDataset(mesh, data)
        plan = DeviceEpochPlan(ds, num_workers=W, local_batch=64, seed=2)
        tables, ls, m = trainer.run_indexed(tables, ls, plan,
                                            jax.random.key(1), epochs=2)
        return np.asarray(store.dump_model(pa_mod.WEIGHT_TABLE)[1]), m

    w_dense, m_dense = run(True)
    w_gathered, m_gathered = run(False)
    assert np.abs(w_dense).max() > 0  # it actually trained
    np.testing.assert_allclose(w_dense, w_gathered, rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# The owner-routed exchange (PR 45): a worker's ids into one lane an owner
# shard, lanes traded by all_to_all, certified in the graph, the gathered
# exchange as the fallback.
# ---------------------------------------------------------------------------

def _routed_case(S, num_ids, dim, B=96, seed=31):
    """``B`` ids a worker on ``S`` shards, each worker's spread evenly over
    the owners in a shuffled order (so every lane fits): half of them
    repeats of 12 hot rows, an eighth negative, onto a non-zero table
    (physical layout); and a batch whose ids all belong to shard 1."""
    rng = np.random.default_rng(seed)
    rows = num_ids // S - 1
    hot = rng.integers(0, rows, 12)
    row = np.where(rng.random(S * B) < 0.5, hot[rng.integers(0, 12, S * B)],
                   rng.integers(0, rows, S * B))
    owner = np.concatenate([rng.permutation(np.arange(B) % S)
                            for _ in range(S)])
    ids = (row * S + owner).astype(np.int32)
    ids[rng.random(S * B) < 0.125] = -1
    one_owner = (row * S + 1).astype(np.int32)
    deltas = rng.normal(0, 1, (S * B, dim)).astype(np.float32)
    rps = rows_per_shard(num_ids, S)
    table = rng.normal(0, 1, (rps * S, dim)).astype(np.float32)
    return table, ids, one_owner, deltas


def _exchange_on(devices, S, fn, table, ids, deltas, *, routed=True, D=1):
    """``fn(local_shard, ids, deltas, data_axis)`` under ``shard_map`` on a
    ``D x S`` mesh with the step's ``routed`` flags watched: ``(output,
    flag of table "t", route log, lowered text)``. ``routed=False`` traces
    with the owner-routed exchange ruled out: the gathered exchange as the
    parent lowered it."""
    mesh = make_ps_mesh(num_shards=S, num_data=D, devices=devices[:D * S])
    workers = P((DATA_AXIS, SHARD_AXIS))

    def body(t, i, d):
        with store_mod.watch_routed() as seen:
            out = fn(t, i, d, DATA_AXIS if D > 1 else None)
        return out, jnp.reshape(seen.get("t", jnp.int32(-1)), (1,))

    rows = P(SHARD_AXIS, None)
    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(rows, workers, P(workers[0], None)),
        out_specs=(rows if fn.pushes else P(workers[0], None), workers),
        check_vma=False))
    args = (jax.device_put(jnp.asarray(table), NamedSharding(mesh, rows)),
            jnp.asarray(ids), jnp.asarray(deltas))
    rule = store_mod._routes_to_owner
    if not routed:
        store_mod._routes_to_owner = lambda *a: False
    try:
        ops.clear_routes()
        text = f.lower(*args).as_text()
        out, flag = f(*args)
    finally:
        store_mod._routes_to_owner = rule
    return np.asarray(out), np.asarray(flag), ops.routes_traced(), text


def _pulls(S, **kw):
    def pulls(t, i, d, data_axis):
        return pull(t, i, num_shards=S, data_axis=data_axis, table="t", **kw)
    pulls.pushes = False  # it returns the workers' rows, not the table
    return pulls


def _pushes(S, **kw):
    def pushes(t, i, d, data_axis):
        return push(t, i, d, num_shards=S, data_axis=data_axis, table="t",
                    **kw)
    pushes.pushes = True
    return pushes


def test_lane_width_is_a_margin_over_the_mean_in_whole_tiles():
    assert store_mod.LANE_MARGIN == 1.25
    # w2v-1bw-hot.x4's two batches, and the smallest that routes.
    assert [store_mod._lane_width(B, 4) for B in (49_182, 8_197, 32)] == [
        15_376, 2_568, 16]
    assert store_mod._routes_to_owner(32, 4, None)
    assert not store_mod._routes_to_owner(31, 4, None)
    assert not store_mod._routes_to_owner(96, 1, None)
    assert not store_mod._routes_to_owner(96, 4, DATA_AXIS)


@pytest.mark.parametrize("S", [4, 8])
def test_owner_lanes_keep_the_batch_order_and_certify_the_fit(devices8, S):
    """A worker's lanes: lane ``d`` holds its live ids owned by shard
    ``d`` in the batch's order, ``-1`` after them; ``src`` the batch
    positions they came from; ``slot`` where each id went (``S * L`` for a
    negative id); ``fits`` the same on every shard, false where ANY
    worker's lane overflows, and then the lane is cut, not spilled into
    the next."""
    B = 96
    L = store_mod._lane_width(B, S)
    _, ids, one_owner, _ = _routed_case(S, 1_000, 1, B)
    mesh = make_ps_mesh(num_shards=S, devices=devices8[:S])

    def lanes(i):
        got = store_mod._owner_lanes(i, num_shards=S, shard_axis=SHARD_AXIS)
        return (jnp.reshape(got.fits, (1,)), got.ids[None], got.src[None],
                got.slot[None])

    f = jax.jit(jax.shard_map(
        lanes, mesh=mesh, in_specs=P(SHARD_AXIS),
        out_specs=(P(SHARD_AXIS),) + (P(SHARD_AXIS, None),) * 3
        , check_vma=False))
    # One worker alone sends every id to shard 1: nobody's lanes fit.
    spoiled = ids.copy()
    spoiled[:B] = one_owner[:B]
    for batch, fit in ((ids, True), (spoiled, False)):
        fits, lane_ids, src, slot = map(np.asarray, f(jnp.asarray(batch)))
        assert fits.tolist() == [fit] * S
        for w in range(S):
            mine = batch[w * B:(w + 1) * B]
            for d in range(S):
                at = np.flatnonzero((mine >= 0) & (mine % S == d))[:L]
                want = np.full(L, -1)
                want[:len(at)] = mine[at]
                np.testing.assert_array_equal(lane_ids[w, d], want)
                want[:len(at)] = at
                np.testing.assert_array_equal(
                    src[w].reshape(S, L)[d], want)
                np.testing.assert_array_equal(
                    slot[w][at], d * L + np.arange(len(at)))
            assert (slot[w][mine < 0] == S * L).all()


@pytest.mark.parametrize("S", [4, 8])
def test_routed_pull_is_the_gathered_pull_bit_for_bit(devices8, S):
    """Each row comes from one shard and nothing is summed: repeats, rows
    of every shard and ``-1`` ids (zero rows) read the same bits by either
    exchange; the route log names the routed one once, with its lanes."""
    num_ids, dim, B = 1_000, 5, 96
    table, ids, _, deltas = _routed_case(S, num_ids, dim, B)
    got, flag, log, text = _exchange_on(devices8, S, _pulls(S), table, ids,
                                        deltas)
    want, off, log_g, text_g = _exchange_on(devices8, S, _pulls(S), table,
                                            ids, deltas, routed=False)
    np.testing.assert_array_equal(got, want)
    rps = rows_per_shard(num_ids, S)
    live = ids >= 0
    np.testing.assert_array_equal(
        got[live], table[np.asarray(id_to_phys(ids[live], S, rps))])
    assert not got[~live].any() and (~live).sum() > S
    assert flag.tolist() == [1] * S and off.tolist() == [0] * S
    L = store_mod._lane_width(B, S)
    assert [(r.route, r.rows, r.dim, r.ids, r.reason) for r in log
            if r.op == "pull"] == [
        ("pull.routed", rps, dim, B, f"table=t lanes={S}x{L}")]
    assert not [r for r in log_g if r.op == "pull"]
    assert "all_to_all" in text and "all_to_all" not in text_g


_DISTINCT_PULL_CASES = {
    # name -> (the local rows of a worker's ids drawn how, pull kwargs)
    "repeats": ("skewed", {}),
    "no_repeats": ("distinct", {}),
    "negative_ids": ("skewed_negative", {}),
    "one_id": ("one", {}),
    "exact": ("skewed", {"exact": True}),
}
_DISTINCT_PULL_EXCHANGES = {"one_shard": (1, True), "gathered": (8, False),
                            "routed": (8, True)}


def _distinct_pull_case(how, S, num_ids, dim, B=96, seed=54):
    """``B`` ids a worker on ``S`` shards, each worker's spread evenly over
    the owners (so every lane fits) but for ``one`` (every id the same: no
    lane holds them), onto a table in physical layout that holds a
    ``-0.0`` and a NaN: what a copy keeps and arithmetic would not."""
    rng = np.random.default_rng(seed)
    rows = num_ids // S - 1
    if how == "distinct":
        row = rng.permutation(rows)[:S * B]
    else:
        hot = rng.integers(0, rows, 12)
        row = np.where(rng.random(S * B) < 0.7,
                       hot[rng.integers(0, 12, S * B)],
                       rng.integers(0, rows, S * B))
    owner = np.concatenate([rng.permutation(np.arange(B) % S)
                            for _ in range(S)])
    ids = (row * S + owner).astype(np.int32)
    if how == "one":
        ids[:] = 77 * S + S // 2
    if how == "skewed_negative":
        ids[rng.random(S * B) < 0.125] = -1
        ids[5] = -7
    rps = rows_per_shard(num_ids, S)
    table = rng.normal(0, 1, (rps * S, dim)).astype(np.float32)
    live = np.asarray(id_to_phys(ids[ids >= 0], S, rps))
    table[live[0], 0], table[live[-1], 1] = -0.0, np.nan
    return table, ids


def _engage_distinct_pulls(monkeypatch, block=32):
    """The regime's constants patched so that a tiny shard answers the
    predicate, the ops layer routing as on the chip."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    monkeypatch.setattr(ops, "XLA_TRANSPOSED_HBM_ROWS", 1_000)
    monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", block)


@pytest.mark.parametrize("case", list(_DISTINCT_PULL_CASES))
@pytest.mark.parametrize("exchange", list(_DISTINCT_PULL_EXCHANGES))
def test_pull_distinct_rows_is_the_plain_pull_bit_for_bit(
        devices8, monkeypatch, exchange, case):
    """The pull through ``pull.distinct_rows`` (a step's ids sorted, each
    distinct row read from the shard once, a block at a time, every
    requested position handed its row out of that buffer) returns the plain
    pull's rows BIT FOR BIT, a ``-0.0`` and a NaN included: on one shard,
    on what the gathered exchange hands each of eight (ids of the other
    shards among them) and on the owner-routed exchange's lanes; with
    repeats, without (the look at the batch then reads every id by the
    plain gather, in the graph), with negative ids (zero rows), with every
    id the same (no lane holds them: the gathered fallback, the route still
    taken), and under ``exact=True``."""
    how, kw = _DISTINCT_PULL_CASES[case]
    S, routed = _DISTINCT_PULL_EXCHANGES[exchange]
    num_ids, dim, B = 3_000 * S, 16, 96
    table, ids = _distinct_pull_case(how, S, num_ids, dim, B)
    rps, handed, plain_gather = rows_per_shard(num_ids, S), [], ops.gather_rows

    def spy(t, i, **k):
        jax.debug.callback(lambda i: handed.append(i.shape[0]), i)
        return plain_gather(t, i, **k)

    def pulled(engaged):
        with monkeypatch.context() as m:
            if engaged:
                _engage_distinct_pulls(m)
                m.setattr(ops, "gather_rows", spy)
            out = _exchange_on(devices8, S, _pulls(S, **kw), table, ids,
                               np.zeros((S * B, dim), np.float32),
                               routed=routed)
            jax.effects_barrier()
            return out

    want, _, log_plain, _ = pulled(False)
    got, flag, log, _ = pulled(True)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    live = ids >= 0
    np.testing.assert_array_equal(   # the exchange's sum reads -0.0 as 0.0
        got[live], table[np.asarray(id_to_phys(ids[live], S, rps))])
    assert not got[~live].any()
    assert not [r for r in log_plain if r.route == "pull.distinct_rows"]
    L = store_mod._lane_width(B, S)
    fits = routed and S > 1 and how != "one"
    sizes = {"one_shard": [B], "gathered": [S * B],
             "routed": [S * L, S * B]}[exchange]
    assert [(r.route, r.rows, r.dim, r.ids, r.reason) for r in log
            if r.route == "pull.distinct_rows"] == [
        ("pull.distinct_rows", rps, dim, n, "xla_transposed_hbm")
        for n in sizes]
    if S > 1 and routed:
        assert flag.tolist() == [int(fits)] * S
    # What the table's gather was handed on each shard: blocks of 32 ids
    # where the batch repeats itself, all of the exchange's ids at once
    # where it does not.
    if how == "distinct":
        assert handed == [S * L if fits else S * B] * S
    else:
        # ceil(live / block) blocks a shard, and no more.
        mine = [np.unique(ids[live & (ids % S == d)]) for d in range(S)]
        assert handed == [32] * sum(-(-len(m) // 32) for m in mine)


@pytest.mark.parametrize("S", [1, 8])
def test_pull_distinct_rows_counts_the_ids_it_kept_and_the_distinct(
        devices8, monkeypatch, S):
    """What the route counts for the step (``watch_distinct_pulls``):
    every shard's own ids kept (none negative, none another shard's) and
    the distinct ids among them, against ``numpy.unique`` on a seeded
    batch; and the route log holds ``pull.distinct_rows`` once."""
    _engage_distinct_pulls(monkeypatch)
    monkeypatch.setattr(store_mod, "_routes_to_owner", lambda *a: False)
    num_ids, dim, B = 3_000 * S, 8, 96
    table, ids = _distinct_pull_case("skewed_negative", S, num_ids, dim, B)
    mesh = make_ps_mesh(num_shards=S, devices=devices8[:S])

    def counted_pull(t, i):
        with store_mod.watch_distinct_pulls() as noted:
            out = pull(t, i, num_shards=S, data_axis=None, table="emb")
        assert set(noted) == {"emb"}
        return out, jnp.stack([noted["emb"][k] for k in (
            "pulled_ids", "live_ids")])[None]

    ops.clear_routes()
    _, counts = jax.jit(jax.shard_map(
        counted_pull, mesh=mesh, in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
        check_vma=False))(
            jax.device_put(jnp.asarray(table),
                           NamedSharding(mesh, P(SHARD_AXIS, None))),
            jnp.asarray(ids))
    counts = np.asarray(counts)
    assert counts.dtype == np.int32 and counts.shape == (S, 2)
    for d in range(S):
        mine = ids[(ids >= 0) & (ids % S == d)]
        assert counts[d].tolist() == [len(mine), len(np.unique(mine))]
    assert counts.sum(axis=0).tolist() == [
        (ids >= 0).sum(), len(np.unique(ids[ids >= 0]))]
    assert [r.route for r in ops.routes_traced()
            if r.op == "pull"] == ["pull.distinct_rows"]


@pytest.mark.parametrize("cell,rps,dim,num_ids,dtype,engages", [
    ("mf-netflix.epochs", 17_770, 10, 32_768, jnp.float32, False),
    ("pa-rcv1.epochs", 47_236, 1, 1_048_576, jnp.float32, False),
    ("mf-netflix.x4", 4_443, 10, 131_072, jnp.float32, False),
    ("w2v-1bw.epochs", 1_115_011, 300, 49_182, jnp.float32, False),
    ("lr-criteo.epochs", 1_000_000, 2, 425_997, jnp.float32, False),
    ("ials-ml20m.sweeps", 138_493, 64, 138_493, jnp.float32, False),
    ("mf-netflix-topk.epochs", 17_770, 10, 32_768, jnp.float32, False),
    ("w2v-1bw-hot.x4", 278_753, 300, 61_504, jnp.float32, False),
    ("dlrm-criteo.epochs", 33_762_577, 16, 425_984, jnp.float32, True),
    ("kge-wikidata5m.epochs", 393_216, 1_000, 49_152, jnp.float32, False),
    # The same table in bfloat16 (a copy rounds nothing: it engages, where
    # the push's sums stay out), wider than float32, under one block of
    # ids, as a shard of four (what the queued ``dlrm-criteo.x4``'s lanes
    # hand it), and with fewer rows than any measured.
    ("dlrm bf16", 33_762_577, 16, 425_984, jnp.bfloat16, True),
    ("dlrm f64", 33_762_577, 16, 425_984, jnp.float64, False),
    ("dlrm one block", 33_762_577, 16, 1_024, jnp.float32, False),
    ("dlrm-criteo.x4", 8_440_645, 16, 4 * 133_120, jnp.float32, True),
    ("unmeasured band", 786_432, 16, 425_984, jnp.float32, False),
])
def test_distinct_pull_route_from_shapes_alone(monkeypatch, cell, rps, dim,
                                               num_ids, dtype, engages):
    """``pull.distinct_rows`` engages by ``pull``'s own shapes, where
    ``push.sum_runs`` does and for its reason: of the ten cells' pulled
    tables only ``dlrm-criteo.epochs``' (narrow float rows, so many that
    XLA keeps the table transposed in HBM, under more ids than a block);
    off the TPU, or under the ``"xla"`` backend, nothing."""
    from fps_tpu.core.store import _distinct_pull_route

    assert not _distinct_pull_route(rps, dim, num_ids, dtype)  # the CPU's
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    assert _distinct_pull_route(rps, dim, num_ids, dtype) is engages
    # Off the TPU under the forced "pallas" backend; under "xla" anywhere.
    for elsewhere in ((True, True), (False, False)):
        monkeypatch.setattr(ops, "_use_pallas", lambda: elsewhere)
        assert not _distinct_pull_route(rps, dim, num_ids, dtype)


_ROUTED_PUSHES = {
    # name -> (push kwargs, the table's ids and width (a mean takes the
    # branch named at them), the branch the route log names, float64
    # oracle of a touched row from its value and its pushes)
    "sum": (dict(), (1_000, 5), None,
            lambda cur, rows: cur + rows.sum(axis=0)),
    "mean_rows": (dict(combine="mean"), (65_536, 64), "push.mean_rows",
                  lambda cur, rows: cur + rows.mean(axis=0)),
    "mean_dense": (dict(combine="mean"), (1_000, 5), "push.mean_dense",
                   lambda cur, rows: cur + rows.mean(axis=0)),
    "fold": (dict(apply_fn=lambda cur, d: 0.5 * cur + d), (1_000, 5),
             "push.fold", lambda cur, rows: 0.5 * cur + rows.sum(axis=0)),
    "max": (dict(combine="max"), (1_000, 5), None,
            lambda cur, rows: cur + rows.max(axis=0)),
    # The accumulator summed by id run first (its predicate's constant
    # patched to engage it), on a shard XLA would keep transposed.
    "acc_runs": (dict(apply_fn=lambda cur, d: 0.5 * cur + d),
                 (2_400_000, 2), "push.fold",
                 lambda cur, rows: 0.5 * cur + rows.sum(axis=0)),
    # The additive push's rows summed by id first (its regime's constants
    # patched to engage it): the shard stays out of the conditional.
    "sum_runs": (dict(), (12_000, 16), "push.sum_runs",
                 lambda cur, rows: cur + rows.sum(axis=0)),
}


@pytest.mark.parametrize("kind", list(_ROUTED_PUSHES))
@pytest.mark.parametrize("S", [4, 8])
def test_routed_push_equals_the_gathered_push_and_the_oracle(devices8,
                                                             monkeypatch, S,
                                                             kind):
    """Everything after the exchange runs on the ``S x L`` pushes a shard
    is handed as it runs on the gathered ``S x B``: the additive scatter,
    both branches of the mean (asked about the rows HANDED), a stateful
    fold (its accumulator plain and summed by id run) and max give the
    gathered branch's table and a float64 oracle's; rows nobody pushed
    keep their bits."""
    kw, (num_ids, dim), route, oracle = _ROUTED_PUSHES[kind]
    B = 96
    if kind == "acc_runs":
        monkeypatch.setattr(ops, "ACC_RUNS_MIN_IDS_PER_ROW", 0.0)
    if kind == "sum_runs":
        monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
        monkeypatch.setattr(ops, "XLA_TRANSPOSED_HBM_ROWS", 1_000)
        monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", 64)
    table, ids, _, deltas = _routed_case(S, num_ids, dim, B)
    got, flag, log, _ = _exchange_on(devices8, S, _pushes(S, **kw), table,
                                     ids, deltas)
    gathered, off, log_g, _ = _exchange_on(devices8, S, _pushes(S, **kw),
                                           table, ids, deltas, routed=False)
    rps = rows_per_shard(num_ids, S)
    L = store_mod._lane_width(B, S)
    assert flag.tolist() == [1] * S and off.tolist() == [0] * S
    pushes = [(r.route, r.ids) for r in log if r.op == "push"]
    assert pushes[0] == ("push.routed", B)
    if route:  # once a branch of the certificate, on what it is handed
        runs = [("push.acc_runs", n) for n in (S * L, S * B)
                if kind == "acc_runs"]
        assert pushes[1:] == [(route, S * L), *runs[:1], (route, S * B),
                              *runs[1:]], pushes
        assert [(r.route, r.ids) for r in log_g if r.op == "push"] == [
            (route, S * B), *runs[1:]]
    want = table.astype(np.float64)
    keep = ids >= 0
    phys = np.asarray(id_to_phys(ids[keep], S, rps))
    touched = np.unique(phys)
    for row in touched:
        want[row] = oracle(want[row],
                           deltas[keep][phys == row].astype(np.float64))
    scale = np.abs(want).max()
    assert np.abs(got - gathered).max() <= 1e-6 * scale
    assert np.abs(got - want).max() <= 1e-6 * scale
    untouched = np.setdiff1d(np.arange(rps * S), touched)
    assert len(untouched) >= 20
    np.testing.assert_array_equal(got[untouched], table[untouched])


@pytest.mark.parametrize("op", ["pull", "push_sum", "push_mean"])
@pytest.mark.parametrize("S", [4, 8])
def test_ids_of_one_owner_fall_back_to_the_gathered_exchange(devices8, S,
                                                             op):
    """A batch whose ids all belong to one shard overflows that shard's
    lane (``B`` ids into ``1.25 B / S`` slots): the certificate reads 0 on
    every shard, the step runs the gathered branch, and the result is the
    gathered exchange's own, bit for bit: nothing is dropped."""
    num_ids, dim, B = 1_000, 5, 96
    table, _, one_owner, deltas = _routed_case(S, num_ids, dim, B)
    assert B > store_mod._lane_width(B, S)
    fn = _pulls(S) if op == "pull" else _pushes(
        S, **({"combine": "mean"} if op == "push_mean" else {}))
    got, flag, log, _ = _exchange_on(devices8, S, fn, table, one_owner,
                                     deltas)
    want, _, _, _ = _exchange_on(devices8, S, fn, table, one_owner, deltas,
                                 routed=False)
    assert flag.tolist() == [0] * S
    assert [r.route for r in log if r.route.endswith(".routed")] == [
        op[:4] + ".routed"]  # the lanes are in the program, not in the step
    np.testing.assert_array_equal(got, want)
    if op == "pull":
        rps = rows_per_shard(num_ids, S)
        np.testing.assert_array_equal(
            got, table[np.asarray(id_to_phys(one_owner, S, rps))])


@pytest.mark.parametrize("op", ["pull", "push"])
def test_a_data_axis_or_a_small_batch_keeps_the_exchange_gathered(devices8,
                                                                  op):
    """With a data axis (a push must reach every replica) and under 8 ids
    a shard, what is lowered is the gathered exchange itself, text for
    text, and the step's flag reads 0; a dense exchange notes no flag."""
    dim = 5
    for D, S, B in ((2, 4, 96), (1, 4, 24)):
        table, ids, _, deltas = _routed_case(D * S, 1_000, dim, B)
        rps = rows_per_shard(1_000, S)
        table = table[:rps * S]
        fn = _pulls(S) if op == "pull" else _pushes(S)
        got, flag, log, text = _exchange_on(devices8, S, fn, table, ids,
                                            deltas, D=D)
        want, _, _, text_g = _exchange_on(devices8, S, fn, table, ids,
                                          deltas, D=D, routed=False)
        assert text == text_g and "all_to_all" not in text
        assert not [r for r in log if r.route.endswith(".routed")]
        assert flag.tolist() == [0] * (D * S)
        np.testing.assert_array_equal(got, want)
    fn = _pulls(4, dense=True) if op == "pull" else _pushes(4, dense=True)
    table, ids, _, deltas = _routed_case(4, 1_000, dim)
    _, flag, log, _ = _exchange_on(devices8, 4, fn, table, ids, deltas)
    assert flag.tolist() == [-1] * 4
    assert not [r for r in log if r.route.endswith(".routed")]
