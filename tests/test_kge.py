"""ComplEx with AdaGrad at the server against its plain reference, and the
table's own stateful fold (``ServerLogic.fold``) it stands on.

Tiny sizes on virtual CPU devices. What is checked is correctness and
counts: the program against ``perfbench/lib/reference/complex_adagrad.py``
over one ``run_indexed`` epoch on one worker and on four shards under the
gathered and the owner-routed exchange, tables, optimizer state and every
step's loss; ``store.push``'s sparse body against the accumulator body and
against float64; untouched rows' bits; a repeated id folded once on its
sum; every unsupported mode refused at construction by name; a
checkpoint's save, restore on another shard count and bit-identical
continuation. No rate is read.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import fps_tpu
import fps_tpu.ops as ops
from fps_tpu import DeviceDataset, DeviceEpochPlan
from fps_tpu.core import store as store_mod
from fps_tpu.core.api import HotFold, ServerLogic
from fps_tpu.core.checkpoint import Checkpointer
from fps_tpu.core.driver import Trainer, TrainerConfig
from fps_tpu.core.store import fold_key
from fps_tpu.models.kge import (
    ENTITY_TABLE, RELATION_TABLE, KGEConfig, KGEWorker, kge, make_store,
    step_loss,
)
from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh
from perfbench.lib import check, resolve, spec, window

CELL = "kge-wikidata5m.epochs"
# 65,536 entities of 12 floats: large against the ids a shard is handed a
# step, so the entity table folds on the sparse body on one shard and on
# four; 11 relations keep the accumulator.
ENTITIES = 65536
TINY = {"model": {"entities": ENTITIES, "relations": 11, "rank": 6,
                  "negatives": 3, "local_batch": 32, "export_blocks": 4},
        "data": {"entities": ENTITIES, "relations": 11,
                 "triples_resident": 1203}}
# kind -> (shards, positives a worker a step): 32 positives are 160 entity
# ids a worker, owner-routed over four shards; 4 are 20, under
# ROUTED_MIN_IDS_PER_SHARD a shard: gathered.
MESHES = {"one": (1, 32), "shards4_routed": (4, 32),
          "shards4_gathered": (4, 4)}
# float32 on both sides; what differs is the order of a row's sums. From
# the configuration's initial accumulator (0.1) the three meshes read 1.4e-7
# to 2.2e-7 on the tables, 5e-8 to 1.8e-7 on the norms and up to 4.4e-7 on
# the loss. From a ZERO accumulator the tables read up to 5e-3: where a
# coordinate's summed gradient all but cancels, lr g / (sqrt(G) + eps)
# turns the last bits of g into a visible share of lr.
F32_GAP, TABLE_GAP = 5e-6, 5e-6


def tiny_cfg(local_batch=32):
    cfg = copy.deepcopy(spec.load_cell(spec.load_benchmark(), CELL)["config"])
    for part, over in TINY.items():
        cfg[part].update(over)
    cfg["model"]["local_batch"] = local_batch
    return cfg


def build(kind, monkeypatch, seed=7):
    shards, local_batch = MESHES[kind]
    cfg = tiny_cfg(local_batch)
    traffic = spec.load_traffic("epochs")
    devices = jax.devices()[:shards]
    monkeypatch.setattr(
        fps_tpu, "make_ps_mesh",
        lambda: make_ps_mesh(num_shards=shards, devices=devices))
    data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    init = resolve.reference(cfg).init_tables(seed, cfg)
    return cfg, system, init, data_sum


@pytest.fixture(scope="module")
def compared():
    """``{mesh kind: (check.compare's numbers, the route log)}`` of one
    epoch against the reference, each mesh built once."""
    out = {}
    mp = pytest.MonkeyPatch()
    try:
        for kind in MESHES:
            cfg, system, init, data_sum = build(kind, mp)
            ops.clear_routes()
            state, warm = window.queue_call(system, system.place(init))
            warm.wait()
            routes = ops.routes_traced()
            numbers, _ = check.compare_call(
                system, cfg, init, system.export(*state), warm.host, data_sum)
            out[kind] = numbers, routes
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("kind", sorted(MESHES))
def test_program_matches_reference(devices8, compared, kind):
    """Every step's loss, the entity table's blocks, the relation table
    and BOTH accumulators after an epoch; every triple fed exactly once."""
    numbers, _ = compared[kind]
    assert numbers["examples"] == 0 and numbers["feed"] == 0
    names = [f"entity_{b:02d}" for b in range(4)] + ["relation"]
    names += [n.replace("entity", "entity_acc") for n in names[:4]] + [
        "relation_acc"]
    for name in names:
        assert numbers[f"table_gap.{name}"] < TABLE_GAP, (kind, name)
        assert numbers[f"update_gap.{name}"] < F32_GAP, (kind, name)
    assert numbers["loss_gap"] < F32_GAP
    assert len(numbers) == 3 + 2 * len(names)


@pytest.mark.parametrize("kind,exchange", [
    ("one", None), ("shards4_routed", True), ("shards4_gathered", False)])
def test_the_fold_body_follows_the_shapes_and_is_logged(devices8, compared,
                                                        kind, exchange):
    """The entity table takes the sparse body, the 11-row relation table
    keeps the accumulator (on four shards filled by the dense exchange),
    and the exchange is the one the batch's size gives."""
    _, routes = compared[kind]
    by_route = {}
    for r in routes:
        by_route.setdefault(r.route, []).append(r)
    shards = MESHES[kind][0]
    fold_rows = by_route["push.fold_rows"]
    assert {r.rows for r in fold_rows} == {ENTITIES // shards}
    assert {r.reason for r in by_route["push.fold"]} == {"small_table"}
    assert "scatter_set.xla" in by_route
    assert ("push.routed" in by_route) == bool(exchange)
    assert ("push.dense_acc" in by_route) == (shards > 1)
    assert "push.mean_rows" not in by_route and "push.sum_runs" not in by_route


# -- the worker's linear form against the written-out definition ------------

def _sum_by_id(ids, rows, num_ids):
    out = np.zeros((num_ids, rows.shape[1]), np.float64)
    np.add.at(out, ids[ids >= 0], rows[ids >= 0])
    return out


@pytest.mark.parametrize("l2", [0.0, 1e-5])
@pytest.mark.parametrize("coins", ["subject", "object", "mixed"])
@pytest.mark.parametrize("negatives", [1, 10])
@pytest.mark.parametrize("rank", [6, 500])
def test_step_is_the_definitions_loss_and_gradient(rank, negatives, coins, l2):
    """``KGEWorker.step`` (the score's linear form on the pulled rows'
    2-D layout) against ``jax.value_and_grad`` of ``step_loss`` on
    ``complex_score`` in FLOAT64: the loss, the relation pushes, and the
    entity pushes matched id by id whatever order the worker lays its ids
    in (the same multiset of ids; every id but two occurs once in the
    step, so its push is one row; the two that repeat are compared as the
    server sees them, summed). Every case has three padding positives
    (``weight`` 0: ``-1`` ids, rows exactly zero), a replacement equal to
    its positive's own subject and one equal to its own object.

    The tolerance is float32's rounding of the definition itself: a
    component of a push is a sum of at most ``2 (1 + N)`` triple products
    of numbers near 0.3, each rounded to ``2^-24`` of its size. Evaluated
    in float32, the DEFINITION reads from the oracle, as a share of the
    largest component (0.29 to 1.9), up to 1.7e-7 on the entity pushes,
    2.8e-7 on the relation pushes and 1.5e-7 of the loss (18 to 130) over
    these 24 cases; the linear form 1.6e-7, 1.7e-7 and 1.1e-7. Held to
    1e-6 of the largest component and of the loss: six times the floor,
    and under what the smallest term left out would read (a row's L2,
    ``2 l2 |e|``: 2e-5 where a component is near 1)."""
    B, N, R = 16, negatives, 5
    E = B * (2 + N) + 8
    rng = np.random.default_rng([rank, N, len(coins)])
    cfg = KGEConfig(num_entities=E, num_relations=R, rank=rank, negatives=N,
                    l2=l2)
    entity = rng.normal(0, 0.3, (E, cfg.dim)).astype(np.float32)
    relation = rng.normal(0, 0.3, (R, cfg.dim)).astype(np.float32)
    distinct = rng.permutation(E).astype(np.int32)
    s, o = distinct[:B], distinct[B:2 * B]
    neg = distinct[2 * B:B * (2 + N)].reshape(B, N).copy()
    neg[0, 0], neg[1, -1] = s[0], o[1]
    side = {"subject": np.ones, "object": np.zeros,
            "mixed": lambda shape, _: rng.random(shape) < 0.5}[coins](
                (B, N), bool)
    weight = np.ones(B, np.float32)
    weight[-3:] = 0
    batch = {k: jnp.asarray(v) for k, v in dict(
        s=s, r=rng.integers(0, R, B).astype(np.int32), o=o, weight=weight,
        neg_side=side, neg_entity=neg).items()}

    worker = KGEWorker(cfg)
    ids = worker.pull_ids(batch)
    assert ids[ENTITY_TABLE].shape == (B * (2 + N),)
    assert ids[RELATION_TABLE].shape == (B,)
    pulled = {ENTITY_TABLE: jnp.asarray(entity)[ids[ENTITY_TABLE]],
              RELATION_TABLE: jnp.asarray(relation)[ids[RELATION_TABLE]]}
    out = worker.step(batch, pulled, (), jax.random.key(0))
    ent_ids, ent_rows = map(np.asarray, out.pushes[ENTITY_TABLE])
    rel_ids, rel_rows = map(np.asarray, out.pushes[RELATION_TABLE])
    assert ent_rows.dtype == rel_rows.dtype == np.float32

    with jax.enable_x64(True):
        f64 = lambda x: jnp.asarray(x, jnp.float64)  # noqa: E731
        loss, (g_s, g_r, g_o, g_n) = jax.value_and_grad(
            lambda *rows: step_loss(cfg, *rows, jnp.asarray(side),
                                    f64(weight)),
            argnums=(0, 1, 2, 3))(f64(entity[s]), f64(relation[batch["r"]]),
                                  f64(entity[o]), f64(entity[neg]))
        loss, g_s, g_r, g_o, g_n = map(np.asarray, (loss, g_s, g_r, g_o, g_n))
    live = weight > 0
    want_ids = np.where(np.concatenate([live, live, np.repeat(live, N)]),
                        np.concatenate([s, o, neg.reshape(-1)]), -1)
    want_rows = -np.concatenate([g_s, g_o, g_n.reshape(B * N, -1)])

    np.testing.assert_array_equal(np.sort(ent_ids), np.sort(want_ids))
    np.testing.assert_array_equal(rel_ids, np.where(live, batch["r"], -1))
    assert (ent_ids < 0).sum() == 3 * (2 + N)
    assert not ent_rows[ent_ids < 0].any() and not rel_rows[~live].any()
    np.testing.assert_allclose(float(out.out["loss"]), loss, rtol=1e-6)
    scale = np.abs(want_rows).max()
    assert 0.2 < scale < 2
    np.testing.assert_allclose(
        _sum_by_id(ent_ids, ent_rows, E), _sum_by_id(want_ids, want_rows, E),
        rtol=0, atol=1e-6 * scale)
    np.testing.assert_allclose(rel_rows, -g_r, rtol=0,
                               atol=1e-6 * np.abs(g_r).max())
    assert float(out.out["n"]) == B - 3


# -- store.push with a fold, by itself --------------------------------------

def _push(table, state, ids, deltas, fold, shards=1):
    """``store.push`` with the table's own fold over ``shards`` devices:
    logical ``(rows, dim)`` arrays in and out, every worker pushing
    ``ids[w]``, ``deltas[w]``."""
    mesh = make_ps_mesh(num_shards=shards, devices=jax.devices()[:shards])
    n = table.shape[0]
    rps = store_mod.rows_per_shard(n, shards)
    phys = np.asarray(store_mod.id_to_phys(np.arange(n), shards, rps))

    def lay(x):
        out = np.zeros((rps * shards,) + x.shape[1:], x.dtype)
        out[phys] = x
        return out

    def body(t, s, i, d):
        return store_mod.push(t, i[0], d[0], num_shards=shards,
                              data_axis=None, fold=fold, fold_state=s,
                              table="t")

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None),) * 2 + (P(SHARD_AXIS),) * 2,
        out_specs=(P(SHARD_AXIS, None),) * 2, check_vma=False))
    new_t, new_s = f(lay(table), lay(state), ids, deltas)
    return np.asarray(new_t)[phys], np.asarray(new_s)[phys]


def _adagrad64(table, state, ids, deltas, fold):
    g = np.zeros(table.shape, np.float64)
    np.add.at(g, ids[ids >= 0], deltas[ids >= 0].astype(np.float64))
    touched = np.zeros(len(table), bool)
    touched[ids[ids >= 0]] = True
    G = state + g * g
    new = table + fold.lr * g / (np.sqrt(G) + fold.eps)
    return (np.where(touched[:, None], new, table),
            np.where(touched[:, None], G, state), touched)


@pytest.mark.parametrize("shards,batch", [(1, 96), (4, 40), (4, 6)])
def test_sparse_fold_is_adagrad_on_each_ids_sum_and_spares_the_rest(
        devices8, shards, batch):
    """Against float64: a row pushed several times in the step (by one
    worker and by several) takes ONE step on the sum of its pushes; a row
    nobody pushed, and its accumulator, keep their bits; dropped ids
    (``-1``) push nothing."""
    rng = np.random.default_rng(shards * 100 + batch)
    n, dim = 32768, 12
    fold = HotFold("adagrad", lr=0.1, eps=1e-8)
    table = rng.normal(0, 0.1, (n, dim)).astype(np.float32)
    state = (rng.random((n, dim)) * (rng.random((n, 1)) < 0.5)).astype(
        np.float32)
    ids = rng.integers(0, 200, (shards, batch)).astype(np.int32)
    ids[:, :3] = 7                       # every worker pushes row 7 thrice
    ids[:, 3] = -1
    deltas = rng.normal(0, 1, (shards, batch, dim)).astype(np.float32)
    ops.clear_routes()
    new_t, new_s = _push(table, state, ids, deltas, fold, shards)
    assert "push.fold_rows" in {r.route for r in ops.routes_traced()}
    want_t, want_s, touched = _adagrad64(
        table, state, ids.reshape(-1), deltas.reshape(-1, dim), fold)
    np.testing.assert_allclose(new_t, want_t, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(new_s, want_s, rtol=2e-5, atol=2e-6)
    assert touched[7] and not touched[:7].all() and (~touched).sum() > 32000
    np.testing.assert_array_equal(new_t[~touched], table[~touched])
    np.testing.assert_array_equal(new_s[~touched], state[~touched])
    # One step on the sum, not one a push: G grew by (sum g)^2.
    g7 = deltas.reshape(-1, dim)[ids.reshape(-1) == 7].astype(
        np.float64).sum(0)
    np.testing.assert_allclose(new_s[7] - state[7], g7 * g7, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("kind", ["adagrad", "adam"])
@pytest.mark.parametrize("shards", [1, 4])
def test_sparse_body_and_accumulator_body_give_the_same_tables(
        devices8, monkeypatch, shards, kind):
    """One push on a table small enough for both: the shapes choose the
    sparse body; with the edge moved they choose the accumulator; table
    and state agree to float32 rounding (the sums' order differs), and the
    untouched rows bit for bit."""
    rng = np.random.default_rng(5)
    n, dim, batch = 32768, 12, 64
    fold = HotFold(kind, lr=0.05)
    table = rng.normal(0, 0.1, (n, dim)).astype(np.float32)
    state = np.zeros((n, fold.state_cols(dim)), np.float32)
    ids = rng.integers(0, 300, (shards, batch)).astype(np.int32)
    deltas = rng.normal(0, 1, (shards, batch, dim)).astype(np.float32)
    out = {}
    for body, ratio in (("fold_rows", ops.MEAN_ROWS_TABLE_RATIO),
                        ("fold", 1e9)):
        monkeypatch.setattr(ops, "MEAN_ROWS_TABLE_RATIO", ratio)
        ops.clear_routes()
        out[body] = _push(table, state, ids, deltas, fold, shards)
        took = {r.route for r in ops.routes_traced() if r.op == "push"}
        assert f"push.{body}" in took and took <= {
            f"push.{body}", "push.routed"}, took
    for a, b in zip(out["fold_rows"], out["fold"]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    untouched = np.ones(n, bool)
    untouched[ids.reshape(-1)] = False
    for a, b, was in zip(out["fold_rows"], out["fold"], (table, state)):
        np.testing.assert_array_equal(a[untouched], was[untouched])
        np.testing.assert_array_equal(b[untouched], was[untouched])


def test_fold_takes_sum_and_no_apply_fn(devices8):
    t = jnp.zeros((64, 4))
    with pytest.raises(ValueError, match="combine='sum'"):
        store_mod.push(t, jnp.zeros((4,), jnp.int32), jnp.zeros((4, 4)),
                       num_shards=1, data_axis=None, combine="mean",
                       fold=HotFold("adagrad"), fold_state=t)


@pytest.mark.parametrize("shards", [1, 4])
def test_fresh_state_is_the_declared_initial_accumulator(devices8, shards):
    """The trainer makes a table's AdaGrad state at
    ``HotFold.initial_accumulator`` (``KGEConfig``'s 0.1; 0 when none is
    declared), every component, sharded like the table."""
    cfg = KGEConfig(num_entities=512, num_relations=5, rank=2, negatives=2)
    mesh = make_ps_mesh(num_shards=shards, devices=jax.devices()[:shards])
    for declared, want in ((kge(mesh, cfg)[0], 0.1),
                           (_trainer(), 0.0)):
        tables, _ = declared.init_state(jax.random.key(0))
        with_state = declared._attach_hot(tables)
        for name in (ENTITY_TABLE, RELATION_TABLE):
            state = with_state[fold_key(name)]
            assert state.shape == tables[name].shape
            assert state.sharding == tables[name].sharding
            np.testing.assert_array_equal(np.asarray(state),
                                          np.float32(want))


@pytest.mark.parametrize("kind,start", [("adam", 0.1), ("adagrad", -0.1)])
def test_initial_accumulator_is_adagrads_and_not_negative(kind, start):
    with pytest.raises(ValueError, match="initial_accumulator"):
        HotFold(kind, initial_accumulator=start)


@pytest.mark.parametrize("start,gap", [(0.0, 0.18), (0.1, 6.3e-8)])
def test_a_zero_accumulator_turns_a_last_bit_into_a_whole_step(start, gap):
    """Why ``KGEConfig.initial_accumulator`` is not 0: two float32 sums of
    one gradient that cancels to within rounding land either side of zero
    (1e-7 here), and AdaGrad's first step from ``G = 0`` is ``lr`` in size
    whatever ``|g|`` is: ``+lr`` against ``-lr``. From 0.1 the same two
    sums step 6e-8 apart."""
    fold = HotFold("adagrad", lr=0.1, eps=1e-8, initial_accumulator=start)
    g = jnp.array([[1e-7], [-1e-7]], jnp.float32)
    step, G = store_mod.apply_hot_fold(
        fold, jnp.full((2, 1), start, jnp.float32), g, jnp.ones((2,)))
    step = np.asarray(step)
    assert abs(float(step[0, 0] - step[1, 0])) == pytest.approx(gap, rel=0.1)
    np.testing.assert_allclose(np.asarray(G), start + 1e-14, rtol=1e-6)


def test_scatter_set_writes_rows_and_drops_the_rest(devices8):
    table = jnp.arange(40.0).reshape(10, 4)
    ids = jnp.array([1, 4, 7, 10, 10, -1], jnp.int32)
    rows = -jnp.ones((6, 4))
    for ids_sorted in (False, True):
        new = np.asarray(ops.scatter_set(table, ids, rows,
                                         ids_sorted=ids_sorted))
        want = np.asarray(table).copy()
        want[[1, 4, 7]] = -1
        np.testing.assert_array_equal(new, want)


# -- refused at construction ------------------------------------------------

def _trainer(config=None, server_logic=None, **spec_over):
    cfg = KGEConfig(num_entities=512, num_relations=5, rank=2, negatives=2)
    mesh = make_ps_mesh(devices=jax.devices()[:2])
    store = make_store(mesh, cfg)
    if spec_over:
        store.specs[ENTITY_TABLE] = dataclasses.replace(
            store.specs[ENTITY_TABLE], **spec_over)
    return Trainer(mesh, store, KGEWorker(cfg),
                   server_logic=server_logic or ServerLogic(fold="adagrad"),
                   config=TrainerConfig(**(config or {})))


@pytest.mark.parametrize("config,logic,spec_over,named", [
    (dict(sync_every=2), None, {}, "sync_every"),
    (dict(push_delay=1), None, {}, "push_delay"),
    (dict(auto_tier=True), None, {}, "auto_tier"),
    ({}, None, dict(hot_tier=16), "hot_tier"),
    ({}, ServerLogic(fold="adagrad", hot_fold="adagrad"), {}, "hot_fold"),
    ({}, ServerLogic(fold="adagrad", combine="mean"), {}, "combine"),
    ({}, ServerLogic(fold="adagrad", apply_fn=lambda r, d: r + d), {},
     "apply_fn"),
])
def test_unsupported_modes_are_refused_by_name(devices8, config, logic,
                                               spec_over, named):
    with pytest.raises(ValueError) as e:
        _trainer(config, logic, **spec_over)
    assert "ServerLogic.fold" in str(e.value) and named in str(e.value)


def test_the_megastep_refuses_a_tables_own_fold(devices8):
    cfg = KGEConfig(num_entities=512, num_relations=5, rank=2, negatives=2)
    mesh = make_ps_mesh(devices=jax.devices()[:1])
    trainer, _ = kge(mesh, cfg, max_steps_per_call=2)
    rows = _triples(64, cfg, seed=3)
    plan = DeviceEpochPlan(DeviceDataset(mesh, rows), num_workers=1,
                           local_batch=8)
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="ServerLogic.fold"):
        trainer.run_megastep(tables, ls, plan, jax.random.key(1),
                             chunks_per_dispatch=2)


def test_a_guarded_trainer_still_folds(devices8):
    """The guard screens pushes before the fold sees them: supported."""
    trainer = _trainer(dict(guard="mask"))
    assert sorted(trainer._row_fold_map()) == [ENTITY_TABLE, RELATION_TABLE]


# -- checkpoint: save, restore, continue bit for bit -------------------------

def _triples(n, cfg, seed):
    rng = np.random.default_rng(seed)
    return {"s": rng.integers(0, 40, n).astype(np.int32),
            "r": rng.integers(0, cfg.num_relations, n).astype(np.int32),
            "o": rng.integers(0, cfg.num_entities, n).astype(np.int32)}


@pytest.mark.parametrize("shards", [1, 4])
def test_checkpoint_continues_bit_for_bit(devices8, tmp_path, shards):
    """Two epochs straight against one epoch, a snapshot, a FRESH trainer
    restored from it and the second epoch: tables and optimizer state
    equal bit for bit; the snapshot holds the state as ``fold::`` arrays
    in LOGICAL id order, the table's own shape."""
    # (no padding rows on four shards: a restore zeroes those)
    cfg = KGEConfig(num_entities=2052, num_relations=8, rank=3, negatives=2)
    rows = _triples(6 * 16 * shards, cfg, seed=5)
    key = jax.random.key(11)

    def fresh(shards=shards):
        mesh = make_ps_mesh(num_shards=shards,
                            devices=jax.devices()[:shards])
        trainer, store = kge(mesh, cfg)
        plan = DeviceEpochPlan(DeviceDataset(mesh, rows), num_workers=shards,
                               local_batch=16, seed=2)
        return trainer, store, plan, trainer.init_state(jax.random.key(0))

    trainer, _, plan, (tables, ls) = fresh()
    straight, _, _ = trainer.run_indexed(tables, ls, plan, key, epochs=2)
    straight = {k: np.asarray(v) for k, v in straight.items()}
    start = np.float32(cfg.initial_accumulator)
    assert np.any(straight[fold_key(ENTITY_TABLE)] != start)

    ck = Checkpointer(str(tmp_path))
    trainer, store, plan, (tables, ls) = fresh()
    tables, ls, _ = trainer.run_indexed(tables, ls, plan, key, epochs=1,
                                        checkpointer=ck, checkpoint_every=1)
    with np.load(ck._path(1)) as z:
        saved = {k: z[k] for k in z.files if k.startswith("fold::")}
    assert {k: v.shape for k, v in saved.items()} == {
        "fold::entity": (2052, 6), "fold::relation": (8, 6)}
    store.tables = dict(tables)
    np.testing.assert_array_equal(
        saved["fold::entity"][:40] != start,
        np.abs(store.dump_model(ENTITY_TABLE)[1][:40]
               - fresh()[1].dump_model(ENTITY_TABLE)[1][:40]) > 0)

    trainer, _, plan, (tables, ls) = fresh()
    tables, ls, step = trainer.restore_checkpoint(ck, ls)
    assert step == 1 and fold_key(ENTITY_TABLE) in tables
    resumed, _, _ = trainer.run_indexed(tables, ls, plan, key, epochs=1,
                                        start_epoch=1)
    assert set(resumed) == set(straight)
    for name, want in straight.items():
        np.testing.assert_array_equal(np.asarray(resumed[name]), want,
                                      err_msg=name)


def test_a_snapshot_restores_its_state_on_another_shard_count(devices8,
                                                              tmp_path):
    """Saved on four shards, restored on one: the state lands on the rows
    it belongs to (the logical order is the snapshot's)."""
    cfg = KGEConfig(num_entities=2051, num_relations=5, rank=3, negatives=2)
    rows = _triples(6 * 16 * 4, cfg, seed=5)

    def fresh(shards):
        mesh = make_ps_mesh(num_shards=shards,
                            devices=jax.devices()[:shards])
        trainer, store = kge(mesh, cfg)
        plan = DeviceEpochPlan(DeviceDataset(mesh, rows), num_workers=shards,
                               local_batch=16, seed=2)
        return trainer, store, plan, trainer.init_state(jax.random.key(0))

    ck = Checkpointer(str(tmp_path))
    trainer, store, plan, (tables, ls) = fresh(4)
    tables, ls, _ = trainer.run_indexed(tables, ls, plan, jax.random.key(3),
                                        epochs=1, checkpointer=ck,
                                        checkpoint_every=1)
    with np.load(ck._path(1)) as z:
        saved = z["fold::entity"]
    trainer, store, plan, (tables, ls) = fresh(1)
    tables, ls, _ = trainer.restore_checkpoint(ck, ls)
    np.testing.assert_array_equal(
        np.asarray(tables[fold_key(ENTITY_TABLE)])[:2051], saved)
    tables = trainer._attach_hot(tables)   # kept, not re-made
    np.testing.assert_array_equal(
        np.asarray(tables[fold_key(ENTITY_TABLE)])[:2051], saved)


def test_fold_counts_arrive_on_the_span_of_a_call_nobody_fetches(devices8):
    """``fold_rows.<table>.*`` leave the step as plain per-step leaves and
    reach the ``device.run_indexed`` span's ``fold_rows`` field."""
    from fps_tpu import obs
    from fps_tpu.obs import events

    cfg = KGEConfig(num_entities=16384, num_relations=5, rank=3, negatives=2)
    mesh = make_ps_mesh(devices=jax.devices()[:1])
    trainer, _ = kge(mesh, cfg)
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    rows = _triples(64, cfg, seed=9)
    plan = DeviceEpochPlan(DeviceDataset(mesh, rows), num_workers=1,
                           local_batch=16, seed=2)
    tables, ls = trainer.init_state(jax.random.key(0))
    events.set_default_recorder(rec)
    try:
        tables, ls, metrics = trainer.run_indexed(
            tables, ls, plan, jax.random.key(1), epochs=1, as_numpy=False)
        m = jax.device_get(metrics[0])
    finally:
        events.set_default_recorder(None)   # waits for the span
    rec.flush()
    handed = float(np.sum(m["fold_rows.entity.handed_ids"]))
    folded = float(np.sum(m["fold_rows.entity.folded_ids"]))
    assert handed == 64 * 4 and 0 < folded < handed   # s repeats: 40 values
    assert not [k for k in m if k.startswith("fold_rows.relation")]
    (span,) = [e for e in sink.events("span")
               if e["span"] == "device.run_indexed"]
    assert span["fold_rows"] == {ENTITY_TABLE: {
        "handed_ids": handed, "folded_ids": folded}}
    for k, v in span["fold_rows"][ENTITY_TABLE].items():
        assert rec.counter_value(f"fold_rows.{k}", table=ENTITY_TABLE) == v
