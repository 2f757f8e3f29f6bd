"""The top-K step tap inside the training step (the reference's
``...AndTopK`` jobs): exact lists over every row, ranked by the tables the
step BEFORE left ("rank, then learn"), the same on one shard and on a
mesh, padding queries answered with the sentinel and counted, no ``cond``
at ``every=1``, its scopes, route and journal counts, and a trainer
without a tap left as it was.

Oracle: brute-force numpy ranking over the logical tables, float64.
"""

import dataclasses
import json
import re

import jax
import numpy as np
import pytest

import fps_tpu.ops as ops
from fps_tpu.core.driver import num_workers_of
from fps_tpu.core.ingest import epoch_chunks
from fps_tpu.models.matrix_factorization import MFConfig, online_mf
from fps_tpu.models.recommendation import (
    NEG_INF,
    make_online_topk_tap,
    mf_topk_query_fn,
    mf_user_vectors,
    topk_journal,
)
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.utils.datasets import synthetic_ratings

NU, NI, RANK, K, Q, B, T = 53, 37, 4, 6, 5, 8, 6


def build(devices, shards, data_axis=1, every=1, q=Q, lr=0.1, tap=True):
    mesh = make_ps_mesh(num_shards=shards, num_data=data_axis,
                        devices=devices[:shards * data_axis])
    W = num_workers_of(mesh)
    trainer, store = online_mf(
        mesh, MFConfig(num_users=NU, num_items=NI, rank=RANK,
                       learning_rate=lr, reg=0.01), donate=False)
    if tap:
        trainer.config = dataclasses.replace(
            trainer.config, step_tap=make_online_topk_tap(
                store, "item_factors", K, every=every,
                query_fn=mf_topk_query_fn(W, q)))
    return trainer, store, W


def chunk_of(W, steps=T, n=None, seed=0):
    data = synthetic_ratings(NU, NI, n or steps * B * W, seed=seed)
    return next(epoch_chunks(data, num_workers=W, local_batch=B,
                             steps_per_chunk=steps, route_key="user"))


def logical(store, tables, local_state, W):
    store.tables = dict(tables)
    items = store.lookup_host("item_factors", np.arange(NI))
    users = mf_user_vectors(np.asarray(local_state), W, np.arange(NU))
    return users.astype(np.float64), items.astype(np.float64)


def oracle(users, items, asked):
    scores = users[asked] @ items.T
    order = np.argsort(-scores, axis=1)[:, :K]
    return order, np.take_along_axis(scores, order, axis=1)


def step_by_step(trainer, store, W, chunk):
    """Run the chunk a step at a time. Yields ``(tap of the step, logical
    tables BEFORE it, logical tables AFTER it)``."""
    tables, ls = trainer.init_state(jax.random.key(0))
    for t in range(T):
        one = jax.tree.map(lambda x: x[t:t + 1], chunk)
        before = logical(store, tables, ls, W)
        tables, ls, m = trainer.run_chunk(tables, ls, one,
                                          jax.random.key(1))
        yield ({k: np.asarray(v)[0] for k, v in m["tap"].items()}, before,
               logical(store, tables, ls, W))


@pytest.mark.parametrize("shards,data_axis", [(1, 1), (4, 1), (2, 2)])
def test_lists_are_ranked_by_the_tables_the_step_before_left(
        devices8, shards, data_axis):
    """Prequential: with a learning rate that moves the tables every step,
    a step's lists are the oracle's over the tables BEFORE the step, and
    are NOT the oracle's over the tables after it."""
    trainer, store, W = build(devices8, shards, data_axis)
    chunk = chunk_of(W)
    lists = leaked = 0
    for tap, before, after in step_by_step(trainer, store, W, chunk):
        for w in range(W):
            live = tap["topk_query"][w] >= 0  # a short queue pads its tail
            if not live.any():
                continue
            asked = tap["topk_query"][w][live]
            want_ids, want_scores = oracle(*before, asked)
            np.testing.assert_array_equal(tap["topk_ids"][w][live], want_ids)
            np.testing.assert_allclose(tap["topk_scores"][w][live],
                                       want_scores, rtol=0, atol=1e-6)
            _, leak_scores = oracle(*after, asked)
            lists += 1
            leaked += int(np.abs(tap["topk_scores"][w][live]
                                 - leak_scores).max() > 1e-4)
    assert lists >= T * W // 2 and leaked == lists


def test_one_shard_and_a_mesh_answer_the_same_lists(devices8):
    """The same global stream on one device and on four shards: a user's
    list at a step is the same, id for id (the workers' batches differ,
    so lists are matched by the user asked for at steps where the tables
    agree: the first, from the same seeded state)."""
    lists = {}
    for shards in (1, 4):
        trainer, store, W = build(devices8, shards, lr=0.0)
        tables, ls = trainer.init_state(jax.random.key(0))
        if shards == 1:
            seeded = logical(store, tables, ls, W)
        _, _, m = trainer.run_chunk(tables, ls, chunk_of(W),
                                    jax.random.key(1))
        tap = {k: np.asarray(v) for k, v in m["tap"].items()}
        users = tap["topk_query"].reshape(-1)
        ids = tap["topk_ids"].reshape(-1, K)
        lists[shards] = {int(u): i for u, i in zip(users, ids) if u >= 0}
        # init_state draws the same logical tables whatever the mesh.
        np.testing.assert_array_equal(logical(store, tables, ls, W)[1],
                                      seeded[1])
    shared = set(lists[1]) & set(lists[4])
    assert len(shared) >= 10
    for u in shared:
        np.testing.assert_array_equal(lists[1][u], lists[4][u])


@pytest.mark.parametrize("shards", [1, 4])
def test_padding_queries_answer_the_sentinel_and_are_counted(devices8,
                                                             shards):
    """More queries than a short last step has live rows: the rows past
    them answer id -1 and NEG_INF and are counted per worker and step;
    the journal's counts are the call's."""
    trainer, store, W = build(devices8, shards, q=B)
    # Two and a half steps of data: the last step is half padding.
    chunk = chunk_of(W, steps=3, n=2 * B * W + (B // 2) * W)
    tables, ls = trainer.init_state(jax.random.key(0))
    _, _, m = trainer.run_chunk(tables, ls, chunk, jax.random.key(1))
    tap = {k: np.asarray(v) for k, v in m["tap"].items()}
    padding = tap["topk_query"] == -1
    live_rows = np.asarray(chunk["weight"]).reshape(3, W, B) > 0
    np.testing.assert_array_equal(padding, ~live_rows)
    assert padding.any() and not padding.all()
    assert (tap["topk_ids"][padding] == -1).all()
    assert (tap["topk_scores"][padding] == NEG_INF).all()
    assert (tap["topk_ids"][~padding] >= 0).all()
    np.testing.assert_array_equal(tap["topk_padding"], padding.sum(-1))
    assert topk_journal(tap) == {
        "topk_answered": int((~padding).sum()),
        "topk_padding": int(padding.sum())}


def test_more_queries_than_a_batch_has_rows_is_refused(devices8):
    trainer, store, W = build(devices8, 1, q=B + 1)
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="exceeds the worker's batch"):
        trainer.run_chunk(tables, ls, chunk_of(W), jax.random.key(1))


def _text(trainer, W):
    return trainer.lowered_chunk_text(chunk_of(W))


def _hlo(trainer, W):
    """The chunk program's StableHLO, ``(instruction, scope path)`` a
    line: the path is the instruction's ``fps.tap/topk.score/dot_general``
    from the text's table of locations."""
    from fps_tpu.core.driver import key_to_replicated

    tables, ls = trainer.init_state(jax.random.key(0))
    lowered = trainer._get_compiled("sync", True).lower(
        tables, ls, trainer._place_chunk(chunk_of(W), "sync"),
        key_to_replicated(jax.random.key(1), trainer.mesh))
    text = lowered.as_text(debug_info=True)
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"\(', text, re.M))
    return [(ln, paths.get(m.group(1), ""))
            for ln in text.splitlines()
            if (m := re.search(r"loc\((#loc\d+)\)$", ln))]


def test_every_one_lowers_no_cond(devices8):
    """``every=1`` ranks unconditionally: the program holds no conditional
    at all, where ``every=2`` holds the tap's."""
    always, _, W = build(devices8, 1, every=1)
    second, _, _ = build(devices8, 1, every=2)
    conditional = ("stablehlo.case", "stablehlo.if", "func.call @_cond")
    assert not any(c in _text(always, W) for c in conditional)
    assert any(c in _text(second, W) for c in conditional)


def test_a_trainer_without_a_tap_holds_nothing_of_it(devices8):
    """No tap: no ``fps.tap`` scope, no ranking, no route; and the program
    is the tap-bearing one less what stands under ``fps.tap`` (the tap
    reads the step's view and changes nothing the step computes: the
    tables and local state the two leave are the same to the bit)."""
    plain, store_p, W = build(devices8, 1, tap=False)
    tapped, store_t, _ = build(devices8, 1)
    ops.clear_routes()
    paths = [path for _, path in _hlo(plain, W)]
    assert any("fps.compute" in p for p in paths)
    assert not any("fps.tap" in p or "topk" in p for p in paths)
    assert not [r for r in ops.routes_traced() if r.op == "tap"]
    ops.clear_routes()
    paths = [path for _, path in _hlo(tapped, W)]
    for scope in ("fps.tap/", "fps.tap/topk.score/", "fps.tap/topk.select/"):
        assert any(scope in p for p in paths), scope
    assert not any("fps.metrics" in p and "fps.tap" in p for p in paths)
    assert [(r.route, r.rows, r.dim, r.ids, r.reason)
            for r in ops.routes_traced() if r.op == "tap"] == [
        ("tap.topk", NI, K, Q, "shards=1")]
    # The queries' user rows are read through the routed gather, under
    # fps.ops inside fps.tap.
    assert any("fps.tap/fps.ops/gather." in p for p in paths)
    chunk = chunk_of(W)
    outs = []
    for trainer in (plain, tapped):
        tables, ls = trainer.init_state(jax.random.key(0))
        tables, ls, m = trainer.run_chunk(tables, ls, chunk,
                                          jax.random.key(1))
        outs.append((np.asarray(tables["item_factors"]), np.asarray(ls),
                     np.asarray(m["se"])))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_scores_are_float32_at_highest_precision(devices8):
    """The product is asked for at ``precision=HIGHEST`` (the TPU's
    default rounds both operands to bfloat16): the lowered program says
    so on its dot."""
    trainer, _, W = build(devices8, 1)
    dots = [ln for ln, path in _hlo(trainer, W)
            if "stablehlo.dot_general" in ln and "topk.score" in path]
    assert len(dots) == 1
    assert "precision = [HIGHEST, HIGHEST]" in dots[0]
    assert "tensor<5x37xf32>" in dots[0]


def test_queries_are_ranked_in_blocks_of_bounded_size(devices8, monkeypatch):
    """With the score block's budget cut to a few rows of scores the
    queries go through in blocks (a loop in the program), and the lists
    are the same."""
    from fps_tpu.models import recommendation

    trainer, store, W = build(devices8, 4, q=B)
    chunk = chunk_of(W)
    tables, ls = trainer.init_state(jax.random.key(0))
    _, _, whole = trainer.run_chunk(tables, ls, chunk, jax.random.key(1))
    # Four shards gather 4 x 8 queries; 8 rows of scores fit the budget.
    monkeypatch.setattr(recommendation, "_SCORE_BLOCK_BYTES",
                        4 * 10 * 8)
    blocked, _, _ = build(devices8, 4, q=B)
    assert (blocked.lowered_chunk_text(chunk).count("stablehlo.while")
            == trainer.lowered_chunk_text(chunk).count("stablehlo.while") + 1)
    tables, ls = blocked.init_state(jax.random.key(0))
    _, _, parts = blocked.run_chunk(tables, ls, chunk, jax.random.key(1))
    for k in ("topk_ids", "topk_scores", "topk_query"):
        np.testing.assert_array_equal(np.asarray(whole["tap"][k]),
                                      np.asarray(parts["tap"][k]))


def test_journal_counts_land_on_the_epochs_event(devices8):
    """``run_indexed`` under a recorder: the epoch's journal event carries
    ``topk_answered`` and ``topk_padding`` beside ``examples``, and the
    counters ``tap.*`` hold the same."""
    from fps_tpu import DeviceDataset, DeviceEpochPlan, obs

    trainer, store, W = build(devices8, 1, q=B)
    data = synthetic_ratings(NU, NI, 5 * B + 3, seed=2)
    plan = DeviceEpochPlan(DeviceDataset(trainer.mesh, data), num_workers=W,
                           local_batch=B, route_key="user", seed=1)
    sink = obs.MemorySink(capacity=1 << 10)
    tables, ls = trainer.init_state(jax.random.key(0))
    _, _, metrics = trainer.run_indexed(
        tables, ls, plan, jax.random.key(1), epochs=1,
        on_epoch=lambda e, m: None,  # a syncing consumer: per-epoch fields
        recorder=obs.Recorder(sinks=[sink]))
    tap = metrics[0]["tap"]
    want = topk_journal(tap)
    steps = int(plan.steps_per_epoch)
    assert want["topk_answered"] == 5 * B + 3
    assert want["topk_padding"] == steps * B - (5 * B + 3)
    event, = sink.events("epoch")
    assert {k: event[k] for k in want} == want
    assert event["examples"] == 5 * B + 3
    counters = {e["name"]: e["value"] for e in sink.metrics()
                if e["name"].startswith("tap.")}
    assert counters == {f"tap.{k}": v for k, v in want.items()}


def test_megastep_hands_the_tap_the_pre_update_view(devices8):
    """The third step builder (``core/megastep.py``) too: its lists are
    ``run_indexed``'s, which are the oracle's over the pre-update
    tables."""
    from fps_tpu import DeviceDataset, DeviceEpochPlan

    data = synthetic_ratings(NU, NI, 6 * B, seed=3)
    outs = []
    for mega in (False, True):
        trainer, store, W = build(devices8, 1)
        plan = DeviceEpochPlan(DeviceDataset(trainer.mesh, data),
                               num_workers=W, local_batch=B,
                               route_key="user", seed=1)
        tables, ls = trainer.init_state(jax.random.key(0))
        if mega:
            before = logical(store, tables, ls, W)
            _, _, m = trainer.run_megastep(
                tables, ls, plan, jax.random.key(1), epochs=1,
                chunks_per_dispatch=1)
        else:
            _, _, m = trainer.run_indexed(tables, ls, plan,
                                          jax.random.key(1), epochs=1)
        outs.append({k: np.asarray(v) for k, v in m[0]["tap"].items()})
    for k in ("topk_ids", "topk_scores", "topk_query"):
        np.testing.assert_array_equal(outs[0][k], outs[1][k])
    asked = outs[1]["topk_query"][0, 0]
    live = asked >= 0
    want_ids, _ = oracle(*before, asked[live])
    np.testing.assert_array_equal(outs[1]["topk_ids"][0, 0][live], want_ids)


def test_mf_example_takes_topk_queries(devices8, capsys):
    """``examples/mf.py --topk K --topk-every 1 --topk-queries Q``: the
    configuration a user can start from the command line."""
    from fps_tpu.examples import mf

    rc = mf.main(["--epochs", "1", "--local-batch", "32",
                  "--steps-per-chunk", "4", "--scale", "100k", "--rank",
                  "4", "--topk", "3", "--topk-every", "1",
                  "--topk-queries", "5"])
    assert rc == 0
    events = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    online = [e for e in events if e["event"] == "topk_online"]
    assert online
    W = len(devices8)
    assert all(len(e["users"]) == 5 * W for e in online)
    assert all(len(e["items"]) == 5 * W and len(e["items"][0]) == 3
               for e in online)
    with pytest.raises(SystemExit, match="--topk-queries"):
        mf.main(["--topk", "3", "--topk-every", "1", "--local-batch", "8",
                 "--topk-queries", "9"])


def _tap_eqns(devices, num_items, k, q):
    """Every equation of the tap traced alone on one shard at a table of
    ``num_items`` rows, ``k`` and ``q`` (inner jaxprs included), and the
    tap's entries of the route log."""
    from jax.sharding import PartitionSpec as P

    from fps_tpu.parallel.mesh import SHARD_AXIS

    mesh = make_ps_mesh(num_shards=1, num_data=1, devices=devices[:1])
    _, store = online_mf(mesh, MFConfig(num_users=NU, num_items=num_items,
                                        rank=10), donate=False)
    tap = make_online_topk_tap(store, "item_factors", k, every=1,
                               query_fn=mf_topk_query_fn(1, q))
    f32 = np.float32
    tables = {"item_factors": jax.ShapeDtypeStruct((num_items, 10), f32)}
    batch = {"user": jax.ShapeDtypeStruct((q,), np.int32),
             "weight": jax.ShapeDtypeStruct((q,), f32)}
    ops.clear_routes()
    jaxpr = jax.make_jaxpr(jax.shard_map(
        lambda tb, b, ls: tap(tb, b, ls, 0), mesh=mesh,
        in_specs=({"item_factors": P(SHARD_AXIS, None)}, P(), P()),
        out_specs=P(), check_vma=False))(
            tables, batch, jax.ShapeDtypeStruct((NU, 10), f32))

    def walk(jp):
        for eqn in jp.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(jaxpr.jaxpr)), [
        (r.route, r.rows, r.dim, r.ids, r.reason)
        for r in ops.routes_traced() if r.op == "tap"]


def test_the_tap_prunes_at_the_cells_shape_and_not_at_a_small_table(devices8):
    """At ``mf-netflix-topk.epochs``' shape (17,770 rows, K 100, 256
    queries, one shard) the tap logs ``tap.topk`` once and
    ``tap.topk_pruned`` once with the chunking the rule gives; no
    ``top_k`` of its program reads more than the maxima or the fetched
    candidates (K out to whole lane tiles), and no gather reads a vector
    of the table's ids. At a small table it logs no pruning and hands all
    the rows to one ``top_k``."""
    from fps_tpu.models.recommendation import _LANES, _prune_plan

    rows, k, q = 17_770, 100, 256
    c, C = _prune_plan(rows, k)
    assert (c, C) == (16, 1152)
    eqns, routes = _tap_eqns(devices8, rows, k, q)
    assert routes == [("tap.topk", rows, k, q, "shards=1"),
                      ("tap.topk_pruned", rows, k, q, f"chunks={C}x{c}")]
    widths = sorted(e.invars[0].aval.shape[-1] for e in eqns
                    if e.primitive.name == "top_k")
    assert widths == [C, c * _LANES]
    assert max(widths) <= max(C, c * -(-k // _LANES) * _LANES) < rows // 2
    assert not [e for e in eqns if e.primitive.name == "gather"
                and e.invars[0].aval.shape == (rows,)]

    eqns, routes = _tap_eqns(devices8, NI, K, Q)
    assert _prune_plan(NI, K) is None
    assert routes == [("tap.topk", NI, K, Q, "shards=1")]
    assert [e.invars[0].aval.shape for e in eqns
            if e.primitive.name == "top_k"] == [(Q, NI)]
