"""DLRM's hybrid job against its plain reference, and the trainer's dense
route (``api.DenseLogic``) it stands on.

Tiny sizes on virtual CPU devices (fields of 3 to 5,000 rows, the
published layer pattern at narrow widths, 32 rows a worker a step). What
is checked is correctness and counts: the program against
``perfbench/lib/reference/dlrm_dot_sgd.py`` over one ``run_indexed`` epoch
on one worker, on four shards and on a 2 x 2 mesh, the field tables and
every MLP matrix; the 26-fields-as-one-key-space mapping against separate
tables; the dense route's graph (one all-reduce of the dense gradients a
step on a multi-worker mesh, none on one, no row route for a dense
parameter); that a logic without dense parameters lowers as it did; every
unsupported mode refused at construction by name; a checkpoint's save,
restore and bit-identical continuation. No rate is read.
"""

import copy
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fps_tpu
import fps_tpu.ops as ops
from fps_tpu import DeviceDataset, DeviceEpochPlan
from fps_tpu.core.api import DenseLogic, StepOutput
from fps_tpu.core.checkpoint import Checkpointer
from fps_tpu.core.driver import Trainer, TrainerConfig
from fps_tpu.core.store import dense_key, split_dense
from fps_tpu.models.dlrm import (
    EMB_TABLE, DLRMConfig, DLRMWorker, dlrm, make_store,
)
from fps_tpu.parallel.mesh import key_to_replicated, make_ps_mesh
from fps_tpu.utils.datasets import synthetic_click_fields
from perfbench.lib import check, resolve, spec, window

CELL = "dlrm-criteo.epochs"
CARDS = [5000, 3, 40, 300, 17, 1200]
B = 32
TINY_MODEL = {"embed_dim": 8, "bottom_mlp": [32, 16, 8, 8],
              "top_mlp": [32, 16, 1], "local_batch": B,
              "field_offsets": [0, 5000, 5003, 5043, 5343, 5360]}
TINY_DATA = {"categorical_cardinalities": CARDS, "categorical_columns": 6,
             "examples_resident": 1203}
MESHES = {"one": (1, 1), "shards4": (4, 1), "data2_shards2": (2, 2)}
# float32 on both sides; what differs is the order of sums (an id's pushes
# in scatter order against the reference's, the workers' dense gradients
# by psum against one jax.grad over the global batch).
F32_GAP = 5e-5


def tiny_cfg(**model):
    cfg = copy.deepcopy(spec.load_cell(spec.load_benchmark(), CELL)["config"])
    cfg["model"].update(TINY_MODEL, **model)
    cfg["data"].update(TINY_DATA)
    return cfg


def model_config(cfg=None) -> DLRMConfig:
    m = (cfg or tiny_cfg())["model"]
    return DLRMConfig(field_rows=CARDS, embed_dim=m["embed_dim"],
                      numeric=m["numeric"], bottom_mlp=m["bottom_mlp"],
                      top_mlp=m["top_mlp"], learning_rate=m["learning_rate"])


def build(kind, monkeypatch, seed=7):
    """The cell's adapter at the tiny size on ``kind``'s mesh (the adapter
    builds its mesh by ``fps_tpu.make_ps_mesh()``: hand it that shape over
    the first devices)."""
    cfg = tiny_cfg()
    traffic = spec.load_traffic("epochs")
    shards, replicas = MESHES[kind]
    devices = jax.devices()[:shards * replicas]
    monkeypatch.setattr(
        fps_tpu, "make_ps_mesh",
        lambda: make_ps_mesh(num_shards=shards, num_data=replicas,
                             devices=devices))
    data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    init = resolve.reference(cfg).init_tables(seed, cfg)
    return cfg, system, init, data_sum


@pytest.fixture(scope="module")
def compared():
    """``{mesh kind: check.compare's numbers}`` of one epoch against the
    reference, each mesh built once."""
    out = {}
    mp = pytest.MonkeyPatch()
    try:
        for kind in MESHES:
            cfg, system, init, data_sum = build(kind, mp)
            state, warm = window.queue_call(system, system.place(init))
            warm.wait()
            out[kind], _ = check.compare_call(
                system, cfg, init, system.export(*state), warm.host, data_sum)
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("kind", sorted(MESHES))
def test_program_matches_reference(devices8, compared, kind):
    """Loss of every step, every field's table and every MLP matrix and
    bias after an epoch of 38 steps (10 on four workers); every example
    fed exactly once."""
    numbers = compared[kind]
    assert numbers["examples"] == 0 and numbers["feed"] == 0
    tables = [f"emb_{f:02d}" for f in range(len(CARDS))] + sorted(
        model_config().layer_shapes())
    for name in tables:
        for gap in ("table_gap", "update_gap"):
            assert numbers[f"{gap}.{name}"] < F32_GAP, (kind, gap, name)
    assert numbers["loss_gap"] < F32_GAP
    # The MLPs did move (an unchanged state reads update_gap 1).
    assert len(numbers) == 3 + 2 * len(tables)


@pytest.mark.parametrize("kind", ["one", "shards4"])
def test_one_key_space_is_the_fields_laid_end_to_end(devices8, monkeypatch,
                                                     kind):
    """``place`` lays the reference's separate field tables into the one
    table and ``export`` cuts them out again bit for bit; a step's ids are
    ``offset[f] + token`` and read the row the separate table holds."""
    cfg, system, init, _ = build(kind, monkeypatch)
    tables, local_state = system.place(init)
    out = system.export(tables, local_state)
    for name, rows in init.items():
        np.testing.assert_array_equal(out[name], np.asarray(rows))
    logic = system.trainer.logic
    tokens = np.array([[4999, 2, 0, 299, 16, 1199], [0, 0, 39, 1, 3, 7]],
                      np.int32)
    ids = np.asarray(logic.pull_ids({"tokens": jnp.asarray(tokens)})[
        EMB_TABLE]).reshape(tokens.shape)
    system.store.tables = dict(tables)
    got = system.store.lookup_host(EMB_TABLE, ids.reshape(-1)).reshape(
        tokens.shape + (8,))
    for f in range(len(CARDS)):
        np.testing.assert_array_equal(
            got[:, f], np.asarray(init[f"emb_{f:02d}"])[tokens[:, f]])
    assert ids.max() == sum(CARDS) - 1 and ids.min() == 0


# -- the dense route's graph ------------------------------------------------

def lowered_chunk(shards, data=1):
    """A two-step chunk program over ``shards x data`` devices, lowered
    with locations; its route log."""
    mcfg = model_config()
    mesh = make_ps_mesh(num_shards=shards, num_data=data,
                        devices=jax.devices()[:shards * data])
    trainer, _ = dlrm(mesh, mcfg)
    W = shards * data
    rows = synthetic_click_fields(2 * B * W, CARDS, seed=3)
    chunk = {k: v.reshape((2, B * W) + v.shape[1:]) for k, v in rows.items()}
    chunk["weight"] = np.ones((2, B * W), np.float32)
    tables, ls = trainer.init_state(jax.random.key(0))
    ops.clear_routes()
    lowered = trainer._get_compiled("sync").lower(
        tables, ls, trainer._place_chunk(chunk),
        key_to_replicated(jax.random.key(1), mesh))
    return lowered.as_text(debug_info=True), ops.routes_traced(), trainer


def all_reduces_under(text, scope):
    """The ``all_reduce`` ops of a lowered text whose location lies under
    ``scope``: the element count of each one's operand."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found = []
    for m in re.finditer(
            r'"stablehlo\.all_reduce"\(.*?\}\) : \(tensor<(\d+)xf32>\) -> '
            r'[^\n]*? loc\((#loc\d+)\)', text, re.S):
        if f"/{scope}/" in "/" + names.get(m.group(2), "") + "/":
            found.append(int(m.group(1)))
    return found


@pytest.mark.parametrize("shards,data", [(4, 1), (2, 2)])
def test_one_all_reduce_of_the_dense_gradients_a_step(devices8, shards, data):
    text, routes, _ = lowered_chunk(shards, data)
    assert all_reduces_under(text, "fps.dense") == [sum(
        int(np.prod(s)) for s in model_config().layer_shapes().values())]
    # (the step's other all-reduces are its metrics', scalars)
    assert "stablehlo.all_reduce" in text.split("fps.dense")[0]


def test_no_all_reduce_on_one_worker(devices8):
    text, _, _ = lowered_chunk(1)
    assert "fps.dense" in text
    assert all_reduces_under(text, "fps.dense") == []


@pytest.mark.parametrize("shards", [1, 4])
def test_dense_parameters_take_no_row_route(devices8, shards):
    """One ``dense.psum_sgd`` entry holding all 14 arrays' parameters; every
    gather and scatter-add logged is ``emb``'s."""
    _, routes, trainer = lowered_chunk(shards)
    mcfg = model_config()
    dense = [r for r in routes if r.op == "dense"]
    assert [(r.route, r.rows, r.dim, r.ids, r.reason) for r in dense] == [
        ("dense.psum_sgd",
         sum(int(np.prod(s)) for s in mcfg.layer_shapes().values()), 1, 0,
         f"workers={shards}")]
    rowops = [r for r in routes if r.op in ("gather", "scatter_add")]
    assert {r.op for r in rowops} == {"gather", "scatter_add"}
    # (a table this small trades its push by the dense exchange on four
    # shards, whose scatter is into all the table's rows)
    rps = -(-mcfg.num_rows // shards)
    assert {r.dim for r in rowops} == {mcfg.embed_dim}
    assert {r.rows for r in rowops} <= {rps, rps * shards}
    assert trainer._dense_fields() == {
        "dense_params": dense[0].rows, "dense_bytes": 4 * dense[0].rows}


def test_a_logic_without_dense_parameters_lowers_without_the_route(devices8):
    """No ``fps.dense`` scope, no dense entry in the tables dict or the
    route log, and the carry's extra slot holds no array."""
    from fps_tpu.models.logistic_regression import (
        LogRegConfig, logistic_regression,
    )
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    mesh = make_ps_mesh(devices=jax.devices()[:1])
    trainer, _ = logistic_regression(mesh, LogRegConfig(num_features=64))
    assert trainer.logic.dense is None and trainer._dense_specs() == {}
    rows = synthetic_sparse_classification(2 * B, 64, 4, seed=1)
    chunk = {k: v.reshape((2, B) + v.shape[1:]) for k, v in rows.items()}
    chunk["weight"] = np.ones((2, B), np.float32)
    tables, ls = trainer.init_state(jax.random.key(0))
    assert split_dense(tables)[1] == {}
    ops.clear_routes()
    text = trainer._get_compiled("sync").lower(
        tables, ls, trainer._place_chunk(chunk),
        key_to_replicated(jax.random.key(1), mesh)).as_text(debug_info=True)
    assert "fps.dense" not in text
    assert not [r for r in ops.routes_traced() if r.op == "dense"]


# -- refused at construction ------------------------------------------------

def _tap(tables, batch, local_state, t):
    return {}


@pytest.mark.parametrize("config,named", [
    (dict(sync_every=2), "sync_every"),
    (dict(push_delay=1), "push_delay"),
    (dict(step_tap=_tap), "step_tap"),
    (dict(guard="mask"), "guard"),
    (dict(guard="observe"), "guard"),
    (dict(hot_sync_every=4), "hot_sync_every"),
    (dict(auto_tier=True), "auto_tier"),
])
def test_unsupported_modes_are_refused_by_name(devices8, config, named):
    mcfg = model_config()
    mesh = make_ps_mesh(devices=jax.devices()[:2])
    with pytest.raises(ValueError) as e:
        Trainer(mesh, make_store(mesh, mcfg), DLRMWorker(mcfg),
                config=TrainerConfig(**config))
    assert "dense parameters" in str(e.value) and "bot_w0" in str(e.value)
    assert named in str(e.value)


def test_a_tiered_table_is_refused(devices8):
    mcfg = model_config()
    mesh = make_ps_mesh(devices=jax.devices()[:2])
    store = make_store(mesh, mcfg)
    store.specs[EMB_TABLE] = dataclasses.replace(store.specs[EMB_TABLE],
                                                 hot_tier=16)
    with pytest.raises(ValueError, match="hot_tier"):
        Trainer(mesh, store, DLRMWorker(mcfg))


def test_the_megastep_refuses_dense_parameters(devices8):
    mcfg = model_config()
    mesh = make_ps_mesh(devices=jax.devices()[:1])
    trainer, _ = dlrm(mesh, mcfg, max_steps_per_call=2)
    rows = synthetic_click_fields(8 * B, CARDS, seed=3)
    plan = DeviceEpochPlan(DeviceDataset(mesh, rows), num_workers=1,
                           local_batch=B)
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="dense parameters.*top_w2"):
        trainer.run_megastep(tables, ls, plan, jax.random.key(1),
                             chunks_per_dispatch=2)


def test_a_step_that_returns_no_dense_gradients_is_refused(devices8):
    class Forgetful(DLRMWorker):
        def step(self, *a, **k):
            return dataclasses.replace(super().step(*a, **k),
                                       dense_grads=None)

    mcfg = model_config()
    mesh = make_ps_mesh(devices=jax.devices()[:1])
    trainer = Trainer(mesh, make_store(mesh, mcfg), Forgetful(mcfg))
    rows = synthetic_click_fields(2 * B, CARDS, seed=3)
    chunk = {k: v.reshape((2, B) + v.shape[1:]) for k, v in rows.items()}
    chunk["weight"] = np.ones((2, B), np.float32)
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="dense_grads"):
        trainer.run_chunk(tables, ls, chunk, jax.random.key(1))


def test_dense_names_may_not_hold_the_separator(devices8):
    class Named(DLRMWorker):
        def __init__(self, cfg):
            super().__init__(cfg)
            self.dense = DenseLogic(
                init_fn=lambda key: {"a::b": jnp.zeros((2,))},
                learning_rate=0.1)

    mcfg = model_config()
    mesh = make_ps_mesh(devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="'::'"):
        Trainer(mesh, make_store(mesh, mcfg), Named(mcfg))


# -- checkpoint: save, restore, continue bit for bit -------------------------

@pytest.mark.parametrize("shards", [1, 4])
def test_checkpoint_continues_bit_for_bit(devices8, tmp_path, shards):
    """Two epochs straight against one epoch, a snapshot, a FRESH trainer
    restored from it and the second epoch: the table and every dense
    parameter equal bit for bit; the snapshot holds ``dense::`` arrays."""
    mcfg = model_config()
    rows = synthetic_click_fields(6 * B * shards, CARDS, seed=5)
    key = jax.random.key(11)

    def fresh():
        mesh = make_ps_mesh(num_shards=shards,
                            devices=jax.devices()[:shards])
        trainer, store = dlrm(mesh, mcfg)
        plan = DeviceEpochPlan(DeviceDataset(mesh, rows), num_workers=shards,
                               local_batch=B, seed=2)
        return trainer, store, plan, trainer.init_state(jax.random.key(0))

    trainer, _, plan, (tables, ls) = fresh()
    straight, _, _ = trainer.run_indexed(tables, ls, plan, key, epochs=2)
    straight = {k: np.asarray(v) for k, v in straight.items()}

    ck = Checkpointer(str(tmp_path))
    trainer, _, plan, (tables, ls) = fresh()
    tables, ls, _ = trainer.run_indexed(tables, ls, plan, key, epochs=1,
                                        checkpointer=ck, checkpoint_every=1)
    with np.load(ck._path(1)) as z:
        saved = sorted(k for k in z.files if k.startswith("dense::"))
    assert saved == sorted("dense::" + k for k in mcfg.layer_shapes())

    trainer, store, plan, (tables, ls) = fresh()
    moved = np.asarray(tables[dense_key("top_w0")])
    tables, ls, step = trainer.restore_checkpoint(ck, ls)
    assert step == 1
    assert not np.array_equal(np.asarray(tables[dense_key("top_w0")]), moved)
    resumed, _, _ = trainer.run_indexed(tables, ls, plan, key, epochs=1,
                                        start_epoch=1)
    assert set(resumed) == set(straight)
    for name, want in straight.items():
        np.testing.assert_array_equal(np.asarray(resumed[name]), want,
                                      err_msg=name)


def test_worker_step_output_keeps_its_old_shape(devices8):
    """``dense_grads`` defaults to ``None``: every other logic's
    ``StepOutput(pushes, local_state, out)`` stands."""
    out = StepOutput(pushes={}, local_state=(), out={})
    assert out.dense_grads is None


@pytest.mark.parametrize("kind", sorted(MESHES))
def test_sum_runs_counts_arrive_with_a_call_nobody_fetches(devices8,
                                                           monkeypatch, kind):
    """With ``push.sum_runs`` engaged (its regime's constants patched so
    that the tiny key space takes it, the ops layer routing as on the
    chip), the runner's own call, which
    fetches nothing, still matches the reference, and what the pushes
    counted reaches the ``device.run_indexed`` span's ``sum_runs`` field
    and the recorder's ``sum_runs.*`` counters when the call's deferred
    metrics arrive: every step's pushes (6 fields a row, every worker
    together, counted once whatever the mesh) and the distinct ids among
    them, plain per-step leaves of the metrics beside the worker's own."""
    from fps_tpu import obs
    from fps_tpu.obs import events

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    monkeypatch.setattr(ops, "XLA_TRANSPOSED_HBM_ROWS", 1_000)
    monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", 16)
    monkeypatch.setattr(ops, "DENSE_TABLE_BYTES", 0)   # a LARGE table's way
    cfg, system, init, data_sum = build(kind, monkeypatch)
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    events.set_default_recorder(rec)
    ops.clear_routes()
    try:
        state, warm = window.queue_call(system, system.place(init))
        warm.wait()
    finally:
        events.set_default_recorder(None)   # waits for the span
    rec.flush()
    shards, replicas = MESHES[kind]
    pushes = [(r.route, r.dim) for r in ops.routes_traced()
              if r.route == "push.sum_runs"]
    # once a traced step program; on lanes once a branch of the certificate
    assert pushes and set(pushes) == {("push.sum_runs", 8)}
    numbers, _ = check.compare_call(system, cfg, init,
                                    system.export(*state), warm.host,
                                    data_sum)
    assert numbers["examples"] == 0 and numbers["feed"] == 0
    assert max(v for k, v in numbers.items()
               if k.startswith(("table_gap", "update_gap", "loss_gap"))
               ) < F32_GAP
    (span,) = [e for e in sink.events("span")
               if e["span"] == "device.run_indexed"]
    (m,) = warm.host
    pushed = np.asarray(m[f"sum_runs.{EMB_TABLE}.pushed_ids"], np.float64)
    live = np.asarray(m[f"sum_runs.{EMB_TABLE}.live_ids"], np.float64)
    n = np.asarray(m["n"], np.float64)
    np.testing.assert_array_equal(pushed, len(CARDS) * n)
    assert (live <= pushed).all() and (live[n > 0] > 0).all()
    assert live.sum() < 0.9 * pushed.sum()   # the fields of 3 and 17 rows
    assert span["sum_runs"] == {EMB_TABLE: {
        "pushed_ids": pushed.sum(), "live_ids": live.sum()}}
    for k, v in span["sum_runs"][EMB_TABLE].items():
        assert rec.counter_value(f"sum_runs.{k}", table=EMB_TABLE) == v


@pytest.mark.parametrize("kind", sorted(MESHES) + ["another_cells_shapes"])
def test_distinct_pull_counts_arrive_with_a_call_nobody_fetches(
        devices8, monkeypatch, kind):
    """With ``pull.distinct_rows`` engaged as ``push.sum_runs`` is above
    (the same regime, the same patched constants), the runner's own call
    still matches the reference (the pulled rows are the plain pull's bit
    for bit), its program logs ``pull.distinct_rows`` and what the pulls
    counted reaches the ``device.run_indexed`` span's ``distinct_pulls``
    field and the recorder's ``distinct_pulls.*`` counters: every step's
    pulled ids (6 fields a row, every worker's own together, whatever the
    mesh) and the distinct ids among them. With the constants as they are
    (a table of every other cell's kind: too few rows) the program logs no
    such route, grows no leaf and the span carries no such field."""
    from fps_tpu import obs
    from fps_tpu.obs import events

    engaged = kind in MESHES
    if engaged:
        monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
        monkeypatch.setattr(ops, "XLA_TRANSPOSED_HBM_ROWS", 1_000)
        monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", 16)
        monkeypatch.setattr(ops, "DENSE_TABLE_BYTES", 0)  # a LARGE table's way
    cfg, system, init, data_sum = build(kind if engaged else "one",
                                        monkeypatch)
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    events.set_default_recorder(rec)
    ops.clear_routes()
    try:
        state, warm = window.queue_call(system, system.place(init))
        warm.wait()
    finally:
        events.set_default_recorder(None)   # waits for the span
    rec.flush()
    pulls = {(r.route, r.dim, r.reason) for r in ops.routes_traced()
             if r.route == "pull.distinct_rows"}
    (span,) = [e for e in sink.events("span")
               if e["span"] == "device.run_indexed"]
    (m,) = warm.host
    if not engaged:
        assert not pulls and "distinct_pulls" not in span
        assert not [k for k in m if k.startswith("distinct_pulls.")]
        return
    # once a traced step program; on lanes once a branch of the certificate
    assert pulls == {("pull.distinct_rows", 8, "xla_transposed_hbm")}
    numbers, _ = check.compare_call(system, cfg, init,
                                    system.export(*state), warm.host,
                                    data_sum)
    assert numbers["examples"] == 0 and numbers["feed"] == 0
    assert max(v for k, v in numbers.items()
               if k.startswith(("table_gap", "update_gap", "loss_gap"))
               ) < F32_GAP
    pulled = np.asarray(m[f"distinct_pulls.{EMB_TABLE}.pulled_ids"],
                        np.float64)
    live = np.asarray(m[f"distinct_pulls.{EMB_TABLE}.live_ids"], np.float64)
    n = np.asarray(m["n"], np.float64)
    # A padded example pulls its ids like any other (and pushes none).
    workers = MESHES[kind][0] * MESHES[kind][1]
    np.testing.assert_array_equal(pulled, len(CARDS) * B * workers)
    assert (pulled >= len(CARDS) * n).all()
    assert (live <= pulled).all() and (live > 0).all()
    assert live.sum() < 0.9 * pulled.sum()   # the fields of 3 and 17 rows
    assert span["distinct_pulls"] == {EMB_TABLE: {
        "pulled_ids": pulled.sum(), "live_ids": live.sum()}}
    for k, v in span["distinct_pulls"][EMB_TABLE].items():
        assert rec.counter_value(f"distinct_pulls.{k}", table=EMB_TABLE) == v
