"""The benchmark cell ``kge-wikidata5m.epochs`` rehearsed on the CPU, and
the files it is made of.

Tiny sizes (16,384 entities of 12 floats, 11 relations, 3 corruptions, 32
positives a step) on one virtual device, as the cell has one chip. What is
checked is correctness and counts: the runner's whole path is ``correct``
against ``complex_adagrad`` under the COMMITTED limits with the entity
table on its sparse fold; each control (bfloat16, the fold's state dropped)
and each broken timed path is not; ``spec.validate`` on the committed
files; the ``rowops`` count against the step's own ids; the data kind's
shape; the new metrics' readers on a made-up trace. No rate is read: a
CPU run has none.
"""

import contextlib
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fps_tpu.ops as ops
from perfbench.lib import check, readers, resolve, runner, spec
from perfbench.lib import trace_reduce as tr

CELL = "kge-wikidata5m.epochs"
TINY = {"model": {"entities": 16384, "relations": 11, "rank": 6,
                  "negatives": 3, "local_batch": 32, "export_blocks": 4},
        "data": {"entities": 16384, "relations": 11,
                 "triples_resident": 1000}}
BLOCKS = [f"entity_{b:02d}" for b in range(16)]
NAMES = BLOCKS + [b.replace("entity", "entity_acc") for b in BLOCKS] + [
    "relation", "relation_acc"]
TINY_NAMES = [n for n in NAMES if not n[-2:].isdigit() or int(n[-2:]) < 4]


def tiny_cell(**model):
    loaded = spec.load_cell(spec.load_benchmark(), CELL)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY.items():
        cfg[part].update(over)
    cfg["model"].update(model)
    loaded["config"] = cfg
    return loaded


@contextlib.contextmanager
def one_device():
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:1]
    try:
        yield
    finally:
        jax.devices = real


def run(seed=2_500_000_011):
    events = []
    ops.clear_routes()
    with one_device():
        result = runner.run_cell(
            tiny_cell(), seed=seed, seconds=0.3, trace=False,
            t_start=time.perf_counter(),
            emit=lambda event, **f: events.append(dict(event=event, **f)),
            out_dir="unused")
    return result, events


# -- the committed files ----------------------------------------------------

def test_spec_validates_the_committed_benchmark_files():
    bench = spec.load_benchmark()
    spec.validate(bench)
    cell = spec.load_cell(bench, CELL)
    assert cell["cell"]["chips"] == 1 and cell["cell"]["traffic"] == "epochs"
    assert {"store.fold_rows_ms_per_step", "store.fold_rows_in_program",
            "store.fold_touched_percent", "worker.score_ms_per_step",
            "worker.prepare_ms_per_step", "store.fold_pushes_in_program",
            "kernel.rowop_roofline", "kernel.rowop_ns_per_row",
            "kernel.xla_gather_ms_per_step", "kernel.sorted_scatter_ms_per_step",
            "device.peak_hbm_gb", "device.idle_share"} <= set(cell["readers"])
    for m in bench["per_layer"]:
        if m["name"].startswith(("store.fold_rows", "store.fold_touched",
                                 "worker.score")):
            assert m["workloads"] == [CELL] and m["moves"] == "examples_per_s"
    entry = next(c for c in bench["configs"] if c["name"] == "kge-wikidata5m")
    assert entry["reduced"] == ["entities", "triples_resident"]
    assert len(entry["source"]) <= 200 and len(cell["cell"]["why"]) <= 200
    # The newest cell and configuration stand last: nothing was put before.
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "kge-wikidata5m"


def test_the_configuration_keeps_the_sources_shapes_and_cuts_scale_alone():
    cfg = spec.load_cell(spec.load_benchmark(), CELL)["config"]
    m, d = cfg["model"], cfg["data"]
    assert cfg["reduced"] == ["entities", "triples_resident"]
    assert (m["rank"], m["row_floats"], m["relations"], m["negatives"],
            m["learning_rate"], m["eps"], m["l2"], m["init_std"],
            m["local_batch"], m["dtype"]) == (
        500, 1000, 822, 10, 0.1, 1e-8, 1e-5, 0.1, 4096, "float32")
    assert m["entities"] == d["entities"] == 393_216 < m[
        "published_entities"] == 4_594_485
    assert d["triples_resident"] == 2**20 < d["published_triples"] == 20_614_279
    assert d["relations"] == 822 and d["entity_zipf"] == d[
        "relation_zipf"] == 1.05
    assert m["entities"] % m["export_blocks"] == 0
    assert cfg["reference"] == "complex_adagrad" and cfg["quality"] is None
    assert set(cfg["limits"]) == {"examples", "feed", "loss_gap"} | {
        f"{gap}.{name}" for gap in ("table_gap", "update_gap")
        for name in NAMES}
    assert cfg["limits"]["examples"] == cfg["limits"]["feed"] == 0
    for key in ("entities", "triples_resident", "negatives", "l2",
                "learning_rate", "initialisation", "local_batch", "triples",
                "complex_storage"):
        assert key in cfg["assumed"], key
    said = " ".join(cfg["guarantees"])
    for word in ("float32", "bit for bit", "once an epoch", "non-finite",
                 "step that made it"):
        assert word in said, word


def test_rowops_are_the_steps_own_counts():
    """The committed count against the ids the worker itself pulls and
    pushes at the cell's shapes (abstractly: nothing of that size runs)."""
    from fps_tpu.models.kge import KGEConfig, KGEWorker

    cfg = spec.load_cell(spec.load_benchmark(), CELL)["config"]
    m = cfg["model"]
    logic = KGEWorker(KGEConfig(num_entities=m["entities"],
                                num_relations=m["relations"], rank=m["rank"],
                                negatives=m["negatives"]))
    B = m["local_batch"]
    column = jax.ShapeDtypeStruct((B,), jnp.int32)
    batch = {"s": column, "r": column, "o": column,
             "weight": jax.ShapeDtypeStruct((B,), jnp.float32)}
    ids = jax.eval_shape(
        lambda b, k: logic.pull_ids(logic.prepare(b, k)), batch,
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
    pulled = sum(int(np.prod(v.shape)) for v in ids.values())
    assert {k: v.shape for k, v in ids.items()} == {
        "entity": (49_152,), "relation": (4_096,)}
    row = 4 * m["row_floats"]
    assert cfg["rowops"]["rows_per_worker_step"] == 2 * pulled == 106_496
    # a pulled row once; a pushed id's row and its accumulator, each once
    assert cfg["rowops"]["bytes_per_worker_step"] == (
        pulled * row + pulled * 2 * row) == 638_976_000
    assert cfg["rowops"]["row_bytes"] == (
        cfg["rowops"]["bytes_per_worker_step"]
        // cfg["rowops"]["rows_per_worker_step"])


# -- the data kind ----------------------------------------------------------

def test_rows_are_three_columns_of_ids_zipf_over_their_ranges():
    cfg = tiny_cell()["config"]
    data, data_sum = resolve.generator(cfg)(11, cfg["data"])
    n = cfg["data"]["triples_resident"]
    assert {k: (v.shape, v.dtype) for k, v in data.items()} == {
        k: ((n,), np.dtype("int32")) for k in "sro"}
    for k, top in (("s", 16384), ("o", 16384), ("r", 11)):
        assert 0 <= data[k].min() and data[k].max() < top
        assert np.bincount(data[k]).argmax() == 0       # rank = id
    assert not np.array_equal(data["s"], data["o"])     # drawn apart
    again, again_sum = resolve.generator(cfg)(11, cfg["data"])
    assert again_sum == data_sum
    np.testing.assert_array_equal(again["s"], data["s"])
    assert resolve.generator(cfg)(12, cfg["data"])[1] != data_sum


# -- the runner's whole path, sound and broken ------------------------------

def test_cell_rehearsal_runs_the_runners_whole_path():
    result, events = run()
    compared = [e for e in events if e["event"] == "compared"]
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "examples_per_s"}
    want = {"examples", "feed", "loss_gap", "programs_lowered_in_window"} | {
        f"{gap}.{name}" for gap in ("table_gap", "update_gap")
        for name in TINY_NAMES}
    assert {e["number"] for e in compared} == want
    routes = {(r.route, r.rows, r.reason) for r in ops.routes_traced()
              if r.op == "push"}
    assert routes == {("push.fold_rows", 16384, ""),
                      ("push.fold", 11, "small_table")}
    readings = next(e for e in events if e["event"] == "readings")
    assert readings["window_examples"] == 1000 * readings["n"]


def _skip_updates(system):
    """The call hands back the state it was given (metrics still flow)."""
    real = system.trainer.run_indexed

    def broken(tables, local_state, *a, **kw):
        _, _, metrics = real(jax.tree.map(jnp.copy, tables), local_state,
                             *a, **kw)
        return tables, local_state, metrics

    system.trainer.run_indexed = broken


def _drop_part_of_the_batch(system):
    real = system.plan.local_batch_at

    def broken(args, w, t):
        batch = real(args, w, t)
        half = batch["weight"].shape[0] // 2
        return dict(batch, weight=batch["weight"].at[half:].set(0.0))

    system.plan.local_batch_at = broken


@pytest.mark.parametrize("break_system", [
    _skip_updates, _drop_part_of_the_batch])
def test_broken_timed_path_is_not_correct(break_system, monkeypatch):
    real = resolve.system_class

    def broken_class(cfg, traffic):
        def build(*a, **kw):
            system = real(cfg, traffic)(*a, **kw)
            break_system(system)
            return system
        return build

    monkeypatch.setattr(resolve, "system_class", broken_class)
    result, events = run()
    assert result["correct"] is False, [
        e for e in events if e["event"] == "compared"]


def test_a_program_that_drops_the_folds_state_is_not_correct(monkeypatch):
    """The fold applied against a zero accumulator every step, none written
    back (every step is AdaGrad's first: plain sign steps): refused, by the
    accumulators' ``update_gap`` (1: a state left unchanged) and the rows'
    ``table_gap``."""
    from fps_tpu.core import store as store_mod

    real = store_mod.apply_hot_fold

    def stateless(fold, state, g, counts):
        step, _ = real(fold, jnp.zeros_like(state), g, counts)
        return step, state

    monkeypatch.setattr(store_mod, "apply_hot_fold", stateless)
    result, events = run()
    compared = {e["number"]: e for e in events if e["event"] == "compared"}
    assert result["correct"] is False
    assert compared["update_gap.entity_acc_00"]["value"] == pytest.approx(1.0)
    assert not compared["update_gap.relation_acc"]["within"]
    assert not compared["table_gap.entity_00"]["within"]
    assert compared["examples"]["within"] and compared["feed"]["within"]


@pytest.fixture(scope="module")
def replayed():
    """The tiny cell's system, the seeded tables and the sound reference's
    replay of the first call, made once for the controls."""
    loaded = tiny_cell()
    cfg, traffic = loaded["config"], loaded["traffic"]
    with one_device():
        data, data_sum = resolve.generator(cfg)(5, cfg["data"])
        system = resolve.system_class(cfg, traffic)(cfg, traffic, data, 5)
    init = resolve.reference(cfg).init_tables(5, cfg)
    return cfg, system, init, data_sum, check.run_reference(system, cfg, init)


@pytest.mark.parametrize("control", ["bf16", "drop_state"])
def test_every_control_fails_a_committed_limit(replayed, control):
    """``perfbench/kge_controls.py``'s two: the reference with one thing
    wrong, put in the program's place, is refused by the limits the
    configuration file commits (and the sound reference itself is not)."""
    from perfbench.kge_controls import CONTROLS, passes_every_limit

    cfg, system, init, data_sum, (ref, ref_loss, ref_n, feed) = replayed
    limits = {k: v for k, v in cfg["limits"].items()
              if k.split(".")[-1] in TINY_NAMES or "." not in k}
    wrong = copy.deepcopy(cfg)
    wrong["model"].update(CONTROLS[control])
    tables, loss, n, wrong_feed = check.run_reference(
        system, wrong, init,
        dtype=jnp.bfloat16 if control == "bf16" else None)
    numbers = check.compare(
        {k: np.asarray(v, np.float32) for k, v in tables.items()}, ref, init,
        loss, n, ref_loss, ref_n, wrong_feed,
        check.call_checksum(system, data_sum), system.examples_per_call)
    assert numbers["examples"] == 0 and numbers["feed"] == 0
    assert not passes_every_limit(numbers, limits), numbers
    sound = check.compare(ref, ref, init, ref_loss, ref_n, ref_loss, ref_n,
                          feed, check.call_checksum(system, data_sum),
                          system.examples_per_call)
    assert passes_every_limit(sound, limits)
    if control == "drop_state":
        # A state left unchanged reads 1; the rows move another way.
        assert numbers["update_gap.entity_acc_00"] == pytest.approx(1.0)
        assert numbers["update_gap.relation_acc"] == pytest.approx(1.0)
        assert numbers["table_gap.entity_00"] > cfg["limits"][
            "table_gap.entity_00"]
        assert numbers["update_gap.entity_00"] > cfg["limits"][
            "update_gap.entity_00"]


def test_the_reversed_reference_is_within_every_committed_limit(replayed):
    """``kge_controls.py``'s floor: the reference on each step's positives
    in the opposite order differs from itself by float32 rounding alone,
    and the committed limits admit it (a limit under it would refuse sound
    programs)."""
    from perfbench.kge_controls import FLOORS, passes_every_limit

    cfg, system, init, data_sum, (ref, ref_loss, ref_n, feed) = replayed
    limits = {k: v for k, v in cfg["limits"].items()
              if k.split(".")[-1] in TINY_NAMES or "." not in k}
    other = copy.deepcopy(cfg)
    other["model"].update(FLOORS["reversed"])
    tables, loss, n, other_feed = check.run_reference(system, other, init)
    numbers = check.compare(
        tables, ref, init, loss, n, ref_loss, ref_n, other_feed,
        check.call_checksum(system, data_sum), system.examples_per_call)
    assert passes_every_limit(numbers, limits), numbers
    gaps = [v for k, v in numbers.items() if "_gap" in k]
    assert 0 < max(gaps) < 1e-5  # another order of the sums, nothing else


def test_the_parents_program_cannot_build_this_cell(monkeypatch):
    """What the parent commit does when handed the cell: the adapter
    imports ``fps_tpu.models.kge`` as it builds, not as it is imported, so
    ``spec.validate`` passes in every cell of a tree without the module and
    only THIS cell fails, at once, as it builds its system."""
    import sys

    loaded = tiny_cell()
    cfg, traffic = loaded["config"], loaded["traffic"]
    monkeypatch.setitem(sys.modules, "fps_tpu.models.kge", None)
    spec.validate(spec.load_benchmark())
    with one_device():
        data, _ = resolve.generator(cfg)(3, cfg["data"])
        with pytest.raises(ImportError):
            resolve.system_class(cfg, traffic)(cfg, traffic, data, 3)


# -- the new metrics' readers -------------------------------------------------

BODY = "jit(run)/while/body/closed_call/"


def _step(pieces):
    out, t = [], 0.0
    for i, (tf_op, dur) in enumerate(pieces):
        out.append(tr.Op(0, "XLA Ops", f"fusion.{i}", t, dur, BODY + tf_op,
                         "f32[8]", "loop fusion"))
        t += dur
    return [tr.Op(0, "XLA Ops", "while.1", 0.0, t, "", "", "while")] + out


def _reader(metric):
    with open(os.path.join(spec.HERE, "metrics", metric + ".json")) as f:
        body = json.load(f)
    return readers.reader(body["reader"]), body["params"]


def test_the_scoped_readers_take_the_fold_and_the_scoring_apart():
    trace = _step([
        ("fps.prepare/random_bits:", 0.05e-3),
        ("fps.pull/fps.ops/gather.xla/gather:", 6e-3),
        ("fps.compute/kge.score/jvp()/mul:", 2e-3),
        ("fps.compute/kge.score/transpose(jvp())/mul:", 2.5e-3),
        ("fps.compute/neg:", 0.3e-3),
        ("fps.push/fps.combine/scatter-add:", 7e-3),
        ("fps.push/fps.fold_rows/fps.ops/gather.xla/gather:", 5e-3),
        ("fps.push/fps.fold_rows/div:", 1e-3),
        ("fps.push/fps.fold_rows/fps.ops/scatter_add.xla_sorted/while/body/"
         "scatter-add:", 4e-3),
        ("fps.push/fps.fold_rows/fps.ops/scatter_set.xla_sorted/while/body/"
         "scatter:", 3e-3),
        ("fps.push/fps.ops/scatter_add.xla/scatter-add:", 0.5e-3)])
    ctx = {"ops": trace}
    for metric, want in (("store.fold_rows_ms_per_step", 13.0),
                         ("worker.score_ms_per_step", 4.5),
                         ("worker.prepare_ms_per_step", 0.05),
                         ("store.combine_dense_ms_per_step", 7.0),
                         ("kernel.sorted_scatter_ms_per_step", 4.0)):
        read, params = _reader(metric)
        assert read(ctx, params) == pytest.approx(want), metric
    # Nothing under the scope (the parent's program): nothing, not a raise.
    read, params = _reader("store.fold_rows_ms_per_step")
    assert read({"ops": _step([("fps.push/fps.combine/add:", 1e-3)])},
                params) is None


def test_the_counters_read_the_route_log_and_the_spans_field():
    from perfbench.lib import program_spans

    ops.clear_routes()
    ops.log_route("push", "fold_rows", 393216, 1000, 49152)
    ops.log_route("push", "fold", 822, 1000, 4096, "small_table")
    routes = program_spans.routes_traced()
    read, p = _reader("store.fold_rows_in_program")
    assert read({"routes": routes}, p) == 1
    assert not read({"routes": routes[1:]}, p)
    read, p = _reader("store.fold_touched_percent")
    span = {"fold_rows": {"entity": {"handed_ids": 200.0,
                                     "folded_ids": 175.0}}}
    events = {"device.run_indexed": {"window": [span, span]}}
    assert read({"program_span_events": events}, p) == pytest.approx(87.5)
    assert read({"program_span_events": {"device.run_indexed": {
        "window": [{"sum_runs": {}}]}}}, p) is None     # the parent's spans
