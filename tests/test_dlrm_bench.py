"""The benchmark cell ``dlrm-criteo.epochs`` rehearsed on the CPU, and the
files it is made of.

Tiny sizes (six fields of 3 to 5,000 rows, the published layer pattern at
narrow widths, 64 rows a step) on one virtual device, as the cell has one
chip. What is checked is correctness and counts: the runner's whole path
is ``correct`` against ``dlrm_dot_sgd`` under the COMMITTED limits; each of
the four controls (bfloat16, one-pass products, dropped dense gradients, a
mean fold) and each broken timed path is not; ``spec.validate`` on the
committed files (from here, where the driver's test command reaches); the
``flops`` and ``rowops`` counts against their derivations; the data kind's
shape; the new reader on a made-up trace. No rate is read: a CPU run has
none.
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

CELL = "dlrm-criteo.epochs"
CARDS = [5000, 3, 40, 300, 17, 1200]
TINY = {"model": {"embed_dim": 8, "bottom_mlp": [32, 16, 8, 8],
                  "top_mlp": [32, 16, 1], "local_batch": 64,
                  "field_offsets": [0, 5000, 5003, 5043, 5343, 5360]},
        "data": {"categorical_cardinalities": CARDS,
                 "categorical_columns": 6, "examples_resident": 3001}}
NAMES = [f"emb_{f:02d}" for f in range(26)] + [
    f"{stack}_{kind}{l}" for stack, depth in (("bot", 4), ("top", 3))
    for l in range(depth) for kind in "wb"]


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
    assert {"dense.reduce_apply_ms_per_step", "dense.routes_in_program",
            "worker.mlp_ms_per_step", "worker.interact_ms_per_step",
            "worker.compute_mfu", "kernel.rowop_roofline",
            "kernel.xla_gather_ms_per_step", "kernel.xla_scatter_ms_per_step",
            "ingest.rows_computed_in_program", "device.peak_hbm_gb",
            "device.idle_share"} <= set(cell["readers"])
    for m in bench["per_layer"]:
        if m["name"].startswith(("dense.", "worker.mlp", "worker.interact",
                                 "worker.compute_mfu")):
            assert m["workloads"] == [CELL] and m["moves"] == "examples_per_s"
    entry = next(c for c in bench["configs"] if c["name"] == "dlrm-criteo")
    assert entry["reduced"] == ["examples_resident"]
    assert len(entry["source"]) <= 200 and len(cell["cell"]["why"]) <= 200


def test_the_configuration_keeps_the_sources_shapes_and_cuts_the_log_alone():
    cfg = spec.load_cell(spec.load_benchmark(), CELL)["config"]
    m, d = cfg["model"], cfg["data"]
    assert cfg["reduced"] == ["examples_resident"]
    assert (m["embed_dim"], m["numeric"], m["bottom_mlp"], m["top_mlp"],
            m["interaction"], m["optimizer"], m["learning_rate"],
            m["matmul_precision"], m["local_batch"]) == (
        16, 13, [512, 256, 64, 16], [512, 256, 1], "dot", "sgd", 0.1,
        "highest", 16_384)
    assert m["arch_mlp_bot"] == "-".join(
        str(n) for n in [m["numeric"]] + m["bottom_mlp"])
    assert m["arch_mlp_top"] == "-".join(str(n) for n in m["top_mlp"])
    cards = d["categorical_cardinalities"]
    assert len(cards) == d["categorical_columns"] == 26
    assert sum(cards) == m["num_rows"] == 33_762_577      # the table whole
    assert m["field_offsets"] == [0] + list(np.cumsum(cards)[:-1])
    # lr-criteo's law over the same columns.
    lr = spec.load_cell(spec.load_benchmark(), "lr-criteo.epochs")["config"]
    for key in ("categorical_cardinalities", "token_zipf", "numeric_log_mu",
                "numeric_log_sigma", "numeric_missing", "log_examples"):
        assert d[key] == lr["data"][key], key
    n = d["examples_resident"]
    # A call of 65 steps: PERF.md section 4 has the rule and its readings.
    assert n == 2**20 < d["log_examples"]
    assert cfg["reference"] == "dlrm_dot_sgd" and cfg["quality"] is None
    assert set(cfg["limits"]) == {"examples", "feed", "loss_gap"} | {
        f"{gap}.{name}" for gap in ("table_gap", "update_gap")
        for name in NAMES}
    assert cfg["limits"]["examples"] == cfg["limits"]["feed"] == 0


def test_flops_and_rowops_are_their_derivations():
    cfg = spec.load_cell(spec.load_benchmark(), CELL)["config"]
    m = cfg["model"]
    F, D, B = 26, m["embed_dim"], m["local_batch"]
    bottom = sum(a * b for a, b in zip([m["numeric"]] + m["bottom_mlp"],
                                       m["bottom_mlp"]))
    pairs = (F + 1) * F // 2
    top = sum(a * b for a, b in zip([D + pairs] + m["top_mlp"], m["top_mlp"]))
    assert (bottom, pairs * D, top) == (155_136, 5_616, 319_232)
    assert cfg["flops"]["per_worker_step"] == B * (
        bottom + pairs * D + top) * 3 * 2 == 47_184_347_136
    assert cfg["rowops"]["rows_per_worker_step"] == 2 * F * B == 851_968
    assert cfg["rowops"]["row_bytes"] == 4 * D == 64
    biases = sum(m["bottom_mlp"]) + sum(m["top_mlp"])
    assert m["dense_parameters"] == bottom + top + biases == 475_985
    # A sixth of the peak at six passes: the share cannot pass 100.
    assert cfg["flops"]["per_worker_step"] / 197e12 < 1e-3


# -- the data kind ----------------------------------------------------------

def test_rows_are_raw_tokens_a_dense_column_and_a_label():
    cfg = tiny_cell()["config"]
    data, data_sum = resolve.generator(cfg)(11, cfg["data"])
    n = cfg["data"]["examples_resident"]
    assert {k: (v.shape, v.dtype) for k, v in data.items()} == {
        "tokens": ((n, 6), np.dtype("int32")),
        "counts": ((n, 13), np.dtype("float32")),
        "label": ((n,), np.dtype("float32"))}
    assert (data["tokens"] >= 0).all()
    assert (data["tokens"].max(axis=0) < np.array(CARDS)).all()
    # Zipf within a field: token 0 is the commonest of the 5,000.
    assert np.bincount(data["tokens"][:, 0]).argmax() == 0
    assert set(np.unique(data["label"])) == {0.0, 1.0}
    assert 0.3 < (data["counts"] == 0).mean() < 0.5     # missing or zero
    again, again_sum = resolve.generator(cfg)(11, cfg["data"])
    assert again_sum == data_sum
    np.testing.assert_array_equal(again["tokens"], data["tokens"])
    other, other_sum = resolve.generator(cfg)(12, cfg["data"])
    assert other_sum != data_sum


# -- the runner's whole path, sound and broken ------------------------------

def test_cell_rehearsal_runs_the_runners_whole_path():
    result, events = run()
    compared = [e for e in events if e["event"] == "compared"]
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "examples_per_s"}
    want = {"examples", "feed", "loss_gap", "programs_lowered_in_window"} | {
        f"{gap}.{name}" for gap in ("table_gap", "update_gap")
        for name in NAMES[:6] + NAMES[26:]}
    assert {e["number"] for e in compared} == want
    routes = [r.route for r in ops.routes_traced()]
    assert routes.count("dense.psum_sgd") >= 1
    assert "ingest.rows_sliced" in routes
    assert "ingest.rows_computed" not in routes
    readings = next(e for e in events if e["event"] == "readings")
    assert readings["window_examples"] == 3001 * readings["n"]


def _skip_updates(system):
    """The call hands back the state it was given (metrics still flow)."""
    real = system.trainer.run_indexed

    def broken(tables, local_state, *a, **kw):
        _, _, metrics = real(jax.tree.map(jnp.copy, tables), local_state,
                             *a, **kw)
        return tables, local_state, metrics

    system.trainer.run_indexed = broken


def _cut_the_dense_route(system):
    """The trainer never folds a dense gradient: the MLPs stay as placed
    while the table trains on."""
    system.trainer._fold_dense = lambda dense, grads: dense


def _drop_part_of_the_batch(system):
    real = system.plan.local_batch_at

    def broken(args, w, t):
        batch = real(args, w, t)
        half = batch["weight"].shape[0] // 2
        return dict(batch, weight=batch["weight"].at[half:].set(0.0))

    system.plan.local_batch_at = broken


@pytest.mark.parametrize("break_system", [
    _skip_updates, _cut_the_dense_route, _drop_part_of_the_batch])
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


@pytest.fixture(scope="module")
def replayed():
    """The tiny cell's system, the seeded tables and the sound reference's
    replay of the first call, made once for the four controls."""
    loaded = tiny_cell()
    cfg, traffic = loaded["config"], loaded["traffic"]
    with one_device():
        data, data_sum = resolve.generator(cfg)(5, cfg["data"])
        system = resolve.system_class(cfg, traffic)(cfg, traffic, data, 5)
    init = resolve.reference(cfg).init_tables(5, cfg)
    return cfg, system, init, data_sum, check.run_reference(system, cfg, init)


@pytest.mark.parametrize("control", [
    "bf16", "default_precision", "drop_dense", "mean_fold"])
def test_every_control_fails_a_committed_limit(replayed, control):
    """``perfbench/dlrm_controls.py``'s four: the reference with one thing
    wrong, put in the program's place, is refused by the limits the
    configuration file commits (and the sound reference itself is not)."""
    from perfbench.dlrm_controls import CONTROLS, passes_every_limit

    cfg, system, init, data_sum, (ref, ref_loss, ref_n, feed) = replayed
    limits = {k: v for k, v in cfg["limits"].items()
              if k.split(".")[-1] in NAMES[:6] + NAMES[26:] or "." not in k}
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
    if control == "drop_dense":
        # A state left unchanged reads 1 on every MLP matrix.
        assert numbers["update_gap.top_w0"] == pytest.approx(1.0)


def test_the_parents_program_cannot_build_this_cell(monkeypatch):
    """What the parent commit does when handed the cell: the adapter
    imports ``fps_tpu.models.dlrm`` as it builds, not as it is imported,
    so ``spec.validate`` passes in every cell of a tree without the module
    and only THIS cell fails, at once, as it builds its system."""
    import sys

    loaded = tiny_cell()
    cfg, traffic = loaded["config"], loaded["traffic"]
    monkeypatch.setitem(sys.modules, "fps_tpu.models.dlrm", None)
    spec.validate(spec.load_benchmark())
    with one_device():
        data, _ = resolve.generator(cfg)(3, cfg["data"])
        with pytest.raises(ImportError):
            resolve.system_class(cfg, traffic)(cfg, traffic, data, 3)


# -- the new reader ---------------------------------------------------------

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


def test_flops_share_divides_the_models_operations_by_its_device_time():
    trace = _step([
        ("fps.pull/fps.ops/gather.xla/gather:", 3e-3),
        ("fps.compute/dlrm.bottom/jvp()/dot_general:", 1e-3),
        ("fps.compute/dlrm.interact/transpose(jvp())/dot_general:", 2e-3),
        ("fps.compute/dlrm.top/transpose(jvp())/dot_general:", 0.9e-3),
        ("fps.dense/sub:", 0.1e-3),
        ("fps.push/fps.ops/scatter_add.xla/scatter-add:", 20e-3)])
    ctx = {"ops": trace, "config": {"flops": {"per_worker_step": 4e10}},
           "peaks": {"bf16_flops_per_s": 2e14}}
    read, p = _reader("worker.compute_mfu")
    assert p["scopes"] == ["fps.compute", "fps.dense"]
    assert read(ctx, p) == pytest.approx(100 * 4e10 / 4e-3 / 2e14)  # 5 %
    # The scoped readers on the same step.
    for metric, want in (("worker.mlp_ms_per_step", 1.9),
                         ("worker.interact_ms_per_step", 2.0),
                         ("dense.reduce_apply_ms_per_step", 0.1)):
        read_ms, params = _reader(metric)
        assert read_ms(ctx, params) == pytest.approx(want)


@pytest.mark.parametrize("ctx", [
    {"ops": None, "config": {"flops": {"per_worker_step": 1.0}}},
    {"ops": _step([("fps.compute/mul:", 1e-3)]), "config": {}},
    {"ops": _step([("fps.pull/gather:", 1e-3)]),
     "config": {"flops": {"per_worker_step": 1.0}}},
])
def test_flops_share_reads_nothing_where_there_is_nothing(ctx):
    """No trace, a configuration without a ``flops`` group (every other
    cell's), no op under the scopes: ``None``, and nothing raised."""
    read, p = _reader("worker.compute_mfu")
    assert read(dict(ctx, peaks={"bf16_flops_per_s": 2e14}), p) is None


def test_dense_routes_counter_reads_the_route_log():
    read, p = _reader("dense.routes_in_program")
    ops.clear_routes()
    ops.log_route("gather", "xla", 10, 16, 4, "shape")
    ops.log_route("dense", "psum_sgd", 475985, 1, 0, "workers=1")
    from perfbench.lib import program_spans

    routes = program_spans.routes_traced()
    assert read({"routes": routes}, p) == 1
    assert not read({"routes": routes[:1]}, p)
