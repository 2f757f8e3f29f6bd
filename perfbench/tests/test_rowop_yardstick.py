"""The row operations' yardstick (PR 37): the least bytes ANY
implementation of the step must move, over ALL the device time under the
store's pull and push and the routing layer.

Made-up reduced traces and the committed configurations; counts and
arithmetic only (a CPU has no device time of its own: the seconds below
are written into the traces by hand).
"""

import json
import os
import re

import pytest

from perfbench.lib import readers, spec
from perfbench.lib import trace_reduce as tr

BENCH = spec.load_benchmark()
PEAK = 819e9
BODY = "jit(run)/while/body/closed_call/"
# The parent's rule, worked out here from op names as its reader did.
PARENT_RULE = re.compile(
    r"/fps\.(pull|compute|push)/(.*/)?(gather|scatter-add|pallas_call):$")


def params(metric):
    with open(os.path.join(spec.HERE, "metrics", metric + ".json")) as f:
        body = json.load(f)
    return body["reader"], body["params"]


def one_step(pieces):
    """A reduced trace of one step: ``(scope path and primitive, seconds)``
    laid end to end inside a ``while``."""
    ops, t = [], 0.0
    for i, (tf_op, dur) in enumerate(pieces):
        ops.append(tr.Op(0, "XLA Ops", f"fusion.{i}", t, dur, BODY + tf_op,
                         "f32[8]", "loop fusion"))
        t += dur
    return [tr.Op(0, "XLA Ops", "while.1", 0.0, t, "", "", "while")] + ops


def context(ops, rowops):
    return {"ops": ops, "config": {"rowops": rowops},
            "peaks": {"hbm_bytes_per_s": PEAK}}


def parent_seconds(ops):
    return sum(o.dur for o in tr.leaves(ops, 0)
               if PARENT_RULE.search(o.tf_op))


def test_the_same_rows_by_other_primitives_read_the_same():
    """Two programs push the same 16,384 rows in 3 ms: one by a
    scatter-add, one by a sort, a matrix product and an add that leave a
    narrow scatter-add of a tenth of the time. The yardstick reads them
    alike; the parent's, which followed op names, read the second several
    times nearer the roofline for no work saved: the reason for the change."""
    pull = ("fps.pull/fps.ops/gather.xla/gather:", 0.5e-3)
    compute = ("fps.compute/mul:", 1.0e-3)
    by_scatter = one_step([pull, compute, (
        "fps.push/fps.ops/scatter_add.xla/scatter-add:", 3.0e-3)])
    by_products = one_step([pull, compute,
                            ("fps.push/fps.combine/sort:", 1.2e-3),
                            ("fps.push/fps.combine/dot_general:", 1.0e-3),
                            ("fps.push/fps.combine/add:", 0.5e-3),
                            ("fps.push/fps.ops/scatter_add.xla/scatter-add:",
                             0.3e-3)])
    rowops = {"rows_per_worker_step": 32768, "row_bytes": 256}
    got = {}
    for name, ops in (("scatter", by_scatter), ("products", by_products)):
        assert tr.steps_traced(ops) == 1.0
        busy, _, _ = tr.busy_and_window(ops)
        assert busy == pytest.approx(4.5e-3)  # equal total time
        got[name] = {}
        for metric in ("kernel.rowop_roofline", "kernel.rowop_ns_per_row"):
            reader, p = params(metric)
            got[name][metric] = readers.reader(reader)(
                context(ops, rowops), p)
    assert got["scatter"] == pytest.approx(got["products"])
    assert got["scatter"]["kernel.rowop_ns_per_row"] == pytest.approx(
        3.5e-3 / 32768 * 1e9)
    assert got["scatter"]["kernel.rowop_roofline"] == pytest.approx(
        100 * 32768 * 256 * 2 / PEAK / 3.5e-3)
    # By the parent's rule the second program's row ops took 0.8 ms of the
    # 3.5: it would have read 4.4 times the first's share.
    assert parent_seconds(by_scatter) == pytest.approx(3.5e-3)
    assert parent_seconds(by_products) == pytest.approx(0.8e-3)
    assert parent_seconds(by_scatter) / parent_seconds(by_products) > 4


def test_a_routed_op_under_the_workers_compute_counts_and_only_once():
    """``fps.ops`` is counted wherever it lies (the worker's ``pull_local``
    / ``push_local`` under ``fps.compute``); a leaf under both ``fps.push``
    and ``fps.ops`` is one leaf; the worker's own arithmetic is not a row
    operation."""
    ops = one_step([
        ("fps.compute/fps.ops/gather.xla_packed/gather:", 1e-3),
        ("fps.compute/mul:", 7e-3),
        ("fps.push/fps.ops/scatter_add.xla/scatter-add:", 2e-3),
        ("fps.ingest/gather:", 5e-3)])
    reader, p = params("kernel.rowop_ns_per_row")
    ns = readers.reader(reader)(context(ops, {
        "rows_per_worker_step": 1000, "row_bytes": 4}), p)
    assert ns == pytest.approx(3e-3 / 1000 * 1e9)


def test_nothing_under_the_scopes_reads_nothing():
    ops = one_step([("fps.compute/mul:", 1e-3)])
    for metric in ("kernel.rowop_roofline", "kernel.rowop_ns_per_row"):
        reader, p = params(metric)
        assert readers.reader(reader)(context(ops, {
            "rows_per_worker_step": 8, "row_bytes": 4}), p) is None
        assert readers.reader(reader)(
            {"ops": ops, "config": {}, "peaks": {}}, p) is None


def test_stated_bytes_take_the_place_of_rows_times_row_bytes():
    ops = one_step([("fps.push/scatter-add:", 1e-3)])
    reader, p = params("kernel.rowop_roofline")
    by_rows = readers.reader(reader)(context(ops, {
        "rows_per_worker_step": 1000, "row_bytes": 4096}), p)
    stated = readers.reader(reader)(context(ops, {
        "rows_per_worker_step": 1000, "row_bytes": 4096,
        "bytes_per_worker_step": 409600}), p)
    assert by_rows == pytest.approx(100 * 1000 * 4096 * 2 / PEAK / 1e-3)
    assert stated == pytest.approx(by_rows / 10)
    # rows_per_worker_step stays the divisor of the time per row
    reader, p = params("kernel.rowop_ns_per_row")
    assert readers.reader(reader)(context(ops, {
        "rows_per_worker_step": 1000, "row_bytes": 4096,
        "bytes_per_worker_step": 409600}), p) == pytest.approx(1000.0)


def config_of(name):
    c = next(c for c in BENCH["configs"] if c["name"] == name)
    return spec._load(os.path.join(spec.ROOT, c["file"]))


def test_ials_counts_the_algorithms_least_from_its_own_shapes():
    """Per worker and pair of steps (a user step and an item step: what a
    traced "step" of the cell is): every rating's two factor rows read,
    and each id's row of the normal equations (left side k x k, right side
    k) once a SWEEP, spread over the sweep's steps. No 16 KB row per rating:
    that is what today's program writes, not what the algorithm must."""
    cfg = config_of("ials-ml20m")
    m, d, r = cfg["model"], cfg["data"], cfg["rowops"]
    k, batch = m["rank"], m["local_batch"]
    steps = -(-d["ratings_resident"] // batch) + 1  # the plan's slack step
    assert steps == m["steps_per_chunk"] == 513
    gathered = 2 * 2 * batch * 4 * k
    equations = (m["num_users"] + m["num_items"]) * 4 * (k * k + k)
    assert (gathered, equations) == (16_777_216, 2_749_543_680)
    assert r["bytes_per_worker_step"] == round(gathered + equations / steps)
    assert r["bytes_per_worker_step"] == 22_136_950
    # a least time of 0.054 ms where rows x row_bytes (today's program's
    # own traffic, 281 MB) said 1.372
    assert tr.rowop_least_seconds(r["bytes_per_worker_step"],
                                  PEAK) == pytest.approx(54.06e-6, rel=1e-3)
    assert tr.rowop_least_seconds(
        r["rows_per_worker_step"] * r["row_bytes"], PEAK) == pytest.approx(
            1.3725e-3, rel=1e-3)
    # A program that formed the same sums with no wide row written, its
    # narrow ops left at 1.03 ms a step, reads under 10 %; by the parent's
    # count and rule it read 133 %.
    cured = one_step([("fps.pull/fps.ops/gather.xla/gather:", 0.22e-3),
                      ("fps.push/fps.combine/dot_general:", 2.0e-3),
                      ("fps.push/fps.ops/scatter_add.xla/scatter-add:",
                       0.81e-3)])
    reader, p = params("kernel.rowop_roofline")
    share = readers.reader(reader)(dict(context(cured, r)), p)
    assert 1.5 < share < 2.0
    assert 100 * 1.3725e-3 / parent_seconds(cured) > 130


@pytest.mark.parametrize("config", sorted(
    c["name"] for c in BENCH["configs"]))
def test_every_configuration_states_its_least_bytes_and_why(config):
    r = config_of(config)["rowops"]
    assert r["rows_per_worker_step"] > 0 and r["row_bytes"] > 0
    assert len(r["what"]) > 80
    least = r.get("bytes_per_worker_step",
                  r["rows_per_worker_step"] * r["row_bytes"])
    # never more than what rows x row_bytes says: a stated count only
    # takes bytes out that no implementation has to move
    assert 0 < least <= r["rows_per_worker_step"] * r["row_bytes"]


@pytest.mark.parametrize("metric", ["kernel.rowop_roofline",
                                    "kernel.rowop_ns_per_row"])
def test_the_metric_files_name_scopes_not_op_names(metric):
    _, p = params(metric)
    assert p == {"scopes": ["fps.pull", "fps.push", "fps.ops"]}
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert sorted(entry["workloads"]) == sorted(
        w["name"] for w in BENCH["workloads"])


def test_a_context_that_lacks_a_scope_leaves_its_metric_out_of_the_line():
    """... which is what a traced run's ``silent`` event then names
    (``test_program_spans.py`` drives the runner's line)."""
    cell = spec.load_cell(BENCH, "w2v-1bw.epochs")["readers"]
    listed = {k: cell[k] for k in ("kernel.sorted_scatter_ms_per_step",
                                   "kernel.xla_gather_ms_per_step",
                                   "worker.prepare_ms_per_step")}
    ops = one_step([
        ("fps.pull/fps.ops/gather.xla/gather:", 1e-3),
        ("fps.push/fps.ops/scatter_add.xla/scatter-add:", 2e-3)])
    read = readers.read_all(listed, {"ops": ops})
    assert set(read) == {"kernel.xla_gather_ms_per_step"}


def test_the_xla_scatter_metric_follows_the_route_that_took_its_place():
    """``kernel.xla_scatter_ms_per_step`` reads XLA's scatter-add under
    both of its routes: the plain one and the one by blocks of sorted ids
    that took its place in ``w2v-1bw.epochs`` (PR 30) and
    ``lr-criteo.epochs`` (PR 34), so a cell that lists it does not fall
    silent when the route changes. The sorted part alone is
    ``kernel.sorted_scatter_ms_per_step``; the lane-packed route is
    another metric's."""
    def read(metric, ops):
        reader, p = params(metric)
        return readers.reader(reader)({"ops": ops}, p)

    plain = one_step([
        ("fps.compute/fps.ops/scatter_add.xla_packed/scatter-add:", 5e-3),
        ("fps.push/fps.ops/scatter_add.xla/scatter-add:", 2e-3)])
    by_blocks = one_step([
        ("fps.push/fps.combine/sort:", 1e-3),
        ("fps.push/fps.ops/scatter_add.xla_sorted/while/body/scatter-add:",
         2.5e-3),
        ("fps.push/fps.ops/scatter_add.xla_sorted/reduce:", 0.5e-3)])
    assert read("kernel.xla_scatter_ms_per_step", plain) == pytest.approx(2.0)
    assert read("kernel.sorted_scatter_ms_per_step", plain) is None
    assert read("kernel.xla_scatter_ms_per_step", by_blocks) == pytest.approx(
        3.0)
    assert read("kernel.sorted_scatter_ms_per_step",
                by_blocks) == pytest.approx(3.0)
    # Every cell that lists the one route's metric lists the whole's.
    cells = {m["name"]: set(m["workloads"]) for m in BENCH["per_layer"]
             if "workloads" in m}
    assert cells["kernel.sorted_scatter_ms_per_step"] <= cells[
        "kernel.xla_scatter_ms_per_step"]
