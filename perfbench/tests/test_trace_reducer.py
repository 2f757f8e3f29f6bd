"""The trace reducer on a small recorded trace: scope times, idle share,
steps, the row-operation arithmetic and the breakdown give known answers.

``data/mf_six_steps.json`` is six whole steps of ``mf-netflix.epochs`` cut
from a trace taken on the chip (TPU v5 lite, PR 23), in the reducer's own
reduced form, with the enclosing ``while`` op kept as the steps' parent.
The expected numbers below were worked out from the file independently of
the reducer (the interval union by rasterising, the sums by plain loops).
"""

import json
import os

import numpy as np
import pytest

from perfbench.lib import readers
from perfbench.lib import trace_reduce as tr
from test_rowop_yardstick import PARENT_RULE  # the rule until PR 37

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def ops():
    with open(os.path.join(HERE, "data", "mf_six_steps.json")) as f:
        return tr.ops_from_json(json.load(f)["ops"])


def ctx(ops, rows=131072, row_bytes=40):
    return {"ops": ops, "spans": {}, "counters": {}, "workers": 1,
            "config": {"rowops": {"rows_per_worker_step": rows,
                                  "row_bytes": row_bytes}},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_leaves_drop_the_enclosing_while(ops):
    lv = tr.leaves(ops, 0)
    assert len(lv) == len(ops) - 1
    assert all(o.category != "while" for o in lv)


def test_steps_are_counted_from_the_loop_body(ops):
    assert tr.steps_traced(ops) == 6.0


def test_idle_share_matches_a_rasterised_union(ops):
    lv = tr.leaves(ops, 0)
    t0, t1 = lv[0].start, max(o.end for o in lv)
    res = 1e-8
    grid = np.zeros(int(round((t1 - t0) / res)) + 1, bool)
    for o in lv:
        grid[int(round((o.start - t0) / res)):
             int(round((o.end - t0) / res))] = True
    busy, window, gaps = tr.busy_and_window(ops)
    assert window == pytest.approx(t1 - t0, rel=1e-9)
    assert busy == pytest.approx(grid.sum() * res, rel=2e-3)
    assert 0 < busy <= window
    # the file's own numbers, to the digit they were recorded with
    assert busy == pytest.approx(0.018138308, rel=1e-6)
    assert window == pytest.approx(0.01814064, rel=1e-6)
    assert sum(e - s for s, e in gaps) == pytest.approx(window - busy,
                                                        rel=1e-6)
    idle = readers.device_idle_percent(ctx(ops), {})
    assert idle == pytest.approx(100 * (1 - busy / window))
    assert 0.005 < idle < 0.05


def test_scope_times_per_step(ops):
    plain = sum(o.dur for o in ops if "/fps.compute/" in o.tf_op)
    assert plain == pytest.approx(0.015548479, rel=1e-6)
    got = readers.scope_time_per_step(
        ctx(ops), {"scopes": ["fps.compute"], "scale": 1000.0})
    assert got == pytest.approx(plain / 6 * 1000.0)
    store = readers.scope_time_per_step(
        ctx(ops), {"scopes": ["fps.pull", "fps.push"], "scale": 1000.0})
    assert store == pytest.approx(0.002074569 / 6 * 1000.0, rel=1e-6)
    assert readers.scope_time_per_step(
        ctx(ops), {"scopes": ["fps.nothing"]}) is None


def test_collective_reader_finds_nothing_on_one_chip(ops):
    assert readers.op_time_per_step(
        ctx(ops), {"name_regex": "^(all-gather|all-reduce)"}) is None


SCOPES = {"scopes": ["fps.pull", "fps.push", "fps.ops"]}


def test_rowop_time_rows_and_roofline(ops):
    """Both denominators on the one recorded trace, worked out by plain
    loops. This trace is PR 23's: its tree had no ``fps.ops`` scope yet, so
    the worker's local gather and scatter-add lie under ``fps.compute``
    alone and only the store's pull and push are under the scopes the
    reader takes now (on a tree that routes them,
    ``test_scoped_traces.py``). What the two rules disagree on here is
    exactly that, and the ops of the push the regex left out."""
    picked = [o for o in ops if PARENT_RULE.search(o.tf_op)]
    # a step's two gathers and two scatter-adds, with the copies XLA makes
    # of a gather's result under the same primitive: seven ops
    assert len(picked) == 7 * 6
    parent = sum(o.dur for o in picked)
    assert parent == pytest.approx(0.017287049, rel=1e-6)
    under = [o for o in ops if o.category != "while" and any(
        f"/{s}/" in o.tf_op for s in SCOPES["scopes"])]
    now = sum(o.dur for o in under)
    assert now == pytest.approx(0.002074569, rel=1e-6)
    # in the parent's and not under the scopes: the worker's local row ops
    local = sum(o.dur for o in picked if o not in under)
    assert {o.tf_op.split("closed_call/")[1] for o in picked
            if o not in under} == {"fps.compute/scatter-add:",
                                   "fps.compute/jit(_take)/gather:"}
    # under the scopes and not in the parent's: the push's own arithmetic
    # (its mask, the mean's divide, the add to the shard), 0.27 ms here
    left_out = [o for o in under if o not in picked]
    assert {o.tf_op.rstrip(":").split("/")[-1] for o in left_out} == {
        "select_n", "div", "add", "and"}
    assert now - sum(o.dur for o in left_out) == pytest.approx(
        parent - local, rel=1e-9)
    per_step = now / 6
    ns = readers.rowop_ns_per_row(ctx(ops), SCOPES)
    assert ns == pytest.approx(per_step / 131072 * 1e9)
    assert 2.5 < ns < 2.8
    # least time: 131,072 rows x 40 B x (one read + one write) at 819 GB/s
    least = tr.rowop_least_seconds(131072 * 40, 819e9)
    assert least == pytest.approx(12.8031e-6, rel=1e-4)
    share = readers.rowop_roofline_percent(ctx(ops), SCOPES)
    assert share == pytest.approx(100 * least / per_step)
    assert 3.6 < share < 3.8


def test_byte_count_is_the_least_so_a_share_cannot_pass_100():
    """An op that moved each row exactly once in and once out at the peak
    reads 100 %; anything real is slower, so reads less."""
    rows, row_bytes, peak = 32768, 40, 819e9
    least = tr.rowop_least_seconds(rows * row_bytes, peak)
    at_peak = [tr.Op(0, "XLA Ops", "fusion.1", 0.0, least,
                     "jit(run)/while/body/closed_call/fps.pull/gather:",
                     "f32[32768,10]", "custom fusion")]
    c = ctx(at_peak, rows=rows, row_bytes=row_bytes)
    assert readers.rowop_roofline_percent(c, SCOPES) == pytest.approx(100.0)
    # the count holds no index bytes, no read-modify-write, no padding
    assert least == rows * row_bytes * 2 / peak
    # ... and a second op under the scopes, whatever it is, only lowers it
    more = at_peak + [tr.Op(0, "XLA Ops", "fusion.2", least, least,
                            "jit(run)/while/body/closed_call/fps.pull/sort:",
                            "s32[32768]", "loop fusion")]
    assert readers.rowop_roofline_percent(
        ctx(more, rows=rows, row_bytes=row_bytes),
        SCOPES) == pytest.approx(50.0)


def test_nested_and_gapped_synthetic_trace():
    mk = lambda name, s, d, tf="jit(f)/while/body/fps.compute/add:": tr.Op(
        0, "XLA Ops", name, s, d, tf, "f32[8]", "loop fusion")
    ops = [tr.Op(0, "XLA Ops", "while.1", 0.0, 10.0, "", "", "while"),
           mk("a", 1.0, 2.0), mk("b", 3.0, 1.0), mk("a", 6.0, 2.0),
           mk("b", 8.0, 1.0),
           tr.Op(-1, "python", "bench.wait", 3.5, 3.0)]
    busy, window, gaps = tr.busy_and_window(ops)
    assert (busy, window) == (6.0, 8.0)
    assert gaps == [(4.0, 6.0)]
    assert tr.steps_traced(ops) == 2.0
    b = tr.breakdown(ops)
    assert b["idle_gaps"] == [["bench.wait", 2.0]]
    assert b["device_ops"][0] == ["fps.compute/add:f32[8]", 6.0]


def test_breakdown_uses_stable_names(ops):
    top = tr.breakdown(ops)["device_ops"]
    assert top[0][0] == "fps.compute/scatter-add:f32[480189,10]"
    assert len(top) <= 10 and all(isinstance(v, float) for _, v in top)


def test_round_trip_of_the_reduced_form(ops):
    assert tr.ops_from_json(tr.ops_to_json(ops)) == ops
