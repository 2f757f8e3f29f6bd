"""The reducer and the five scope metrics of PR 24 on two small recorded
traces of the tree that has the scopes.

``data/mf_three_steps_scoped.json``: three whole steps of
``mf-netflix.epochs`` from the start of a call's loop.
``data/pa_call_end_scoped.json``: the last two whole steps of a call of
``pa-rcv1.epochs``, the small programs the device runs after it (two
``fold_in`` key derivations: once a call, outside every ``fps.`` scope) and
the host's spans while the call after next is queued — the runner's
``bench.dispatch`` and, inside it, the program's ``fps.host.run_indexed``
> ``fps.host.epoch_args``, ``fps.host.dispatch`` > ``fps.host.enqueue``.
Both were cut from traces taken on the chip (TPU v5 lite, PR 24), in the
reducer's own reduced form. Expected numbers are worked out from the
files' rows by plain loops, independently of the reducer.
"""

import gzip
import json
import os

import pytest

from perfbench.lib import readers, spec
from perfbench.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
BOTH = ("bench.", "fps.host.")


def load(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        d = json.load(f)
    return d["ops"], d["steps"], tr.ops_from_json(d["ops"])


@pytest.fixture(scope="module")
def mf():
    return load("mf_three_steps_scoped")


@pytest.fixture(scope="module")
def pa():
    return load("pa_call_end_scoped")


def per_step_ms(rows, steps, *scopes):
    """Plain loop: device rows that are not the enclosing ``while`` and
    whose scope path holds one of ``scopes``, in ms a step."""
    return sum(r[4] for r in rows if r[0] == 0 and r[7] != "while"
               and any(f"/{s}/" in r[5] for s in scopes)) / steps * 1e3


def read_metric(name, ops):
    with open(os.path.join(spec.HERE, "metrics", name + ".json")) as f:
        m = json.load(f)
    assert m["reader"] == "scope_time_per_step"
    return readers.READERS[m["reader"]]({"ops": ops}, m["params"])


def test_steps_are_whole_and_counted(mf, pa):
    assert tr.steps_traced(mf[2]) == mf[1] == 3
    assert tr.steps_traced(pa[2]) == pa[1] == 2


@pytest.mark.parametrize("cell,metric,scopes,about_ms", [
    ("mf", "ingest.device_ms_per_step", ["fps.ingest"], 0.002826),
    ("pa", "ingest.device_ms_per_step", ["fps.ingest"], 0.8997),
    ("mf", "ops.routed_ms_per_step", ["fps.ops"], 0.9930),
    ("pa", "ops.routed_ms_per_step", ["fps.ops"], 1.8194),
    ("mf", "kernel.xla_scatter_ms_per_step", ["scatter_add.xla"], 0.2465),
    ("pa", "kernel.dim1_head_ms_per_step",
     ["gather.dim1_head", "scatter_add.dim1_head"], 0.1092),
    ("pa", "kernel.dim1_full_ms_per_step",
     ["gather.dim1", "scatter_add.dim1"], 1.7102),
])
def test_scope_metric_reads_its_scope(mf, pa, cell, metric, scopes,
                                      about_ms):
    rows, steps, ops = {"mf": mf, "pa": pa}[cell]
    want = per_step_ms(rows, steps, *scopes)
    assert want == pytest.approx(about_ms, rel=2e-3)  # the file's numbers
    assert read_metric(metric, ops) == pytest.approx(want, rel=1e-9)


def test_scope_metrics_find_nothing_where_the_route_is_not_taken(mf, pa):
    """What decides each metric's list of cells: MF takes no dim-1 route,
    PA no XLA scatter; a reader with nothing to read says None."""
    assert read_metric("kernel.dim1_head_ms_per_step", mf[2]) is None
    assert read_metric("kernel.dim1_full_ms_per_step", mf[2]) is None
    assert read_metric("kernel.xla_scatter_ms_per_step", pa[2]) is None


def test_a_routes_scope_holds_only_its_own_call(pa):
    """Head and tail of the head-prefix composite lie BESIDE each other:
    the two metrics add up to the time through the routing layer."""
    rows, steps, ops = pa
    head = read_metric("kernel.dim1_head_ms_per_step", ops)
    full = read_metric("kernel.dim1_full_ms_per_step", ops)
    assert head + full == pytest.approx(
        read_metric("ops.routed_ms_per_step", ops), rel=1e-9)
    assert not [r for r in rows if "dim1_head/" in r[5]
                and ("gather.dim1/" in r[5] or "scatter_add.dim1/" in r[5])]
    # The kernels carry their own names and still end in pallas_call.
    calls = sorted({r[5].split("/")[-2] for r in rows
                    if r[5].endswith("/pallas_call:")})
    assert calls == ["gather_dim1", "scatter_add_dim1"]


def test_the_existing_scope_readers_read_the_same_ops_as_before(mf, pa):
    """``fps.ops/...`` nests under pull / compute / push, so the eleven
    older metrics' selections are unmoved by it."""
    for rows, steps, ops in (mf, pa):
        store = readers.scope_time_per_step(
            {"ops": ops}, {"scopes": ["fps.pull", "fps.push"],
                           "scale": 1000.0})
        assert store == pytest.approx(
            per_step_ms(rows, steps, "fps.pull", "fps.push"))
    # kernel.rowop_* read ALL the time under pull, push and the routing
    # layer (PR 37), each leaf once though fps.ops nests under the others:
    # on PA the two Pallas kernels and what the parent's rule (ops named
    # gather, scatter-add or pallas_call) left out, the push's mask, the
    # pull's concatenate of head and tail, the kernels' own casts.
    with open(os.path.join(spec.HERE, "metrics",
                           "kernel.rowop_ns_per_row.json")) as f:
        params = json.load(f)["params"]
    assert params == {"scopes": ["fps.pull", "fps.push", "fps.ops"]}
    rows_pa = 2 * 16384 * 64
    ctx = {"ops": pa[2], "config": {"rowops": {
        "rows_per_worker_step": rows_pa, "row_bytes": 4}},
        "peaks": {"hbm_bytes_per_s": 819e9}}
    ns = readers.rowop_ns_per_row(ctx, params)
    assert ns == pytest.approx(per_step_ms(pa[0], 2, *params["scopes"])
                               * 1e-3 / rows_pa * 1e9)
    assert 0.91 < ns < 0.92
    kernels = sum(r[4] for r in pa[0] if r[5].endswith("/pallas_call:"))
    assert 0.81 < kernels / 2 / rows_pa * 1e9 < 0.83  # ledger, PR 23
    # On MF (PR 24's tree) the worker's local gather went through the
    # routing layer and lies under fps.compute/fps.ops: it is in, with the
    # store's pull and push, and no leaf is counted twice.
    ctx = {"ops": mf[2], "config": {"rowops": {
        "rows_per_worker_step": 131072, "row_bytes": 40}},
        "peaks": {"hbm_bytes_per_s": 819e9}}
    want = per_step_ms(mf[0], 3, *params["scopes"])
    assert want == pytest.approx(1.016699, rel=1e-5)
    assert want > per_step_ms(mf[0], 3, "fps.pull", "fps.push") + 0.6
    assert readers.rowop_ns_per_row(ctx, params) == pytest.approx(
        want * 1e-3 / 131072 * 1e9)
    assert readers.rowop_roofline_percent(ctx, params) == pytest.approx(
        100 * 131072 * 40 * 2 / 819e9 / (want * 1e-3))


def test_once_a_call_ops_leave_the_step_count_alone(mf, pa):
    """The naming rule: ``steps_traced`` takes the median count of the
    distinct ops under ``/fps.``, so what runs once a call carries no
    ``fps.`` scope. The PA file holds two real such programs (``fold_in``);
    an ``ingest.tbuf`` op is made up here in the form MF's trace gives it
    (the cut holds no call boundary of MF)."""
    rows, steps, ops = pa
    once = [o for o in tr.leaves(ops, 0) if "_threefry_fold_in" in o.tf_op]
    assert len(once) == 4 and not [o for o in once if "/fps." in o.tf_op]
    tbuf = tr.Op(0, "XLA Ops", "transpose_copy_fusion", 0.0084, 0.0009,
                 "jit(build)/ingest.tbuf/transpose:", "s32[1,100483072,3]",
                 "loop fusion")
    assert tr.steps_traced(mf[2] + [tbuf]) == 3
    assert tr.stable_name(tbuf).startswith("-/transpose:")
    # ... and under an fps. scope it WOULD be counted among the step's ops.
    wrong = tr.Op(0, "XLA Ops", "transpose_copy_fusion", 0.0084, 0.0009,
                  "jit(build)/fps.ingest/transpose:", "s32[8]",
                  "loop fusion")
    counts = {}
    for o in tr.leaves(mf[2] + [wrong], 0):
        if "/fps." in o.tf_op:
            counts[o.name] = counts.get(o.name, 0) + 1
    assert counts["transpose_copy_fusion"] == 1


def test_a_gap_takes_the_innermost_program_span_that_covers_it(pa):
    """A gap whose middle lies under ``bench.dispatch`` >
    ``fps.host.run_indexed`` > ``fps.host.epoch_args`` is named by the
    inner span once the reducer is given the program's prefix, and keeps
    the ``bench.*`` name without it. The host spans are the recorded ones;
    two device ops are made up to open a gap under them."""
    rows, steps, ops = pa
    spans = {o.name: o for o in ops if o.device < 0
             and o.name != "bench.wait"}
    ea = spans["fps.host.epoch_args"]
    assert (spans["bench.dispatch"].start
            < spans["fps.host.run_indexed"].start < ea.start
            < ea.end < spans["fps.host.dispatch"].start
            < spans["fps.host.enqueue"].start)
    mk = lambda s, d: tr.Op(0, "XLA Ops", "made_up", s, d,  # noqa: E731
                            "jit(f)/x:", "f32[8]", "loop fusion")
    device = [o for o in ops if o.device == 0]
    gapped = device + [mk(ea.start + 0.0001, 0.0001),
                       mk(ea.end - 0.0002, 0.0001)]
    host = [o for o in ops if o.device < 0]
    seen = tr.breakdown(gapped + host)["idle_gaps"]
    assert ["fps.host.epoch_args", pytest.approx(ea.dur - 0.0004)] in seen
    only_bench = [o for o in host if o.name.startswith("bench.")]
    seen = tr.breakdown(gapped + only_bench)["idle_gaps"]
    assert ["bench.dispatch", pytest.approx(ea.dur - 0.0004)] in seen


def test_load_trace_takes_both_prefixes(tmp_path):
    """``str.startswith`` takes a tuple: the runner's one changed line
    (``host_prefix=("bench.", "fps.host.")``) needs nothing of the
    reducer."""
    meta = lambda pid, tid, kind, name: {  # noqa: E731
        "ph": "M", "pid": pid, "tid": tid, "name": kind,
        "args": {"name": name}}
    events = [
        meta(1, 0, "process_name", "/device:TPU:0"),
        meta(1, 1, "thread_name", "XLA Ops"),
        meta(9, 0, "process_name", "/host:CPU"),
        meta(9, 7, "thread_name", "python3"),
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 10.0,
         "dur": 5.0, "args": {"tf_op": "jit(run)/fps.ingest/gather:",
                              "shape_with_layout": "f32[8]{0}"}},
        {"ph": "X", "pid": 9, "tid": 7, "name": "bench.dispatch",
         "ts": 0.0, "dur": 30.0},
        {"ph": "X", "pid": 9, "tid": 7, "name": "fps.host.epoch_args",
         "ts": 2.0, "dur": 8.0},
        {"ph": "X", "pid": 9, "tid": 7, "name": "PjitFunction(run)",
         "ts": 12.0, "dur": 3.0},
    ]
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    names = lambda ops: sorted(o.name for o in ops)  # noqa: E731
    assert names(tr.load_trace(str(path))) == ["bench.dispatch", "fusion.1"]
    assert names(tr.load_trace(str(path), host_prefix=BOTH)) == [
        "bench.dispatch", "fps.host.epoch_args", "fusion.1"]
