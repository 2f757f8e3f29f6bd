"""The program's own spans and route log, read through the runner's wiring.

``runner.run_cell(trace=True)`` installs the recorder before the data is
made, clears and reads the route log round the warm-up call, collects the
sink at the window's opening time and hands the readers a context; these
tests drive exactly that on the rehearsal's tiny cells. The one thing a
CPU cannot give is a device trace: the profiled calls after the window
are left out and the reducer is handed a trace recorded on the chip
(``data/``). Counts and control flow only: the host times read here are
asserted present, ordered and consistent, never printed.
"""

import gzip
import json
import os
import time

import pytest

from perfbench.lib import program_spans, readers, runner, spec, window
from perfbench.lib import trace_reduce as tr
from test_rehearsal import mesh_devices, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
WIRED = ("ops.pallas_routes_in_program", "setup.dataset_s", "setup.plan_s",
         "setup.compile_s", "driver.epoch_args_ms", "driver.enqueue_ms")
RECORDED = {"mf-netflix.epochs": "mf_three_steps_scoped",
            "pa-rcv1.epochs": "pa_call_end_scoped"}


def traced_run(workload, out_dir, trace=True, seed=7):
    """``run_cell`` as a traced run makes it, the device trace stood in
    for. Returns ``(loaded, result, events, the readers' context)``."""
    from fps_tpu.obs import events as obs_events

    loaded = tiny_cell(workload)
    with open(os.path.join(HERE, "data", RECORDED[workload] + ".json")) as f:
        recorded = tr.ops_from_json(json.load(f)["ops"])
    events, seen = [], {}

    def no_profiler(system, state, seconds, call_s, trace_dir):
        os.makedirs(trace_dir)
        with gzip.open(os.path.join(trace_dir, "cpu.trace.json.gz"),
                       "wt") as f:
            json.dump({"traceEvents": []}, f)
        return state

    def read_all(specs, ctx):
        seen.update(ctx)
        return real_read_all(specs, ctx)

    real_read_all = readers.read_all
    with pytest.MonkeyPatch.context() as mp, mesh_devices(1):
        mp.setattr(window, "run_traced", no_profiler)
        mp.setattr(tr, "load_trace", lambda path, host_prefix: recorded)
        mp.setattr(readers, "read_all", read_all)
        mp.setattr(runner, "_peaks",
                   lambda kind: {"hbm_bytes_per_s": 819e9})
        try:
            result = runner.run_cell(
                loaded, seed=seed, seconds=0.2, trace=trace,
                t_start=time.perf_counter(),
                emit=lambda event, **f: events.append(dict(event=event, **f)),
                out_dir=str(out_dir))
            installed = obs_events.get_default_recorder()
        finally:
            obs_events.set_default_recorder(None)
    return loaded, result, events, dict(seen, installed=installed)


@pytest.fixture(scope="module", params=sorted(RECORDED))
def traced(request, tmp_path_factory):
    return (request.param,) + traced_run(
        request.param, tmp_path_factory.mktemp("trace"))


@pytest.mark.parametrize("name", WIRED)
def test_a_traced_run_reports_the_span_and_counter_metrics(traced, name):
    workload, loaded, result, _, _ = traced
    assert name in loaded["readers"], "listed for the cell in BENCHMARK.json"
    value = result["metrics"][name]["value"]
    assert value >= 0.0, (workload, name)
    if name == "ops.pallas_routes_in_program":
        # CPU "auto" keeps every route on XLA; the log is there and says 0,
        # as the monkeypatch counter does.
        assert value == 0.0 == result["metrics"][
            "ops.pallas_kernels_in_program"]["value"]


def test_packed_routes_are_counted_where_the_cell_lists_them(traced):
    workload, loaded, result, events, ctx = traced
    name = "ops.xla_packed_routes_in_program"
    assert (name in loaded["readers"]) == (workload == "mf-netflix.epochs")
    # A tiny table stays on the plain XLA route: the count is there, 0.
    assert result["metrics"].get(name, {}).get("value") == (
        0.0 if workload == "mf-netflix.epochs" else None)
    logged = next(e for e in events if e["event"] == "program")["routes"]
    assert logged == ctx["routes"] and logged
    assert all(r["route"].startswith(r["op"] + ".") for r in logged)


def test_spans_fall_on_the_right_side_of_the_windows_opening(traced):
    workload, _, result, events, ctx = traced
    spans = ctx["program_spans"]
    for name in ("dataset.place", "dataset.queues", "plan.build",
                 "init_state"):
        assert spans[name]["setup"] and not spans[name]["window"], name
    # One run_indexed, one epoch_args and one enqueue per queued call; the
    # warm-up call and the first timed call were queued before the window
    # opened. What began after it closed is the reference's replay, which
    # asks the plan for the warm-up call's arguments once more.
    for name in ("run_indexed", "epoch_args", "enqueue"):
        both = spans[name]["setup"] + spans[name]["window"]
        assert len(both) == result["attempted"], name
        assert len(spans[name]["setup"]) >= 2
        assert len(spans[name]["after"]) == (name == "epoch_args")
    if workload.startswith("mf"):
        assert spans["dataset.pack"]["setup"]  # MF's columns pack, PA's not
    # Everything JAX compiled for the cell, it compiled before the window.
    assert spans["compile.backend"]["setup"]
    assert not spans["compile.backend"]["window"]
    # The event line carries the same, as counts and seconds.
    line = next(e for e in events if e["event"] == "program")["spans"]
    assert line["setup"]["plan.build"][0] == 1
    assert "plan.build" not in line["window"]
    assert (line["setup"]["enqueue"][0] + line["window"]["enqueue"][0]
            == result["attempted"])


def test_plan_self_time_leaves_out_the_dataset_spans_inside_it(traced):
    _, _, result, _, ctx = traced
    spans = ctx["program_spans"]
    (build,) = spans["plan.build"]["setup"]
    inside = [iv for n in ("dataset.queues", "dataset.pack")
              for iv in spans.get(n, {}).get("setup", ())
              if build[0] <= iv[0] and iv[1] <= build[1]]
    assert inside  # the plan is what first asks for the queues
    whole = build[1] - build[0]
    own = program_spans.program_span_total(
        ctx, {"part": "setup", "self_spans": ["plan.build"]})
    assert own == pytest.approx(whole - sum(b - a for a, b in inside))
    first = min(spans["epoch_args"]["setup"])
    assert result["metrics"]["setup.plan_s"]["value"] == pytest.approx(
        own + first[1] - first[0])


def test_a_traced_run_names_the_metrics_it_lists_and_could_not_read(traced):
    """One more event line, ``silent``: every metric the cell lists whose
    reader found nothing, so a route or scope the program lost shows in
    the run that lost it. The recorded traces are PR 24's tree's: no
    lane-packed route (PR 25) and no ``fps.combine`` (PR 27) in them."""
    workload, loaded, result, events, _ = traced
    (line,) = [e for e in events if e["event"] == "silent"]
    assert line["metrics"] == sorted(
        set(loaded["readers"]) - set(result["metrics"]))
    want = {"mf-netflix.epochs": ["kernel.xla_packed_ms_per_step",
                                  "store.combine_dense_ms_per_step"],
            "pa-rcv1.epochs": []}[workload]
    assert [m for m in line["metrics"] if m in want] == want
    # what read is not named, and the result object carries no new key
    assert "kernel.rowop_roofline" in result["metrics"]
    assert "kernel.rowop_roofline" not in line["metrics"]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown", "compared"}


def test_span_events_ride_whole_beside_their_intervals(traced):
    """``program_span_events``: the same names and parts as
    ``program_spans``, each span event with every field the program set
    (here ``call``, the driver call's index), for a reader that wants more
    of a span than its length."""
    _, _, result, _, ctx = traced
    spans, whole = ctx["program_spans"], ctx["program_span_events"]
    assert set(whole) == {n for n in spans if not n.startswith("compile.")}
    for name, parts in whole.items():
        for part, evs in parts.items():
            assert [(e["t0"], e["t1"]) for e in evs] == spans[name][part]
            assert all(e["span"] == name and e["event"] == "span"
                       for e in evs)
    calls = [e["call"] for part in ("setup", "window")
             for e in whole["run_indexed"][part]]
    assert len(calls) == result["attempted"] == len(set(calls))
    assert program_spans.collect_events(None, 0.0, 1.0) == {}


def test_an_untraced_run_installs_no_recorder_and_reads_no_route_log(
        tmp_path):
    _, result, events, ctx = traced_run("mf-netflix.epochs", tmp_path,
                                        trace=False)
    assert ctx == {"installed": None}  # the readers were never called
    assert "setup_s" in result["metrics"]
    assert not [e for e in events if e["event"] in ("program", "silent")]


def test_a_program_without_the_spans_reads_nothing_and_raises_nothing():
    """What a parent commit gives: no recorder to install, no route log."""
    ctx = {"program_spans": program_spans.collect(None, 0.0, 1.0),
           "routes": None,
           "counters": {"pallas_routes_in_program":
                        program_spans.pallas_routes_in_program(None)}}
    assert ctx["program_spans"] == {}
    specs = spec.load_cell(spec.load_benchmark(),
                           "mf-netflix.epochs")["readers"]
    wired = {k: v for k, v in specs.items()
             if k in WIRED + ("ops.xla_packed_routes_in_program",)}
    assert len(wired) == 7 and readers.read_all(wired, ctx) == {}


def test_routes_are_counted_by_pattern_and_by_kind():
    routes = [
        {"op": "gather", "route": "gather.xla_packed", "pallas": False,
         "interpret": False},
        {"op": "scatter_add", "route": "scatter_add.xla_packed",
         "pallas": False, "interpret": False},
        {"op": "gather", "route": "gather.xla", "pallas": False,
         "interpret": False},
        {"op": "gather", "route": "gather.dim1", "pallas": True,
         "interpret": False},
        {"op": "scatter_add", "route": "scatter_add.dim1", "pallas": True,
         "interpret": True},
    ]
    count = program_spans.routes_logged
    assert count({"routes": routes}, {"route_regex": r"\.xla_packed$"}) == 2
    assert count({"routes": routes}, {"route_regex": r"^gather\."}) == 3
    assert count({"routes": []}, {"route_regex": "x"}) == 0.0
    assert count({}, {"route_regex": "x"}) is None
    assert program_spans.pallas_routes_in_program(routes) == 1.0


def test_self_seconds_and_parts_on_made_up_spans():
    ctx = {"program_spans": {
        "plan.build": {"setup": [(10.0, 20.0)], "window": []},
        "dataset.queues": {"setup": [(11.0, 14.0)], "window": []},
        "dataset.pack": {"setup": [(14.0, 18.0), (30.0, 31.0)],
                         "window": []},
        "epoch_args": {"setup": [(40.0, 40.5), (50.0, 50.25)],
                       "window": [(61.0, 61.002), (62.0, 62.004),
                                  (63.0, 63.003)],
                       "after": [(70.0, 70.5)]},
    }}
    total = program_spans.program_span_total
    assert total(ctx, {"spans": ["dataset.queues", "dataset.pack"]}) == 8.0
    assert total(ctx, {"self_spans": ["plan.build"]}) == 3.0
    assert total(ctx, {"self_spans": ["plan.build"],
                       "first_of": ["epoch_args"]}) == 3.5
    assert total(ctx, {"spans": ["nothing"]}) is None
    assert program_spans.program_span_median(
        ctx, {"span": "epoch_args", "scale": 1000.0}) == pytest.approx(3.0)
    assert program_spans.program_span_median(ctx, {"span": "enqueue"}) is None
    assert program_spans.totals(ctx["program_spans"], "window") == {
        "epoch_args": [3, pytest.approx(0.009)]}


def test_epoch_of_puts_a_perf_counter_reading_on_the_spans_clock():
    assert program_spans.epoch_of(time.perf_counter()) == pytest.approx(
        time.time(), abs=0.05)
