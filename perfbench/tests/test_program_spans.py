"""The program's own spans and route log, read the way the runner will.

``perfbench/lib/program_spans.py`` is not wired into ``runner.py`` yet (a
PR that is not a ``benchmark`` PR may edit no file the benchmark has), so
these tests do the wiring's four steps themselves — install the recorder
before the system is built, clear and read the route log round the
warm-up call, collect the sink at the window's opening time, hand the
readers a context — on the rehearsal's tiny cells, and read the six
metric files that wait for it. Counts and control flow only: a CPU run
has no device time, and the host times read here are asserted present,
ordered and consistent, never printed.
"""

import json
import os
import time

import jax
import pytest

from perfbench.lib import (check, datagen, program_spans, readers, spec,
                           systems, window)
from test_rehearsal import mesh_devices, tiny_cell

WAITING = ("ops.pallas_routes_in_program", "setup.dataset_s",
           "setup.plan_s", "setup.compile_s", "driver.epoch_args_ms",
           "driver.enqueue_ms")


def metric_file(name):
    with open(os.path.join(spec.HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def read(name, ctx):
    m = metric_file(name)
    fn = {**readers.READERS, **program_spans.READERS}[m["reader"]]
    return fn(ctx, m["params"])


def drive(workload, seed=7, recorder=True):
    """The runner's set-up and window on a tiny cell, with the wiring."""
    from fps_tpu.obs import events

    loaded = tiny_cell(workload)
    cfg, traffic = loaded["config"], loaded["traffic"]
    sink = None
    if recorder:
        _, sink = program_spans.install_recorder()
    try:
        with mesh_devices(1):
            data, _ = datagen.KINDS[cfg["data"]["kind"]](seed, cfg["data"])
            system = systems.KINDS[cfg["model"]["kind"]](
                cfg, traffic, data, seed)
            state = system.place(
                check.load_reference(cfg).init_tables(seed, cfg))
            program_spans.clear_routes()
            state, warm = window.queue_call(system, state)
            routes = program_spans.pallas_routes_in_program()
            state, first = window.queue_call(system, state)
            state, t_open, done = window.run_window(system, state, warm,
                                                    first, 0.2)
            jax.block_until_ready(state)
        spans = program_spans.collect(sink, program_spans.epoch_of(t_open))
    finally:
        events.set_default_recorder(None)
    return {"program_spans": spans, "done": done,
            "counters": {"pallas_routes_in_program": routes}}


@pytest.fixture(scope="module", params=["mf-netflix.epochs",
                                        "pa-rcv1.epochs"])
def driven(request):
    return request.param, drive(request.param)


@pytest.mark.parametrize("name", WAITING)
def test_rehearsal_reports_the_span_and_counter_metrics(driven, name):
    workload, ctx = driven
    value = read(name, ctx)
    assert value is not None and value >= 0.0, (workload, name)
    if name == "ops.pallas_routes_in_program":
        # CPU "auto" keeps every route on XLA; the log is there and says 0,
        # as the monkeypatch counter does.
        assert value == 0.0


def test_spans_fall_on_the_right_side_of_the_windows_opening(driven):
    workload, ctx = driven
    spans = ctx["program_spans"]
    for name in ("dataset.place", "dataset.queues", "plan.build",
                 "init_state"):
        assert spans[name]["setup"] and not spans[name]["window"], name
    # One run_indexed, one epoch_args and one enqueue per queued call; the
    # warm-up call and the first timed call were queued before the window
    # opened.
    calls = len(ctx["done"]) + 1
    for name in ("run_indexed", "epoch_args", "enqueue"):
        both = spans[name]["setup"] + spans[name]["window"]
        assert len(both) == calls, (name, len(both), calls)
        assert len(spans[name]["setup"]) >= 2
    if workload.startswith("mf"):
        assert spans["dataset.pack"]["setup"]  # MF's columns pack, PA's not
    # Everything JAX compiled for the cell, it compiled before the window.
    assert spans["compile.backend"]["setup"]
    assert not spans["compile.backend"]["window"]


def test_plan_self_time_leaves_out_the_dataset_spans_inside_it(driven):
    _, ctx = driven
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
    plan_s = read("setup.plan_s", ctx)
    first = min(spans["epoch_args"]["setup"])
    assert plan_s == pytest.approx(own + first[1] - first[0])


def test_a_program_without_the_spans_reads_nothing_and_raises_nothing():
    """What a parent commit gives: no recorder installed, an empty sink."""
    _, ctx = "mf-netflix.epochs", drive("mf-netflix.epochs", recorder=False)
    assert ctx["program_spans"] == {}
    for name in WAITING[1:]:
        assert read(name, ctx) is None
    assert read("ops.pallas_routes_in_program",
                {"counters": {"pallas_routes_in_program": None}}) is None


def test_self_seconds_and_parts_on_made_up_spans():
    ctx = {"program_spans": {
        "plan.build": {"setup": [(10.0, 20.0)], "window": []},
        "dataset.queues": {"setup": [(11.0, 14.0)], "window": []},
        "dataset.pack": {"setup": [(14.0, 18.0), (30.0, 31.0)],
                         "window": []},
        "epoch_args": {"setup": [(40.0, 40.5), (50.0, 50.25)],
                       "window": [(61.0, 61.002), (62.0, 62.004),
                                  (63.0, 63.003)]},
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


def test_epoch_of_puts_a_perf_counter_reading_on_the_spans_clock():
    assert program_spans.epoch_of(time.perf_counter()) == pytest.approx(
        time.time(), abs=0.05)
