"""The per-layer metrics that read the program's device spans (ISSUE 38):
the two readers found by name (``perfbench/readers/span_event_median.py``,
``span_event_share.py``) on hand-made contexts, the committed benchmark
files with the four new metrics in the cells named, and the whole way from
the program to a metric on the CPU: a recorder installed the way a traced
run installs it, three calls of a tiny cell queued through
``window.queue_call``, the sink collected, the metrics read through
``readers.read_all``. What is checked is counts and identities of the
spans' own numbers: a CPU run has no rate and no device time to report.
"""

import contextlib
import copy
import statistics

import jax
import pytest

from fps_tpu.obs import events
from perfbench.lib import program_spans, readers, resolve, spec, window

NEW = {"driver.call_device_ms", "driver.starved_share",
       "solver.user_sweep_device_s", "solver.item_sweep_device_s"}
TRAINER_CELLS = ["mf-netflix.epochs", "pa-rcv1.epochs", "mf-netflix.x4",
                 "w2v-1bw.epochs", "lr-criteo.epochs"]
TINY = {
    "mf-netflix": {
        "model": {"num_users": 1201, "num_items": 97, "local_batch": 256},
        "data": {"num_users": 1201, "num_items": 97, "num_ratings": 40013},
    },
    "ials-ml20m": {
        "model": {"num_users": 301, "num_items": 97, "rank": 8,
                  "local_batch": 64, "steps_per_chunk": 8},
        "data": {"num_users": 301, "num_items": 97, "num_ratings": 9001,
                 "ratings_resident": 9001, "user_shift": 3.0,
                 "item_shift": 2.0},
    },
}


def _span(name, t0, t1, **fields):
    return dict(event="span", span=name, t0=t0, t1=t1, **fields)


def _ctx(*spans, part="window"):
    out = {}
    for e in spans:
        out.setdefault(e["span"], {"setup": [], "window": [], "after": []})[
            part].append(e)
    return {"program_span_events": out}


# -- the readers, on hand-made contexts -------------------------------------

SWEEPS = [_span("device.als.half_epoch", 0.0, 7.0, solve="user", steps=5),
          _span("device.als.half_epoch", 7.0, 10.5, solve="item", steps=5),
          _span("device.als.half_epoch", 10.5, 17.7, solve="user", steps=6),
          _span("device.als.half_epoch", 17.7, 21.0, solve="item")]


@pytest.mark.parametrize("params,expected", [
    ({"where": {"solve": "user"}}, statistics.median([7.0, 7.2])),
    ({"where": {"solve": "item"}, "scale": 1000.0},
     1000.0 * statistics.median([3.5, 3.3])),
    ({}, statistics.median([7.0, 3.5, 7.2, 3.3])),
    # Every field of ``where`` has to match; an event without one is out.
    ({"where": {"solve": "user", "steps": 5}}, 7.0),
    ({"where": {"steps": 5}}, statistics.median([7.0, 3.5])),
    ({"where": {"solve": "neither"}}, None),
    ({"part": "setup"}, None),
])
def test_span_event_median_filters_by_field_and_reads_the_length(
        params, expected):
    read = readers.reader("span_event_median")
    got = read(_ctx(*SWEEPS), dict(params, span="device.als.half_epoch"))
    assert got == (None if expected is None else pytest.approx(expected))


STARVED = {"prefix": "device.", "field": "starved_s",
           "ends_at": "t_enqueued", "scale": 100.0}


def test_span_event_share_counts_a_gap_between_spans_and_none_before_them():
    read = readers.reader("span_event_share")
    # Queued ahead: every unit starts at its predecessor's end. The first
    # unit's starved time lies before the extent and is not in the share.
    ahead = [_span("device.run_indexed", 10.0, 12.0, t_enqueued=10.0,
                   starved_s=4.0),
             _span("device.run_indexed", 12.0, 14.0, t_enqueued=10.5,
                   starved_s=0.0),
             _span("device.run_indexed", 14.0, 16.0, t_enqueued=12.5,
                   starved_s=0.0)]
    assert read(_ctx(*ahead), STARVED) == 0.0
    # The host late once: the device had nothing queued for 0.5 s of 6.5.
    late = ahead[:2] + [_span("device.run_indexed", 14.5, 16.5,
                              t_enqueued=14.5, starved_s=0.5)]
    assert read(_ctx(*late), STARVED) == pytest.approx(100 * 0.5 / 6.5)
    # Every device.* name together, and no other span.
    mixed = late + [_span("device.als.half_epoch", 17.0, 18.0,
                          t_enqueued=17.0, starved_s=0.5),
                    _span("run_indexed", 0.0, 30.0)]
    assert read(_ctx(*mixed), STARVED) == pytest.approx(100 * 1.0 / 8.0)


def test_span_readers_return_none_on_a_sink_without_device_spans():
    """A parent commit records no device span: the metric is left out of
    the line, nothing raises."""
    host_only = _ctx(_span("run_indexed", 0.0, 1.0), _span("enqueue", 0, 1))
    for ctx in ({}, {"program_span_events": {}}, host_only,
                _ctx(*SWEEPS, part="after")):
        assert readers.reader("span_event_share")(ctx, STARVED) is None
        assert readers.reader("span_event_median")(
            ctx, {"span": "device.als.half_epoch"}) is None
    assert program_spans.collect_events(None, 0.0, 1.0) == {}


# -- the files ----------------------------------------------------------------

def test_committed_benchmark_lists_the_four_metrics_in_the_cells_named():
    bench = spec.load_benchmark()
    spec.validate(bench)
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert NEW <= set(listed)
    assert all(listed[n]["source"] == "program_span"
               and listed[n]["moves"] == "examples_per_s" for n in NEW)
    # Cells of later PRs are appended (PR 42: the top-K tap's, a Trainer's).
    # PR 44: word2vec under the two-tier storage, a Trainer's too.
    # PR 48: DLRM, a Trainer's with the dense route. PR 51: ComplEx, a
    # Trainer's with the table's own fold.
    later = ["mf-netflix-topk.epochs", "w2v-1bw-hot.x4",
             "dlrm-criteo.epochs", "kge-wikidata5m.epochs"]
    assert listed["driver.call_device_ms"]["workloads"] == (
        TRAINER_CELLS + later)
    assert listed["driver.starved_share"]["workloads"] == TRAINER_CELLS + [
        "ials-ml20m.sweeps"] + later
    for n in ("solver.user_sweep_device_s", "solver.item_sweep_device_s"):
        assert listed[n]["workloads"] == ["ials-ml20m.sweeps"]
    # Appended together, in this order (later PRs append after them).
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("driver.call_device_ms")
    assert names[at:at + 4] == [
        "driver.call_device_ms", "driver.starved_share",
        "solver.user_sweep_device_s", "solver.item_sweep_device_s"]
    for cell in [w["name"] for w in bench["workloads"]]:
        have = NEW & set(spec.load_cell(bench, cell)["readers"])
        assert have == ({"driver.starved_share", "solver.user_sweep_device_s",
                         "solver.item_sweep_device_s"}
                        if cell == "ials-ml20m.sweeps"
                        else {"driver.starved_share",
                              "driver.call_device_ms"})


# -- from the program to the metric, as a traced run goes --------------------

@contextlib.contextmanager
def mesh_devices(n):
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:n]
    try:
        yield
    finally:
        jax.devices = real


@pytest.mark.parametrize("cell", ["ials-ml20m.sweeps", "mf-netflix.epochs"])
def test_three_queued_calls_read_as_the_cells_device_span_metrics(cell):
    loaded = spec.load_cell(spec.load_benchmark(), cell)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY[cfg["name"]].items():
        cfg[part].update(over)
    traffic, seed = loaded["traffic"], 2_147_484_123
    sink = program_spans.install_recorder()  # as runner.run_cell(trace=True)
    try:
        with mesh_devices(1):
            data, _ = resolve.generator(cfg)(seed, cfg["data"])
            system = resolve.system_class(cfg, traffic)(cfg, traffic, data,
                                                        seed)
        state = system.place(resolve.reference(cfg).init_tables(seed, cfg))
        calls = []
        for _ in range(3):
            state, completion = window.queue_call(system, state)
            calls.append(completion)
        for c in calls:
            c.wait()
    finally:
        events.set_default_recorder(None)  # drains the watcher
    everything = (0.0, float("inf"))       # the whole run as the window
    ctx = {"program_spans": program_spans.collect(sink, *everything),
           "program_span_events": program_spans.collect_events(
               sink, *everything)}
    mine = {n: r for n, r in loaded["readers"].items() if n in NEW}
    got = readers.read_all(mine, ctx)
    assert set(got) == set(mine) and len(mine) in (2, 3)  # none is None

    units = 2 if cell == "ials-ml20m.sweeps" else 1       # sweeps a call
    spans = [e for name, parts in ctx["program_span_events"].items()
             if name.startswith("device.") for e in parts["window"]]
    assert len(spans) == 3 * units
    # The device spans and the time the device starved between them tile
    # their extent: each unit starts where the one before ended, or where
    # it was queued if that came later.
    lengths = [e["t1"] - e["t0"] for e in spans]
    starved = sum(e["starved_s"] for e in spans[1:])
    extent = spans[-1]["t1"] - spans[0]["t0"]
    assert sum(lengths) + starved == pytest.approx(extent, abs=1e-6)
    assert got["driver.starved_share"]["value"] == pytest.approx(
        100 * starved / extent, abs=1e-6)
    if cell == "ials-ml20m.sweeps":
        # The two sweeps' seconds sum to the calls': every span is one
        # sweep of one call, user then item.
        assert [e["solve"] for e in spans] == ["user", "item"] * 3
        user, item = lengths[0::2], lengths[1::2]
        assert got["solver.user_sweep_device_s"]["value"] == pytest.approx(
            statistics.median(user))
        assert got["solver.item_sweep_device_s"]["value"] == pytest.approx(
            statistics.median(item))
        assert sum(user) + sum(item) + starved == pytest.approx(
            extent, abs=1e-6)
    else:
        assert got["driver.call_device_ms"]["value"] == pytest.approx(
            1000 * statistics.median(lengths))
        assert got["driver.call_device_ms"]["unit"] == "ms"
        assert [e["epoch"] for e in spans] == [0, 1, 2]
