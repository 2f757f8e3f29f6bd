"""The per-layer metrics that read the program's device spans (ISSUE 38):
the two readers found by name (``perfbench/readers/span_event_median.py``,
``span_event_share.py``) on hand-made contexts, the committed benchmark
files with the four new metrics in the cells named, and the whole way from
the program to a metric on the CPU: a recorder installed the way a traced
run installs it, three calls of a tiny cell queued through
``window.queue_call``, the sink collected, the metrics read through
``readers.read_all``. What is checked is counts and identities of the
spans' own numbers: a CPU run has no rate and no device time to report.
The second half does the same for the metrics that read the device's MEMORY
off those spans (ISSUE 53).
"""

import contextlib
import copy
import statistics

import jax
import pytest

from fps_tpu.obs import events, timing
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


def three_queued_calls(cell, placed=lambda system: None):
    """A tiny form of ``cell`` under a recorder installed the way a traced
    run installs it, three calls queued through ``window.queue_call`` and
    waited for; ``placed(system)`` runs once the state is on the device.
    Returns ``(loaded cell, the recorder's sink)``."""
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
        placed(system)
        calls = []
        for _ in range(3):
            state, completion = window.queue_call(system, state)
            calls.append(completion)
        for c in calls:
            c.wait()
    finally:
        events.set_default_recorder(None)  # drains the watcher
    return loaded, sink


@pytest.mark.parametrize("cell", ["ials-ml20m.sweeps", "mf-netflix.epochs"])
def test_three_queued_calls_read_as_the_cells_device_span_metrics(cell):
    loaded, sink = three_queued_calls(cell)
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


# ---------------------------------------------------------------------------
# The device's MEMORY off the same spans (ISSUE 53): the reader
# ``perfbench/readers/span_field_stat.py`` on hand-made contexts, the
# committed files with the four metrics in all ten cells, and from the
# program to a metric under a fake ``device_bytes`` (the CPU's
# ``memory_stats()`` is ``None``: a CPU run has no device memory to report).
# ---------------------------------------------------------------------------

MEMORY = ["device.resident_gb", "device.call_held_gb",
          "device.program_temp_gb", "device.span_peak_hbm_gb"]
CALLS = ["run_indexed", "fit_stream", "run_megastep", "als.half_epoch"]

# -- the reader, on hand-made contexts ---------------------------------------

ROOTS = [
    _span("als.half_epoch", 0.0, 0.1, solve="user", hbm_open=10, hbm_close=40),
    _span("als.half_epoch", 0.2, 0.3, solve="item", hbm_open=40, hbm_close=50),
    _span("run_indexed", 0.4, 0.5, hbm_open=50, hbm_close=57),
    _span("als.half_epoch", 0.6, 0.7, solve="user", hbm_open=60, hbm_close=92),
    _span("run_indexed", 0.8, 0.9, hbm_open=70),           # never closed
    _span("device.run_indexed", 0.5, 1.5, hbm_peak=80, hbm_done=5),
    _span("device.als.half_epoch", 1.5, 2.5, hbm_peak=95, hbm_done=6),
    _span("enqueue", 0.45, 0.46),
]
HELD = {"span": CALLS, "minus": ["hbm_close", "hbm_open"]}


@pytest.mark.parametrize("params,expected", [
    # Each stat over a field; the events of several names in t0 order.
    (dict(span=CALLS, field="hbm_open", stat="first"), 10),
    (dict(span=CALLS, field="hbm_open", stat="max"), 70),
    (dict(span=CALLS, field="hbm_open", stat="median"), 50),
    (dict(span="run_indexed", field="hbm_open", stat="first", scale=0.5), 25),
    # ``minus``: a difference an event; one that lacks a side is passed over.
    (dict(HELD, stat="median"), statistics.median([30, 10, 7, 32])),
    (dict(HELD, stat="max"), 32),
    (dict(HELD, span="run_indexed", stat="median"), 7),
    # ``where`` picks among the events that CARRY the field: the root spans
    # of the other entry, which have no ``solve``, stay in.
    (dict(HELD, where={"solve": "user"}, stat="median"),
     statistics.median([30, 7, 32])),
    (dict(HELD, span="als.half_epoch", where={"solve": "item"}), 10),
    (dict(HELD, span="als.half_epoch", where={"solve": "neither"}), None),
    # A name ending in ``*`` is a prefix; the default stat is the median.
    (dict(span="device.*", field="hbm_peak", stat="max", scale=1e-1), 9.5),
    (dict(span="device.*", field="hbm_done"), 5.5),
    # Nothing to read: another part, no such span, no such field.
    (dict(span=CALLS, field="hbm_open", part="setup"), None),
    (dict(span="program.memory", field="temp_bytes", stat="max"), None),
    (dict(span="enqueue", field="hbm_open", stat="first"), None),
])
def test_span_field_stat_reads_one_statistic_of_one_number(params, expected):
    got = readers.reader("span_field_stat")(_ctx(*ROOTS), params)
    assert got == (None if expected is None else pytest.approx(expected))


def test_span_field_stat_reads_none_where_the_spans_carry_no_bytes():
    """A parent commit's spans (or the CPU's) carry no ``hbm_*`` field and
    there is no ``program.memory`` span: every one of the four metrics is
    left out of the line, nothing raises."""
    bare = _ctx(_span("run_indexed", 0.0, 1.0, call=0),
                _span("device.run_indexed", 0.0, 2.0, t_enqueued=0.0))
    files = spec.load_cell(spec.load_benchmark(),
                           "mf-netflix.epochs")["readers"]
    for ctx in ({}, {"program_span_events": {}}, bare,
                _ctx(*ROOTS, part="after")):
        assert readers.read_all({n: files[n] for n in MEMORY}, ctx) == {}


# -- the files ----------------------------------------------------------------

def test_committed_benchmark_lists_the_four_metrics_in_all_ten_cells():
    bench = spec.load_benchmark()
    spec.validate(bench)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) >= 10
    memory = [m for m in bench["per_layer"] if m["name"] in MEMORY]
    assert [m["name"] for m in memory] == MEMORY
    peak = next(m for m in bench["per_layer"]
                if m["name"] == "device.peak_hbm_gb")
    for m in memory:
        assert m == dict(peak, name=m["name"], source="program_span",
                         workloads=cells)
    for cell in cells:
        files = spec.load_cell(bench, cell)["readers"]
        assert all(files[n]["reader"] == "span_field_stat" for n in MEMORY)
    held = files["device.call_held_gb"]["params"]
    assert held["where"] == {"solve": "user"} and held["span"] == CALLS


# -- from the program to the metric, as a traced run goes --------------------

GB = 10 ** 9


class _Allocator:
    """Stands for ``timing.device_bytes``: ``in_use`` is what the test set,
    the peak the most it ever read."""

    def __init__(self):
        self.in_use = self.peak = 0

    def __call__(self, where=None):
        self.peak = max(self.peak, self.in_use)
        return timing.DeviceBytes(self.in_use, self.peak, 16 * GB)


@pytest.mark.parametrize("cell", ["ials-ml20m.sweeps", "mf-netflix.epochs"])
def test_three_queued_calls_read_as_the_cells_memory_metrics(
        cell, monkeypatch):
    alloc = _Allocator()
    monkeypatch.setattr(timing, "device_bytes", alloc)

    def placed(system):
        alloc.in_use = 3 * GB            # what set-up left resident
        real_call = system.call

        def call(*args):                 # every call queued holds 2 GB more
            out = real_call(*args)
            alloc.in_use += 2 * GB
            return out

        monkeypatch.setattr(system, "call", call)

    loaded, sink = three_queued_calls(cell, placed)
    # The warm-up call is set-up, the two behind it the window.
    whole = program_spans.collect_events(sink, 0.0, float("inf"))
    entry = "als.half_epoch" if cell.startswith("ials") else "run_indexed"
    roots = whole[entry]["window"]
    opened_at = roots[2 if cell.startswith("ials") else 1]["t0"]
    ctx = {"program_span_events": program_spans.collect_events(
        sink, opened_at, float("inf"))}
    got = readers.read_all({n: loaded["readers"][n] for n in MEMORY}, ctx)
    assert set(got) == set(MEMORY) and all(
        v["unit"] == "GB" for v in got.values())
    assert got["device.resident_gb"]["value"] == 3.0
    # The test's ``call`` adds its 2 GB after the entry point returned: the
    # root spans of a call see what the calls before it left (an ALS call
    # is two sweeps, the second opening where the first closed).
    assert got["device.call_held_gb"]["value"] == 0.0
    assert [e["hbm_open"] // GB for e in roots] == (
        [3, 3, 5, 5, 7, 7] if cell.startswith("ials") else [3, 5, 7])
    assert all(e["hbm_limit"] == 16 * GB for e in roots)
    assert got["device.span_peak_hbm_gb"]["value"] == pytest.approx(
        alloc.peak / GB)
    programs = whole["program.memory"]["window"]
    assert got["device.program_temp_gb"]["value"] == pytest.approx(
        max(e["temp_bytes"] for e in programs) / GB)
    assert got["device.program_temp_gb"]["value"] > 0
    # Every program the cell's entry builds was read once, by the warm-up
    # call, before the window.
    assert all(e["t1"] <= opened_at for e in programs)
    labels = sorted(e["label"] for e in programs)
    assert len(labels) == len(set(labels)) >= 2
