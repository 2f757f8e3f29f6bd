"""CPU rehearsal: every cell's configuration at a tiny size through the
runner's own functions, on 1 and on 4 virtual devices.

Checks control flow only — the queue-ahead loop, the seeded state handed
to program and reference alike, the comparison passing in float32 and
FAILING for the bfloat16 control and for a timed path broken underneath.
Nothing here prints a rate or a device metric: a CPU run has none.
"""

import contextlib
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import check, resolve, runner, spec, window

TINY = {
    "mf-netflix": {
        "model": {"num_users": 1201, "num_items": 97, "local_batch": 256},
        "data": {"num_users": 1201, "num_items": 97, "num_ratings": 40013},
    },
    "pa-rcv1": {
        "model": {"num_features": 997, "local_batch": 128,
                  "head_features": 64, "head_prefix_cols": 4},
        "data": {"num_features": 997, "num_docs": 5003, "nnz": 16,
                 "head_features": 64, "head_prefix_cols": 4},
    },
}
# Interpreted Pallas kernels (CPU) round like the compiled ones; tiny
# tables leave the f32 gaps far under these, and bf16 far over.
LIMITS = {"loss_gap": 2e-3, "table_gap": 2e-3, "update_gap": 2e-3}


def tiny_cell(workload: str):
    bench = spec.load_benchmark()
    loaded = spec.load_cell(bench, workload)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY[cfg["name"]].items():
        cfg[part].update(over)
    cfg["limits"] = {k: (LIMITS[k.split(".")[0]] if k.split(".")[0] in LIMITS
                         else v) for k, v in cfg["limits"].items()}
    if cfg.get("quality"):
        cfg["quality"] = dict(cfg["quality"], target=0.45)
    loaded["config"] = cfg
    loaded["traffic"] = dict(loaded["traffic"], quality_trailing_steps=4)
    return loaded


@contextlib.contextmanager
def mesh_devices(n):
    """``jax.devices()`` cut to ``n`` virtual devices for the body (the
    program builds its mesh from it)."""
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:n]
    try:
        yield
    finally:
        jax.devices = real


def run(workload, n_devices, seed=11):
    events = []
    loaded = tiny_cell(workload)
    with mesh_devices(n_devices):
        result = runner.run_cell(
            loaded, seed=seed, seconds=0.3, trace=False,
            t_start=time.perf_counter(),
            emit=lambda event, **f: events.append(dict(event=event, **f)),
            out_dir="unused")
    return loaded, result, events


CELLS = [("mf-netflix.epochs", 1), ("mf-netflix.x4", 4),
         ("pa-rcv1.epochs", 1), ("pa-rcv1.epochs", 4)]


def test_validate_accepts_the_committed_files():
    spec.validate(spec.load_benchmark())


@pytest.mark.parametrize("workload,n", CELLS)
def test_cell_runs_and_agrees_with_its_reference(workload, n):
    loaded, result, events = run(workload, n)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    compared = [e for e in events if e["event"] == "compared"]
    assert result["correct"], compared
    # Last in the line: every number compared beside its limit.
    assert result["compared"] == {e["number"]: {"value": e["value"],
                                                "limit": e["limit"]}
                                  for e in compared}
    assert result["failed"] == 0 and result["attempted"] >= 2
    want = {m["name"] for m in loaded["end_to_end"]}
    assert set(result["metrics"]) == want
    assert {e["number"] for e in compared} == set(
        loaded["config"]["limits"]) | {"programs_lowered_in_window"}
    readings = next(e for e in events if e["event"] == "readings")
    assert readings["n"] == result["attempted"] - 1 >= 1
    # The end-to-end rate is all the window's examples over all its time,
    # and the time to the target is the count over that rate.
    rate = result["metrics"]["examples_per_s"]["value"]
    assert rate == readings["window_examples"] / readings["window_wall_s"]
    if "time_to_target_s" in want:
        count = next(e for e in events
                     if e["event"] == "quality")["examples_to_target"]
        assert result["metrics"]["time_to_target_s"]["value"] == count / rate


@pytest.mark.parametrize("workload,n", CELLS)
def test_bf16_control_fails_the_comparison(workload, n):
    """The reference in the program's place, in bfloat16, against the
    float32 reference: at least one number passes its limit."""
    loaded = tiny_cell(workload)
    cfg = loaded["config"]
    with mesh_devices(n):
        data, _ = resolve.generator(cfg)(5, cfg["data"])
        system = resolve.system_class(cfg, loaded["traffic"])(
            cfg, loaded["traffic"], data, 5)
        init = resolve.reference(cfg).init_tables(5, cfg)
        ref, loss, n_ref, feed = check.run_reference(system, cfg, init)
        low, low_loss, low_n, _ = check.run_reference(
            system, cfg, init, dtype=jnp.bfloat16)
    numbers = check.compare(
        {k: np.asarray(v, np.float32) for k, v in low.items()}, ref, init,
        low_loss, low_n, loss, n_ref, feed, feed,
        system.examples_per_call)
    ok, rows = check.judge(numbers, cfg["limits"])
    assert not ok, rows


def _skip_updates(system):
    """A step that returns its state unchanged: the call hands back the
    tables it was given (metrics still flow)."""
    real = system.trainer.run_indexed

    def broken(tables, local_state, *a, **kw):
        _, _, metrics = real(jax.tree.map(jnp.copy, tables),
                             jax.tree.map(jnp.copy, local_state), *a, **kw)
        return tables, local_state, metrics

    system.trainer.run_indexed = broken


def _drop_part_of_the_batch(system):
    """Part of the batch left out: the plan feeds weight 0 for the second
    half of every worker's rows."""
    real = system.plan.local_batch_at

    def broken(args, w, t):
        batch = real(args, w, t)
        half = batch["weight"].shape[0] // 2
        return dict(batch, weight=batch["weight"].at[half:].set(0.0))

    system.plan.local_batch_at = broken


@pytest.mark.parametrize("break_system", [_skip_updates,
                                          _drop_part_of_the_batch])
@pytest.mark.parametrize("workload,n", [("mf-netflix.epochs", 1),
                                        ("pa-rcv1.epochs", 1),
                                        ("mf-netflix.x4", 4)])
def test_broken_timed_path_is_not_correct(workload, n, break_system,
                                          monkeypatch):
    """The rest of a run with the timed path broken underneath: the
    adapter the resolver finds is swapped for one that breaks what it
    built."""
    real = resolve.system_class

    def broken_class(cfg, traffic):
        def build(*a, **kw):
            system = real(cfg, traffic)(*a, **kw)
            break_system(system)
            return system
        return build

    monkeypatch.setattr(resolve, "system_class", broken_class)
    _, result, events = run(workload, n)
    assert result["correct"] is False, [
        e for e in events if e["event"] == "compared"]


def test_window_keeps_a_call_queued_ahead_and_stops_on_time():
    """The loop on a fake system: every timed call is a reading, a call is
    always queued before the one before it is waited for, and none starts
    after the window's end."""
    log = []

    class Fake:
        calls = 0

        def call(self, tables, local_state):
            self.calls += 1
            log.append(("queue", self.calls))
            return tables, local_state, [{"n": np.array([10.0])}]

    class Done(window.Completion):
        def wait(self, poll=None):
            if self.host is None:
                time.sleep(0.05)
                log.append(("wait", None))
                self.host, self.device = self.device, None
                self.done_at = time.perf_counter()
            return self

    fake = Fake()
    real = window.Completion
    window.Completion = Done
    try:
        state, warm = window.queue_call(fake, ({}, {}))
        state, first = window.queue_call(fake, state)
        state, t0, done = window.run_window(fake, state, warm, first, 0.22)
    finally:
        window.Completion = real
    assert len(done) == fake.calls - 1
    assert 4 <= len(done) <= 6
    # never two waits without a queue in between while the window is open
    kinds = [k for k, _ in log]
    assert "wait,wait,wait" not in ",".join(kinds[:-2])
    rates = window.readings(t0, done)
    assert len(rates) == len(done) and all(r > 0 for r in rates)


def test_traced_calls_come_after_the_window_and_cover_the_span(monkeypatch):
    """``run_traced`` on a fake system: the profiler starts once, stops
    once in the middle of a call, every queued call is waited for, and
    calls are queued ahead to cover the traced span."""
    log = []

    class Ready:
        def __init__(self, at):
            self.at = at

        def is_ready(self):
            return time.perf_counter() >= self.at

    class Fake:
        free_at = 0.0

        def call(self, tables, local_state):
            start = max(time.perf_counter(), self.free_at)
            self.free_at = start + 0.05
            log.append(("queue", start))
            return tables, local_state, [{"n": Ready(self.free_at)}]

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: log.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: log.append(("stop", time.perf_counter())))
    monkeypatch.setattr(jax, "device_get", lambda m: m)
    fake = Fake()
    t0 = time.perf_counter()
    window.run_traced(fake, ({}, {}), 0.12, 0.05, "unused")
    kinds = [k for k, _ in log]
    assert kinds[0] == "start" and kinds.count("start") == 1
    assert kinds.count("stop") == 1
    stop_at = dict(log)["stop"]
    assert 0.12 <= stop_at - t0 < 0.2
    # the device had work until the profiler stopped, and every call ended
    assert fake.free_at >= stop_at
    assert time.perf_counter() >= fake.free_at
    assert 3 <= kinds.count("queue") <= 5


@pytest.mark.parametrize("call_s", [0.03, 0.0])
def test_traced_calls_that_return_finished_still_stop_the_profiler(
        monkeypatch, call_s):
    """An entry whose call returns only when its work is done (a streamed
    entry; a span that waits for the device under a recorder, PR 35): the
    wait's loop, and with it its poll, is never entered. ``run_traced``
    polls before each wait too, so the profiler stops exactly once and the
    function returns; without that it queued calls for ever."""
    log = []

    class Ready:
        def is_ready(self):
            return True

    class Fake:
        calls = 0

        def call(self, tables, local_state):
            self.calls += 1
            if self.calls > 10_000:
                raise AssertionError("run_traced never stopped queueing")
            time.sleep(call_s)  # blocks: finished when it returns
            return tables, local_state, [{"n": Ready()}]

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: log.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: log.append(("stop", time.perf_counter())))
    monkeypatch.setattr(jax, "device_get", lambda m: m)
    fake = Fake()
    t0 = time.perf_counter()
    window.run_traced(fake, ({}, {}), 0.1, max(call_s, 1e-3), "unused")
    assert [k for k, _ in log] == ["start", "stop"]
    assert 0.1 <= dict(log)["stop"] - t0 < 0.1 + 2 * call_s + 0.05
    assert fake.calls >= 2


def test_traffic_like_lays_its_keys_over_the_mix_it_names():
    base, x4 = spec.load_traffic("epochs"), spec.load_traffic("x4")
    assert x4["name"] == "x4" and x4["like"] == "epochs"
    assert {k: v for k, v in x4.items()
            if k not in ("name", "like", "what")} == {
                k: v for k, v in base.items() if k not in ("name", "what")}


def test_route_groups_hold_their_stated_shares_for_every_seed():
    """Queue lengths are constants of the compiled epoch program: a
    rating's route group follows from its position, so the four counts are
    the same for every seed, and unequal as the configuration states."""
    d = tiny_cell("mf-netflix.x4")["config"]["data"]
    counts = []
    for seed in (3, 2147483659):
        data, _ = resolve.load("data", "mf_ratings").generate(seed, d)
        assert data["user"].min() >= 0
        assert data["user"].max() < d["num_users"]
        counts.append(np.bincount(data["user"] % 4, minlength=4))
    assert (counts[0] == counts[1]).all()
    shares = counts[0] / d["num_ratings"]
    assert np.abs(shares - d["route_group_shares"]).max() < 1e-3
    assert len(set(counts[0])) == 4


def test_examples_to_target_counts_from_fresh_state():
    se = [9.0, 9.0, 4.0, 1.0, 1.0, 1.0]
    n = [1.0] * 6
    q = {"root": True, "target": 1.0}
    assert runner.examples_to_target(se, n, q, 2) == 5.0
    assert runner.examples_to_target(se, n, q, 1) == 4.0
    assert runner.examples_to_target(se, n, {"root": True, "target": 0.5},
                                     2) is None


def _broken(edit):
    bench = copy.deepcopy(spec.load_benchmark())
    edit(bench)
    return bench


@pytest.mark.parametrize("edit", [
    lambda b: b["workloads"][0].update(name="mf netflix"),       # a space
    lambda b: b["end_to_end"][1].update(unit="examples per s"),  # a unit
    lambda b: b["per_layer"][0].update(moves="nothing_s"),
    lambda b: b["per_layer"][3].update(workloads=["pa-rcv1.epochs"]),
    lambda b: b["workloads"].pop(1),                  # a config with no cell
    lambda b: b["workloads"][0].update(traffic="no-such-mix"),
    lambda b: b["per_layer"][1].update(unit="M/s"),   # differs from its file
    lambda b: b["per_layer"].append(dict(b["per_layer"][0], name="no.file")),
], ids=["name", "unit", "moves", "moves-not-reported", "config-without-cell",
        "traffic-file", "reader-unit", "reader-file"])
def test_validate_refuses(edit):
    with pytest.raises(spec.SpecError):
        spec.validate(_broken(edit))
