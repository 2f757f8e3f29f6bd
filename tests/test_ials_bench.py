"""Implicit-feedback ALS against its plain reference, and the benchmark
cell ``ials-ml20m.sweeps`` rehearsed on the CPU.

Tiny sizes (301 users x 97 movies, rank 8, 64 ratings a worker a step) on
1 and on 4 virtual devices. What is checked is correctness and counts: the
files the cell is made of (``spec.validate`` from here, where the driver's
test command reaches), the runner's whole path for the cell, that a call
of the timed entry reads nothing back from the device, the program against
``perfbench/lib/reference/ials_normal_eq.py`` step for step over both
sweeps (ids with no rating, one id that every rating names, padding steps
of weight 0), the bfloat16 control, and that the exact iALS objective
never rises from sweep to sweep. No rate is read: a CPU run has none.
"""

import contextlib
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fps_tpu.ops as ops
from perfbench.lib import check, resolve, runner, spec, window

CELL = "ials-ml20m.sweeps"
NU, NI, K, B = 301, 97, 8, 64
TINY = {"model": {"num_users": NU, "num_items": NI, "rank": K,
                  "local_batch": B, "steps_per_chunk": 8},
        "data": {"num_users": NU, "num_items": NI, "num_ratings": 9001,
                 "ratings_resident": 9001, "user_shift": 3.0,
                 "item_shift": 2.0}}
# float32 on both sides; what differs is the order of an id's sums (the
# store's scatter against the reference's) and the solve (Cholesky against
# LU), through systems whose condition number is a few units: a few ulp.
# bfloat16 (8 bits) reads 1e-2 and more.
F32_GAP = 5e-5


def tiny_cell():
    loaded = spec.load_cell(spec.load_benchmark(), CELL)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY.items():
        cfg[part].update(over)
    loaded["config"] = cfg
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


def build(n, seed=7, data=None):
    loaded = tiny_cell()
    cfg, traffic = loaded["config"], loaded["traffic"]
    with mesh_devices(n):
        if data is None:
            data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
        else:
            data_sum = None
        system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    return loaded, system, resolve.reference(cfg).init_tables(seed, cfg), \
        data, data_sum


# -- the files -------------------------------------------------------------

def test_spec_validates_the_committed_benchmark_files():
    bench = spec.load_benchmark()
    spec.validate(bench)
    cell = spec.load_cell(bench, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["traffic"]["epochs_per_call"] == 2
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "examples_per_s"}
    assert {"solver.gram_ms_per_step", "solver.solve_ms_per_step",
            "solver.accumulate_routes_in_program",
            "solver.grouped_routes_in_program", "solver.half_epoch_ms",
            "kernel.xla_scatter_ms_per_step", "kernel.xla_gather_ms_per_step",
            "kernel.rowop_roofline", "device.peak_hbm_gb"} <= set(
        cell["readers"])
    # Read nothing on this entry: no Trainer, no fps.ingest in the step.
    assert not {"ingest.device_ms_per_step", "driver.enqueue_ms"} & set(
        cell["readers"])
    cfg = cell["config"]
    m, d = cfg["model"], cfg["data"]
    # The data set's shape, unchanged; at most the resident ratings cut.
    assert (m["num_users"], m["num_items"], m["rank"], m["dtype"]) == (
        138_493, 26_744, 64, "float32")
    assert (d["num_users"], d["num_items"], d["num_ratings"]) == (
        138_493, 26_744, 20_000_263)
    assert 2 ** 23 <= d["ratings_resident"] <= d["num_ratings"]
    assert cfg["reduced"] == ([] if d["ratings_resident"] == d["num_ratings"]
                              else ["ratings_resident"])
    assert {"rank", "alpha", "users", "movies", "rating"} <= set(
        cfg["assumed"])
    assert cfg["guarantees"] and cfg["step"] and cfg["departures"]
    # Per step of a sweep: two gathers of rank-wide rows, one push of
    # rank x rank wide rows, one of rank-wide ones; a traced step is one
    # of each sweep; row_bytes the rows' mean by count.
    rows = 2 * 4 * m["local_batch"]
    total = 2 * 4 * m["local_batch"] * (3 * m["rank"] + m["rank"] ** 2)
    assert cfg["rowops"]["rows_per_worker_step"] == rows
    assert cfg["rowops"]["row_bytes"] * rows == total
    assert set(cfg["limits"]) == {"examples", "feed", "loss_gap"} | {
        f"{gap}.{t}" for gap in ("table_gap", "update_gap")
        for t in ("user_factors", "item_factors", "normal_lhs",
                  "normal_rhs")}
    assert all(cfg["limits"][f"{gap}.{t}"] == 0
               for gap in ("table_gap", "update_gap")
               for t in ("normal_lhs", "normal_rhs"))


# -- the runner's whole path -------------------------------------------------

@pytest.mark.parametrize("n,seed", [(1, 11), (4, 2_147_484_123)])
def test_cell_rehearsal_runs_the_runners_whole_path(n, seed):
    """The benchmark's own path for the cell (data, system, seeded state,
    warm-up, queue-ahead window, comparison) at a tiny size; the limits
    are the committed file's."""
    events = []
    ops.clear_routes()
    with mesh_devices(n):
        result = runner.run_cell(
            tiny_cell(), seed=seed, seconds=0.3, trace=False,
            t_start=time.perf_counter(),
            emit=lambda event, **f: events.append(dict(event=event, **f)),
            out_dir="unused")
    compared = {e["number"]: e["value"] for e in events
                if e["event"] == "compared"}
    assert result["correct"], compared
    assert compared["examples"] == 0 and compared["feed"] == 0
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "examples_per_s"}
    assert set(compared) == set(tiny_cell()["config"]["limits"]) | {
        "programs_lowered_in_window"}
    # One accumulate and one solve program a side, traced once each.
    routes = [(r.route, r.reason) for r in ops.routes_traced()
              if r.op == "als"]
    assert routes == [("als.grouped", "user"), ("als.solve", "cholesky"),
                      ("als.grouped", "item"), ("als.solve", "cholesky")]
    readings = next(e for e in events if e["event"] == "readings")
    # Every call reads every resident rating twice.
    assert readings["window_examples"] == 2 * 9001 * readings["n"]


@pytest.mark.parametrize("recorder", [False, True])
def test_a_call_reads_nothing_back_from_the_device(monkeypatch, recorder):
    """``System.call`` (two sweeps of ``IALSSolver.half_epoch``) only
    queues, with a process-default recorder installed (a traced run) as
    without: with every way a device value reaches the host, and every
    wait for the device, made to raise ON THE CALLING THREAD, two calls go
    through, and the metrics come back afterwards. (The CPU backend
    honours no device-to-host transfer guard, so the array's own host
    accessors are what is guarded; the guard is set all the same.) A call
    that returned FINISHED under a recorder hung a traced run of the cell
    on the chip: the runner stops its profiler from inside its wait for a
    call. Under a recorder the sweeps' completion is the watcher thread's
    to wait for (``obs.timing.watch_device``): its wait is held back here
    until both calls have returned, so the four ``device.als.half_epoch``
    spans arrive AFTER them, and no caller waited."""
    import threading

    from jax._src import array

    from fps_tpu import obs
    from fps_tpu.obs import events

    _, system, init, _, _ = build(1)
    state = system.place(init)
    state, _ = window.queue_call(system, state)  # compiles
    jax.block_until_ready(state)

    caller = threading.current_thread()
    returned = threading.Event()

    def guarded(real):
        def wait_or_read(*a, **k):
            if threading.current_thread() is caller:
                raise AssertionError(
                    "a device value was read inside a sweep")
            assert returned.wait(60)  # the watcher's thread: not before
            return real(*a, **k)      # the calls have returned
        return wait_or_read

    def device_spans():
        return [e for e in sink.events("span")
                if e["span"] == "device.als.half_epoch"]

    sink = obs.MemorySink(capacity=1 << 10)
    with monkeypatch.context() as m, \
            jax.transfer_guard_device_to_host("disallow"):
        if recorder:
            events.set_default_recorder(obs.Recorder(sinks=[sink]))
        m.setattr(array.ArrayImpl, "_value",
                  property(guarded(array.ArrayImpl._value.fget)))
        m.setattr(array.ArrayImpl, "__array__",
                  guarded(array.ArrayImpl.__array__))
        m.setattr(array.ArrayImpl, "block_until_ready",
                  guarded(array.ArrayImpl.block_until_ready))
        m.setattr(jax, "block_until_ready", guarded(jax.block_until_ready))
        try:
            state, _ = window.queue_call(system, state)
            state, third = window.queue_call(system, state)
            assert not device_spans()
            watchers = [t for t in threading.enumerate()
                        if t.name == "fps-device-watcher"]
            assert len(watchers) == (1 if recorder else 0)
        finally:
            returned.set()
            if recorder:
                # Clearing the recorder drains the watcher: the one place
                # a caller waits for it (a condition, not the device).
                events.set_default_recorder(None)
    host = third.wait().host
    assert len(host) == 2 and all(
        float(np.sum(h["n"])) == 9001 for h in host)
    spans = [e["span"] for e in sink.events("span")]
    assert (spans.count("als.half_epoch") == 4) is recorder
    assert len(device_spans()) == (4 if recorder else 0)
    if recorder:
        # One accumulate program a chunk: ceil(steps / steps_per_chunk).
        T = int(system.plan.steps_per_epoch)
        assert spans.count("als.accumulate") == 4 * -(-T // 8)
        assert spans.count("als.gram") == spans.count("als.solve") == 4
        # Each sweep's device span hangs under the sweep that queued it,
        # in queue order; the steps are the sweep's chunks' (padded).
        roots = [e for e in sink.events("span")
                 if e["span"] == "als.half_epoch"]
        assert [e["parent_id"] for e in device_spans()] == [
            r["span_id"] for r in roots]
        assert [e["solve"] for e in device_spans()] == [
            "user", "item"] * 2
        assert all(e["steps"] == 8 * -(-T // 8) for e in device_spans())


# -- the program against the reference ---------------------------------------

def _planted(seed, every=3, hot_user=5):
    """Ratings with users 290-300 and movies 90-96 absent, user
    ``hot_user`` in a third of the rows and a row count that leaves the
    last steps padded."""
    rng = np.random.default_rng(seed)
    n = 5003
    user = rng.integers(0, 290, n).astype(np.int32)
    user[::every] = hot_user
    return {"user": user,
            "item": rng.integers(0, 90, n).astype(np.int32),
            "rating": (rng.integers(1, 11, n) * 0.5).astype(np.float32)}


@pytest.fixture(scope="module", params=[1, 4])
def compared_call(request):
    """One call (both sweeps) of the timed entry from seeded random
    tables, and the reference's replay of it."""
    n = request.param
    data = _planted(3)
    loaded, system, init, _, _ = build(n, seed=5, data=data)
    rng = np.random.default_rng(9)
    init = dict(init,
                user_factors=jnp.asarray(
                    rng.uniform(-0.5, 0.5, (NU, K)), jnp.float32),
                item_factors=jnp.asarray(
                    rng.uniform(-0.5, 0.5, (NI, K)), jnp.float32))
    state, warm = window.queue_call(system, system.place(init))
    host = warm.wait().host
    program = system.export(*state)
    ref, ref_loss, ref_n, _ = check.run_reference(system, loaded["config"],
                                                  init)
    return system, data, init, program, host, ref, ref_loss, ref_n


def test_program_agrees_with_the_reference_step_for_step(compared_call):
    system, data, init, program, host, ref, ref_loss, ref_n = compared_call
    n = np.concatenate([h["n"] for h in host])
    loss = np.concatenate([h["loss"] for h in host])
    T = int(system.plan.steps_per_epoch)
    assert n.shape == loss.shape == (2 * T,) == ref_n.shape
    # Per-step counts are exact, both sweeps see every rating once, and
    # the steps past the data are padding of weight 0.
    np.testing.assert_array_equal(n, ref_n)
    assert n[:T].sum() == n[T:].sum() == len(data["user"])
    assert n[T - 1] < system.W * B
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5)
    for name in ("user_factors", "item_factors"):
        r = np.asarray(ref[name])
        np.testing.assert_allclose(program[name], r,
                                   atol=F32_GAP * np.abs(r).max())
    # An id with no rating is solved against the Gramian and the
    # regulariser alone: its row is zero, on both sides.
    assert not np.asarray(program["user_factors"])[290:].any()
    assert not np.asarray(program["item_factors"])[90:].any()
    assert not np.asarray(ref["user_factors"])[290:].any()
    # The id every third rating names is solved like any other.
    assert np.abs(program["user_factors"][5]).max() > 0
    # The reference's sums are zero again after the call, as the adapter
    # answers for them.
    assert not np.asarray(ref["normal_lhs"]).any()
    assert not np.asarray(ref["normal_rhs"]).any()
    assert not program["normal_lhs"].any()


def test_second_sweep_reads_the_first_sweeps_solve(compared_call):
    """The item sweep's loss is under the users the user sweep solved, not
    the seeded ones: a wrong solve shows in ``loss_gap`` within one call."""
    system, data, init, program, host, *_ = compared_call
    U0 = np.asarray(init["user_factors"], np.float64)
    V0 = np.asarray(init["item_factors"], np.float64)
    c = 1.0 + 40.0 * data["rating"].astype(np.float64)

    def observed(U, V):
        return float(np.sum(c * (1.0 - np.sum(
            U[data["user"]] * V[data["item"]], axis=-1)) ** 2))

    np.testing.assert_allclose(host[0]["loss"].sum(dtype=np.float64),
                               observed(U0, V0), rtol=1e-5)
    # The item table the call leaves was solved in the second sweep; the
    # loss that sweep reports is under the table it FOUND.
    solved_u = np.asarray(program["user_factors"], np.float64)
    np.testing.assert_allclose(host[1]["loss"].sum(dtype=np.float64),
                               observed(solved_u, V0), rtol=1e-4)


def test_every_compared_number_is_inside_a_float32_gap(compared_call):
    system, data, init, program, host, ref, ref_loss, ref_n = compared_call
    numbers = check.compare(
        program, ref, init, np.concatenate([h["loss"] for h in host]),
        np.concatenate([h["n"] for h in host]), ref_loss, ref_n, 0, 0,
        system.examples_per_call)
    assert numbers["examples"] == 0
    for k, v in numbers.items():
        assert v <= (0 if "normal_" in k else F32_GAP), (k, numbers)


def test_bf16_control_fails_the_comparison():
    loaded, system, init, _, data_sum = build(1, seed=5)
    cfg = loaded["config"]
    ref, ref_loss, ref_n, feed = check.run_reference(system, cfg, init)
    lt, low_loss, low_n, low_feed = check.run_reference(
        system, cfg, init, dtype=jnp.bfloat16)
    low = check.compare(
        {k: np.asarray(v, np.float32) for k, v in lt.items()}, ref, init,
        low_loss, low_n, ref_loss, ref_n, low_feed,
        check.call_checksum(system, data_sum), system.examples_per_call)
    assert low["examples"] == 0 and low["feed"] == 0
    within, _ = check.judge(low, cfg["limits"])
    assert not within, low
    worst = max(v for k, v in low.items() if k not in ("examples", "feed"))
    assert worst > 100 * F32_GAP, low


def test_objective_never_rises_from_sweep_to_sweep():
    """The exact iALS objective (every unobserved pair through the
    Gramian, no sampling) after each of six sweeps of the timed entry:
    each sweep minimises it exactly in one side, so it cannot rise."""
    loaded, system, init, data, _ = build(4, seed=13)
    m = loaded["config"]["model"]
    alpha, reg = m["alpha"], m["reg"]
    c = 1.0 + alpha * data["rating"].astype(np.float64)

    def objective(tables):
        t = system.export(tables, ())
        U = np.asarray(t["user_factors"], np.float64)
        V = np.asarray(t["item_factors"], np.float64)
        p = np.sum(U[data["user"]] * V[data["item"]], axis=-1)
        every_pair = np.sum((U.T @ U) * (V.T @ V))  # sum over (u, i) of p^2
        return (np.sum(c * (1.0 - p) ** 2) - np.sum(p * p) + every_pair
                + reg * (np.sum(U * U) + np.sum(V * V)))

    tables, _ = system.place(init)
    system.epochs_per_call = 1  # one sweep a call, to look between them
    seen = [objective(tables)]
    for _ in range(6):
        tables, _, _ = system.call(tables, ())
        seen.append(objective(tables))
    assert all(b <= a * (1 + 1e-6) for a, b in zip(seen, seen[1:])), seen
    assert seen[-1] < 0.5 * seen[0], seen
