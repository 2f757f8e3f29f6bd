"""Telemetry subsystem (fps_tpu.obs): registry/recorder contracts, sinks,
phase timers, health alerting (monitor escalation + watchdog), run
journal, and the driver wiring.

Acceptance contract (ISSUE 2):

* a logreg run with telemetry attached produces phase timings, per-table
  health totals, and journal events (rendered end-to-end in
  tests/test_obs_report.py);
* HealthMonitor escalation observe→mask is exercised under chaos
  poisoning, and its abort tier raises PoisonedStreamError;
* recorder off ⇒ the compiled program is bit-identical to a
  recorder-attached build (telemetry is host-side only).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from fps_tpu import obs
from fps_tpu.core.driver import Trainer, TrainerConfig, num_workers_of
from fps_tpu.core.resilience import GuardConfig, PoisonedStreamError
from fps_tpu.core.store import ParamStore, TableSpec
from fps_tpu.models.logistic_regression import (
    LogRegConfig,
    logistic_regression,
)
from fps_tpu.obs import events as obs_events
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.testing import chaos
from fps_tpu.testing.workloads import (
    NF,
    logreg_chunks as _logreg_chunks,
    logreg_data as _logreg_data,
    weights as _weights,
)


# ---------------------------------------------------------------------------
# Registry + recorder contracts (pure host, no mesh needed).
# ---------------------------------------------------------------------------

def test_metric_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        obs.MetricSpec("x", "timer")
    with pytest.raises(ValueError, match="name"):
        obs.MetricSpec("a b", "counter")
    reg = obs.MetricsRegistry([obs.MetricSpec("x", "counter")])
    reg.register(obs.MetricSpec("x", "counter"))  # same spec: idempotent
    with pytest.raises(ValueError, match="already registered"):
        reg.register(obs.MetricSpec("x", "gauge"))
    with pytest.raises(KeyError, match="unregistered"):
        reg.get("nope")


def test_recorder_typed_leaves_and_aggregates():
    reg = obs.MetricsRegistry([
        obs.MetricSpec("c", "counter", labels=("table",)),
        obs.MetricSpec("g", "gauge"),
        obs.MetricSpec("h", "histogram"),
    ])
    sink = obs.MemorySink()
    rec = obs.Recorder(reg, sinks=[sink], run_id="r1")
    rec.inc("c", 2, table="a")
    rec.inc("c", 3, table="a")
    rec.inc("c", 1, table="b")
    rec.set("g", 7.5)
    for v in (0.1, 0.3):
        rec.observe("h", v)
    # Typed: wrong kind / unknown name / undeclared label all fail loudly.
    with pytest.raises(TypeError, match="counter"):
        rec.set("c", 1, table="a")
    with pytest.raises(KeyError):
        rec.inc("unknown")
    with pytest.raises(ValueError, match="undeclared"):
        rec.inc("c", 1, shard="a")
    with pytest.raises(ValueError, match="negative"):
        rec.inc("c", -1, table="a")

    assert rec.counter_value("c", table="a") == 5
    snap = rec.snapshot()
    assert snap["counters"]["c{table=b}"] == 1
    assert snap["gauges"]["g"] == 7.5
    h = snap["histograms"]["h"]
    assert h["count"] == 2 and abs(h["sum"] - 0.4) < 1e-9
    assert h["min"] == 0.1 and h["max"] == 0.3
    # Every sample reached the sink, stamped with the run id.
    ms = sink.metrics()
    assert len(ms) == 6 and all(m["run_id"] == "r1" for m in ms)


def test_memory_sink_ring_bound():
    sink = obs.MemorySink(capacity=3)
    for i in range(10):
        sink.write({"kind": "event", "event": "e", "i": i})
    assert [r["i"] for r in sink.records] == [7, 8, 9]


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    sink = obs.JsonlSink(path, flush_every=1)
    sink.write({"kind": "metric", "name": "x", "value": np.float32(1.5)})
    sink.write({"kind": "event", "event": "e", "arr": np.arange(2)})
    sink.close()
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["value"] == 1.5  # numpy degraded to plain JSON
    assert lines[1]["arr"] == [0, 1]


def test_jsonl_sink_nonfinite_is_strict_json(tmp_path):
    """The serving watcher legitimately sets a NaN gauge (orphaned
    snapshot); the JSONL artifact must stay strict JSON — null, never
    the Python-only NaN/Infinity tokens strict parsers reject."""
    path = str(tmp_path / "ev.jsonl")
    sink = obs.JsonlSink(path, flush_every=1)
    sink.write({"kind": "metric", "name": "serve.snapshot_lag_steps",
                "mtype": "gauge", "value": float("nan")})
    sink.write({"kind": "metric", "name": "g", "mtype": "gauge",
                "value": np.float32("inf")})
    sink.write({"kind": "metric", "name": "ok", "value": 2.0})
    sink.close()
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    lines = [json.loads(l) for l in raw.splitlines()]
    assert lines[0]["value"] is None and lines[1]["value"] is None
    assert lines[2]["value"] == 2.0  # finite fast path untouched


def test_prometheus_sink_exposition(tmp_path):
    path = str(tmp_path / "m.prom")
    sink = obs.PrometheusSink(path)
    rec = obs.Recorder(sinks=[sink])
    rec.inc("health.nonfinite_rows", 4, table="weights")
    rec.set("checkpoint.bytes", 1024)
    rec.observe("driver.phase_seconds", 0.25, phase="dispatch")
    rec.flush()
    text = open(path).read()
    assert ('fps_tpu_health_nonfinite_rows{table="weights"} 4' in text)
    assert "# TYPE fps_tpu_health_nonfinite_rows counter" in text
    assert "fps_tpu_checkpoint_bytes 1024" in text
    assert ('fps_tpu_driver_phase_seconds_count{phase="dispatch"} 1'
            in text)
    assert ('fps_tpu_driver_phase_seconds_sum{phase="dispatch"} 0.25'
            in text)


def test_phase_timer_accumulates_and_records():
    rec = obs.Recorder(sinks=[])
    t = obs.PhaseTimer(rec)
    with t.phase("dispatch"):
        pass
    with t.phase("dispatch"):
        pass
    with t.phase("host_sync"):
        pass
    chunk = t.chunk_summary()
    assert set(chunk) == {"dispatch", "host_sync"}
    assert t.chunk_summary() == {}  # reset
    # Run-level totals live on the recorder, the single source of truth.
    assert rec.phase_totals()["dispatch"]["n"] == 2


# ---------------------------------------------------------------------------
# Health monitor + watchdog (pure policy).
# ---------------------------------------------------------------------------

def test_health_monitor_thresholds():
    m = obs.HealthMonitor(escalate_after_rows=10, abort_after_chunks=3)
    assert m.update(0, 0) == obs.HEALTH_OK
    assert m.update(1, 4) == obs.HEALTH_OK
    assert m.update(2, 7) == obs.HEALTH_ESCALATE  # 11 rows >= 10
    assert m.escalated_at == 2
    assert m.update(3, 5) == obs.HEALTH_ABORT  # 3rd poisoned chunk
    assert m.aborted_at == 3
    assert m.log == [(1, 4), (2, 7), (3, 5)]
    with pytest.raises(ValueError):
        obs.HealthMonitor(escalate_after_rows=0)


def test_step_watchdog_flags_and_recovers():
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    seen = []
    wd = obs.StepWatchdog(0.05, on_stall=seen.append, recorder=rec)
    with wd.watch("chunk", 3):
        time.sleep(0.15)
    assert len(wd.stalls) == 1
    assert wd.stalls[0]["index"] == 3
    assert wd.stalls[0]["elapsed_s"] >= 0.1  # recovery recorded real time
    assert seen and seen[0]["what"] == "chunk"
    assert rec.counter_value("watchdog.stalls") == 1
    assert [e["event"] for e in sink.events()] == ["stall",
                                                   "stall_recovered"]
    # Fast region: timer cancelled, nothing fires.
    with wd.watch("chunk", 4):
        pass
    time.sleep(0.08)
    assert len(wd.stalls) == 1


def test_watchdog_callback_exception_swallowed():
    wd = obs.StepWatchdog(0.02, on_stall=lambda info: 1 / 0)
    with wd.watch("chunk", 0):
        time.sleep(0.06)
    assert len(wd.stalls) == 1  # the run survived the broken callback


# ---------------------------------------------------------------------------
# Journal + open_run + process-default events.
# ---------------------------------------------------------------------------

def test_run_journal_keeps_events_only(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = obs.RunJournal(path, run_id="r9", meta={"process": 0})
    j.write({"kind": "metric", "name": "x", "value": 1})
    j.write({"kind": "event", "t": 1.0, "event": "chunk", "index": 0})
    j.close()
    j.close()  # idempotent
    recs = [json.loads(l) for l in open(path)]
    assert [r["event"] for r in recs] == ["run_start", "chunk", "run_end"]
    assert recs[0]["run_id"] == "r9" and recs[0]["process"] == 0


def test_config_digest_stable_and_discriminating():
    a = obs.config_digest({"lr": 0.1, "mesh": (1, 8)})
    assert a == obs.config_digest({"mesh": (1, 8), "lr": 0.1})  # order-free
    assert a != obs.config_digest({"lr": 0.2, "mesh": (1, 8)})
    assert obs.config_digest({"fn": open})  # non-JSON degrades, not raises


def test_open_run_writes_standard_files_and_installs(tmp_path):
    d = str(tmp_path / "obs")
    rec = obs.open_run(d, config={"x": 1}, meta={"workload": "t"})
    try:
        assert obs_events.get_default_recorder() is rec
        rec.inc("driver.chunks")
        obs_events.emit("rollback", index=2, total=1, budget=8)
        rec.flush()
    finally:
        rec.close()
    assert obs_events.get_default_recorder() is None  # uninstalled on close
    names = sorted(os.listdir(d))
    assert names == ["events-p0.jsonl", "journal-p0.jsonl",
                     "metrics-p0.prom"]
    journal = [json.loads(l) for l in
               open(os.path.join(d, "journal-p0.jsonl"))]
    assert journal[0]["event"] == "run_start"
    assert journal[0]["workload"] == "t"
    assert journal[0]["config_digest"] == obs.config_digest({"x": 1})
    assert [r["event"] for r in journal] == ["run_start", "rollback",
                                             "run_end"]
    # Base labels (process identity) ride every series.
    assert 'fps_tpu_driver_chunks{process="0"} 1' in open(
        os.path.join(d, "metrics-p0.prom")).read()


def test_default_recorder_scoped_and_noop():
    obs_events.emit("whatever")  # no recorder installed: silent no-op
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        obs_events.emit("rollback", index=1)
        obs_events.record_metric("inc", "rollback.quarantined", 1)
    assert obs_events.get_default_recorder() is None
    assert [e["event"] for e in sink.events()] == ["rollback"]
    assert sink.metrics("rollback.quarantined")


# ---------------------------------------------------------------------------
# Checkpoint + rollback event emission (the deep-layer trail).
# ---------------------------------------------------------------------------

def test_checkpoint_save_and_fallback_events(tmp_path, devices8):
    from fps_tpu.core.checkpoint import Checkpointer

    mesh = make_ps_mesh(num_shards=1, num_data=1, devices=devices8[:1])
    store = ParamStore(mesh, [TableSpec("t", 16, 2).zeros_init()])
    store.init(jax.random.key(0))
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    with obs_events.default_recorder(rec):
        ckpt = Checkpointer(str(tmp_path / "c"), keep=2)
        ckpt.save(1, store)
        ckpt.save(2, store)
        chaos.corrupt_latest_snapshot(str(tmp_path / "c"), "truncate")
        _, step = ckpt.restore_tables(store)
    assert step == 1
    saves = sink.events("checkpoint_saved")
    assert [e["step"] for e in saves] == [1, 2]
    assert all(e["bytes"] > 0 and e["seconds"] >= 0 for e in saves)
    # The saved event must carry the published path (and the byte size
    # above): the serving plane's SnapshotWatcher opens snapshots straight
    # from these fields, no directory re-stat on the hot path.
    from fps_tpu.core.checkpoint import SNAPSHOT_FMT

    assert [e["path"] for e in saves] == [
        str(tmp_path / "c" / SNAPSHOT_FMT.format(step=s)) for s in (1, 2)
    ]
    fb = sink.events("checkpoint_fallback")
    assert len(fb) == 1 and fb[0]["step"] == 2
    assert rec.counter_value("checkpoint.saves") == 2
    assert rec.counter_value("checkpoint.fallbacks") == 1


# ---------------------------------------------------------------------------
# Driver wiring (multi-device mesh).
# ---------------------------------------------------------------------------

def _poisoned_stream(W, kind="huge", idx=(1,), epochs=1, nchunks=None):
    train, _ = _logreg_data()
    clean = _logreg_chunks(train, W, epochs=epochs)
    if nchunks is not None:
        clean = clean[:nchunks]
    out = iter(clean)
    for i in sorted(idx):
        out = chaos.poison_chunks(out, chunk_index=i, column="feat_vals",
                                  kind=kind, frac=0.5, seed=1)
    return list(out)


def test_fit_stream_records_phases_health_and_events(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    W = num_workers_of(mesh)
    chunks = _poisoned_stream(W, kind="nan", idx=(1,), nchunks=3)
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, store = logistic_regression(mesh, cfg, guard="mask")
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    trainer.recorder = rec
    tables, ls = trainer.init_state(jax.random.key(0))
    trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1),
                       on_chunk=lambda i, m: None)
    assert rec.counter_value("driver.chunks") == 3
    assert rec.counter_value("driver.examples") > 0
    assert rec.counter_value("health.nonfinite_rows", table="weights") > 0
    assert rec.counter_value("health.masked_rows", table="weights") > 0
    assert rec.counter_value("health.poisoned_chunks") == 1
    ev = sink.events("chunk")
    assert [e["index"] for e in ev] == [0, 1, 2]
    assert ev[1]["poison_rows"] > 0 and "poison_rows" not in ev[0]
    for e in ev:
        assert {"ingest", "place", "dispatch", "host_sync",
                "callback"} <= set(e["phases"])
    pt = rec.phase_totals()
    assert pt["dispatch"]["n"] == 3 and pt["dispatch"]["s"] > 0


def test_health_monitor_escalates_observe_to_mask(devices8):
    """ISSUE acceptance: chaos-poisoned stream under guard='observe' +
    HealthMonitor escalates to 'mask' after the row threshold. Paired
    with rollback (the production posture): the pre-escalation poisoned
    chunk is quarantined whole, the post-escalation one is ALSO masked
    in-step — its poison never reaches the fold even before the
    host-loop rollback decision lands."""
    from fps_tpu.core.resilience import RollbackPolicy

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    W = num_workers_of(mesh)
    chunks = _poisoned_stream(W, kind="huge", idx=(1, 3), nchunks=5)
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, store = logistic_regression(
        mesh, cfg, guard=GuardConfig(mode="observe", norm_limit=100.0))
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    monitor = obs.HealthMonitor(escalate_after_rows=1)
    policy = RollbackPolicy(max_rollbacks=4)
    tables, ls = trainer.init_state(jax.random.key(0))
    trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1),
                       recorder=rec, health=monitor, rollback=policy)
    # Escalated exactly at the first poisoned chunk...
    assert monitor.escalated_at == 1
    from fps_tpu.core import resilience
    assert resilience.as_guard(trainer.config.guard).mode == "mask"
    esc = sink.events("guard_escalated")
    assert len(esc) == 1 and esc[0]["index"] == 1
    # ...chunk 1's poison was observed-only, chunk 3's was masked in-step
    # (mask mode still counts, so rollback quarantines both — documented
    # mask+rollback semantics).
    assert rec.counter_value("health.norm_rows", table="weights") > 0
    assert rec.counter_value("health.masked_rows", table="weights") > 0
    assert policy.quarantined == [1, 3]
    assert rec.counter_value("rollback.quarantined") == 2
    assert monitor.poisoned_chunks == 2
    assert np.all(np.isfinite(_weights(store)))


def test_health_monitor_abort_raises(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    W = num_workers_of(mesh)
    chunks = _poisoned_stream(W, kind="nan", idx=(0, 1, 2), nchunks=3)
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, _ = logistic_regression(mesh, cfg, guard="mask")
    sink = obs.MemorySink()
    monitor = obs.HealthMonitor(abort_after_chunks=2)
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(PoisonedStreamError, match="health monitor abort"):
        trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1),
                           recorder=obs.Recorder(sinks=[sink]),
                           health=monitor)
    assert monitor.poisoned_chunks == 2
    assert sink.events("health_abort")


def test_health_monitor_requires_guard(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, _ = logistic_regression(mesh, cfg)  # no guard
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="health channel"):
        trainer.fit_stream(tables, ls, iter([]), jax.random.key(1),
                           health=obs.HealthMonitor())
    with pytest.raises(TypeError, match="HealthMonitor"):
        trainer.fit_stream(tables, ls, iter([]), jax.random.key(1),
                           health=object())


def test_watchdog_clean_run_no_stalls(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    W = num_workers_of(mesh)
    train, _ = _logreg_data()
    chunks = _logreg_chunks(train, W, epochs=1)[:2]
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, _ = logistic_regression(mesh, cfg)
    wd = obs.StepWatchdog(120.0)
    tables, ls = trainer.init_state(jax.random.key(0))
    trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1),
                       watchdog=wd)
    assert wd.stalls == []


def test_run_indexed_records_epochs(devices8):
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    W = num_workers_of(mesh)
    data = synthetic_ratings(57, 31, 800, seed=0)
    cfg = MFConfig(num_users=57, num_items=31, rank=4, learning_rate=0.1)
    trainer, store = online_mf(mesh, cfg, donate=False)
    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    tables, ls = trainer.init_state(jax.random.key(0))
    plan = DeviceEpochPlan(DeviceDataset(mesh, data), num_workers=W,
                           local_batch=32, route_key="user", seed=5)
    trainer.run_indexed(tables, ls, plan, jax.random.key(1), epochs=2,
                        recorder=rec)
    assert rec.counter_value("driver.epochs") == 2
    assert rec.counter_value("driver.examples") == 1600.0
    ev = sink.events("epoch")
    assert [e["index"] for e in ev] == [0, 1]
    assert all("dispatch" in e["phases"] for e in ev)


def test_recorder_off_and_on_compile_identically(devices8):
    """ISSUE acceptance: the recorder is host-side only — attaching one
    must not change the traced program at all (bit-identical lowered
    text), unlike e.g. the guard which is part of the program."""
    from fps_tpu.parallel.mesh import host_to_sharded, key_to_replicated

    from fps_tpu.core.api import StepOutput, WorkerLogic

    class _Pusher(WorkerLogic):
        def pull_ids(self, batch):
            return {"t": batch["id"].astype(np.int32)}

        def step(self, batch, pulled, local_state, key):
            return StepOutput(
                pushes={"t": (batch["id"].astype(np.int32), batch["val"])},
                local_state=local_state, out={},
            )

    def lowered_text(recorder):
        mesh = make_ps_mesh(num_shards=1, num_data=1, devices=devices8[:1])
        store = ParamStore(mesh, [TableSpec("t", 16, 2).zeros_init()])
        trainer = Trainer(mesh, store, _Pusher(),
                          config=TrainerConfig(donate=False),
                          recorder=recorder)
        tables, ls = trainer.init_state(jax.random.key(0))
        chunk = {
            "id": np.zeros((1, 4), np.int32),
            "val": np.zeros((1, 4, 2), np.float32),
        }
        sharding = trainer._batch_sharding_for("sync")
        batches = jax.tree.map(lambda x: host_to_sharded(x, sharding), chunk)
        key = key_to_replicated(jax.random.key(1), mesh)
        return trainer._get_compiled("sync").lower(
            tables, ls, batches, key).as_text()

    assert lowered_text(None) == lowered_text(
        obs.Recorder(sinks=[obs.MemorySink()]))


@pytest.mark.parametrize("program", ["chunk", "indexed", "megastep"])
def test_default_recorder_off_and_on_compile_identically(devices8, program):
    """ISSUE 24: the set-up spans and every span with no timer report
    through the PROCESS-DEFAULT recorder. Installing one must leave each
    driver's program (lowered text) and what it computes bit-identical —
    spans and their waits are host-side only."""
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    data = synthetic_ratings(57, 31, 600, seed=0)
    entry = {"chunk": "fit_stream", "indexed": "run_indexed",
             "megastep": "run_megastep"}[program]

    text_off, _, out_off = _entry_point_run(entry, mesh, data)
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        text_on, _, out_on = _entry_point_run(entry, mesh, data)
    assert text_off == text_on
    jax.tree.map(np.testing.assert_array_equal, out_off, out_on)
    # ... and the recorder did see the run: set-up spans and the call.
    spans = {e["span"] for e in sink.events("span")}
    assert {"dataset.place", "dataset.queues", "plan.build", "init_state",
            "epoch_args", "program_lookup", "enqueue"} <= spans
    assert entry in spans


# ---------------------------------------------------------------------------
# host_span: the one host span primitive (ISSUE 24).
# ---------------------------------------------------------------------------

def _spans(sink):
    return {e["span"]: e for e in sink.events("span")}


def test_host_span_parent_call_index_and_self_time():
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        for _ in range(2):
            with obs.host_span("run_indexed", call=True):
                with obs.host_span("dispatch"):
                    time.sleep(0.002)
                    with obs.host_span("enqueue") as span:
                        span["built"] = True
                        time.sleep(0.002)
                with obs.host_span("host_sync"):
                    time.sleep(0.002)
    calls = [e for e in sink.events("span") if e["span"] == "run_indexed"]
    assert calls[1]["call"] == calls[0]["call"] + 1  # the calls are numbered
    tree = [e for e in sink.events("span") if e["call"] == calls[1]["call"]]
    by = {e["span"]: e for e in tree}
    assert set(by) == {"run_indexed", "dispatch", "enqueue", "host_sync"}
    root = by["run_indexed"]
    assert root["parent_id"] is None
    assert by["dispatch"]["parent_id"] == root["span_id"]
    assert by["host_sync"]["parent_id"] == root["span_id"]
    assert by["enqueue"]["parent_id"] == by["dispatch"]["span_id"]
    assert by["enqueue"]["built"] is True
    # Self time (a span's length less its children's) sums to the root.
    length = {e["span_id"]: e["t1"] - e["t0"] for e in tree}
    self_time = dict(length)
    for e in tree:
        if e["parent_id"] is not None:
            self_time[e["parent_id"]] -= length[e["span_id"]]
    assert all(v >= -1e-9 for v in self_time.values())
    assert sum(self_time.values()) == pytest.approx(length[root["span_id"]])
    # Children lie inside their parents on the record's clock.
    for e in tree:
        if e["parent_id"] is not None:
            parent = next(p for p in tree if p["span_id"] == e["parent_id"])
            assert parent["t0"] <= e["t0"] and e["t1"] <= parent["t1"] + 1e-6


def test_host_span_is_inert_with_no_recorder(monkeypatch):
    """No recorder, no profiler: nothing recorded, nothing registered."""
    from fps_tpu.obs import timing

    assert obs_events.get_default_recorder() is None
    registered = []
    monkeypatch.setattr(timing, "_watching", False)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        registered.append)
    with obs.host_span("run_indexed", call=True) as span:
        with obs.host_span("dispatch"):
            pass
    assert registered == [] and span == {}
    assert timing._watching is False
    # The first span under a recorder registers the compile listeners.
    with obs_events.default_recorder(obs.Recorder(sinks=[])):
        with obs.host_span("dispatch"):
            pass
    assert len(registered) == 2 and timing._watching is True


def test_explicit_phase_timer_is_not_double_counted_by_the_default():
    mine, default = obs.MemorySink(), obs.MemorySink()
    timer = obs.PhaseTimer(obs.Recorder(sinks=[mine]))
    with obs_events.default_recorder(obs.Recorder(sinks=[default])):
        with timer.phase("dispatch"):          # PhaseTimer.phase IS host_span
            with obs.host_span("enqueue", timer):
                pass
        with obs.host_span("epoch_args"):      # no timer: the default's
            pass
    assert set(timer.chunk_summary()) == {"dispatch", "enqueue"}
    assert {m["labels"]["phase"] for m in mine.metrics(
        "driver.phase_seconds")} == {"dispatch", "enqueue"}
    assert set(_spans(mine)) == {"dispatch", "enqueue"}
    assert {m["labels"]["phase"] for m in default.metrics(
        "driver.phase_seconds")} == {"epoch_args"}
    assert set(_spans(default)) == {"epoch_args"}


def test_compiles_fold_into_the_default_recorder():
    """watch_compiles: JAX's own compile timings as compile.* phases, a
    program_compiled event naming the function."""
    import jax.numpy as jnp

    sink = obs.MemorySink()
    rec = obs.Recorder(sinks=[sink])
    with obs_events.default_recorder(rec):
        with obs.host_span("dispatch"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    phases = rec.phase_totals()
    assert {"compile.trace", "compile.lower", "compile.backend"} <= set(
        phases)
    compiled = sink.events("program_compiled")
    assert compiled and all(e["seconds"] > 0 for e in compiled)
    assert any("lambda" in e.get("fun_name", "") for e in compiled)
    # With the recorder gone the listeners are inert.
    n = len(sink.records)
    jax.jit(lambda x: x * 5)(jnp.arange(3)).block_until_ready()
    assert len(sink.records) == n


def test_driver_phases_cover_what_the_drivers_emit():
    """DRIVER_PHASES and its companions name every span in the tree (the
    list went stale once: ``retier`` was emitted and not declared)."""
    import ast

    from fps_tpu.obs import timing

    declared = (set(timing.DRIVER_PHASES) | set(timing.NESTED_PHASES)
                | set(timing.SETUP_PHASES) | set(timing.CALL_SPANS)
                | set(timing.SWEEP_PHASES) | set(timing.PROGRAM_SPANS))
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fps_tpu")
    emitted = set()
    for d, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(d, f)).read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and getattr(node.func, "id", getattr(
                            node.func, "attr", "")) in ("_phase", "host_span",
                                                        "phase")):
                    for a in node.args:
                        if isinstance(a, ast.Constant) and isinstance(
                                a.value, str):
                            emitted.add(a.value)
    assert emitted and emitted <= declared, emitted - declared
    assert declared <= emitted, declared - emitted


def test_device_spans_cover_what_the_entry_points_watch():
    """DEVICE_SPANS names every unit an entry point hands to the watcher
    (``watch_device("device.<entry>", ...)``), and every name is one a
    driver call opens (``CALL_SPANS``): a device span hangs under its
    entry's root span."""
    import ast

    from fps_tpu.obs import timing

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fps_tpu")
    watched = set()
    for d, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(d, f)).read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and getattr(node.func, "id", getattr(
                            node.func, "attr", "")) == "watch_device"
                        and node.args
                        and isinstance(node.args[0], ast.Constant)):
                    watched.add(node.args[0].value)
    assert watched == set(timing.DEVICE_SPANS)
    assert {n[len("device."):] for n in timing.DEVICE_SPANS} == set(
        timing.CALL_SPANS)


# ---------------------------------------------------------------------------
# watch_device: the completion watcher (ISSUE 38). Fake handles whose
# readiness the test controls stand for a unit's device outputs.
# ---------------------------------------------------------------------------

class _Handle:
    """A unit's output: ready when the test says so; ``fail`` makes the
    wait raise, as the wait for a failed call's output does."""

    def __init__(self, fail=False):
        self.ready = threading.Event()
        self.fail = fail

    def block_until_ready(self):
        assert self.ready.wait(10), "the test never released the handle"
        if self.fail:
            raise RuntimeError("the call failed")
        return self


def _device_spans(sink, n=0):
    """The sink's device spans, once ``n`` of them are there (the watcher's
    thread records them; the wait is for that thread alone)."""
    deadline = time.monotonic() + 5.0
    while True:
        spans = [e for e in sink.events("span")
                 if e["span"].startswith("device.")]
        if len(spans) >= n or time.monotonic() > deadline:
            return spans
        time.sleep(0.002)


def _watcher_threads(gone=False):
    """The live watcher threads; with ``gone`` a thread that is on its way
    out (its last unit stamped) is given a moment to end."""
    deadline = time.monotonic() + 5.0
    while True:
        threads = [t for t in threading.enumerate()
                   if t.name == "fps-device-watcher"]
        if not (gone and threads) or time.monotonic() > deadline:
            return threads
        time.sleep(0.002)


def test_device_unit_queued_ahead_starts_at_its_predecessors_end():
    """Two units in flight: stamps come in queue order whatever order the
    outputs turn ready in; the second starts at the first's end, waited
    for the device that long and starved it of nothing."""
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        with obs.host_span("run_indexed", call=True):
            a, b = _Handle(), _Handle()
            obs.watch_device("device.run_indexed", {"n": a}, epoch=0)
            obs.watch_device("device.run_indexed", {"n": b}, epoch=1)
            b.ready.set()            # the later unit's output first
            time.sleep(0.02)
            assert not _device_spans(sink)
            a.ready.set()
            first, second = _device_spans(sink, 2)
    assert (first["epoch"], second["epoch"]) == (0, 1)
    assert first["t1"] <= second["t1"]
    assert first["t0"] == first["t_enqueued"] and first["wait_s"] == 0
    assert second["t0"] == first["t1"] > second["t_enqueued"]
    assert second["wait_s"] == pytest.approx(
        first["t1"] - second["t_enqueued"])
    assert second["wait_s"] >= 0.02 and second["starved_s"] == 0
    assert (first["in_flight"], second["in_flight"]) == (0, 1)
    # On the host spans' clock: queued inside the root span that was open.
    (root,) = [e for e in sink.events("span") if e["span"] == "run_indexed"]
    assert root["t0"] <= first["t_enqueued"] <= second["t_enqueued"]
    assert second["t_enqueued"] <= root["t1"]


def test_device_unit_queued_late_counts_the_time_the_device_starved():
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        a, b = _Handle(), _Handle()
        a.ready.set()
        obs.watch_device("device.fit_stream", [a], chunk=0)
        assert len(_device_spans(sink, 1)) == 1
        time.sleep(0.03)             # the host is late with the next unit
        b.ready.set()
        obs.watch_device("device.fit_stream", [b], chunk=1)
        first, second = _device_spans(sink, 2)
    assert second["in_flight"] == 0
    assert second["t0"] == second["t_enqueued"] and second["wait_s"] == 0
    assert second["starved_s"] == pytest.approx(
        second["t_enqueued"] - first["t1"])
    assert second["starved_s"] >= 0.03
    # The span is the whole record: no metric is kept beside it.
    assert not sink.metrics()


def test_device_unit_whose_call_raised_is_dropped():
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        bad, good = _Handle(fail=True), _Handle()
        obs.watch_device("device.run_megastep", [bad])
        obs.watch_device("device.run_megastep", [good])
        bad.ready.set()
        good.ready.set()
    (only,) = _device_spans(sink)
    assert only["in_flight"] == 1 and only["t0"] == only["t_enqueued"]


def test_watcher_thread_lives_while_a_unit_is_queued():
    assert not _watcher_threads()
    obs.watch_device("device.run_indexed", [_Handle()])
    assert not _watcher_threads()    # no recorder: nothing of it runs
    sink = obs.MemorySink()
    h = _Handle()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        obs.watch_device("device.run_indexed", [np.zeros(3)])
        assert not _watcher_threads()  # nothing to wait for: no unit
        obs.watch_device("device.run_indexed", [h], steps=7)
        (thread,) = _watcher_threads()
        assert thread.daemon
        # Clearing the recorder drains: the one place a caller waits.
        threading.Timer(0.02, h.ready.set).start()
    (span,) = _device_spans(sink)
    assert span["steps"] == 7 and span["parent_id"] is None
    assert "call" not in span
    assert not _watcher_threads(gone=True)
    thread.join(5.0)
    assert not thread.is_alive()


def test_watcher_thread_ends_by_itself_under_a_recorder_nobody_closes():
    """A recorder held by a timer and never closed leaves no thread behind:
    the thread ends with the last unit stamped, and the next unit starts
    another."""
    sink = obs.MemorySink()
    timer = obs.PhaseTimer(obs.Recorder(sinks=[sink]))
    for chunk in range(2):
        h = _Handle()
        h.ready.set()
        obs.watch_device("device.fit_stream", [h], timer, chunk=chunk)
        assert len(_device_spans(sink, chunk + 1)) == chunk + 1
        assert not _watcher_threads(gone=True)
    assert [e["chunk"] for e in _device_spans(sink)] == [0, 1]


def test_device_spans_go_to_the_timers_recorder_not_the_default():
    mine, default = obs.MemorySink(), obs.MemorySink()
    rec = obs.Recorder(sinks=[mine])
    timer = obs.PhaseTimer(rec)
    h = _Handle()
    with obs_events.default_recorder(obs.Recorder(sinks=[default])):
        obs.watch_device("device.fit_stream", [h], timer, chunk=3)
    # Clearing the default waited for nothing: the unit is not its own.
    assert not _device_spans(mine)
    threading.Timer(0.02, h.ready.set).start()
    rec.close()                      # closing the unit's recorder drains
    assert [e["chunk"] for e in _device_spans(mine)] == [3]
    assert not _device_spans(default)
    assert not _watcher_threads(gone=True)


class _FlushedSink(obs.MemorySink):
    """Remembers how many records it held when it was first flushed."""

    flushed_at = None

    def flush(self):
        if self.flushed_at is None:
            self.flushed_at = len(self.records)


def test_closing_a_recorder_waits_for_a_hung_device_no_longer_than_bounded(
        monkeypatch, caplog):
    """A device that never finishes must not hang ``Recorder.close``: the
    wait for the watcher is bounded, the unit's span is dropped with a log
    line, and the sinks were flushed BEFORE the wait (a kill during it
    loses no buffered line)."""
    from fps_tpu.obs import timing

    monkeypatch.setattr(timing, "DRAIN_SECONDS", 0.05)
    sink = _FlushedSink()
    rec = obs.Recorder(sinks=[sink])
    hung = _Handle()
    with obs.host_span("fit_stream", obs.PhaseTimer(rec), call=True):
        obs.watch_device("device.fit_stream", [hung], obs.PhaseTimer(rec))
    t = time.monotonic()
    with caplog.at_level("WARNING", logger="fps_tpu.obs"):
        rec.close()
    assert time.monotonic() - t < 2.0 and rec.closed
    assert sink.flushed_at is not None and sink.flushed_at >= 1
    assert "1 device span(s) dropped" in caplog.text
    hung.ready.set()                 # the device ends after all: no span,
    assert not _watcher_threads(gone=True)   # and the thread ends
    assert not _device_spans(sink)
    # The next unit's start is still its predecessor's (unrecorded) end.
    sink2 = obs.MemorySink()
    h = _Handle()
    h.ready.set()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink2])):
        obs.watch_device("device.fit_stream", [h])
    (span,) = _device_spans(sink2)
    assert span["starved_s"] > 0


def test_watcher_thread_that_dies_is_replaced_by_the_next_unit(monkeypatch):
    """A fault outside the watcher's guards ends its thread; the next unit
    starts a new one and a drain does not wait for the dead one's units."""
    from fps_tpu.obs import timing

    class Fatal(BaseException):
        pass

    def die(*a, **k):
        raise Fatal

    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        h = _Handle()
        h.ready.set()
        with monkeypatch.context() as m:
            m.setattr(timing._DeviceWatcher, "_emit", staticmethod(die))
            m.setattr(threading, "excepthook", lambda args: None)
            obs.watch_device("device.fit_stream", [h], chunk=0)
            assert not _watcher_threads(gone=True)
        obs.watch_device("device.fit_stream", [h], chunk=1)
        assert [e["chunk"] for e in _device_spans(sink, 1)] == [1]
    assert not _watcher_threads(gone=True)


def _entry_point_run(entry, mesh, data):
    """One run of a driver entry point: ``(lowered text of its program,
    units it queued, what it computed as host arrays)``."""
    from fps_tpu import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.device_ingest import device_epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf

    key = jax.random.key(1)
    if entry == "als.half_epoch":
        from fps_tpu.models.ials import IALSConfig, IALSSolver

        solver = IALSSolver(mesh, IALSConfig(num_users=57, num_items=31,
                                             rank=4))
        solver.store.tables = solver.init(jax.random.key(0))
        plan = DeviceEpochPlan(DeviceDataset(mesh, data), num_workers=2,
                               local_batch=16, seed=3)

        def chunks():
            return device_epoch_chunks(
                plan.dataset, num_workers=2, local_batch=16,
                steps_per_chunk=8, plan=plan)

        metrics = solver.epoch(chunks)
        chunk = {"solve_ids": "user", "fixed_ids": "item",
                 "rating": "rating", "weight": "weight"}
        first = next(chunks())
        tables = solver.store.tables
        text = solver._compiled_acc["user"].lower(
            tables["item_factors"], tables["user_factors"],
            solver._zeros_acc(tables["user_factors"].shape[0], 16),
            solver._zeros_acc(tables["user_factors"].shape[0], 4),
            {k: first[v] for k, v in chunk.items()}).as_text()
        return text, 2, jax.tree.map(np.asarray, (tables, metrics))
    trainer, _ = online_mf(mesh, MFConfig(num_users=57, num_items=31,
                                          rank=4), max_steps_per_call=4)
    plan = DeviceEpochPlan(DeviceDataset(mesh, data), num_workers=2,
                           local_batch=16, route_key="user", seed=3)
    tables, ls = trainer.init_state(jax.random.key(0))
    T = int(plan.steps_per_epoch)
    if entry == "fit_stream":
        def chunks():
            return device_epoch_chunks(
                plan.dataset, num_workers=2, local_batch=16,
                steps_per_chunk=4, plan=plan)

        text = trainer.lowered_chunk_text(
            jax.tree.map(np.asarray, next(chunks())))
        out = trainer.fit_stream(tables, ls, chunks(), key)
        return text, -(-T // 4), jax.tree.map(np.asarray, out)
    if entry == "run_indexed":
        from fps_tpu.parallel.mesh import key_to_replicated

        text = trainer._get_indexed_fn(plan, "sync").lower(
            tables, ls, plan.epoch_args(0), np.int32(0),
            key_to_replicated(key, mesh)).as_text()
        out = trainer.run_indexed(tables, ls, plan, key, epochs=2)
        return text, 2, jax.tree.map(np.asarray, out)
    text = trainer.lowered_megastep_text(plan, chunks_per_dispatch=2)
    out = trainer.run_megastep(tables, ls, plan, key, chunks_per_dispatch=2)
    return text, -(-(-(-T // 4)) // 2), jax.tree.map(np.asarray, out)


@pytest.mark.parametrize("entry", ["run_indexed", "fit_stream",
                                   "run_megastep", "als.half_epoch"])
def test_each_entry_point_queues_one_device_span_a_unit(devices8, entry):
    """ISSUE 38: under a recorder every unit an entry point queues (an
    epoch, a chunk, a megastep, a sweep) comes back as one ``device.<entry>``
    span carrying the ``call`` index of the root host span that queued it
    and that span as its parent; without a recorder nothing of the watcher
    runs; the program's lowered text is the same either way."""
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    data = synthetic_ratings(57, 31, 600, seed=0)
    data["weight"] = np.ones(len(data["rating"]), np.float32)

    text_off, units, out_off = _entry_point_run(entry, mesh, data)
    assert not _watcher_threads()
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        text_on, _, out_on = _entry_point_run(entry, mesh, data)
    assert text_on == text_off
    jax.tree.map(np.testing.assert_array_equal, out_off, out_on)
    assert not _watcher_threads()
    spans = _device_spans(sink)
    assert {e["span"] for e in spans} == {"device." + entry}
    assert len(spans) == units
    roots = {e["call"]: e for e in sink.events("span")
             if e["span"] == entry}
    assert len(roots) == (2 if entry == "als.half_epoch" else 1)
    for e in spans:
        assert e["parent_id"] == roots[e["call"]]["span_id"]
        assert e["t_enqueued"] <= e["t0"] <= e["t1"]
        assert e["t0"] - e["t_enqueued"] == pytest.approx(e["wait_s"])
        assert e["starved_s"] >= 0 and e["steps"] > 0
    # In queue order, each starting no earlier than the one before ended.
    assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))
    if entry == "als.half_epoch":
        assert [e["solve"] for e in spans] == ["user", "item"]


# ---------------------------------------------------------------------------
# The device's memory on the spans (ISSUE 53): obs.timing.device_bytes is
# the one place memory_stats() is read, the spans carry it under a
# recorder, watch_program states each compiled program's own.
# ---------------------------------------------------------------------------

STEP = 1000


class _FakeBytes:
    """Stands for ``timing.device_bytes``: on the calling thread every
    reading is ``STEP`` bytes over the one before, so a difference of two
    readings counts the readings between them; the watcher's thread reads
    a running peak of its own."""

    def __init__(self):
        self.main = threading.get_ident()
        self.n = self.units = 0

    def __call__(self, where=None):
        from fps_tpu.obs import timing

        if threading.get_ident() != self.main:
            self.units += 1
            return timing.DeviceBytes(7, 9000 + self.units, 10 ** 6)
        self.n += 1
        return timing.DeviceBytes(STEP * self.n, 9000, 10 ** 6)


def _lowerings():
    """Programs lowered, counted as ``perfbench/lib/runner.py`` counts
    them; returns the list the listener appends to."""
    seen = []

    def lowered(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(lowered)
    return seen


_ENTRIES = ["run_indexed", "fit_stream", "run_megastep", "als.half_epoch"]
_PROGRAMS = {
    "run_indexed": {"ingest.tbuf", "indexed/sync"},
    "fit_stream": {"ingest.tbuf", "chunk/sync"},
    "run_megastep": {"ingest.tbuf", "megastep/sync"},
    "als.half_epoch": {"ingest.tbuf"} | {
        f"als.{p}/{s}" for p in ("gram", "accumulate", "solve")
        for s in ("user", "item")},
}


def _entry_mesh_data(devices8):
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    data = synthetic_ratings(57, 31, 600, seed=0)
    data["weight"] = np.ones(len(data["rating"]), np.float32)
    return mesh, data


@pytest.mark.parametrize("entry", _ENTRIES)
def test_spans_carry_the_devices_memory_under_a_recorder(
        devices8, monkeypatch, entry):
    """With a fake ``device_bytes``: the root call span carries
    ``hbm_open`` / ``hbm_close`` / ``hbm_peak`` / ``hbm_limit`` and the
    difference of the first two is
    the fake's step a reading made inside the call; every set-up span and
    ``epoch_args`` carries ``hbm_delta``; every device span ``hbm_done``
    and ``hbm_peak`` as the watcher's own thread read them; one
    ``program.memory`` span a program built."""
    from fps_tpu.obs import timing

    mesh, data = _entry_mesh_data(devices8)
    fake = _FakeBytes()
    monkeypatch.setattr(timing, "device_bytes", fake)
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        _, units, _ = _entry_point_run(entry, mesh, data)
    spans = sink.events("span")
    roots = [e for e in spans if e["span"] == entry]
    assert roots
    for root in roots:
        inside = [e for e in spans if e is not root and "hbm_delta" in e
                  and root["t0"] <= e["t0"] and e["t1"] <= root["t1"]]
        # A span inside the call read twice, the call's own close once.
        assert root["hbm_close"] - root["hbm_open"] == STEP * (
            2 * len(inside) + 1)
        assert root["hbm_limit"] == 10 ** 6 and root["hbm_peak"] == 9000
    carrying = {e["span"] for e in spans if "hbm_delta" in e}
    assert carrying == {"dataset.place", "dataset.queues", "dataset.pack",
                        "plan.build", "epoch_args"} | (
        set() if entry == "als.half_epoch" else {"init_state"})
    for e in spans:
        if e["span"] in ("dataset.place", "init_state", "epoch_args"):
            assert e["hbm_delta"] == STEP  # no memory span inside it
    (plan,) = [e for e in spans if e["span"] == "plan.build"]
    assert plan["hbm_delta"] == 5 * STEP   # the queues' and the pack's too
    device = _device_spans(sink, units)
    assert len(device) == units == fake.units
    assert [e["hbm_peak"] for e in device] == [
        9001 + i for i in range(units)]
    assert all(e["hbm_done"] == 7 for e in device)
    assert all(not k.startswith("hbm_") for e in spans
               if e["span"] not in carrying | {entry}
               and not e["span"].startswith("device.") for k in e)
    programs = [e for e in spans if e["span"] == "program.memory"]
    assert sorted(e["label"] for e in programs) == sorted(_PROGRAMS[entry])
    for e in programs:
        assert all(isinstance(e[k], int) and e[k] >= 0 for k in (
            "argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
            "code_bytes")), e
    assert any(e["temp_bytes"] > 0 for e in programs)


@pytest.mark.parametrize("entry", _ENTRIES)
def test_no_recorder_reads_no_memory_and_a_recorder_lowers_nothing_more(
        devices8, monkeypatch, entry):
    """With NO recorder a ``device_bytes`` that raises is never reached and
    the entry points dispatch the bare jitted callables; on the CPU, whose
    ``memory_stats()`` is ``None``, a recorder's spans carry no ``hbm_*``
    field and nothing fails; and the recorder's run lowers exactly the
    programs the run without one lowers (``program.memory`` finds the
    executable its call built: no lowering, no compile)."""
    from fps_tpu.obs import timing

    mesh, data = _entry_mesh_data(devices8)
    assert timing.device_bytes() is None  # the CPU counts no memory
    lowered = _lowerings()
    _entry_point_run(entry, mesh, data)   # warms jax's own helper programs

    def unreachable(where=None):
        raise AssertionError("device_bytes reached without a recorder")

    with monkeypatch.context() as m:
        m.setattr(timing, "device_bytes", unreachable)
        del lowered[:]
        _entry_point_run(entry, mesh, data)
        off = len(lowered)
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        del lowered[:]
        _entry_point_run(entry, mesh, data)
        on = len(lowered)
    assert on == off > 0
    spans = sink.events("span")
    assert {e["span"] for e in spans} >= {entry, "program.memory",
                                          "plan.build", "epoch_args"}
    assert not [k for e in spans for k in e if k.startswith("hbm_")]


def test_untraced_unaudited_trainer_keeps_the_bare_jitted_callable(devices8):
    from fps_tpu.obs import timing

    calls = []
    fn = jax.jit(lambda x: x + 1)
    assert obs_events.get_default_recorder() is None
    assert timing.watch_program(fn, "p") is fn
    hooked = timing.watch_program(fn, "p", calls.append)
    assert hooked is not fn and hooked.__wrapped__ is fn
    assert hooked.lower == fn.lower
    hooked(np.float32(1.0))
    hooked(np.float32(2.0))
    assert len(calls) == 1  # the hook ran before the first call only


def test_one_program_memory_span_a_built_program_and_none_on_a_cache_hit(
        devices8):
    """A second call of the same entry on the same trainer and plan finds
    its programs in the trainer's cache: no span more. The audit and the
    span share ONE first-call wrapper."""
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf

    mesh, data = _entry_mesh_data(devices8)
    sink = obs.MemorySink()
    with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
        trainer, _ = online_mf(mesh, MFConfig(num_users=57, num_items=31,
                                              rank=4))
        trainer.audit = True  # read where a program is built
        plan = DeviceEpochPlan(DeviceDataset(mesh, data), num_workers=2,
                               local_batch=16, route_key="user", seed=3)
        tables, ls = trainer.init_state(jax.random.key(0))
        key = jax.random.key(1)
        for start in (0, 1):
            tables, ls, _ = trainer.run_indexed(
                tables, ls, plan, key, epochs=1, start_epoch=start)
    labels = [e["label"] for e in sink.events("span")
              if e["span"] == "program.memory"]
    assert sorted(labels) == ["indexed/sync", "ingest.tbuf"]
    (fn,) = trainer._compiled.values()
    # ... and under that one wrapper the jitted callable itself.
    assert fn._fps_audited and isinstance(
        fn.__wrapped__, type(jax.jit(lambda: 0)))
    assert [c.ok for c in trainer.audit.certificates] == [True]
    assert len([e for e in sink.events("span")
                if e["span"] == "run_indexed"]) == 2


def test_compile_events_under_program_memory_stay_out_of_compile_phases():
    """The reading traces once more (a cached trace) to find the
    executable: JAX's timing of that is not a ``compile.*`` sample, so a
    watched program's compile phases count what a bare call's count."""
    import jax.numpy as jnp

    from fps_tpu.obs import timing

    def phases(watch):
        fn = jax.jit(lambda x, s: (x * s + 1).sum())
        sink = obs.MemorySink()
        with obs_events.default_recorder(obs.Recorder(sinks=[sink])):
            if watch:
                fn = timing.watch_program(fn, "toy")
            with obs.host_span("enqueue"):
                fn(jnp.arange(7.0), np.int32(3)).block_until_ready()
        spans = [e for e in sink.events("span")
                 if e["span"] == "program.memory"]
        counts = {}
        for m in sink.metrics("driver.phase_seconds"):
            phase = m["labels"]["phase"]
            counts[phase] = counts.get(phase, 0) + 1
        return counts, spans

    phases(False)  # warm jnp's own helper programs
    bare, none = phases(False)
    watched, spans = phases(True)
    assert not none and len(spans) == 1 and spans[0]["label"] == "toy"
    assert spans[0]["temp_bytes"] >= 0 and spans[0]["argument_bytes"] > 0
    assert watched.pop("program.memory") == 1
    assert watched == bare and bare["compile.backend"] >= 1


# ---------------------------------------------------------------------------
# Registry completeness (ISSUE 12 satellite): every metric name the
# package emits has a spec — the silently-unregistered-metric class.
# ---------------------------------------------------------------------------

_METRIC_NAME_RE = None  # compiled lazily below


def _emitted_metric_names():
    """AST scan of fps_tpu/ for metric emissions: ``<recv>.inc/set/
    observe("name", ...)`` calls, the ``events.record_metric(kind,
    "name", ...)`` indirection, and wrapper helpers (``_emit_metric`` /
    ``_inc``-style) — the first string argument shaped like a dotted
    metric name is the emission."""
    import ast
    import re

    name_re = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
    emitters = {"inc", "set", "observe", "record_metric",
                "_emit_metric", "_obs_metric", "_inc", "_set",
                "_observe"}
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fps_tpu")
    found = {}  # name -> first "path:line" site
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                leaf = (func.attr if isinstance(func, ast.Attribute)
                        else func.id if isinstance(func, ast.Name)
                        else None)
                if leaf not in emitters:
                    continue
                for arg in node.args:
                    if (isinstance(arg, ast.Constant)
                            and isinstance(arg.value, str)
                            and name_re.match(arg.value)):
                        found.setdefault(
                            arg.value,
                            f"{os.path.relpath(path, root)}:"
                            f"{node.lineno}")
                        break
    return found


def test_every_emitted_metric_name_is_registered():
    """The silently-unregistered-metric class: an emission through the
    process-default path (events.record_metric) degrades to a logged
    DROP when its name has no spec — this scan fails the build instead,
    for every emission site anywhere in fps_tpu/."""
    emitted = _emitted_metric_names()
    # Non-vacuity: the scan must see the known emission styles — direct
    # recorder calls (driver), the process-default indirection
    # (checkpoint), and the serve-side _emit_metric wrapper.
    for expected in ("driver.chunks", "checkpoint.saves",
                     "serve.rejected_snapshots",
                     "analysis.budget_drift",
                     "analysis.certified_programs"):
        assert expected in emitted, f"scan lost {expected}"
    registry = obs.default_registry()
    unregistered = {name: site for name, site in sorted(emitted.items())
                    if name not in registry}
    assert not unregistered, (
        "metric(s) emitted without a MetricSpec in "
        f"obs/registry.py: {unregistered}")


def test_registry_scan_catches_a_seeded_unregistered_emission(tmp_path):
    """The scanner itself is not vacuous: a seeded emission of an
    unknown name would be caught by the same name-shape matcher."""
    import ast
    import re

    name_re = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
    src = 'rec.inc("totally.unregistered_metric", 2, table="x")\n'
    call = ast.parse(src).body[0].value
    [arg] = [a for a in call.args if isinstance(a, ast.Constant)
             and isinstance(a.value, str) and name_re.match(a.value)]
    assert arg.value not in obs.default_registry()
