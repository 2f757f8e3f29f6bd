"""tools/obs_report.py smoke (ISSUE 2 acceptance + CI satellite): a
2-chunk logreg `fit_stream` run with --obs-dir produces a JSONL event log
+ run journal that the report tool renders into a digest with per-phase
timings, per-table health totals, and incident events."""

import importlib.util
import json
import os

import pytest


def _load_report():
    spec = importlib.util.spec_from_file_location(
        "obs_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "obs_report.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_digest_from_logreg_run(devices8, capsys, tmp_path):
    from fps_tpu.examples import logreg_ssp

    obs_dir = str(tmp_path / "obs")
    rc = logreg_ssp.main([
        "--epochs", "1", "--local-batch", "32", "--steps-per-chunk", "4",
        "--num-examples", "2000", "--num-features", "500",
        "--sync-every", "2", "--guard", "observe",
        "--checkpoint-dir", str(tmp_path / "ck"), "--checkpoint-every", "2",
        "--obs-dir", obs_dir, "--obs-watchdog-s", "300",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    events = [json.loads(l) for l in out.splitlines()]
    assert any(e["event"] == "obs" and e["dir"] == obs_dir for e in events)

    report = _load_report()
    digest = report.render_digest(obs_dir)
    # Required shape (REQUIRED_FIELDS is the tool's own contract).
    for field in report.REQUIRED_FIELDS:
        assert field in digest, field
    assert digest["chunks"] == 2
    assert digest["examples"] > 0
    assert digest["run_complete"] is True
    assert len(digest["run_ids"]) == 1 and digest["processes"] == [0]
    # Per-phase timings: every driver phase observed, with real time.
    for phase in ("ingest", "place", "dispatch", "host_sync", "checkpoint"):
        assert phase in digest["phase_seconds"], phase
        assert digest["phase_seconds"][phase]["n"] >= 1
    assert digest["phase_seconds"]["dispatch"]["total_s"] > 0
    # Per-table health totals: the guard watched (clean run => zeros).
    assert digest["health"] == {
        "weights": {"nonfinite": 0, "norm": 0, "masked": 0}
    }
    assert digest["checkpoint_saves"] >= 1
    assert digest["watchdog_stalls"] == 0 and digest["incidents"] == {}
    assert digest["wall_span_s"] >= 0

    # main() prints the digest as one JSON line.
    assert report.main([obs_dir]) == 0
    line = capsys.readouterr().out.strip()
    assert json.loads(line)["chunks"] == 2

    # --json: the pinned machine contract — identical payload, compact,
    # versioned schema field, strict JSON (digest_json is the importable
    # form fps_tpu/obs/fleet.py consumers use).
    assert report.main([obs_dir, "--json"]) == 0
    machine = json.loads(capsys.readouterr().out.strip())
    assert machine["schema"] == report.DIGEST_SCHEMA_VERSION
    assert machine == report.digest_json(obs_dir)
    # The causal-trace anchor rides the journal: the run_start carries
    # trace/span ids (fps_tpu.obs.trace) without perturbing the digest.
    journal = os.path.join(obs_dir, "journal-p0.jsonl")
    start = json.loads(open(journal).readline())
    assert start["event"] == "run_start" and start["span_id"]


def test_obs_report_surfaces_incidents(tmp_path):
    """Rollback / stall / escalation / checkpoint-fallback events written
    by a run land in the digest's incident lists (synthetic event files —
    the report tool is a pure JSONL consumer)."""
    report = _load_report()
    d = str(tmp_path)
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        for rec in [
            {"kind": "metric", "t": 1.0, "name": "driver.chunks",
             "mtype": "counter", "value": 1},
            {"kind": "metric", "t": 1.2, "name": "rollback.quarantined",
             "mtype": "counter", "value": 1},
            {"kind": "event", "t": 1.2, "event": "rollback", "index": 4,
             "total": 1, "budget": 8},
            {"kind": "event", "t": 1.3, "event": "chunk", "index": 4,
             "quarantined": True, "phases": {}},
            {"kind": "event", "t": 1.4, "event": "stall", "what": "chunk",
             "index": 5, "deadline_s": 2.0},
            {"kind": "event", "t": 1.5, "event": "guard_escalated",
             "index": 5, "what": "chunk", "poison_rows": 12},
            {"kind": "event", "t": 1.6, "event": "checkpoint_fallback",
             "step": 3, "error": "boom"},
            "garbage that is not json",  # torn tail line must not break it
        ]:
            f.write(rec if isinstance(rec, str) else json.dumps(rec))
            f.write("\n")
    # Journal holds: a duplicate of the rollback (same record fanned to
    # both sinks — must dedupe) plus a stall the buffered event sink LOST
    # (SIGKILL before flush) — must still surface in the digest.
    with open(os.path.join(d, "journal-p0.jsonl"), "w") as f:
        for rec in [
            {"kind": "event", "t": 0.5, "event": "run_start",
             "run_id": "r", "process": 0},
            {"kind": "event", "t": 1.2, "event": "rollback", "index": 4,
             "total": 1, "budget": 8},
            {"kind": "event", "t": 1.7, "event": "stall", "what": "chunk",
             "index": 9, "deadline_s": 2.0},
        ]:
            f.write(json.dumps(rec) + "\n")
    digest = report.render_digest(d)
    assert digest["quarantined"] == [4]
    assert digest["rollbacks"] == 1
    assert [i["index"] for i in digest["incidents"]["rollback"]] == [4]
    # The journal-only stall survived; the duplicated rollback didn't fork.
    assert sorted(i["index"] for i in digest["incidents"]["stall"]) == [5, 9]
    assert digest["incidents"]["guard_escalated"][0]["poison_rows"] == 12
    assert digest["incidents"]["checkpoint_fallback"][0]["step"] == 3
    assert digest["run_complete"] is False  # no journal run_end


def test_obs_report_raw_speed_sections(tmp_path):
    """ISSUE 20 telemetry lands in the digest: the checkpoint
    dump/capture split, the auto-K gauge, and the adaptive-prefetch
    raise counter (synthetic event files — pure JSONL consumer)."""
    report = _load_report()
    d = str(tmp_path)
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        for rec in [
            {"kind": "metric", "t": 1.0, "name": "checkpoint.dump_seconds",
             "mtype": "histogram", "value": 0.001},
            {"kind": "metric", "t": 1.1, "name": "checkpoint.dump_seconds",
             "mtype": "histogram", "value": 0.003},
            {"kind": "metric", "t": 1.2,
             "name": "checkpoint.capture_seconds",
             "mtype": "histogram", "value": 0.05},
            {"kind": "metric", "t": 1.3, "name": "megastep.auto_k",
             "mtype": "gauge", "value": 12.0},
            {"kind": "metric", "t": 1.4,
             "name": "prefetch.depth_adjustments",
             "mtype": "counter", "value": 3},
        ]:
            f.write(json.dumps(rec) + "\n")
    digest = report.render_digest(d)
    ck = digest["checkpoint"]
    assert ck["dump"]["n"] == 2
    assert ck["dump"]["total_s"] == pytest.approx(0.004)
    assert ck["dump"]["max_s"] == pytest.approx(0.003)
    assert ck["capture"] == {"n": 1, "total_s": 0.05, "mean_s": 0.05,
                             "p99_s": 0.05, "max_s": 0.05}
    assert digest["megastep"]["auto_k"] == 12.0
    assert digest["prefetch"]["depth_adjustments"] == 3
    # No samples at all still yields the full shape (nulls, n=0).
    assert report._seconds_stats([]) == {
        "n": 0, "total_s": None, "mean_s": None, "p99_s": None,
        "max_s": None}


def test_obs_report_device_section(tmp_path):
    """ISSUE 38: the device's time from inside. The ``device.<entry>``
    spans (one a unit queued) fold into a ``device`` section per entry
    point; a span in both the event log and the journal counts once, and
    the idle time before the first unit is not starved time."""
    report = _load_report()
    d = str(tmp_path)

    def span(of, t0, t1, t_enq, starved, in_flight, **kw):
        return {"kind": "event", "t": t1, "event": "span",
                "span": "device." + of, "t0": t0, "t1": t1,
                "t_enqueued": t_enq, "wait_s": t0 - t_enq,
                "starved_s": starved, "in_flight": in_flight, **kw}

    sweeps = [span("als.half_epoch", 100.0, 107.0, 100.0, 30.0, 0,
                   solve="user"),
              span("als.half_epoch", 107.0, 110.5, 100.5, 0.0, 1,
                   solve="item"),
              # The host came back late: 0.5 s with nothing queued.
              span("als.half_epoch", 111.0, 118.0, 111.0, 0.5, 0,
                   solve="user")]
    epochs = [span("run_indexed", 10.0, 12.0, 10.0, 0.0, 0, epoch=0)]
    host = {"kind": "event", "t": 1.0, "event": "span", "span": "enqueue",
            "t0": 0.0, "t1": 1.0}
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        for rec in sweeps + epochs + [host]:
            f.write(json.dumps(rec) + "\n")
    with open(os.path.join(d, "journal-p0.jsonl"), "w") as f:
        for rec in sweeps:  # the same records again: folded once
            f.write(json.dumps(rec) + "\n")
    dev = report.render_digest(d)["device"]
    assert set(dev) == {"als.half_epoch", "run_indexed"}
    assert dev["als.half_epoch"] == {
        "units": 3, "device_s": 17.5, "starved_s": 0.5,
        "starved_share": round(0.5 / 18.0, 6), "wait_median_s": 0.0,
        "in_flight_max": 1}
    assert dev["run_indexed"]["units"] == 1
    assert dev["run_indexed"]["starved_share"] == 0.0
    # A run that recorded no device span has the section, empty.
    os.remove(os.path.join(d, "journal-p0.jsonl"))
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        f.write(json.dumps(host) + "\n")
    assert report.render_digest(d)["device"] == {}


def test_obs_report_memory_section(tmp_path):
    """ISSUE 53: the device's memory from inside, from the journal alone:
    what is resident before any call, what a queued call holds, the peak,
    the limit and what is left, each program's own; a span in both files
    counts once, and a journal whose spans carry no bytes (the CPU's, an
    untraced run's) leaves the section empty."""
    report = _load_report()
    d = str(tmp_path)
    GB = 10 ** 9

    def span(name, t0, **kw):
        return {"kind": "event", "t": t0 + 0.1, "event": "span",
                "span": name, "t0": t0, "t1": t0 + 0.1, **kw}

    recs = [
        span("dataset.place", 1.0, hbm_delta=2 * GB),
        span("dataset.queues", 2.1, hbm_delta=1 * GB),
        span("plan.build", 2.0, hbm_delta=1 * GB),
        span("epoch_args", 3.0, hbm_delta=3 * GB),
        span("epoch_args", 5.0, hbm_delta=3 * GB),
        span("program.memory", 3.2, label="ingest.tbuf", temp_bytes=7,
             argument_bytes=3 * GB, output_bytes=3 * GB, alias_bytes=0,
             code_bytes=11),
        span("program.memory", 3.5, label="indexed/ssp", temp_bytes=5 * GB,
             argument_bytes=6 * GB, output_bytes=GB, alias_bytes=GB,
             code_bytes=13),
        span("run_indexed", 2.9, hbm_open=3 * GB, hbm_close=6 * GB,
             hbm_peak=8 * GB, hbm_limit=16 * GB),
        span("run_indexed", 4.9, hbm_open=6 * GB, hbm_close=9 * GB,
             hbm_limit=16 * GB),
        span("run_indexed", 6.9, hbm_open=6 * GB, hbm_close=10 * GB,
             hbm_limit=16 * GB),
        span("als.half_epoch", 8.0, solve="user", hbm_open=7 * GB,
             hbm_close=9 * GB, hbm_limit=16 * GB),
        span("device.run_indexed", 3.0, t_enqueued=3.0, hbm_done=6 * GB,
             hbm_peak=9 * GB),
        span("device.run_indexed", 5.0, t_enqueued=5.0, hbm_done=6 * GB,
             hbm_peak=11 * GB),
        span("enqueue", 3.1),
    ]
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        for rec in recs:
            f.write(json.dumps(rec) + "\n")
    with open(os.path.join(d, "journal-p0.jsonl"), "w") as f:
        for rec in recs[:6]:  # the same records again: folded once
            f.write(json.dumps(rec) + "\n")
    mem = report.render_digest(d)["memory"]
    assert mem == {
        "resident_bytes": 3 * GB, "setup_peak_bytes": 8 * GB,
        "held_per_call_bytes": {"als.half_epoch": 2 * GB,
                                "run_indexed": 3 * GB},
        "peak_bytes": 11 * GB,
        "done_bytes": {"first": 6 * GB, "last": 6 * GB},
        "limit_bytes": 16 * GB,
        "left_bytes": 5 * GB,
        "setup_bytes": {"dataset.place": 2 * GB, "dataset.queues": GB,
                        "epoch_args": 6 * GB, "plan.build": GB},
        "programs": {
            "indexed/ssp": {"argument_bytes": 6 * GB, "output_bytes": GB,
                            "alias_bytes": GB, "temp_bytes": 5 * GB,
                            "code_bytes": 13},
            "ingest.tbuf": {"argument_bytes": 3 * GB,
                            "output_bytes": 3 * GB, "alias_bytes": 0,
                            "temp_bytes": 7, "code_bytes": 11}},
        "largest_program_temp_bytes": 5 * GB,
    }
    # The compiles beside them: the program_compiled events and the
    # persistent cache's counters (obs.timing.watch_compiles).
    with open(os.path.join(d, "events-p0.jsonl"), "a") as f:
        for name, secs in (("run", 81.0), ("build", 0.5)):
            f.write(json.dumps({"kind": "event", "t": 3.3, "seconds": secs,
                                "event": "program_compiled",
                                "fun_name": name}) + "\n")
        for name, n in (("compile.cache_hits", 1), ("compile.cache_hits", 1),
                        ("compile.cache_misses", 1)):
            f.write(json.dumps({"kind": "metric", "t": 3.3, "name": name,
                                "mtype": "counter", "value": n}) + "\n")
    assert report.render_digest(d)["compile"] == {
        "programs": 2, "backend_s": 81.5, "cache_hits": 2,
        "cache_misses": 1,
        "slowest": {"fun_name": "run", "seconds": 81.0}}
    # Spans without bytes: the section is there, empty.
    os.remove(os.path.join(d, "journal-p0.jsonl"))
    with open(os.path.join(d, "events-p0.jsonl"), "w") as f:
        f.write(json.dumps(span("run_indexed", 1.0)) + "\n")
        f.write(json.dumps(span("device.run_indexed", 1.0,
                                t_enqueued=1.0)) + "\n")
    empty = report.render_digest(d)
    assert empty["memory"] == {}
    assert empty["compile"] == {"programs": 0, "backend_s": 0.0,
                                "cache_hits": 0, "cache_misses": 0,
                                "slowest": None}


def test_obs_report_recovery_slo_breach(tmp_path):
    """--recovery-slo-s turns a late paired restart into a
    recovery_slo_breach incident and annotates the recovery section;
    without the flag the same dir reports without judging."""
    report = _load_report()
    d = str(tmp_path)
    with open(os.path.join(d, "journal-supervisor.jsonl"), "w") as f:
        for rec in [
            # Attempt 0 dies at t=10; attempt 1 first signal at t=18
            # (recovery 8s). Attempt 1 dies at t=30; attempt 2 first
            # signal at t=90 (recovery 60s — over a 20s bound).
            {"kind": "event", "t": 10.0, "event": "attempt_end",
             "attempt": 0},
            {"kind": "event", "t": 18.0, "event": "attempt_first_signal",
             "attempt": 1},
            {"kind": "event", "t": 30.0, "event": "attempt_end",
             "attempt": 1},
            {"kind": "event", "t": 90.0, "event": "attempt_first_signal",
             "attempt": 2},
        ]:
            f.write(json.dumps(rec) + "\n")

    plain = report.render_digest(d)
    assert plain["recovery"]["times_s"] == [8.0, 60.0]
    assert plain["recovery"]["slo_s"] is None
    assert plain["recovery"]["breaches"] == 0
    assert "recovery_slo_breach" not in plain["incidents"]

    judged = report.render_digest(d, recovery_slo_s=20.0)
    assert judged["recovery"]["slo_s"] == 20.0
    assert judged["recovery"]["breaches"] == 1
    [breach] = judged["incidents"]["recovery_slo_breach"]
    assert breach["time_to_recovered_s"] == 60.0
    assert breach["slo_s"] == 20.0

    # The CLI spelling reaches the same path.
    assert report.main([d, "--recovery-slo-s", "20"]) == 0


def test_obs_report_empty_dir_errors(tmp_path):
    report = _load_report()
    with pytest.raises(FileNotFoundError):
        report.render_digest(str(tmp_path))
    assert report.main([str(tmp_path)]) == 2
