"""Causal tracing (fps_tpu.obs.trace + tools/trace_export.py).

ISSUE 12 acceptance pins:
* trace on/off lowers BYTE-IDENTICAL programs (HLO asserted) and
  bit-identical numerics on MF + logreg — tracing is host-side only;
* the env-contract mirrors (obs/trace.py vs supervise/child.py vs
  supervise/supervisor.py) cannot drift;
* trace_export reconstructs one causally-linked span tree from pod +
  supervisor + run journals (the full cross-host assertion lives in the
  slow pod chaos scenarios / tools/chaos_sweep.py).
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax

from fps_tpu import obs
from fps_tpu.core.driver import num_workers_of
from fps_tpu.core.ingest import epoch_chunks
from fps_tpu.obs.trace import (
    PARENT_SPAN_ENV,
    TRACE_ID_ENV,
    TraceContext,
    Tracer,
    new_span_id,
    new_trace_id,
)
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.testing.workloads import NF, logreg_chunks, logreg_data, weights

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_export():
    spec = importlib.util.spec_from_file_location(
        "trace_export", os.path.join(_ROOT, "tools", "trace_export.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- unit: ids, context, env mirror --------------------------------------


def test_env_contract_mirrors_match():
    """The stdlib-only supervisor/child layers mirror the env names (they
    are loaded by file path, without the package) — the three definitions
    must be identical or propagation silently breaks."""
    from fps_tpu.supervise import child, supervisor

    assert child.TRACE_ID_ENV == TRACE_ID_ENV
    assert child.PARENT_SPAN_ENV == PARENT_SPAN_ENV
    assert supervisor.TRACE_ID_ENV == TRACE_ID_ENV
    assert supervisor.PARENT_SPAN_ENV == PARENT_SPAN_ENV


def test_trace_context_env_round_trip(monkeypatch):
    monkeypatch.delenv(TRACE_ID_ENV, raising=False)
    monkeypatch.delenv(PARENT_SPAN_ENV, raising=False)
    assert not TraceContext.from_env().active
    ctx = TraceContext(trace_id="t" * 32, parent_id="p" * 16)
    for k, v in ctx.child_env("s" * 16).items():
        monkeypatch.setenv(k, v)
    got = TraceContext.from_env()
    assert got.trace_id == "t" * 32
    assert got.parent_id == "s" * 16  # re-parented under the new span

    from fps_tpu.supervise import child

    assert child.trace_from_env() == {"trace_id": "t" * 32,
                                      "parent_id": "s" * 16}


def test_ids_are_fresh_and_well_formed():
    a, b = new_trace_id(), new_trace_id()
    assert a != b and len(a) == 32 and int(a, 16) >= 0
    s, t = new_span_id(), new_span_id()
    assert s != t and len(s) == 16 and int(s, 16) >= 0


def test_tracer_span_records(tmp_path):
    mem = obs.MemorySink()
    rec = obs.Recorder(sinks=[mem])
    tr = Tracer(rec, trace_id="trace1", parent_id="root1")
    with tr.span("work", epoch=3) as sid:
        child_sid = tr.instant("inner", parent_id=sid)
    spans = mem.events("span")
    assert [s["span"] for s in spans] == ["inner", "work"]
    outer = spans[1]
    assert outer["trace_id"] == "trace1"
    assert outer["parent_id"] == "root1"
    assert outer["span_id"] == sid
    assert outer["epoch"] == 3
    assert outer["t1"] >= outer["t0"]
    inner = spans[0]
    assert inner["parent_id"] == sid and inner["span_id"] == child_sid


def test_open_run_carries_trace_context(tmp_path, monkeypatch):
    monkeypatch.setenv(TRACE_ID_ENV, "f" * 32)
    monkeypatch.setenv(PARENT_SPAN_ENV, "a" * 16)
    d = str(tmp_path / "obs")
    rec = obs.open_run(d, config={"x": 1}, install=False)
    with rec.trace.span("custom"):
        pass
    rec.close()
    [journal] = [os.path.join(d, f) for f in os.listdir(d)
                 if f.startswith("journal-")]
    recs = [json.loads(line) for line in open(journal)]
    start = next(r for r in recs if r["event"] == "run_start")
    assert start["trace_id"] == "f" * 32
    assert start["parent_id"] == "a" * 16
    assert start["span_id"]
    span = next(r for r in recs if r["event"] == "span")
    assert span["trace_id"] == "f" * 32
    assert span["parent_id"] == start["span_id"]  # parents under the run


# -- acceptance: trace on/off is invisible to the program ----------------


def _logreg_harness(devices8):
    from fps_tpu.models.logistic_regression import (
        LogRegConfig,
        logistic_regression,
    )

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data(2000)
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=1)

    def build():
        return logistic_regression(
            mesh, LogRegConfig(num_features=NF, learning_rate=0.5))

    return build, chunks, lambda store: weights(store)


def _mf_harness(devices8):
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=4, num_data=2, devices=devices8[:8])
    W = num_workers_of(mesh)
    data = synthetic_ratings(96, 64, 3000, rank=3, noise=0.05, seed=3)
    chunks = list(epoch_chunks(data, num_workers=W, local_batch=32,
                               steps_per_chunk=4, route_key="user",
                               seed=11))

    def build():
        return online_mf(mesh, MFConfig(num_users=96, num_items=64,
                                        rank=4, learning_rate=0.08))

    def final(store):
        # MF keeps user factors worker-LOCAL; the canonical table is the
        # item table.
        return store.lookup_host("item_factors", np.arange(64)).ravel()

    return build, chunks, final


@pytest.mark.parametrize("workload", ["logreg", "mf"])
def test_trace_on_off_byte_identical_hlo_and_numerics(
        devices8, tmp_path, monkeypatch, workload):
    """THE tentpole invariant: tracing (env contract + open_run journal
    + Tracer spans) is pure host bookkeeping — the lowered program is
    byte-identical and the trained tables bit-identical with it on or
    off, on MF and logreg."""
    harness = _logreg_harness if workload == "logreg" else _mf_harness
    build, chunks, final = harness(devices8)

    def run(traced: bool):
        if traced:
            monkeypatch.setenv(TRACE_ID_ENV, new_trace_id())
            monkeypatch.setenv(PARENT_SPAN_ENV, new_span_id())
            rec = obs.open_run(str(tmp_path / f"obs-{workload}"),
                               config={"w": workload})
        else:
            monkeypatch.delenv(TRACE_ID_ENV, raising=False)
            monkeypatch.delenv(PARENT_SPAN_ENV, raising=False)
            rec = None
        trainer, store = build()
        trainer.recorder = rec
        hlo = trainer.lowered_chunk_text(chunks[0], "sync")
        tables, ls = trainer.init_state(jax.random.key(0))
        if rec is not None:
            with rec.trace.span("fit", workload=workload):
                trainer.fit_stream(tables, ls, iter(chunks),
                                   jax.random.key(1))
            rec.close()
        else:
            trainer.fit_stream(tables, ls, iter(chunks),
                               jax.random.key(1))
        return hlo, final(store)

    hlo_off, out_off = run(False)
    hlo_on, out_on = run(True)
    assert hlo_on == hlo_off  # byte-identical lowered program
    np.testing.assert_array_equal(out_on, out_off)  # bit-identical


# -- trace_export: journals -> one causal tree ---------------------------


def _write_jsonl(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def _synthetic_pod_dir(tmp_path):
    """A minimal 2-host pod trail: pod journal with launch + one
    coordinated restart, per-host supervisor journals with attempts
    parented to the decisions, one host's run journal with a chunk."""
    pod = str(tmp_path / "pod")
    trace = "t" * 32
    _write_jsonl(os.path.join(pod, "journal-pod.jsonl"), [
        {"kind": "event", "t": 100.0, "event": "pod_start", "host": "h0",
         "trace_id": trace, "span_id": "root", "roster": ["h0", "h1"],
         "pod_size": 2, "elastic": False},
        {"kind": "event", "t": 100.5, "event": "fence_written",
         "host": "h0", "trace_id": trace, "span_id": "f1",
         "parent_id": "d1", "min_epoch": 1, "step": 0},
        {"kind": "event", "t": 101.0, "event": "pod_launch", "host": "h0",
         "trace_id": trace, "span_id": "d1", "parent_id": "root",
         "epoch": 1, "step": 0, "world": 2, "members": ["h0", "h1"],
         "failed": [], "reason": "start", "restarts": 0},
        {"kind": "event", "t": 110.0, "event": "member_failed",
         "host": "h0", "trace_id": trace, "failed_host": "h1",
         "fail_kind": "crash", "epoch": 1},
        {"kind": "event", "t": 110.2, "event": "fence_written",
         "host": "h0", "trace_id": trace, "span_id": "f2",
         "parent_id": "d2", "min_epoch": 2, "step": 3},
        {"kind": "event", "t": 110.5, "event": "pod_restart",
         "host": "h0", "trace_id": trace, "span_id": "d2",
         "parent_id": "root", "epoch": 2, "step": 3, "world": 2,
         "members": ["h0", "h1"], "failed": ["h1"], "reason": "failure",
         "restarts": 1},
        {"kind": "event", "t": 130.0, "event": "pod_shutdown",
         "host": "h0", "trace_id": trace, "span_id": "end",
         "parent_id": "root", "epoch": 3, "reason": "complete"},
    ])
    for host, a1 in (("h0", "a0"), ("h1", "a1")):
        _write_jsonl(os.path.join(pod, host, "journal-supervisor.jsonl"), [
            {"kind": "event", "t": 101.2, "event": "attempt_start",
             "attempt": 0, "pid": 1, "trace_id": trace,
             "span_id": a1 + "x", "parent_id": "d1", "pod_epoch": 1},
            {"kind": "event", "t": 110.4, "event": "attempt_end",
             "attempt": 0, "rc": 1, "trace_id": trace,
             "span_id": a1 + "x", "parent_id": "d1", "pod_epoch": 1},
            {"kind": "event", "t": 110.8, "event": "attempt_start",
             "attempt": 1, "pid": 2, "trace_id": trace,
             "span_id": a1 + "y", "parent_id": "d2", "pod_epoch": 2},
            {"kind": "event", "t": 129.0, "event": "attempt_end",
             "attempt": 1, "rc": 0, "trace_id": trace,
             "span_id": a1 + "y", "parent_id": "d2", "pod_epoch": 2},
        ])
    _write_jsonl(os.path.join(pod, "h0", "journal-p0.jsonl"), [
        {"kind": "event", "t": 111.0, "event": "run_start",
         "run_id": "r1", "trace_id": trace, "span_id": "run0",
         "parent_id": "a0y", "host": "h0", "process": 0},
        {"kind": "event", "t": 112.0, "event": "chunk", "index": 3,
         "run_id": "r1",
         # enqueue lies INSIDE dispatch (obs.timing.NESTED_PHASES): the
         # serial sum, and with it the chunk span's start, leave it out.
         "phases": {"ingest": 0.1, "dispatch": 0.3, "enqueue": 0.25,
                    "host_sync": 0.1, "prefetch": 0.2}},
        {"kind": "event", "t": 112.5, "event": "checkpoint_saved",
         "run_id": "r1", "step": 4, "seconds": 0.2, "bytes": 1024},
        {"kind": "event", "t": 128.0, "event": "run_end", "run_id": "r1"},
    ])
    return pod, trace


def test_trace_export_builds_one_restart_tree(tmp_path):
    te = _load_trace_export()
    pod, trace = _synthetic_pod_dir(tmp_path)
    spans = te.collect_spans([pod])
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    # The coordinated restart: ONE tree, both hosts' attempts under it,
    # every child carrying the fencing epoch.
    trees = te.coordinated_restart_trees(spans)
    assert len(trees) == 1
    tree = trees[0]
    assert tree["epoch"] == 2
    kids = tree["children"]
    attempts = [c for c in kids if c["cat"] == "attempt"]
    assert sorted(c["host"] for c in attempts) == ["h0", "h1"]
    assert all(c["attrs"]["pod_epoch"] == 2 for c in attempts)
    fence = [c for c in kids if c["name"] == "fence_written"]
    assert len(fence) == 1 and fence[0]["attrs"]["min_epoch"] == 2

    # Decision spans are closed by the next decision; the pod root spans
    # the whole run; the run journal hangs under the attempt.
    launch = by_name["pod_launch"][0]
    assert launch["t1"] == pytest.approx(110.5)
    assert by_name["pod"][0]["t1"] >= 130.0
    run = by_name["run"][0]
    assert run["parent_id"] == "a0y"
    chunk = by_name["chunk"][0]
    assert chunk["parent_id"] == run["span_id"]
    # Phase children reconstruct the PhaseTimer breakdown: serial phases
    # tile [t-serial, t], the overlapped prefetch rides alongside.
    assert chunk["t0"] == pytest.approx(112.0 - 0.5)
    phases = [s for s in spans if s["cat"] == "phase"
              and s["parent_id"] == chunk["span_id"]]
    assert sorted(p["name"] for p in phases) == [
        "dispatch", "host_sync", "ingest", "prefetch"]
    pre = next(p for p in phases if p["name"] == "prefetch")
    assert pre["attrs"] == {"overlapped": True}
    ckpt = by_name["checkpoint_publish"][0]
    assert ckpt["t1"] - ckpt["t0"] == pytest.approx(0.2)

    # Every span carries the one trace id it inherited.
    assert {s["trace_id"] for s in spans if s["trace_id"]} == {trace}


def test_trace_export_renders_the_drivers_host_spans_unchanged(
        devices8, tmp_path):
    """ISSUE 24: the drivers' host spans are the canonical span record,
    so the exporter needs no change to hang a call's phases under the
    call and the call under the run."""
    te = _load_trace_export()
    build, chunks, _ = _mf_harness(devices8)
    d = str(tmp_path / "obs")
    rec = obs.open_run(d, config={"w": "mf"})
    try:
        trainer, _ = build()
        trainer.recorder = rec
        tables, ls = trainer.init_state(jax.random.key(0))
        trainer.fit_stream(tables, ls, iter(chunks[:2]), jax.random.key(1))
    finally:
        rec.close()
    assert te._NESTED_PHASES == obs.timing.NESTED_PHASES
    spans = [s for s in te.collect_spans([d]) if s["cat"] == "span"]
    by_id = {s["span_id"]: s for s in spans}
    run = next(s for s in te.collect_spans([d]) if s["cat"] == "run")
    call = next(s for s in spans if s["name"] == "fit_stream")
    assert call["parent_id"] == run["span_id"]
    enq = [s for s in spans if s["name"] == "enqueue"]
    assert len(enq) == 2  # one per chunk
    for s in enq:
        parent = by_id[s["parent_id"]]
        assert parent["name"] == "dispatch"
        assert by_id[parent["parent_id"]] is call
        assert s["attrs"]["call"] == call["attrs"]["call"]
        assert parent["t0"] <= s["t0"] and s["t1"] <= parent["t1"] + 1e-6
    # Set-up spans (no timer) went to the installed default: same journal.
    assert any(s["name"] == "init_state" for s in spans)


def test_trace_export_chrome_and_cli(tmp_path, capsys):
    te = _load_trace_export()
    pod, _ = _synthetic_pod_dir(tmp_path)
    spans = te.collect_spans([pod])
    doc = te.export_chrome(spans)
    events = doc["traceEvents"]
    named = [e for e in events if e.get("ph") == "X"]
    # Valid Chrome trace: parseable strict JSON, metadata names the
    # hosts, micros are ints, args carry the causal links.
    json.loads(json.dumps(doc, allow_nan=False))
    procs = {e["args"]["name"] for e in events if e.get("ph") == "M"}
    assert {"h0", "h1"} <= procs
    restart = next(e for e in named if e["name"] == "pod_restart")
    assert isinstance(restart["ts"], int) and restart["dur"] >= 1
    assert restart["args"]["span_id"] == "d2"

    out = str(tmp_path / "trace.json")
    assert te.main([pod, "-o", out]) == 0
    assert json.load(open(out))["traceEvents"]
    # Empty input dir: loud nonzero exit, not an empty trace.
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert te.main([empty]) == 2


def test_supervisor_attempts_carry_trace(tmp_path, monkeypatch):
    """A real (stub-speed) RunSupervisor run: attempt events carry
    trace/span ids, the child inherits them via env, and the exported
    spans parent child-run -> attempt -> supervisor."""
    from fps_tpu.supervise.supervisor import RunSupervisor, SupervisorConfig

    monkeypatch.setenv(TRACE_ID_ENV, "e" * 32)
    monkeypatch.setenv(PARENT_SPAN_ENV, "b" * 16)
    state = str(tmp_path / "state")
    probe = str(tmp_path / "env.json")
    import sys

    code = (
        "import json,os;"
        "json.dump({k: os.environ.get(k) for k in "
        "('" + TRACE_ID_ENV + "', '" + PARENT_SPAN_ENV + "')}, "
        "open(" + repr(probe) + ", 'w'))"
    )
    sup = RunSupervisor([sys.executable, "-c", code], state_dir=state,
                        config=SupervisorConfig(stall_timeout_s=30,
                                                max_restarts=0,
                                                poll_interval_s=0.05))
    digest = sup.run()
    assert digest["success"]
    env = json.load(open(probe))
    assert env[TRACE_ID_ENV] == "e" * 32  # inherited, not re-minted
    recs = [json.loads(line) for line in open(sup.journal_path)]
    start = next(r for r in recs if r["event"] == "attempt_start")
    end = next(r for r in recs if r["event"] == "attempt_end")
    assert start["trace_id"] == "e" * 32
    assert start["span_id"] == end["span_id"] == env[PARENT_SPAN_ENV]
    sup_start = next(r for r in recs if r["event"] == "supervisor_start")
    assert start["parent_id"] == sup_start["span_id"]
    assert sup_start["parent_id"] == "b" * 16

    te = _load_trace_export()
    spans = te.collect_spans([state])
    attempt = next(s for s in spans if s["name"] == "attempt")
    supv = next(s for s in spans if s["name"] == "supervise")
    assert attempt["parent_id"] == supv["span_id"]
    assert attempt["t1"] >= attempt["t0"]
