"""fps_tpu.obs — first-class telemetry for the TPU parameter server.

One subsystem, four altitudes (see ``docs/observability.md``):

* **schema** — :class:`MetricsRegistry` / :class:`MetricSpec` name and
  type every metrics leaf; :class:`Recorder` validates emissions and fans
  them out to pluggable sinks (:class:`JsonlSink`,
  :class:`PrometheusSink`, :class:`MemorySink`).
* **timing** — :func:`host_span` is the one host span primitive: a
  ``fps.host.<name>`` annotation on the profiler's clock, a
  ``driver.phase_seconds`` sample, and a span record with its parent and
  the enclosing driver call; :class:`PhaseTimer` splits each chunk into
  host phases through it (ingest/place/dispatch/host_sync/checkpoint/
  callback/...); :func:`watch_compiles` folds JAX's compile timings and
  cache hits in; :func:`watch_device` hands every unit of work an entry
  point queues to a watcher thread that stamps its completion (the
  ``device.<entry>`` spans: how long the device ran the unit, how long
  it starved before it, with no caller made to wait);
  :func:`device_bytes` reads the device's memory, which those spans carry
  under a recorder (``hbm_open`` / ``hbm_close`` of a call, ``hbm_delta``
  of a set-up span, ``hbm_done`` / ``hbm_peak`` of a device span), and
  :func:`watch_program` states each compiled program's own
  (``program.memory``); :func:`trace` completes the clock set.
* **alerting** — :class:`HealthMonitor` thresholds the guard's health
  channel (observe→mask escalation, poison abort);
  :class:`StepWatchdog` deadline-flags stalled chunks/stragglers.
* **journal** — :class:`RunJournal` writes the per-process run narrative
  that ``tools/obs_report.py`` renders into a digest.
* **tracing** — :mod:`fps_tpu.obs.trace` mints trace/span ids propagated
  through the supervised-child env contract, so supervisor decisions,
  pod restarts, attempts, and chunk phases link into ONE causal tree
  (``tools/trace_export.py`` renders Chrome/Perfetto JSON).
* **fleet** — :mod:`fps_tpu.obs.fleet` tails N per-host obs dirs into
  windowed rollups with declarative SLO burn-rate evaluation
  (``tools/obs_report.py --fleet``).
* **drift** — :mod:`fps_tpu.obs.drift` checks the live data plane's
  measured collective traffic against the budgets pinned in
  ``AUDIT_r*.json`` (``analysis.budget_drift`` + incidents).

Everything is host-side: attaching a recorder never changes the compiled
program (tested), and ``recorder=None`` costs nothing.

Names a trace or a journal is read by (``docs/observability.md`` has the
table with what opens each):

=========================  ==============================================
device scope               ``fps.ingest`` ``fps.pull`` ``fps.compute``
(``jax.named_scope``,      ``fps.push`` ``fps.metrics`` and, inside pull
step bodies ONLY: a        and push, ``fps.ops/<op>.<route>`` with route
reader counts steps by     one of ``dim1_head|dim1|xla_packed|xla`` under
the ops under ``fps.*``)   ``gather.`` and ``scatter_add.`` alike
once a call / a chunk      ``ingest.pack`` ``ingest.tbuf`` ``ingest.perm``
(no ``fps.`` prefix)       ``ingest.chunk``
host span                  ``run_indexed`` ``fit_stream`` ``run_megastep``
(``fps.host.<name>``,      (the call) > ``attach_hot`` > ``reconcile``,
:data:`DRIVER_PHASES`      ``program_lookup``, ``epoch_args``, ``ingest``,
and companions in          ``place``, ``dispatch`` > ``enqueue``,
``obs/timing.py``)         ``megastep``, ``host_sync``, ``checkpoint``,
                           ``callback``, ``retier``, ``prefetch``;
                           set-up: ``dataset.place`` ``dataset.queues``
                           ``dataset.pack`` ``plan.build`` ``init_state``
device span                ``device.run_indexed`` (an epoch)
(:func:`watch_device`;     ``device.fit_stream`` (a chunk)
``fps.device.<entry>``     ``device.run_megastep`` ``device.als.half_epoch``
in a profiler trace)       (a sweep): ``t0`` ``t1`` ``t_enqueued``
                           ``wait_s`` ``starved_s`` ``in_flight`` ``steps``
                           ``hbm_done`` ``hbm_peak``
program span               ``program.memory`` (one a compiled program):
(:func:`watch_program`)    ``label`` ``argument_bytes`` ``output_bytes``
                           ``alias_bytes`` ``temp_bytes`` ``code_bytes``
compile phase              ``compile.trace`` ``compile.lower``
                           ``compile.backend``; counters
                           ``compile.cache_hits`` / ``_misses``; event
                           ``program_compiled``
route log                  ``fps_tpu.ops.routes_traced()``: ``(op, route,
                           rows, dim, ids, interpret, reason)``
=========================  ==============================================
"""

from __future__ import annotations

import os

from fps_tpu.obs import events
from fps_tpu.obs.drift import BudgetDriftDetector, load_pinned_budgets
from fps_tpu.obs.fleet import (
    DEFAULT_SLOS,
    SLO,
    evaluate_slos,
    fleet_digest,
)
from fps_tpu.obs.health import (
    HEALTH_ABORT,
    HEALTH_ESCALATE,
    HEALTH_OK,
    HealthMonitor,
    StepWatchdog,
)
from fps_tpu.obs.journal import (
    RunJournal,
    config_digest,
    new_run_id,
    process_index,
)
from fps_tpu.obs.registry import (
    MetricSpec,
    MetricsRegistry,
    Recorder,
    default_registry,
)
from fps_tpu.obs.sinks import JsonlSink, MemorySink, PrometheusSink, Sink
from fps_tpu.obs.timing import (
    DRIVER_PHASES,
    PhaseTimer,
    device_bytes,
    host_span,
    trace,
    watch_compiles,
    watch_device,
    watch_program,
)
from fps_tpu.obs.trace import (
    PARENT_SPAN_ENV,
    TRACE_ID_ENV,
    TraceContext,
    Tracer,
    new_span_id,
    new_trace_id,
)

__all__ = [
    "MetricSpec", "MetricsRegistry", "Recorder", "default_registry",
    "Sink", "JsonlSink", "MemorySink", "PrometheusSink",
    "PhaseTimer", "trace", "DRIVER_PHASES",
    "host_span", "watch_compiles", "watch_device", "device_bytes",
    "watch_program",
    "HealthMonitor", "StepWatchdog",
    "HEALTH_OK", "HEALTH_ESCALATE", "HEALTH_ABORT",
    "RunJournal", "new_run_id", "config_digest", "process_index",
    "TraceContext", "Tracer", "new_trace_id", "new_span_id",
    "TRACE_ID_ENV", "PARENT_SPAN_ENV",
    "BudgetDriftDetector", "load_pinned_budgets",
    "SLO", "DEFAULT_SLOS", "evaluate_slos", "fleet_digest",
    "events", "open_run",
]


def open_run(obs_dir: str, *, config=None, run_id: str | None = None,
             meta: dict | None = None, registry: MetricsRegistry | None = None,
             install: bool = True) -> Recorder:
    """Standard on-disk telemetry for one training run (the ``--obs-dir``
    CLI path): under ``obs_dir`` this process writes

    * ``events-p<K>.jsonl``  — every metric sample + event (JSONL),
    * ``journal-p<K>.jsonl`` — events only, bracketed run_start/run_end,
    * ``metrics-p<K>.prom``  — Prometheus text exposition (rewritten at
      flush; point a file scrape at it),

    where ``<K>`` is the process index (multi-host: one set per process;
    ``tools/obs_report.py`` joins on the shared run id). ``config`` is
    digested into the journal's run_start record; ``install=True`` also
    makes this the process-default recorder so checkpoint/rollback events
    flow without explicit plumbing. Close (or ``with``-scope) the
    recorder to get the run_end record and final flush.
    """
    run_id = run_id or new_run_id()
    proc = process_index()
    os.makedirs(obs_dir, exist_ok=True)
    # Causal tracing (fps_tpu.obs.trace): inherit the trace/parent-span
    # from the supervisor env contract (or mint a standalone trace) and
    # mint this run's own span — the journal's run_start is the causal
    # anchor everything in this obs dir hangs under when
    # tools/trace_export.py renders the tree. Host-side only: these are
    # env vars and journal fields, never traced into a program.
    ctx = TraceContext.from_env()
    run_span = new_span_id()
    run_meta = {"process": proc, "config_digest": config_digest(config),
                "trace_id": ctx.trace_id or new_trace_id(),
                "span_id": run_span, "parent_id": ctx.parent_id}
    if meta:
        run_meta.update(meta)
    journal = RunJournal(
        os.path.join(obs_dir, f"journal-p{proc}.jsonl"),
        run_id=run_id, meta=run_meta,
    )
    rec = Recorder(
        registry,
        sinks=[
            JsonlSink(os.path.join(obs_dir, f"events-p{proc}.jsonl")),
            PrometheusSink(os.path.join(obs_dir, f"metrics-p{proc}.prom")),
            journal,
        ],
        run_id=run_id,
        base_labels={"process": str(proc)},
    )
    # The run's tracer: explicit spans emitted through it parent under
    # this run's span by default (rec.trace.span("my_phase"): ...).
    rec.trace = Tracer(rec, trace_id=run_meta["trace_id"],
                       parent_id=run_span)
    if install:
        events.set_default_recorder(rec)
        _prev_close = rec.close

        def close_and_uninstall():
            if events.get_default_recorder() is rec:
                events.set_default_recorder(None)
            _prev_close()

        rec.close = close_and_uninstall
    return rec
