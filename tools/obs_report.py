"""Render an ``--obs-dir`` telemetry directory into one run digest.

Reads every per-process ``events-p*.jsonl`` and ``journal-p*.jsonl``
under the directory (multi-host runs write one pair per process; they
join on ``run_id``) and prints a single JSON digest:

* run identity — run ids, config digest, processes, wall-clock span;
* progress — chunks/epochs/steps/examples, quarantined indices;
* **per-phase timings** — total/mean/max seconds per host phase
  (prefetch / ingest / place / dispatch / host_sync / checkpoint /
  callback — ``prefetch`` is the background pipeline's worker-thread
  time, i.e. host work OVERLAPPED with the phases beside it);
* **device** — the device's time from inside (the ``device.<entry>``
  spans of ``fps_tpu.obs.timing.watch_device``), per entry point: units
  (epochs, chunks, megasteps, ALS sweeps) completed, the seconds the
  device ran them, the share of their extent it had nothing of the
  program's queued (``starved_share``: the host was late), the median
  time a unit waited for the device, and the most units in flight;
* **memory** — the device's memory from inside (the ``hbm_*`` fields the
  spans carry under a recorder on a backend that counts its memory, and
  the ``program.memory`` spans; ``fps_tpu.obs.timing.device_bytes`` /
  ``watch_program``), in bytes of the fullest local device: what is
  resident before any call is queued (``resident_bytes``: the first root
  call span's ``hbm_open``; ``setup_peak_bytes``: the running peak as that
  call returned, set-up's transients), what each set-up span left there, what one
  call queued ahead holds from its dispatch (per entry point: the median
  ``hbm_close - hbm_open``), the running peak as the last unit left it,
  what was in use as the first and the last unit ended (a leak shows as
  growth), the allocator's limit and what is left under it, and what each compiled
  program needs beside its operands and outputs while it runs
  (``programs``: ``temp_bytes`` and the rest of ``memory_analysis()``);
* **compile** — compiles from inside (``obs.timing.watch_compiles``):
  programs the backend compiled or loaded and their seconds, the
  persistent cache's hits and misses, and the slowest program by name
  ("which program recompiled, and was the cache warm");
* **host pipeline** — chunks prefetched and the queue-depth gauge's
  last/max (the gauge samples after every put/get, so with any traffic
  the max is >= 1; a max STUCK at 1 means the driver drained each chunk
  the moment it landed — assembly is the bottleneck, a deeper queue
  won't help — while a max at the configured depth means the worker
  kept the buffer full: the device-bound good case);
* **per-table health totals** — nonfinite/norm/masked row counts;
* **hot tier** — two-tier storage hit rate (rows served by the
  replicated hot head over total pulled rows) and the last/max
  pending-delta gauge (parameter-plane staleness;
  `docs/performance.md` "Two-tier storage");
* **tiering** — adaptive-tiering activity (`fps_tpu.tiering`):
  re-ranks applied, promoted/demoted row totals, and the churn gauge
  (`docs/performance.md` "Adaptive tiering");
* **serve** — read-path tier (`fps_tpu.serve`): requests/rows served,
  exact p50/p99 request latency, the served step + step lag + the
  write→servable freshness SLO gauges, forward/backward swap counts, and
  rejected (CRC-failing) snapshot candidates (`docs/serving.md`);
* **incidents** — rollbacks, watchdog stalls (+ recoveries), guard
  escalations, health aborts, checkpoint fallbacks, checkpoint saves —
  plus, from the supervisor journal, `deadline_abort` events whose
  `stall_kind` is `source_stall` (a stalled `prefetch`-phase heartbeat:
  the SOURCE wedged while the driver waited on it, a distinct incident
  from a wedged driver) summarized as `source_stalls`;
* **analysis** — program-contract certification (`Trainer(audit=...)`,
  `fps_tpu.analysis`): programs certified clean, contract violations
  found at compile time, and each `analysis.contract_violation` event
  verbatim under `incidents` (`docs/analysis.md`).

Pure host tool: no jax import, safe to run on a login node against a
live or finished run directory.

Fleet mode (``--fleet DIR [DIR...]``) aggregates N per-host obs dirs
through ``fps_tpu/obs/fleet.py`` (loaded by file path, still jax-free):
windowed rollups (throughput, tiering hit rate, cold-route certification
rate, write→servable freshness, restart/fence counts) plus SLO burn-rate
evaluation, with each host's standard digest attached. ``--json`` pins
the machine-readable contract: compact strict JSON, non-finite floats
scrubbed to null, and a versioned ``schema`` field.

Usage:
  python tools/obs_report.py RUN_DIR [--pretty|--json]
  python tools/obs_report.py --fleet HOST_DIR... [--window-s S] [--json]
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import math
import os
import sys

# Event types surfaced verbatim (bounded lists) in the digest. The last
# five come from the run SUPERVISOR's journal (journal-supervisor.jsonl,
# written by tools/supervise.py into its --state-dir) — point this tool
# at a dir holding both and the digest narrates the whole supervised run.
_INCIDENT_EVENTS = (
    "rollback",
    "preset_skip",
    "stall",
    "stall_recovered",
    "guard_escalated",
    "health_abort",
    "poisoned_stream_abort",
    "checkpoint_fallback",
    "checkpoint_fenced",
    "checkpoint_resplit",
    "deadline_abort",
    "supervisor_restart",
    "attempt_first_signal",
    "chunk_quarantined",
    "heartbeat_rejected",
    "supervisor_give_up",
    "supervised_run_end",
    # Time-to-recovered SLO (ISSUE 20): synthesized by this tool when a
    # paired attempt_end -> attempt_first_signal gap exceeds the
    # --recovery-slo-s bound; also folded verbatim if a journal carries
    # one (tools/chaos_sweep.py keeps its own per-scenario bounds).
    "recovery_slo_breach",
    "analysis.contract_violation",
    # Runtime budget-drift detection (fps_tpu.obs.drift): measured
    # collective traffic departed from the AUDIT_r*.json pinned shape.
    "budget_drift",
    # Hostile-filesystem degradation (fps_tpu.core.retry + the async
    # writer's degraded mode): skipped publishes, aborted compactions,
    # and the backlog-drain marker after storage recovery.
    "checkpoint_degraded",
    "checkpoint_backlog_drained",
    "compaction_aborted",
    "leader_io_error",
    # Hostile-network survival (fps_tpu.serve.wire / serve.fleet): a
    # silent reader became an incident the supervisor can act on, and
    # torn frames were rejected loudly instead of decoded.
    "reader_wedged",
    "reader_restarted",
    "wire_torn_frame",
    # Pod coordination (journal-pod.jsonl, written into the pod dir by
    # the lease-holding member — point this tool at the pod dir and the
    # digest narrates the whole pod run).
    "lease_seized",
    "member_failed",
    "member_evicted",
    "member_readmitted",
    "pod_restart",
    "pod_quarantine",
    "pod_give_up",
    "pod_shutdown",
)

# Digest keys that must always be present (the smoke test asserts these —
# consumers can rely on the shape even for an empty run). The digest is
# versioned: DIGEST_SCHEMA_VERSION bumps whenever an existing field
# changes meaning (new fields may appear without a bump) — `--json`
# consumers (CI, fps_tpu/obs/fleet.py) key on it instead of scraping.
DIGEST_SCHEMA_VERSION = 1
REQUIRED_FIELDS = (
    "schema", "obs_dir", "run_ids", "processes", "chunks", "epochs",
    "steps", "examples", "phase_seconds", "health", "incidents",
    "checkpoint", "checkpoint_saves", "quarantined", "wall_span_s",
    "prefetch", "device", "memory", "compile", "tap",
    "hot_tier", "megastep", "tiering", "source_stalls", "analysis",
    "serve", "pod", "net", "recovery",
)


def _seconds_stats(samples: list) -> dict:
    """Summary of one histogram's raw samples (n/total/mean/p99/max) —
    the checkpoint dump/capture split in the digest."""
    if not samples:
        return {"n": 0, "total_s": None, "mean_s": None,
                "p99_s": None, "max_s": None}
    s = sorted(samples)
    return {"n": len(s),
            "total_s": round(sum(s), 6),
            "mean_s": round(sum(s) / len(s), 6),
            "p99_s": round(_quantile(s, 0.99), 6),
            "max_s": round(s[-1], 6)}


def _quantile(sorted_vals: list, q: float):
    """Exact quantile over a sorted sample list (the ReadServer
    reservoir's index formula, so the two reports agree)."""
    n = len(sorted_vals)
    if not n:
        return None
    return sorted_vals[min(n - 1, int(q * (n - 1) + 0.5))]


def _device_section(spans: dict) -> dict:
    """Per entry point (``of``), from its ``device.<of>`` span events:
    how long the device ran the units the program queued, and how long it
    had nothing of them queued. A unit's starved time lies before its
    ``t_enqueued``; only what lies inside the extent (first ``t0`` to last
    ``t1``) counts, so the idle time before a run's first unit does not."""
    out = {}
    for of, evs in sorted(spans.items()):
        first = min(e["t0"] for e in evs)
        extent = max(e["t1"] for e in evs) - first
        starved = sum(
            max(0.0, e["t_enqueued"]
                - max(first, e["t_enqueued"] - e.get("starved_s", 0.0)))
            for e in evs)
        waits = sorted(e.get("wait_s", 0.0) for e in evs)
        out[of] = {
            "units": len(evs),
            "device_s": round(sum(e["t1"] - e["t0"] for e in evs), 6),
            "starved_s": round(starved, 6),
            "starved_share": (round(starved / extent, 6)
                              if extent > 0 else None),
            "wait_median_s": round(_quantile(waits, 0.5), 6),
            "in_flight_max": max(int(e.get("in_flight", 0)) for e in evs),
        }
    return out


# The driver entry points' root spans (``obs.timing.CALL_SPANS``; this tool
# imports nothing of the package).
_CALL_SPANS = ("run_indexed", "fit_stream", "run_megastep", "als.half_epoch")
_PROGRAM_FIELDS = ("argument_bytes", "output_bytes", "alias_bytes",
                   "temp_bytes", "code_bytes")


def _memory_section(spans: list) -> dict:
    """The device's memory from the journal's span events alone: empty
    where no span carries bytes (no recorder on the chip, or a backend
    that counts none). Bytes of the fullest local device."""
    spans = sorted(spans, key=lambda e: (e["t0"], e["t1"]))
    calls = [e for e in spans if e["span"] in _CALL_SPANS
             and "hbm_open" in e and "hbm_close" in e]
    units = [e for e in spans if e["span"].startswith("device.")
             and "hbm_peak" in e]
    programs, setup = {}, collections.defaultdict(int)
    for e in spans:
        if e["span"] == "program.memory" and "temp_bytes" in e:
            programs[str(e.get("label"))] = {
                k: int(e[k]) for k in _PROGRAM_FIELDS if k in e}
        if "hbm_delta" in e:
            setup[e["span"]] += int(e["hbm_delta"])
    out: dict = {}
    if calls:
        held = collections.defaultdict(list)
        for e in calls:
            held[e["span"]].append(int(e["hbm_close"]) - int(e["hbm_open"]))
        out["resident_bytes"] = int(calls[0]["hbm_open"])
        if "hbm_peak" in calls[0]:
            # The peak as the FIRST call returned, none of its programs
            # run: what set-up's transients (data made, tables placed)
            # had reached. A run's peak at or under it is set-up's.
            out["setup_peak_bytes"] = int(calls[0]["hbm_peak"])
        out["held_per_call_bytes"] = {
            of: _quantile(sorted(v), 0.5) for of, v in sorted(held.items())}
    peaks = [int(e["hbm_peak"]) for e in units + calls if "hbm_peak" in e]
    limits = [int(e["hbm_limit"]) for e in calls
              if e.get("hbm_limit") is not None]
    if peaks:
        out["peak_bytes"] = max(peaks)
    done = [int(e["hbm_done"]) for e in units if "hbm_done" in e]
    if done:
        # In use as the first and the last unit ended: a run that holds
        # more at every completion is leaking buffers.
        out["done_bytes"] = {"first": done[0], "last": done[-1]}
    if limits:
        out["limit_bytes"] = min(limits)
        if peaks:
            out["left_bytes"] = min(limits) - max(peaks)
    if setup:
        out["setup_bytes"] = dict(sorted(setup.items()))
    if programs:
        out["programs"] = dict(sorted(programs.items()))
        out["largest_program_temp_bytes"] = max(
            p["temp_bytes"] for p in programs.values())
    return out


def _compile_section(compiled: list, counters) -> dict:
    """From the ``program_compiled`` events (one a backend compile or
    cache load, JAX's own timing) and the persistent cache's counters."""
    slowest = max(compiled, key=lambda e: e.get("seconds", 0.0),
                  default=None)
    return {
        "programs": len(compiled),
        "backend_s": round(sum(float(e.get("seconds", 0.0))
                               for e in compiled), 6),
        "cache_hits": int(counters.get("compile.cache_hits", 0)),
        "cache_misses": int(counters.get("compile.cache_misses", 0)),
        "slowest": (None if slowest is None else {
            "fun_name": slowest.get("fun_name"),
            "seconds": round(float(slowest.get("seconds", 0.0)), 6)}),
    }


def _read_jsonl(path: str):
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                # A torn final line (live run, killed writer) is expected;
                # everything before it is still a valid prefix.
                return


def render_digest(obs_dir: str, *, recovery_slo_s: float | None = None) -> dict:
    """Digest dict from an obs directory (see module docstring).

    ``recovery_slo_s`` enforces a time-to-recovered bound: every paired
    restart whose kill→first-signal gap exceeds it becomes a
    ``recovery_slo_breach`` incident, and the ``recovery`` section gains
    ``slo_s`` / ``breaches`` fields. ``None`` (default) reports without
    judging."""
    event_files = sorted(glob.glob(os.path.join(obs_dir, "events-p*.jsonl")))
    # journal-* (not journal-p*): also picks up journal-supervisor.jsonl
    # when the supervisor's --state-dir is (or is joined into) this dir.
    journal_files = sorted(
        glob.glob(os.path.join(obs_dir, "journal-*.jsonl")))
    if not event_files and not journal_files:
        raise FileNotFoundError(
            f"no events-p*.jsonl / journal-p*.jsonl under {obs_dir!r} — "
            "was the run started with --obs-dir (fps_tpu.obs.open_run)?"
        )

    counters: dict[str, float] = collections.defaultdict(float)
    gauges: dict[str, dict] = {}  # name -> {"last": v, "max": v}
    serve_latency: list[float] = []  # serve.request_seconds samples
    # Raw-speed split (ISSUE 20): what a save costs the TRAINING thread
    # (dump = enqueue) vs what the WRITER pays off-thread (capture).
    ckpt_seconds: dict[str, list[float]] = {
        "checkpoint.dump_seconds": [],
        "checkpoint.capture_seconds": [],
    }
    swap_directions: dict[str, int] = collections.defaultdict(int)
    phases: dict[str, dict] = {}
    device_spans: dict[str, list] = collections.defaultdict(list)
    memory_spans: list = []  # every span that carries bytes
    compiled: list = []      # program_compiled events
    health: dict[str, dict] = {}
    incidents: dict[str, list] = {k: [] for k in _INCIDENT_EVENTS}
    run_ids: set[str] = set()
    processes: set[int] = set()
    config_digests: set[str] = set()
    quarantined: list[int] = []
    t_min = t_max = None

    def see_time(t):
        nonlocal t_min, t_max
        if t is None:
            return
        t_min = t if t_min is None else min(t_min, t)
        t_max = t if t_max is None else max(t_max, t)

    # Events appear in BOTH the event log and the journal (one Recorder
    # emission fans out to every sink) — and after a crash the journal
    # (flushed per record) can hold incidents the event log's buffered
    # tail lost. Fold both sources, deduping on exact record content.
    seen_events: set[str] = set()
    # Supervisor recovery pairing (mirrors
    # fps_tpu.supervise.supervisor.recovery_times — this tool stays
    # import-free): attempt -> timestamp for each side of the pair.
    attempt_firsts: dict[int, float] = {}
    attempt_ends: dict[int, float] = {}

    def fold_event(rec):
        key = json.dumps(rec, sort_keys=True, default=str)
        if key in seen_events:
            return
        seen_events.add(key)
        et = rec.get("event")
        if et in incidents:
            incidents[et].append(
                {k: v for k, v in rec.items() if k != "kind"})
        if et in ("chunk", "epoch") and rec.get("quarantined"):
            quarantined.append(rec.get("index"))
        if (et == "span" and str(rec.get("span", "")).startswith("device.")
                and all(isinstance(rec.get(k), (int, float))
                        for k in ("t0", "t1", "t_enqueued"))):
            device_spans[rec["span"][len("device."):]].append(rec)
        if et == "program_compiled":
            compiled.append(rec)
        if (et == "span" and isinstance(rec.get("span"), str)
                and any(k.startswith("hbm_") or k == "temp_bytes"
                        for k in rec)
                and all(isinstance(rec.get(k), (int, float))
                        for k in ("t0", "t1"))):
            memory_spans.append(rec)
        if (et in ("attempt_first_signal", "attempt_end")
                and rec.get("t") is not None
                and rec.get("attempt") is not None):
            try:
                a, t = int(rec["attempt"]), float(rec["t"])
            except (TypeError, ValueError):
                return
            if et == "attempt_end":
                attempt_ends[a] = max(attempt_ends.get(a, t), t)
            else:
                attempt_firsts.setdefault(a, t)  # first signal wins

    for rec in (r for p in event_files for r in _read_jsonl(p)):
        see_time(rec.get("t"))
        if rec.get("run_id"):
            run_ids.add(rec["run_id"])
        kind = rec.get("kind")
        if kind == "metric":
            name = rec.get("name", "")
            labels = rec.get("labels") or {}
            raw = rec.get("value", 0.0)
            # A null value is the strict-JSON spelling of a non-finite
            # sample (the serving watcher's orphaned-snapshot gauge).
            v = math.nan if raw is None else float(raw)
            if name == "driver.phase_seconds":
                ph = phases.setdefault(
                    labels.get("phase", "?"),
                    {"total_s": 0.0, "n": 0, "max_s": 0.0},
                )
                ph["total_s"] += v
                ph["n"] += 1
                ph["max_s"] = max(ph["max_s"], v)
            elif name.startswith("health.") and name.endswith("_rows"):
                table = labels.get("table", "?")
                tier = name[len("health."):-len("_rows")]
                health.setdefault(
                    table, {"nonfinite": 0, "norm": 0, "masked": 0}
                )[tier] += int(v)
            elif name == "serve.request_seconds":
                serve_latency.append(v)
            elif name in ckpt_seconds:
                ckpt_seconds[name].append(v)
            elif rec.get("mtype") == "counter":
                if name == "serve.swaps":
                    swap_directions[labels.get("direction", "?")] += int(v)
                counters[name] += v
            elif rec.get("mtype") == "gauge":
                # "last" by record TIMESTAMP, not file-iteration order —
                # a multi-process dir's files fold in name order.
                t = float(rec.get("t") or 0.0)
                g = gauges.setdefault(
                    name, {"last": v, "last_t": t, "max": v})
                if t >= g["last_t"]:
                    g["last"], g["last_t"] = v, t
                # Non-finite samples mark outages; they must not poison
                # the max (which would turn order-dependently NaN).
                if math.isfinite(v):
                    g["max"] = (v if not math.isfinite(g["max"])
                                else max(g["max"], v))
        elif kind == "event":
            fold_event(rec)

    # Journals: run identity + anything the event files missed (a process
    # may have died before its event sink flushed; journals flush per
    # record, so their incident trail survives a SIGKILL).
    started: set[str] = set()
    ended: set[str] = set()
    for rec in (r for p in journal_files for r in _read_jsonl(p)):
        see_time(rec.get("t"))
        if rec.get("run_id"):
            run_ids.add(rec["run_id"])
        fold_event(rec)
        if rec.get("event") == "run_start":
            started.add(rec.get("run_id"))
            if "process" in rec:
                processes.add(int(rec["process"]))
            if rec.get("config_digest"):
                config_digests.add(rec["config_digest"])
        elif rec.get("event") == "run_end":
            ended.add(rec.get("run_id"))

    for ph in phases.values():
        ph["total_s"] = round(ph["total_s"], 6)
        ph["mean_s"] = round(ph["total_s"] / max(ph["n"], 1), 6)
        ph["max_s"] = round(ph["max_s"], 6)

    # time_to_recovered_s per restart: the gap from an attempt's end to
    # the NEXT attempt's first liveness signal (kill -> first
    # post-restart dispatch) — the MTTR figure the chaos sweep records.
    recovery_times: list[float] = []
    for a in sorted(attempt_firsts):
        t_first = attempt_firsts[a]
        prior = [te for ae, te in attempt_ends.items()
                 if ae < a and te <= t_first]
        if prior:
            recovery_times.append(round(t_first - max(prior), 3))

    # Time-to-recovered SLO enforcement: every paired restart slower
    # than the bound becomes an incident, synthesized here next to any
    # recovery_slo_breach events a journal already carried.
    if recovery_slo_s is not None and recovery_slo_s > 0:
        for i, t in enumerate(recovery_times):
            if t > recovery_slo_s:
                incidents["recovery_slo_breach"].append({
                    "event": "recovery_slo_breach", "restart": i,
                    "time_to_recovered_s": t,
                    "slo_s": round(float(recovery_slo_s), 3),
                })

    digest = {
        "schema": DIGEST_SCHEMA_VERSION,
        "obs_dir": os.path.abspath(obs_dir),
        "run_ids": sorted(run_ids),
        "config_digests": sorted(config_digests),
        "processes": sorted(processes) or [0],
        "chunks": int(counters.get("driver.chunks", 0)),
        "epochs": int(counters.get("driver.epochs", 0)),
        "steps": int(counters.get("driver.steps", 0)),
        "examples": counters.get("driver.examples", 0.0),
        "phase_seconds": dict(sorted(phases.items())),
        # What a step tap counted on the host (TrainerConfig.step_tap's
        # ``journal``): the top-K tap's lists answered and the padding
        # queries that asked; empty without such a tap.
        "tap": {name[len("tap."):]: int(v)
                for name, v in sorted(counters.items())
                if name.startswith("tap.")},
        # The device's time, from inside (obs.timing.watch_device): the
        # phases above time the host QUEUEING; these say how long the
        # device ran what was queued, and whether the host kept it fed.
        "device": _device_section(device_spans),
        # The device's MEMORY, from inside (obs.timing.device_bytes on the
        # spans, watch_program's program.memory): what is resident, what a
        # queued call holds, the peak, the limit, each program's own.
        "memory": _memory_section(memory_spans),
        # Compiles, from inside (obs.timing.watch_compiles).
        "compile": _compile_section(compiled, counters),
        # Host pipeline (fps_tpu.core.prefetch): the 'prefetch' entry in
        # phase_seconds is this worker's time, overlapped with the rest.
        "prefetch": {
            "chunks": int(counters.get("prefetch.chunks", 0)),
            "queue_depth_last": gauges.get(
                "prefetch.queue_depth", {}).get("last"),
            "queue_depth_max": gauges.get(
                "prefetch.queue_depth", {}).get("max"),
            # Adaptive depth (ISSUE 20): each +1 raise the stall-driven
            # sizing applied. 0 with a pinned max at the starting depth
            # means the fixed depth was already enough (or adaptation
            # was off); nonzero narrates how far the buffer grew.
            "depth_adjustments": int(
                counters.get("prefetch.depth_adjustments", 0)),
        },
        # Two-tier storage (labels fold across tables; the per-table
        # split lives in the raw event files if needed).
        "hot_tier": {
            "hot_rows": int(counters.get("hot_tier.hot_rows", 0)),
            "pulled_rows": int(counters.get("hot_tier.pulled_rows", 0)),
            "hit_rate": (
                round(counters["hot_tier.hot_rows"]
                      / counters["hot_tier.pulled_rows"], 4)
                if counters.get("hot_tier.pulled_rows") else None),
            "pending_delta_last": gauges.get(
                "hot_tier.pending_delta", {}).get("last"),
            "pending_delta_max": gauges.get(
                "hot_tier.pending_delta", {}).get("max"),
            # Payload-proportional cold routing (TableSpec.cold_budget):
            # per-chunk program selection + the device-side drop net
            # (nonzero cold_dropped = a certifier bug, not load).
            "compact_chunks": int(
                counters.get("cold_route.compact_chunks", 0)),
            "overflow_chunks": int(
                counters.get("cold_route.overflow_chunks", 0)),
            "cold_dropped": int(
                counters.get("hot_tier.cold_dropped", 0)),
        },
        # Device-resident megastep (fps_tpu.core.megastep): K-chunk
        # fused dispatches with in-graph boundaries, plus the
        # device-side overflow vote's window-level program selection.
        "megastep": {
            "windows": int(counters.get("megastep.windows", 0)),
            "chunks_per_dispatch": gauges.get(
                "megastep.chunks_per_dispatch", {}).get("last"),
            # Auto-K calibration (ISSUE 20): the K chosen by
            # chunks_per_dispatch="auto" (null when K was explicit).
            "auto_k": gauges.get("megastep.auto_k", {}).get("last"),
            "vote_compact_windows": int(
                counters.get("cold_route.vote_compact_windows", 0)),
            "vote_overflow_windows": int(
                counters.get("cold_route.vote_overflow_windows", 0)),
        },
        # Adaptive tiering (fps_tpu.tiering): online hot-set re-ranking
        # + auto-planner activity — re-rank/promotion totals (labels
        # fold across tables) and the churn gauge's last/max.
        "tiering": {
            "re_ranks": int(counters.get("tiering.re_ranks", 0)),
            "promoted_rows": int(
                counters.get("tiering.promoted_rows", 0)),
            "demoted_rows": int(
                counters.get("tiering.demoted_rows", 0)),
            "churn_last": gauges.get("tiering.churn", {}).get("last"),
            "churn_max": gauges.get("tiering.churn", {}).get("max"),
        },
        # Program contract auditor (fps_tpu.analysis): certification
        # totals; the per-violation events ride incidents verbatim.
        "analysis": {
            "certified_programs": int(
                counters.get("analysis.certified_programs", 0)),
            "contract_violations": int(
                counters.get("analysis.contract_violations", 0)),
            # Runtime budget drift (fps_tpu.obs.drift): the gauge's
            # last/max measured-vs-pinned byte ratio and how many
            # departure incidents fired (events ride incidents verbatim).
            "budget_drift_ratio_last": gauges.get(
                "analysis.budget_drift", {}).get("last"),
            "budget_drift_ratio_max": gauges.get(
                "analysis.budget_drift", {}).get("max"),
            "budget_drift_incidents": len(
                incidents.get("budget_drift", ())),
        },
        # Read-path serving tier (fps_tpu.serve; docs/serving.md): query
        # volume, exact request-latency quantiles over every recorded
        # sample, the freshness gauges (served step, step lag, the
        # write->servable SLO), and the swap trail — backward swaps mean
        # the trainer quarantined a served snapshot and readers rolled
        # back with it.
        "serve": {
            "requests": int(counters.get("serve.requests", 0)),
            "rows": int(counters.get("serve.rows", 0)),
            "latency_p50_s": _quantile(sorted(serve_latency), 0.5),
            "latency_p99_s": _quantile(sorted(serve_latency), 0.99),
            "snapshot_step_last": gauges.get(
                "serve.snapshot_step", {}).get("last"),
            "snapshot_lag_steps_last": gauges.get(
                "serve.snapshot_lag_steps", {}).get("last"),
            "write_to_servable_s_last": gauges.get(
                "serve.write_to_servable_s", {}).get("last"),
            "write_to_servable_s_max": gauges.get(
                "serve.write_to_servable_s", {}).get("max"),
            "swaps": dict(sorted(swap_directions.items())),
            "rejected_snapshots": int(
                counters.get("serve.rejected_snapshots", 0)),
            # Delta-snapshot chains + the step-fenced serving fleet
            # (ISSUE 14): publish-bytes proportionality on the write
            # side, the shared fence's last published step on the read
            # side (forward-monotone within a fencing epoch).
            "delta": {
                "delta_publishes": int(
                    counters.get("checkpoint.delta_publishes", 0)),
                "delta_bytes": int(
                    counters.get("checkpoint.delta_bytes", 0)),
                "compactions": int(
                    counters.get("checkpoint.compactions", 0)),
                "full_bytes_last": gauges.get(
                    "checkpoint.bytes", {}).get("last"),
            },
            "fence_step_last": gauges.get(
                "serve.fence_step", {}).get("last"),
            "fence_step_max": gauges.get(
                "serve.fence_step", {}).get("max"),
        },
        # Pod coordination (fps_tpu.supervise.pod): the control-plane
        # narrative folded from journal-pod.jsonl — lease churn, the
        # pod-wide decisions, membership changes, and the child-side
        # fence refusals / elastic re-splits from the run journals.
        "pod": {
            "lease_seizures": len(incidents.get("lease_seized", ())),
            "member_failures": len(incidents.get("member_failed", ())),
            "restarts": len(incidents.get("pod_restart", ())),
            "evictions": len(incidents.get("member_evicted", ())),
            "readmissions": len(incidents.get("member_readmitted", ())),
            "quarantines": len(incidents.get("pod_quarantine", ())),
            # The counter and the event fire together from _check_fence;
            # max() so a dir holding both sources doesn't double-count.
            "fenced_publishes": max(
                int(counters.get("checkpoint.fenced_publishes", 0)),
                len(incidents.get("checkpoint_fenced", ()))),
            "resplit_restores": int(
                counters.get("checkpoint.resplits", 0)),
            "heartbeat_rejected": len(
                incidents.get("heartbeat_rejected", ())),
            "completed": bool(incidents.get("pod_shutdown")),
            "gave_up": bool(incidents.get("pod_give_up")),
        },
        # Supervisor deadline aborts whose last heartbeat was a stalled
        # 'prefetch'-phase beat: the SOURCE wedged, not the driver.
        "source_stalls": sum(
            1 for e in incidents.get("deadline_abort", ())
            if e.get("stall_kind") == "source_stall"),
        # Supervised-restart MTTR evidence (attempt_first_signal events
        # ride incidents verbatim; this is their paired summary).
        "recovery": {
            "count": len(recovery_times),
            "times_s": recovery_times,
            "mean_s": (round(sum(recovery_times) / len(recovery_times), 3)
                       if recovery_times else None),
            "max_s": (round(max(recovery_times), 3)
                      if recovery_times else None),
            # Only meaningful when --recovery-slo-s was given: the bound
            # and how many paired restarts broke it (each breach also
            # rides incidents verbatim).
            "slo_s": (round(float(recovery_slo_s), 3)
                      if recovery_slo_s else None),
            "breaches": len(incidents.get("recovery_slo_breach", ())),
        },
        "health": dict(sorted(health.items())),
        "poisoned_chunks": int(counters.get("health.poisoned_chunks", 0)),
        "incidents": {k: v for k, v in incidents.items() if v},
        # Hostile-filesystem survival (fps_tpu.core.retry + degraded-
        # mode storage): retry traffic, skipped publishes + backlog
        # (recency spent to keep training alive through a brownout),
        # and read-plane polls that degraded to last-good state.
        "storage": {
            "retries": int(counters.get("storage.retries", 0)),
            "degraded_publishes": int(
                counters.get("storage.degraded_publishes", 0)),
            "publish_backlog_last": gauges.get(
                "checkpoint.publish_backlog", {}).get("last"),
            "publish_backlog_max": gauges.get(
                "checkpoint.publish_backlog", {}).get("max"),
            "poll_errors": int(counters.get("storage.poll_errors", 0)),
            "sidecar_skips": int(
                counters.get("storage.sidecar_skips", 0)),
            "compaction_aborts": int(
                counters.get("storage.compaction_aborts", 0)),
        },
        # Hostile-network survival (fps_tpu.serve.wire / serve.net;
        # docs/resilience.md "Hostile network"): retry/reconnect
        # traffic, frames the length/CRC gates rejected, requests shed
        # by admission control or abandoned on a dead deadline, and
        # per-reader liveness — a wedged reader is a reader_wedged
        # incident here, never a silent zero.
        "net": {
            "retries": int(counters.get("net.retries", 0)),
            "reconnects": int(counters.get("net.reconnects", 0)),
            "torn_frames": int(counters.get("net.torn_frames", 0)),
            "shed_requests": int(
                counters.get("net.shed_requests", 0)),
            "deadline_exceeded": int(
                counters.get("net.deadline_exceeded", 0)),
            "reader_heartbeat_age_s_last": gauges.get(
                "serve.reader_heartbeat_age_s", {}).get("last"),
            "reader_heartbeat_age_s_max": gauges.get(
                "serve.reader_heartbeat_age_s", {}).get("max"),
            "reader_wedged_incidents": len(
                incidents.get("reader_wedged", ())),
        },
        # Raw-speed split (ISSUE 20): dump_seconds is what a save costs
        # the TRAINING thread (deferred captures make this the enqueue
        # cost only); capture_seconds is the device->host materialization
        # the WRITER pays off-thread. dump collapsing toward zero while
        # capture stays flat is the off-thread capture working.
        "checkpoint": {
            "dump": _seconds_stats(
                ckpt_seconds["checkpoint.dump_seconds"]),
            "capture": _seconds_stats(
                ckpt_seconds["checkpoint.capture_seconds"]),
        },
        "checkpoint_saves": int(counters.get("checkpoint.saves", 0)),
        # Async writer: enqueued > saved means a write was still in
        # flight at the last flush — saves are the TRUE durability points.
        "checkpoint_enqueues": int(counters.get("checkpoint.enqueues", 0)),
        "checkpoint_fallbacks": int(
            counters.get("checkpoint.fallbacks", 0)),
        "watchdog_stalls": int(counters.get("watchdog.stalls", 0)),
        "rollbacks": int(counters.get("rollback.quarantined", 0)),
        "preset_skips": int(counters.get("rollback.preset_skipped", 0)),
        "quarantined": sorted(q for q in quarantined if q is not None),
        # Complete only when EVERY started run ended — a dir holding a
        # finished first run and a killed second run is not complete.
        "run_complete": bool(started) and started <= ended,
        # Append-mode sinks stack re-runs into the same files; counts and
        # phases above are then aggregates over all of them. Surfaced so
        # consumers don't mistake a 2-run dir for one double-sized run.
        "aggregated_runs": max(len(run_ids), 1),
        "wall_span_s": (round(t_max - t_min, 3)
                        if t_min is not None else None),
    }
    missing = [k for k in REQUIRED_FIELDS if k not in digest]
    assert not missing, f"digest contract violated: missing {missing}"
    return digest


# Strict JSON out: a NaN gauge (serving outage marker) prints as
# null, never the Python-only NaN token — the digest's consumers
# include jq and non-Python tooling. Mirrors
# fps_tpu.obs.sinks.scrub_nonfinite (this tool stays import-free).
def scrub(x):
    if isinstance(x, dict):
        return {k: scrub(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [scrub(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def digest_json(obs_dir: str, *, recovery_slo_s: float | None = None) -> dict:
    """The `--json` payload: the digest with every non-finite float
    scrubbed to null — the stable machine-readable schema
    (``DIGEST_SCHEMA_VERSION``) CI and ``fps_tpu/obs/fleet.py`` consume
    without scraping text."""
    return scrub(render_digest(obs_dir, recovery_slo_s=recovery_slo_s))


def _load_fleet():
    """fps_tpu/obs/fleet.py by FILE PATH (the tools/supervise.py
    pattern): importing the package would drag fps_tpu/__init__ — and
    with it jax — into a tool whose contract is running on login nodes."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "fps_tpu", "obs", "fleet.py")
    spec = importlib.util.spec_from_file_location("_fps_obs_fleet", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve via sys.modules
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render fps_tpu --obs-dir telemetry into a run "
                    "digest (one dir) or a fleet rollup + SLO burn "
                    "report (--fleet, N dirs)")
    ap.add_argument("obs_dirs", nargs="+", metavar="OBS_DIR",
                    help="directory written by --obs-dir / "
                         "fps_tpu.obs.open_run (with --fleet: one per "
                         "host/member)")
    ap.add_argument("--fleet", action="store_true",
                    help="aggregate the dirs as one fleet: windowed "
                         "rollups (throughput, tiering hit rate, "
                         "cold-route certification rate, freshness, "
                         "restart/fence counts) + SLO burn rates "
                         "(fps_tpu.obs.fleet), with each host's "
                         "standard digest attached")
    ap.add_argument("--window-s", type=float, default=None,
                    help="fleet rollup window width in seconds "
                         "(default: span/6)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output: compact strict JSON "
                         "with non-finite floats scrubbed to null and a "
                         "versioned 'schema' field — the contract for "
                         "CI and fleet consumers (the default output is "
                         "the same JSON; --json pins it and refuses "
                         "--pretty)")
    ap.add_argument("--pretty", action="store_true",
                    help="indent the JSON for humans")
    ap.add_argument("--recovery-slo-s", type=float, default=None,
                    metavar="S",
                    help="time-to-recovered bound: every paired restart "
                         "whose kill->first-signal gap exceeds S seconds "
                         "becomes a recovery_slo_breach incident and the "
                         "recovery section reports slo_s/breaches "
                         "(default: report without judging)")
    args = ap.parse_args(argv)
    if args.json and args.pretty:
        ap.error("--json is the compact machine form; drop --pretty")
    if not args.fleet and len(args.obs_dirs) > 1:
        ap.error("multiple OBS_DIRs need --fleet")

    if args.fleet:
        fleet = _load_fleet()
        def _digest_or_none(d):
            try:
                return render_digest(
                    d, recovery_slo_s=args.recovery_slo_s)
            except FileNotFoundError:
                return None

        out = fleet.fleet_digest(args.obs_dirs, window_s=args.window_s,
                                 digest_fn=_digest_or_none)
        # Multi-tenant pods (fps_tpu.tenancy): a dir holding a
        # tenants/ namespace gets a per-tenant rollup + SLO-burn +
        # recovery section — each tenant's burn rates are its own,
        # never a neighbor's (blast-radius isolation in telemetry).
        tenants = {}
        for d in args.obs_dirs:
            if os.path.isdir(os.path.join(d, fleet.TENANTS_DIRNAME)):
                td = fleet.tenant_fleet_digest(d, window_s=args.window_s)
                tenants.update(td["tenants"])
        if tenants:
            out["tenants"] = tenants
        if not out["rollup"]["windows"] and not tenants:
            print(f"no telemetry under {args.obs_dirs}", file=sys.stderr)
            return 2
    else:
        try:
            out = render_digest(args.obs_dirs[0],
                                recovery_slo_s=args.recovery_slo_s)
        except FileNotFoundError as e:
            print(str(e), file=sys.stderr)
            return 2

    print(json.dumps(scrub(out), indent=2 if args.pretty else None,
                     allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
