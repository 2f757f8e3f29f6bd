"""Phase timers, device spans, and device tracing.

The reference has no tracing subsystem — only Flink's built-in operator
metrics (SURVEY.md §5 tracing row). On TPU we get device-level tracing
from ``jax.profiler`` for free; this module packages it plus the two
host-side clocks the chunked driver makes natural:

* :func:`host_span` — THE host span primitive: every host phase of a
  call opens one. It is a ``jax.profiler.TraceAnnotation`` named
  ``fps.host.<name>`` (so a profiler trace shows what the host was doing
  on the device's own clock), a ``driver.phase_seconds{phase=<name>}``
  sample, and — with a recorder — a canonical ``span`` event carrying
  its parent span and the index of the enclosing driver call, from
  which a reader takes self time.
* :class:`PhaseTimer` — splits each chunk's host wall-clock into named
  segments (``ingest`` / ``place`` / ``dispatch`` / ``host_sync`` /
  ``checkpoint`` / ``callback``), so a BENCH regression is attributable
  to a phase instead of a single opaque number; its phases ARE host
  spans. The compiled program fuses ingest/pull/compute/push into one
  dispatch, so those sub-phases are visible on the DEVICE timeline
  instead: the driver wraps them in ``jax.named_scope`` (:data:`STEP_SCOPES`),
  which costs nothing outside a profiler trace
  (``docs/observability.md`` has the table of names).
* :func:`watch_compiles` — folds JAX's own compile timings and
  persistent-cache hits and misses into the process-default recorder.
* :func:`watch_device` — the DEVICE's time, from inside: every unit of
  work a driver entry point queues (an epoch, a chunk, a megastep, an
  ALS sweep) is handed to one watcher thread that stamps its completion,
  so a ``device.<entry>`` span says how long the device ran the unit,
  how long the unit waited for it and how long the device starved before
  it — no profiler, and no caller made to wait.
* :func:`device_bytes` — the DEVICE's memory, from inside: the one place
  the package reads ``memory_stats()``. Under a recorder the spans above
  carry it (a call's ``hbm_open`` / ``hbm_close``, a set-up span's
  ``hbm_delta``, a device span's ``hbm_done`` / ``hbm_peak``) and
  :func:`watch_program` adds what each compiled program needs beyond its
  operands (``program.memory``); without one not a byte is read.
* :func:`trace` — context manager writing a Perfetto/XProf-compatible
  trace of everything (XLA ops, collectives, host callbacks).

(Grew out of ``fps_tpu/utils/profiling.py``, which remains as a compat
shim.)
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import logging
import threading
import time
from typing import NamedTuple

from fps_tpu.obs import events
from fps_tpu.obs.trace import new_span_id

# Phase names the driver emits, in pipeline order. PhaseTimer accepts any
# name (custom loops may add their own); these are the declared ones.
# The serial ones tile a chunk's host time; NESTED_PHASES lie inside
# another phase and COMPILE_PHASES are JAX's own timings of work done
# under "dispatch"/"enqueue", so a sum over phases must leave both out.
DRIVER_PHASES = (
    "prefetch",    # background pipeline: chunk assembly + placement on
                   # the worker thread (fps_tpu.core.prefetch) — OVERLAPS
                   # the phases below, it is not part of their serial sum
    "ingest",      # pulling the next chunk from the host iterator (with
                   # the pipeline on: waiting on the prefetch buffer)
    "place",       # host->device transfer (host_to_sharded)
    "dispatch",    # all the host does to queue one call: key derivation
                   # and placement, then "enqueue" (+ first-call compile)
    "host_sync",   # blocked fetching metrics back to host
    "checkpoint",  # snapshot save on the training thread
    "callback",    # user on_chunk / on_epoch hooks
    "reconcile",   # two-tier re-split at run entry (hot replica derive)
    "retier",      # adaptive-tiering boundary (Retierer.on_boundary)
    "megastep",    # K-chunk device-resident dispatch (enqueue + first-
                   # call compile): the megastep driver's analog of
                   # "dispatch", kept distinct so the A/B's host-serial
                   # attribution can tell the two loop shapes apart
    "epoch_args",      # DeviceEpochPlan.epoch_args: host RNG, operand
                       # upload, the per-epoch ingest.tbuf / ingest.perm
                       # programs (under "ingest" in the megastep loop);
                       # Word2VecDevicePlan.epoch_args likewise ("compact")
    "program_lookup",  # compiled-program cache lookup; built=True when
                       # the lookup had to build (trace set-up, no compile)
)
NESTED_PHASES = (
    "enqueue",     # the jitted call alone, inside dispatch / megastep
    "attach_hot",  # Trainer._attach_hot, holding "reconcile"
    "compact",     # Word2VecDevicePlan's per-epoch subsample-and-compact
                   # program (ingest.compact) queued, inside "epoch_args"
)
COMPILE_PHASES = ("compile.trace", "compile.lower", "compile.backend")
# Device scopes (``jax.named_scope``: op metadata, free outside a profiler
# trace). STEP_SCOPES lie in step bodies, in step order: ``fps.prepare``
# is the worker's own sampling before the pull; ``fps.combine`` the
# table-sized work of a non-"sum" push (``core/store.COMBINE_SCOPE``),
# inside ``fps.push`` beside the routed scatter's ``fps.ops`` (under which
# ``fps_tpu.ops`` names the route), and so does ``fps.hot_accumulate``,
# the two-tier storage's fold of a step's hot pushes into the pending
# buffer (``Trainer._apply_hot_split``: push work, so what divides by the
# push's time keeps all of it); the rest belong to the tiered and
# megastep paths. Programs that run once a call or once a chunk are named
# WITHOUT the prefix (ONCE_SCOPES), and so is what a compiled loop runs
# once a ROUND of steps (ROUND_SCOPES: ``ssp.snapshot``, the SSP round's
# snapshot gather, ``Trainer._ssp_round``; ``hot.reconcile``, the hot
# tier's window-end reduce-scatter, apply and all-gather, once in
# ``hot_sync_every`` steps, ``Trainer._reconcile_carry``, in sync mode
# and at an SSP round's foot, there as ``ssp.snapshot/hot.reconcile``):
# a reader counts steps by the ops under ``fps.*``, and an op that runs
# once in ``sync_every`` steps would sit in that count at a fraction of a
# step. (The routed gather inside the reconcile keeps its
# ``fps.ops/<route>`` name, one op a table a window.)
# ``fps.tap`` is ``TrainerConfig.step_tap`` on the step's pre-update view
# (``Trainer._tap_step``, before the pull); what a tap names INSIDE it has
# no prefix either (INNER_SCOPES: the top-K ranking's parts, which the
# serving program ``recommendation.build_topk_fn`` shares; so have the
# parts a worker names inside ``fps.compute``). ``fps.dense`` is the
# trainer's dense route (``Trainer._fold_dense``: the all-reduce and the
# fold of a logic's dense parameters, right after ``fps.compute``). A
# test walks the tree against the four lists.
STEP_SCOPES = ("fps.ingest", "fps.tap", "fps.prepare", "fps.sketch",
               "fps.pull", "fps.compute", "fps.dense", "fps.push",
               "fps.combine", "fps.ops",
               "fps.hot_accumulate", "fps.sketch_merge",
               "fps.megastep_vote", "fps.megastep_tick", "fps.metrics")
ONCE_SCOPES = ("ingest.pack", "ingest.tbuf", "ingest.perm", "ingest.chunk",
               "ingest.compact",
               # models/ials.py, once a sweep: the Gramian of the fixed
               # table, the accumulators' zero fill, the batched solve
               "als.gram", "als.zeros", "als.solve")
ROUND_SCOPES = ("ssp.snapshot", "hot.reconcile")
INNER_SCOPES = ("topk.score", "topk.select", "topk.merge",
                # models/dlrm.py, inside fps.compute: the step's three
                # parts, each part's backward ops under the part's name
                "dlrm.bottom", "dlrm.interact", "dlrm.top",
                # models/kge.py, inside fps.compute: the scoring of a
                # step's triples and its backward
                "kge.score")
# Set-up spans (no timer: they report through the process-default
# recorder). Those that queue device work close on its completion when a
# recorder is installed, and only then (settle()).
SETUP_PHASES = ("dataset.place", "dataset.queues", "dataset.pack",
                "plan.build", "init_state")
# The driver entry points: each opens a root span and numbers the call.
# ``als.half_epoch`` is models/ials.py's (one ALS sweep; no Trainer).
CALL_SPANS = ("run_indexed", "fit_stream", "run_megastep", "als.half_epoch")
# Inside ``als.half_epoch``, in order: the Gramian queued, one
# ``als.accumulate`` a chunk queued, the solve queued: the host's cost of
# queueing, like ``enqueue`` (a sweep reads nothing back).
SWEEP_PHASES = ("als.gram", "als.accumulate", "als.solve")
# One span a compiled program, right after the call that built it
# (:func:`watch_program`): what the program needs in device memory.
PROGRAM_SPANS = ("program.memory",)
# Device spans (:func:`watch_device`), one per unit of work an entry point
# queues: an epoch of ``run_indexed``, a chunk of ``fit_stream`` /
# ``run_chunk``, a megastep, an ALS sweep. In a profiler trace the
# watcher's wait for a unit is ``fps.<name>`` (``fps.device.run_indexed``):
# NOT under ``fps.host.``, where a reader names the device's idle gaps by
# the shortest host span covering them; one of these is always open.
DEVICE_SPANS = ("device.run_indexed", "device.fit_stream",
                "device.run_megastep", "device.als.half_epoch")

HOST_SPAN_PREFIX = "fps.host."
DEVICE_SPAN_PREFIX = "fps."

_calls = itertools.count()
_open = threading.local()  # .stack: [(span_id, call index)] of this thread

# The spans' one clock, host and device spans alike: epoch seconds that
# advance with ``perf_counter`` (monotonic: a step of the wall clock moves
# no span against another).
_EPOCH = time.time() - time.perf_counter()


def _now() -> float:
    return _EPOCH + time.perf_counter()


class DeviceBytes(NamedTuple):
    """What :func:`device_bytes` reads, in bytes."""

    in_use: int        # allocated now, on the fullest device
    peak: int          # the most ever allocated at once, likewise
    limit: int | None  # what the allocator may hand out (the smallest)


def device_bytes(where=None) -> DeviceBytes | None:
    """The device's memory as its allocator counts it: the fullest local
    device's ``bytes_in_use`` and ``peak_bytes_in_use`` (each the largest
    over ``where``'s local devices: a mesh, an iterable of devices, or
    ``None`` for every local device) and the smallest ``bytes_limit``.
    ``None`` where the backend reports nothing (the CPU's
    ``memory_stats()`` is ``None``).

    THE place ``memory_stats()`` is read: the spans below call it under a
    recorder and only then, ``DeviceEpochPlan`` reads the limit through it
    at plan build. A buffer counts from the moment the program that
    writes it is QUEUED, so a reading taken right after a dispatch holds
    what the dispatch allocated. A compiled program's temporaries are in
    NEITHER number on the TPU runtime read (v5e, PR 53: 9.9 GB of them ran
    under a peak of 5.03): ``program.memory`` states them."""
    if where is None:
        import jax

        where = jax.local_devices()
    stats = [s for s in (d.memory_stats() for d in
                         getattr(where, "local_devices", where)) if s]
    if not stats:
        return None
    limits = [s["bytes_limit"] for s in stats if "bytes_limit" in s]
    return DeviceBytes(
        max(int(s.get("bytes_in_use", 0)) for s in stats),
        max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
        int(min(limits)) if limits else None)


def settle(tree):
    """Wait for ``tree``'s device work — WHEN a process-default recorder is
    installed, and not otherwise. The set-up spans that queue device work
    (uploads, the packed pre-gather, ``init_state``) end with this:
    set-up is serial, so waiting changes no overlap, and without it the
    span would measure an enqueue. Returns ``tree``."""
    if events.get_default_recorder() is not None:
        import jax

        jax.block_until_ready(tree)
    return tree


@contextlib.contextmanager
def host_span(name: str, timer: "PhaseTimer | None" = None, *,
              call: bool = False, memory: bool = False, **attrs):
    """One host phase, on every clock the repo has.

    Always a ``TraceAnnotation("fps.host.<name>")`` (a flag test when no
    profiler runs). With ``timer`` the segment folds into it (and through
    it into the timer's recorder); without, it is a
    ``driver.phase_seconds{phase=name}`` sample on the process-default
    recorder (a no-op when none is installed). Whichever recorder that is
    also gets the canonical span record of :mod:`fps_tpu.obs.trace`
    (``event: "span"``, ``span``, ``span_id``, ``parent_id``, ``t0``,
    ``t1``) with the parent taken from this thread's stack of open spans
    and ``call``, the index of the enclosing driver call — so
    ``tools/trace_export.py`` renders it and a reader can take self time
    (a span's length less its children's). ``call=True`` opens such a
    driver call: ``@host_span("run_indexed", call=True)`` on the entry
    point (a context manager made by ``contextlib`` is a decorator too,
    opening a fresh span per call). Yields a dict: keys set on it before
    the span closes ride the record (``built=True``).

    Under a recorder, and where the backend counts its memory
    (:func:`device_bytes`), a ``call=True`` span also carries ``hbm_open``
    and ``hbm_close``, the bytes in use when the entry point is entered
    and when it returns with its last program queued (their difference is
    what queueing this call COST: outputs nothing donates, the plan's
    per-call buffers, whatever the runtime allocates at dispatch),
    ``hbm_peak``, the allocator's running peak at that return (the FIRST
    call's: what set-up's transients reached before any program of a call
    had run) and ``hbm_limit``; a ``memory=True`` span carries
    ``hbm_delta``, the bytes in use at its close less at its open: what it
    left resident, its children's included. Both readings lie OUTSIDE
    ``t0`` .. ``t1``: the span is no longer for them.

    No recorder, no profiler: two flag tests. Spans are per call or per
    chunk, never per step.
    """
    import jax

    rec = timer.recorder if timer is not None else None
    guarded = rec is None
    if guarded:
        rec = events.get_default_recorder()
    if rec is not None:
        watch_compiles()
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    parent, index = stack[-1] if stack else (None, None)
    if call:
        index = next(_calls)
    sid = new_span_id() if rec is not None else None
    timed = rec is not None or timer is not None
    stack.append((sid, index))
    opened = device_bytes() if rec is not None and (call or memory) else None
    t0 = _now() if timed else 0.0
    try:
        with jax.profiler.TraceAnnotation(HOST_SPAN_PREFIX + name):
            yield attrs
    finally:
        stack.pop()
        if timed:
            t1 = _now()
            if timer is not None:
                timer.add(name, t1 - t0)
            else:
                events.record_metric("observe", "driver.phase_seconds",
                                     t1 - t0, phase=name)
            if rec is not None:
                if index is not None:
                    attrs.setdefault("call", index)
                if opened is not None:
                    closed = device_bytes()
                    if call:
                        attrs.update(hbm_open=opened.in_use,
                                     hbm_close=closed.in_use,
                                     hbm_peak=closed.peak,
                                     hbm_limit=closed.limit)
                    if memory:
                        attrs["hbm_delta"] = closed.in_use - opened.in_use
                _emit_span(rec, guarded, name, sid, parent, t0, t1, attrs)


def _emit_span(rec, guarded, name, sid, parent, t0, t1, attrs):
    tracer = getattr(rec, "trace", None)
    fields = dict(span=name, span_id=sid, t0=float(t0), t1=float(t1),
                  trace_id=getattr(tracer, "trace_id", None),
                  parent_id=parent or getattr(tracer, "parent_id", None),
                  **attrs)
    if guarded:
        events.emit("span", **fields)
    else:
        rec.event("span", **fields)


# -- the device's time, from inside ---------------------------------------

_log = logging.getLogger("fps_tpu.obs")

# The longest a recorder's closing (or clearing) waits for the device spans
# of the units still queued under it; past it they are dropped. A device
# that hangs must not hang ``Recorder.close``.
DRAIN_SECONDS = 5.0


class _DeviceUnit:
    """One unit of queued device work in the watcher's FIFO."""

    __slots__ = ("name", "leaves", "rec", "parent", "attrs", "t_enqueued",
                 "in_flight", "on_done")

    def __init__(self, name, leaves, rec, parent, attrs, on_done=None):
        self.name, self.leaves, self.rec = name, leaves, rec
        self.parent, self.attrs, self.on_done = parent, attrs, on_done
        self.t_enqueued = _now()
        self.in_flight = 0


class _DeviceWatcher:
    """The completion watcher: a FIFO of units and one daemon thread that
    waits for each in turn and stamps it. The thread lives while the FIFO
    holds a unit: the first unit put on an empty FIFO starts it, and it
    ends with the last one stamped, so none is left behind by a recorder
    nobody closes.

    thread-safety: ``_cond`` guards ``_fifo`` and ``_thread``; ``_last_t1``
    is the running thread's own (one that ends leaves it to the next under
    ``_cond``). A unit stays at the head of ``_fifo`` while the thread
    waits for it (so ``in_flight`` counts it) and is stamped and emitted by
    the thread alone, straight on the recorder it was queued under; callers
    only append, and :meth:`drain` may take a unit's recorder away. The
    thread never touches a donated buffer: a unit holds outputs nothing
    donates, and one whose wait raises all the same (a failed call, a
    deleted buffer) is dropped.
    """

    def __init__(self):
        self._cond = threading.Condition()
        self._fifo: collections.deque = collections.deque()
        self._thread = None
        self._last_t1 = None

    def put(self, unit: _DeviceUnit) -> None:
        with self._cond:
            unit.in_flight = len(self._fifo)
            self._fifo.append(unit)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="fps-device-watcher", daemon=True)
                self._thread.start()

    def drain(self, rec, timeout: float) -> None:
        """Wait, ``timeout`` seconds at most, until every unit queued under
        ``rec`` is stamped and emitted; what is not by then is dropped (the
        thread still waits for it, for its successor's ``t0``, and records
        nothing). The one place a caller waits for the watcher."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while any(u.rec is rec for u in self._fifo):
                left = deadline - time.monotonic()
                if left <= 0:
                    late = [u for u in self._fifo if u.rec is rec]
                    for u in late:
                        u.rec = None
                    _log.warning(
                        "%d device span(s) dropped: the device had not "
                        "finished %s %.1f s after its recorder closed",
                        len(late), late[0].name, timeout)
                    return
                self._cond.wait(left)

    def _run(self) -> None:
        import jax

        try:
            while True:
                with self._cond:
                    unit = self._fifo[0]
                try:
                    with jax.profiler.TraceAnnotation(
                            DEVICE_SPAN_PREFIX + unit.name):
                        jax.block_until_ready(unit.leaves)
                    t1 = _now()
                except Exception:  # noqa: BLE001 - a failed call, a
                    # deleted buffer: no span, and the watcher carries on
                    t1 = None
                    _log.debug("device unit %s dropped", unit.name,
                               exc_info=True)
                unit.leaves = None
                if t1 is not None:
                    prev, self._last_t1 = self._last_t1, t1
                    try:
                        # After the stamp: what the caller left to be
                        # counted once the unit's outputs exist (they
                        # do: a copy to the host, no wait) rides the span.
                        if unit.on_done is not None and unit.rec is not None:
                            unit.attrs.update(unit.on_done(unit.rec) or {})
                        # ... and the device's memory as the unit left it.
                        held = (device_bytes() if unit.rec is not None
                                else None)
                        if held is not None:
                            unit.attrs.update(hbm_done=held.in_use,
                                              hbm_peak=held.peak)
                        self._emit(unit, prev, t1)
                    except Exception:  # noqa: BLE001 - telemetry must
                        # not end the watcher
                        _log.warning("device span %s not recorded",
                                     unit.name, exc_info=True)
                with self._cond:
                    self._fifo.popleft()
                    self._cond.notify_all()
                    if not self._fifo:
                        self._thread = None
                        return
        finally:
            with self._cond:  # died outside the guards: put() starts anew
                if self._thread is threading.current_thread():
                    self._thread = None
                    self._fifo.clear()
                    self._cond.notify_all()

    @staticmethod
    def _emit(unit: _DeviceUnit, prev, t1) -> None:
        rec = unit.rec
        if rec is None:  # its recorder closed before the device was done
            return
        t0 = unit.t_enqueued if prev is None else max(unit.t_enqueued, prev)
        sid, call = unit.parent
        attrs = dict(
            unit.attrs, t_enqueued=unit.t_enqueued,
            wait_s=t0 - unit.t_enqueued,
            starved_s=0.0 if prev is None else max(0.0,
                                                   unit.t_enqueued - prev),
            in_flight=unit.in_flight)
        if call is not None:
            attrs.setdefault("call", call)
        _emit_span(rec, False, unit.name, new_span_id(), sid, t0, t1, attrs)


_watcher = _DeviceWatcher()


def watch_device(name: str, outputs, timer: "PhaseTimer | None" = None,
                 on_done=None, **attrs) -> None:
    """Hand one unit of queued device work to the completion watcher.

    Called by a driver entry point right after the LAST program of the
    unit is queued, with outputs of the unit that nothing donates (its
    metrics; the solved table of an ALS sweep). The recorder is
    :func:`host_span`'s: ``timer``'s, else the process default; with
    neither this is two ``None`` tests, no thread exists and nothing is
    recorded. Otherwise the watcher's thread waits for the outputs (the
    caller never does: the call returns as unfinished as without a
    recorder) and emits the canonical ``span`` record under ``name`` (one of
    :data:`DEVICE_SPANS`), with the ``call`` index of the enclosing driver
    call and that call's root span as ``parent_id``:

    * ``t_enqueued``: host time of this call (the unit's last program
      queued); ``t1``: the completion stamp;
    * ``t0 = max(t_enqueued, t1 of the unit before)``: the device is one
      in-order stream, so a unit starts when it is queued or when its
      predecessor ends, whichever is later. A unit of several programs
      queued onto an IDLE device began up to its own queueing time
      earlier than ``t0``; with a unit queued ahead the span is completion
      to completion, so the runtime's launch latency is inside it;
    * ``wait_s = t0 - t_enqueued``: how long the work waited for the
      device; ``starved_s = max(0, t_enqueued - previous t1)``: how long
      the device had nothing of this program's queued. It is zero whenever
      work was queued ahead, whatever the runtime then did with it: a
      launch or transfer latency is NOT in it;
    * ``in_flight``: units queued and not complete when this one was
      queued; ``attrs`` (``steps``, ``epoch``, ``chunk``, ``solve``):
      what the call site knows without reading the device (``steps``
      defaults to the leading dimension of the first output, the shape of
      per-step metrics);
    * ``hbm_done`` / ``hbm_peak`` (where the backend counts its memory,
      :func:`device_bytes`): the bytes in use and the allocator's running
      peak, read by the watcher right after the stamp: the unit is done,
      what was queued behind it holds its buffers already;
    * ``on_done(recorder) -> dict | None``: run by the watcher right after
      the stamp, on its own thread, for accounting the caller deferred
      because the outputs it reads did not exist yet (the hot tier's hit
      counts of a ``run_indexed(as_numpy=False)`` epoch): it may count on
      the recorder, and what it returns joins the span's fields. The
      caller is never made to wait for it.

    The span is the whole record: ``tools/obs_report.py``'s ``device``
    section and the benchmark's per-layer metrics read it, and nothing
    else is kept beside it.
    """
    rec = timer.recorder if timer is not None else None
    if rec is None:
        rec = events.get_default_recorder()
        if rec is None:
            return
    import jax

    leaves = [x for x in jax.tree.leaves(outputs)
              if hasattr(x, "block_until_ready")]
    if not leaves:
        return  # nothing of the unit is on the device to wait for
    if "steps" not in attrs and getattr(leaves[0], "ndim", 0):
        attrs["steps"] = int(leaves[0].shape[0])  # per-step metrics
    stack = getattr(_open, "stack", None)
    _watcher.put(_DeviceUnit(name, leaves, rec,
                             stack[-1] if stack else (None, None), attrs,
                             on_done))


def drain_device_spans(recorder) -> None:
    """Wait, :data:`DRAIN_SECONDS` at most, for the device spans of the
    units still queued under ``recorder``, and drop those the device has
    not finished by then. ``Recorder.close`` (its sinks flushed first) and
    a change of the process-default recorder call it, so a journal holds
    its last sweep and a device that hangs hangs neither."""
    _watcher.drain(recorder, DRAIN_SECONDS)


# -- compiles, from inside ----------------------------------------------

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
_watching = False
_watch_lock = threading.Lock()


_probing = threading.local()  # .on: this thread is inside program.memory


def _on_compile_duration(event, duration, **kw):
    phase = _COMPILE_EVENTS.get(event)
    if phase is None or getattr(_probing, "on", False):
        return  # not JAX's compile timings, or a probe's look-up of them
    events.record_metric("observe", "driver.phase_seconds", duration,
                         phase=phase)
    if phase == "compile.backend":
        events.emit("program_compiled", seconds=float(duration),
                    **({"fun_name": str(kw["fun_name"])}
                       if "fun_name" in kw else {}))


def _on_cache_event(event, **kw):
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        events.record_metric("inc", name, 1.0)


def watch_compiles() -> None:
    """Register (once a process) ``jax.monitoring`` listeners that fold
    JAX's compile timings into ``driver.phase_seconds{phase="compile.
    trace" | "compile.lower" | "compile.backend"}``, the persistent
    cache's hits and misses into ``compile.cache_hits`` /
    ``compile.cache_misses``, and a ``program_compiled`` event naming the
    function where JAX passes its name — all on the process-default
    recorder, so the listeners are inert when none is installed.
    :func:`host_span` calls this the first time it sees a recorder."""
    global _watching
    if _watching:
        return
    with _watch_lock:
        if _watching:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_cache_event)
        _watching = True


# -- a program's memory, from inside --------------------------------------

_MEMORY_FIELDS = {
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
    "temp_bytes": "temp_size_in_bytes",
    "code_bytes": "generated_code_size_in_bytes",
}


def _abstract(x):
    """A device array as the shape, dtype and sharding a lowering keys on
    (the call that follows may donate the array itself)."""
    import jax

    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding,
            weak_type=getattr(x, "weak_type", False))  # a key array has none
    return x


def _program_memory(label: str, fn, operands) -> None:
    """The ``program.memory`` span of ``fn`` as ``operands`` compiled it."""
    with host_span("program.memory", label=label) as span:
        _probing.on = True
        try:
            # The call before this built the executable, and JAX keeps it
            # by the lowering's own key: this looks it up (one cached
            # trace; no lowering, no compile, persistent cache untouched).
            stats = fn.lower(*operands).compile().memory_analysis()
        except Exception:  # noqa: BLE001 - telemetry must not end a run
            _log.warning("program.memory: %s not read", label, exc_info=True)
            stats = None
        finally:
            _probing.on = False
        for field, attr in _MEMORY_FIELDS.items():
            value = getattr(stats, attr, None)
            if value is not None:
                span[field] = int(value)


def watch_program(fn, label: str, first=None):
    """The first-call seam of a compiled program (a jitted callable an
    entry point builds once and keeps): ``first(args)`` runs before the
    first call (the auditor's certification, ``Trainer._wrap_audit``), and
    with a process-default recorder a ``program.memory`` span follows it,
    carrying ``label`` and, per device, from the executable's
    ``memory_analysis()``: ``argument_bytes``, ``output_bytes``,
    ``alias_bytes`` (arguments the outputs reuse: donation), ``temp_bytes``
    (what the program needs BESIDE its operands and outputs while it runs,
    which the allocator's counters leave out: :func:`device_bytes`) and
    ``code_bytes``. The span's length is what
    the reading cost.

    With neither a hook nor a recorder at BUILD time ``fn`` itself comes
    back: an untraced, unaudited run dispatches the bare jitted callable.
    The reading pays no compile and no lowering: it runs after the call,
    on the call's abstract operands (taken before it: the call may donate
    them), and finds the executable that call built under JAX's own key.
    The compile timings JAX raises on the way (a cached trace) are not
    sampled: ``compile.*`` reads what the call itself cost. Later calls go
    straight through; a second shape the same callable compiles is not
    read."""
    if first is None and events.get_default_recorder() is None:
        return fn
    pending = [True]

    def watched(*args):
        if not pending:
            return fn(*args)
        pending.clear()
        if first is not None:
            first(args)
        if events.get_default_recorder() is None:
            return fn(*args)
        import jax

        operands = jax.tree.map(_abstract, args)
        out = fn(*args)
        _program_memory(label, fn, operands)
        return out

    watched.lower = fn.lower
    watched.__wrapped__ = fn
    return watched


class PhaseTimer:
    """Named wall-clock segments, accumulated per chunk and per run.

    Feed it a :class:`~fps_tpu.obs.registry.Recorder` and every closed
    phase lands one ``driver.phase_seconds{phase=...}`` histogram sample;
    the per-chunk dict from :meth:`chunk_summary` rides the journal's
    chunk/epoch events. Dispatch is asynchronous in jax, so ``dispatch``
    measures enqueue (+ compile on the first call) and the device compute
    surfaces in ``host_sync`` wherever the host loop actually blocks —
    honest host-side attribution, not a guess at device internals.
    """

    def __init__(self, recorder=None):
        self.recorder = recorder
        self._chunk: dict[str, float] = {}
        # The prefetch worker thread folds its segments in via add()
        # while the driver thread closes phases and takes summaries.
        self._lock = threading.Lock()

    def phase(self, name: str):
        """One timed phase: a :func:`host_span` feeding this timer."""
        return host_span(name, self)

    def add(self, name: str, seconds: float) -> None:
        """Fold an externally-measured segment into the current chunk —
        how the background prefetch worker reports its assemble+place
        time (``prefetch``) without a context manager spanning threads.
        A segment landing exactly at a chunk boundary may attribute to
        either side of it; overlapped phases are inherently concurrent
        with the driver's, so the ambiguity is real, not an artifact."""
        with self._lock:
            self._chunk[name] = self._chunk.get(name, 0.0) + seconds
        if self.recorder is not None:
            self.recorder.observe("driver.phase_seconds", seconds, phase=name)

    def chunk_summary(self, *, reset: bool = True) -> dict[str, float]:
        """Seconds per phase since the last reset (one chunk's breakdown).
        Whole-run totals live where every consumer already reads them:
        ``Recorder.phase_totals()`` over the ``driver.phase_seconds``
        histogram — the timer keeps no duplicate run-level state."""
        with self._lock:
            out = {k: round(v, 6) for k, v in self._chunk.items()}
            if reset:
                self._chunk = {}
        return out


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device+host profile under ``log_dir`` (view with XProf /
    Perfetto). Usable around any training region::

        with obs.trace("/tmp/trace"):
            trainer.run_chunk(...)
    """
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
