"""Phase timers, throughput accounting, and device tracing.

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
* :class:`Throughput` — per-chunk wall-clock + examples/sec accounting
  for ``Trainer.fit_stream(on_chunk=...)``.
* :func:`trace` — context manager writing a Perfetto/XProf-compatible
  trace of everything (XLA ops, collectives, host callbacks).

(Grew out of ``fps_tpu/utils/profiling.py``, which remains as a compat
shim.)
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time

import numpy as np

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
# ``fps_tpu.ops`` names the route); the rest belong to the tiered and
# megastep paths. Programs that run once a call or once a chunk are named
# WITHOUT the prefix (ONCE_SCOPES), and so is what a compiled loop runs
# once a ROUND of steps (ROUND_SCOPES: ``ssp.snapshot``, the SSP round's
# snapshot gather and its hot reconcile, ``Trainer._ssp_round``): a reader
# counts steps by the ops under ``fps.*``, and an op that runs once in
# ``sync_every`` steps would sit in that count at a fraction of a step. A
# test walks the tree against the three lists.
STEP_SCOPES = ("fps.ingest", "fps.prepare", "fps.sketch", "fps.pull",
               "fps.compute", "fps.push", "fps.combine", "fps.ops",
               "fps.hot_accumulate", "fps.reconcile", "fps.sketch_merge",
               "fps.megastep_vote", "fps.megastep_tick", "fps.metrics")
ONCE_SCOPES = ("ingest.pack", "ingest.tbuf", "ingest.perm", "ingest.chunk",
               "ingest.compact",
               # models/ials.py, once a sweep: the Gramian of the fixed
               # table, the accumulators' zero fill, the batched solve
               "als.gram", "als.zeros", "als.solve")
ROUND_SCOPES = ("ssp.snapshot",)
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

HOST_SPAN_PREFIX = "fps.host."

_calls = itertools.count()
_open = threading.local()  # .stack: [(span_id, call index)] of this thread


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
              call: bool = False, **attrs):
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
    t0, p0 = (time.time(), time.perf_counter()) if timed else (0.0, 0.0)
    try:
        with jax.profiler.TraceAnnotation(HOST_SPAN_PREFIX + name):
            yield attrs
    finally:
        stack.pop()
        if timed:
            dt = time.perf_counter() - p0
            if timer is not None:
                timer.add(name, dt)
            else:
                events.record_metric("observe", "driver.phase_seconds", dt,
                                     phase=name)
            if rec is not None:
                if index is not None:
                    attrs.setdefault("call", index)
                _emit_span(rec, guarded, name, sid, parent, t0, t0 + dt,
                           attrs)


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


def _on_compile_duration(event, duration, **kw):
    phase = _COMPILE_EVENTS.get(event)
    if phase is None:
        return
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


class Throughput:
    """Callable chunk hook accumulating wall-clock and example counts.

    ``count_key`` names the metrics leaf holding per-step example counts
    (every shipped model emits ``"n"``). The first chunk is recorded
    separately (``first_s``) since it includes compilation.

    Timing origin: :meth:`start` marks the stream start explicitly; when
    it was never called, the first observation measures from CONSTRUCTION
    time. (It used to fall back to "now", which recorded a zero-width
    first chunk and understated compile time — the hook is conventionally
    built immediately before ``fit_stream``, so construction time is the
    honest origin; any setup between the two is attributed to the first
    chunk, which already absorbs one-time costs by design. Call
    ``start()`` right before the run when that setup is expensive, and
    before any *second* stream reusing this hook, or the inter-run gap
    lands in ``steady_s``.)
    """

    def __init__(self, count_key: str = "n"):
        self.count_key = count_key
        self.chunks = 0
        self.first_s: float | None = None
        self._first_examples = 0.0
        self.steady_s = 0.0
        self._steady_examples = 0.0
        self._last: float | None = None
        self._created = time.perf_counter()

    def start(self) -> None:
        """Mark the stream start (see the class docstring for when the
        implicit construction-time origin is not what you want)."""
        self._last = time.perf_counter()

    def __call__(self, step: int, metrics) -> None:
        now = time.perf_counter()
        if self._last is None:
            # No explicit start(): the stream began, as far as this hook
            # can know, when the hook was constructed.
            self._last = self._created
        dt = now - self._last
        self._last = now
        count = (
            float(np.sum(metrics[self.count_key]))
            if self.count_key in metrics
            else 0.0
        )
        if self.first_s is None:
            self.first_s = dt
            self._first_examples = count
        else:
            self.steady_s += dt
            self._steady_examples += count
        self.chunks += 1

    @property
    def examples(self) -> float:
        return self._first_examples + self._steady_examples

    @property
    def examples_per_sec(self) -> float:
        """Steady-state throughput (excludes the compile-laden first chunk)."""
        return self._steady_examples / self.steady_s if self.steady_s else 0.0

    def summary(self) -> dict:
        return {
            "chunks": self.chunks,
            "examples": self.examples,
            "first_chunk_s": round(self.first_s or 0.0, 4),
            "steady_s": round(self.steady_s, 4),
            "examples_per_sec": round(self.examples_per_sec, 1),
        }
