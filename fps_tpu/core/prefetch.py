"""Overlapped host pipeline: background chunk prefetch + placement.

The compiled pull→compute→push loop is one fused dispatch, but the host
driver around it was fully serial: assemble the next chunk (numpy fancy
indexing in :mod:`fps_tpu.core.ingest`), place it onto the batch sharding
(``host_to_sharded``), dispatch, block for whatever consumer needs host
metrics, repeat. Every one of those host segments is time the device
spends idle — BENCH round 5 measured ~28% of the MF epoch as exactly this
gap (0.63 s/epoch against a 0.49 s fused-loop floor).

:class:`ChunkPrefetcher` closes the ingest+place part of the gap: a
single worker thread pulls from any chunk iterator, runs host assembly
AND host→device placement up to ``depth`` chunks ahead, and hands the
driver already-device-resident chunks (wrapped in :class:`PlacedChunk`
so ``Trainer.run_chunk`` skips its place phase) in the exact order the
source yielded them. The training numerics cannot change: placement
produces the same sharded arrays the synchronous path would, the
compiled program is looked up from the same cache, and chunk order is
preserved — prefetch on/off is bit-identical (tested, including the
lowered HLO).

Contracts:

* **deterministic order** — one worker thread, FIFO buffer: chunks come
  out in source order, always.
* **bounded depth** — at most ``depth`` placed chunks are buffered (plus
  the one being assembled); the worker blocks when the buffer is full,
  so host and device memory stay bounded on an unbounded stream.
* **errors re-raise on the caller** — an exception inside the source
  iterator (or placement) is delivered at the position it occurred:
  every chunk assembled before it is yielded first, then the original
  exception object is raised from ``__next__`` on the consuming thread.
* **no thread leaks** — :meth:`close` wakes a blocked worker and joins
  it; every exit path of ``Trainer.fit_stream`` (normal end, a raising
  ``on_chunk``, health abort, quarantine-budget abort) closes the
  pipeline in a ``finally``. The thread is a daemon as a last resort, so
  even an unjoinable worker (source wedged in a blocking read) cannot
  hang interpreter exit.

Telemetry (all optional): a :class:`~fps_tpu.obs.timing.PhaseTimer` gets
the worker's assemble+place seconds folded in as the ``prefetch`` phase,
and a :class:`~fps_tpu.obs.registry.Recorder` gets a
``prefetch.queue_depth`` gauge plus a ``prefetch.chunks`` counter — the
evidence ``tools/obs_report.py`` renders as the overlap breakdown.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
from typing import Callable, Iterable

from fps_tpu.obs.timing import host_span

_log = logging.getLogger("fps_tpu.prefetch")

# Worker→consumer end-of-stream marker (never buffered, never yielded).
_END = object()

#: Adaptive depth: consumed chunks per adaptation window, and the
#: queue-empty stall count within one window that triggers a raise.
ADAPT_WINDOW = 8
ADAPT_STALLS = 2

#: A depth raise must keep the whole buffer under this share of the
#: currently-available host memory.
ADAPT_MEM_SHARE = 0.25


def _available_host_bytes() -> int | None:
    """Available (not merely free) host memory, or ``None`` when the
    platform can't say — ``None`` means the memory veto abstains."""
    try:
        return os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return None


def _chunk_nbytes(item) -> int:
    """Dependency-free byte estimate of one buffered chunk (device
    arrays count too — a placed chunk's device footprint tracks its
    host footprint, and overestimating only makes the veto stricter)."""
    if isinstance(item, PlacedChunk):
        return _chunk_nbytes(item.batches) + _chunk_nbytes(item.host_ids)
    if isinstance(item, dict):
        return sum(_chunk_nbytes(v) for v in item.values())
    if isinstance(item, (list, tuple)):
        return sum(_chunk_nbytes(v) for v in item)
    try:
        return int(getattr(item, "nbytes", 0) or 0)
    except TypeError:
        return 0


class PlacedChunk:
    """A chunk already placed on the batch sharding by the pipeline.

    ``Trainer.run_chunk`` unwraps it and skips the place phase — the
    wrapper exists so an already-uploaded chunk can never be mistaken
    for a host chunk and re-placed (or worse, a host chunk silently
    skip placement).

    ``host_ids`` optionally carries the raw host id columns the
    compacted-cold-route certifier needs
    (``WorkerLogic.pulled_ids_host``): placement happens on the prefetch
    worker thread, but hot-set membership can change between placement
    and dispatch (re-ranks), so certification itself runs at dispatch
    time against these retained host arrays — references to the source
    chunk's columns, not copies.
    """

    __slots__ = ("batches", "host_ids")

    def __init__(self, batches, host_ids=None):
        self.batches = batches
        self.host_ids = host_ids


class ChunkPrefetcher:
    """Bounded-depth background prefetch+place over a chunk iterator.

    Args:
      chunks: any iterator/iterable of chunks (host pytrees or
        device-resident chunks — both flow through with unchanged
        semantics).
      place_fn: optional host→device placement (e.g. the driver's batch
        upload); when given, yielded items are :class:`PlacedChunk`
        wrappers around its result. ``None`` overlaps assembly only.
      depth: max chunks buffered ahead (>= 1; default 2 — one in flight
        on the device, one ready, one being assembled). With
        ``max_depth`` set this is the STARTING depth.
      max_depth: enable adaptive depth — when the consumer keeps
        draining the buffer empty (>= ``ADAPT_STALLS`` queue-empty
        stalls inside a window of ``ADAPT_WINDOW`` consumed chunks) the
        depth is raised one chunk at a time up to this bound, provided
        the grown buffer stays under ``ADAPT_MEM_SHARE`` of available
        host memory. Each raise increments the
        ``prefetch.depth_adjustments`` counter. ``None`` (default)
        keeps the fixed-depth behavior. Depth never adapts downward:
        the buffer bound is what certifies memory, and a transiently
        fast consumer should keep the headroom it earned.
      mem_probe: available-host-bytes callable for the memory veto
        (test seam; default reads ``SC_AVPHYS_PAGES``; returning
        ``None`` abstains).
      recorder: optional :class:`fps_tpu.obs.Recorder` for the
        ``prefetch.queue_depth`` gauge and ``prefetch.chunks`` counter.
      timer: optional :class:`fps_tpu.obs.PhaseTimer`; worker seconds are
        folded in under the ``prefetch`` phase (thread-safe).
      start_index: stream index of the first chunk (``fit_stream``'s
        ``start_step`` on a resume) — only used to key ``skip_place``.
      skip_place: stream indices whose chunks are yielded UNPLACED (raw)
        — the driver's preset-quarantine set: those chunks are consumed
        but never dispatched, so paying their host→device upload would
        be pure waste.

    Iterate it like the source iterator; call :meth:`close` (or use it
    as a context manager) on every exit path.
    """

    def __init__(self, chunks: Iterable, place_fn: Callable | None = None, *,
                 depth: int = 2, max_depth: int | None = None,
                 mem_probe: Callable | None = None, recorder=None,
                 timer=None, start_index: int = 0,
                 skip_place=frozenset(), name: str = "fps-prefetch"):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if max_depth is not None and max_depth < depth:
            raise ValueError(
                f"prefetch max_depth={max_depth} must be >= depth={depth}")
        self.depth = depth
        self.max_depth = max_depth
        self._mem_probe = (mem_probe if mem_probe is not None
                           else _available_host_bytes)
        self._stalls = 0
        self._consumed = 0
        self._it = iter(chunks)
        self._place = place_fn
        self._index = start_index
        self._skip_place = frozenset(skip_place)
        self._rec = recorder
        self._timer = timer
        self._cv = threading.Condition()
        self._buf: collections.deque = collections.deque()
        self._done = False
        self._stop = False
        self._error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._worker, name=name, daemon=True
        )
        self._thread.start()

    # -- worker side ------------------------------------------------------

    def _gauge(self, depth: int) -> None:
        # Called OUTSIDE self._cv: recorder sinks may do file I/O, which
        # must not serialize the producer/consumer handoff.
        if self._rec is not None:
            self._rec.set("prefetch.queue_depth", float(depth))

    def _worker(self) -> None:
        try:
            while True:
                with self._cv:
                    while len(self._buf) >= self.depth and not self._stop:
                        self._cv.wait()
                    if self._stop:
                        return
                # Worker-thread time (assemble + place), overlapped with
                # the driver's phases; the pull that finds the stream
                # exhausted is a (near-empty) segment too.
                with host_span("prefetch", self._timer):
                    item = next(self._it, _END)
                    if (item is not _END and self._place is not None
                            and self._index not in self._skip_place):
                        placed = self._place(item)
                        # A place_fn may return a ready PlacedChunk itself
                        # (the driver's certifying wrapper does, to attach
                        # host_ids); only wrap bare batch pytrees.
                        item = (placed if isinstance(placed, PlacedChunk)
                                else PlacedChunk(placed))
                    self._index += 1
                if item is not _END and self._rec is not None:
                    self._rec.inc("prefetch.chunks")
                with self._cv:
                    if self._stop:
                        return
                    if item is _END:
                        self._done = True
                    else:
                        self._buf.append(item)
                        depth = len(self._buf)
                    self._cv.notify_all()
                if item is _END:
                    return
                self._gauge(depth)
        except BaseException as e:  # noqa: BLE001 - re-raised on consumer
            with self._cv:
                self._error = e
                self._done = True
                self._cv.notify_all()

    # -- consumer side ----------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        raised = False
        with self._cv:
            if not self._buf and not self._done:
                # The device is about to idle waiting on the host
                # pipeline — the signal adaptive depth sizes from.
                self._stalls += 1
            while not self._buf and not self._done:
                self._cv.wait()
            if self._buf:
                item = self._buf.popleft()
                depth = len(self._buf)
                self._cv.notify_all()  # free a slot for the worker
            elif self._error is not None:
                err, self._error = self._error, None
                # The original exception OBJECT (traceback included)
                # crosses threads; the stream is dead past this point.
                raise err
            else:
                raise StopIteration
            self._consumed += 1
            if self._consumed >= ADAPT_WINDOW:
                raised = self._maybe_raise_depth_locked(item)
                self._stalls = 0
                self._consumed = 0
        self._gauge(depth)
        if raised and self._rec is not None:
            # Outside the cv, like _gauge: sinks may do file I/O.
            self._rec.inc("prefetch.depth_adjustments")
        return item

    def _maybe_raise_depth_locked(self, item) -> bool:
        """One-chunk depth raise at a window boundary (cv held):
        stall-justified and memory-vetoed."""
        if self.max_depth is None or self.depth >= self.max_depth:
            return False
        if self._stalls < ADAPT_STALLS:
            return False
        nbytes = _chunk_nbytes(item)
        avail = self._mem_probe()
        if (avail is not None and nbytes > 0
                and (self.depth + 1) * nbytes > ADAPT_MEM_SHARE * avail):
            return False
        self.depth += 1
        self._cv.notify_all()  # the worker may now run further ahead
        return True

    def close(self, timeout: float = 10.0) -> None:
        """Stop the worker and join it (idempotent).

        Buffered chunks are dropped. A worker blocked on the full buffer
        is woken; one blocked inside the SOURCE (a wedged ``next``)
        cannot be preempted from Python — after ``timeout`` seconds it
        is left as a daemon to die with the process (logged)."""
        with self._cv:
            self._stop = True
            self._buf.clear()
            self._cv.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            _log.warning(
                "prefetch worker did not exit within %.1fs (source blocked "
                "in next()?); leaving the daemon thread behind", timeout,
            )

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
