"""The measured window: calls of the system's entry with work queued ahead.

The next call is always queued before the current one is waited for, so
the device goes from one call straight into the next and a host stall
shorter than a call delays no device work. The window opens at the
completion of the warm-up call (which compiled everything, and is also the
call the reference replays) with the first timed call already queued
behind it, and closes at the first completion at or past ``seconds``: no
call is queued that would start after that. The end-to-end rate is ALL the
window's examples over ALL its time, open to close, stalls and all. Inside
the window the garbage collector is off, nothing large is allocated, no
file is written and the profiler is not running: a traced run profiles
further calls AFTER the window has closed (:func:`run_traced`).

A *reading* is one call: its examples over the time from the previous
call's completion to its own, completion being the moment its metrics are
on the host. A late completion stamp lengthens one reading and shortens
its neighbour; the readings' median stands beside the window's rate as a
per-layer metric and decides nothing.
"""

from __future__ import annotations

import gc
import time

import numpy as np


class Completion:
    """A queued call: its device metrics, later its host metrics."""

    def __init__(self, metrics, dispatch_s: float):
        self.device = metrics
        self.dispatch_s = dispatch_s
        self.host = None
        self.done_at = None

    def wait(self, poll=None):
        """Block until the call's metrics are on the host. ``poll`` (trace
        runs only) is called every few milliseconds while waiting."""
        import jax

        if self.host is not None:
            return self
        if poll is not None:
            # Short annotated pieces, so that a trace which stops in the
            # middle of a long wait still holds what the host was doing.
            last = jax.tree.leaves(self.device[-1])[-1]
            while not last.is_ready():
                with jax.profiler.TraceAnnotation("bench.wait"):
                    poll()
                    time.sleep(0.002)
        self.host = jax.device_get(self.device)
        self.done_at = time.perf_counter()
        self.device = None
        return self


def queue_call(system, state):
    """Dispatch one call; returns (new state, Completion)."""
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        tables, local_state, metrics = system.call(*state)
    return (tables, local_state), Completion(
        metrics, time.perf_counter() - t0)


def run_window(system, state, warm: Completion, first: Completion,
               seconds: float):
    """Drive the window. ``warm`` is the queued warm-up call, ``first`` the
    timed call already queued behind it. Returns ``(state, t0, done)``:
    the completion time of the warm-up call and the timed calls in order
    (each with ``done_at``, ``host``, ``dispatch_s``)."""
    import jax

    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        with jax.profiler.TraceAnnotation("bench.wait"):
            warm.wait()
        t0 = warm.done_at
        queued, done = [first], []
        prev, more = t0, True
        while queued:
            if more and len(queued) < 2:
                # Before the first reading lands there is no estimate of a
                # call's length: queue one more (two is the depth kept).
                state, nxt = queue_call(system, state)
                queued.append(nxt)
            with jax.profiler.TraceAnnotation("bench.wait"):
                cur = queued.pop(0).wait()
            done.append(cur)
            length = cur.done_at - prev
            prev = cur.done_at
            # The call now running ends at about prev + length; queue
            # behind it only if that is still inside the window.
            more = more and (prev + length - t0) < seconds
    finally:
        gc.enable()
        gc.unfreeze()
    return state, t0, done


def run_traced(system, state, seconds: float, call_s: float,
               trace_dir: str):
    """Profile ``seconds`` of further calls of the same entry, after the
    window has closed, queued ahead as the window queues them (two deep).
    ``call_s`` is a call's length as the window read it: calls are queued
    to cover the traced span and a quarter of a call more, so the device
    does not run dry before the profiler stops. The profiler is stopped
    from the wait loop's poll, in the middle of a call, or before a wait
    where the call has already ended (a call that returns finished never
    enters the wait's loop: without that poll nothing would stop the
    profiler and calls would be queued for ever); stopping can block the
    host for seconds, which is why none of this runs in the window."""
    import jax

    jax.profiler.start_trace(trace_dir)
    covered = time.perf_counter()
    due = [covered + seconds]

    def poll():
        if due and time.perf_counter() >= due[0]:
            jax.profiler.stop_trace()
            due.clear()

    queued = []
    while due:
        while not queued or (len(queued) < 2 and due
                             and covered < due[0] + 0.25 * call_s):
            state, nxt = queue_call(system, state)
            queued.append(nxt)
            covered += call_s
        poll()
        queued.pop(0).wait(poll)
    for c in queued:
        c.wait()
    return state


def readings(t0: float, done) -> list:
    """Examples per second of each timed call, completion to completion."""
    out, prev = [], t0
    for c in done:
        n = sum(float(np.sum(m["n"], dtype=np.float64)) for m in c.host)
        out.append(n / (c.done_at - prev))
        prev = c.done_at
    return out
