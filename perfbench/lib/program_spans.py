"""What the PROGRAM says about its own host time and routes, for the readers.

fps_tpu names every host phase of a call (``fps_tpu.obs.timing.host_span``:
set-up spans, the driver's phases, JAX's compile timings) and logs every
ops route it takes (``fps_tpu.ops.routes_traced``). With a process-default
``Recorder`` installed those land in its sink; this module turns the sink
into the ``program_spans`` of a run's context — name -> the spans' ``(t0,
t1)`` in epoch seconds, set-up, window and what came after it apart, and
beside it (``program_span_events``) every span event whole — and holds
the readers over it. A program without the spans (a parent commit)
leaves the sink empty: every reader then returns ``None`` and the metric is
left out of the line; nothing here raises for want of something to read.

The runner does this on a traced run only (``runner.run_cell``): the
recorder goes in before the data is made, the route log is cleared before
and read after the warm-up call (what a program holds is what was logged
while IT was traced), and the sink is collected once the trace is read.
``readers.READERS`` takes in :data:`READERS`.
"""

from __future__ import annotations

import re
import statistics
import time

COMPILE_PHASES = ("compile.trace", "compile.lower", "compile.backend")


def install_recorder():
    """A memory-only recorder as the process default, before the system is
    built (traced runs only). Returns its sink, or ``None`` where the
    program has no recorder to install."""
    try:
        from fps_tpu import obs
        from fps_tpu.obs import events
    except ImportError:
        return None
    sink = obs.MemorySink(capacity=1 << 16)
    events.set_default_recorder(obs.Recorder(sinks=[sink]))
    return sink


def epoch_of(perf_counter_s: float) -> float:
    """A ``time.perf_counter`` reading on the spans' clock (epoch seconds)."""
    return time.time() - (time.perf_counter() - perf_counter_s)


def _part(t0: float, t1: float, opened_at: float, closed_at: float) -> str:
    return ("setup" if t1 <= opened_at
            else "after" if t0 >= closed_at else "window")


def collect(sink, opened_at: float, closed_at: float) -> dict:
    """``{name: {"setup": [(t0, t1), ...], "window": [...], "after":
    [...]}}`` from a recorder's sink: every ``span`` event, and JAX's
    compile timings (which the program records as
    ``driver.phase_seconds{phase="compile.*"}`` samples: a duration ending
    at the sample's time). A span that ended before ``opened_at`` (epoch
    seconds: the window's opening) is set-up; one that began after
    ``closed_at`` (the profiled calls, the reference's replay) is in no
    reading of the window."""
    out: dict = {}

    def put(name, t0, t1):
        out.setdefault(name, {"setup": [], "window": [], "after": []})[
            _part(t0, t1, opened_at, closed_at)].append(
                (float(t0), float(t1)))

    if sink is None:
        return out
    for e in sink.events("span"):
        put(e["span"], e["t0"], e["t1"])
    for m in sink.metrics("driver.phase_seconds"):
        phase = (m.get("labels") or {}).get("phase")
        if phase in COMPILE_PHASES:
            put(phase, m["t"] - m["value"], m["t"])
    for spans in out.values():
        for part in spans.values():
            part.sort()
    return out


def collect_events(sink, opened_at: float, closed_at: float) -> dict:
    """The same parts as :func:`collect`, holding each ``span`` event WHOLE
    (its ``t0`` and ``t1`` and whatever else the program set on it: a
    call's index, a chunk's ``steps``, bytes, a queue's depth), in the
    order of their ``t0``: the ``program_span_events`` of a run's context,
    for a reader that wants more of a span than its length."""
    out: dict = {}
    if sink is None:
        return out
    for e in sorted(sink.events("span"), key=lambda e: (e["t0"], e["t1"])):
        out.setdefault(e["span"], {"setup": [], "window": [], "after": []})[
            _part(e["t0"], e["t1"], opened_at, closed_at)].append(dict(e))
    return out


def totals(spans: dict, part: str) -> dict:
    """``{name: [count, seconds]}`` of one part of :func:`collect`'s
    output, for the runner's event line."""
    return {name: [len(parts[part]), sum(b - a for a, b in parts[part])]
            for name, parts in sorted(spans.items()) if parts[part]}


def routes_traced():
    """The program's route log since :func:`clear_routes`, one dict an
    entry (the fields of ``fps_tpu.ops.Route`` and ``pallas``: whether the
    route is a Pallas kernel's); ``None`` where it keeps no route log."""
    try:
        from fps_tpu import ops
        log = ops.routes_traced()
    except (ImportError, AttributeError):
        return None
    return [dict(r._asdict(), pallas=r.route in ops.PALLAS_ROUTES)
            for r in log]


def pallas_routes_in_program(routes):
    """Pallas routes, compiled (``interpret=False``), among ``routes``."""
    if routes is None:
        return None
    return float(sum(1 for r in routes
                     if r["pallas"] and not r["interpret"]))


def clear_routes() -> None:
    try:
        from fps_tpu import ops
        ops.clear_routes()
    except (ImportError, AttributeError):
        pass


# -- readers ---------------------------------------------------------------

def _intervals(ctx, name, part):
    return (ctx.get("program_spans") or {}).get(name, {}).get(part, [])


def _self_seconds(span, others) -> float:
    """A span's length less the part of it other spans cover (set-up runs
    on one thread, so what lies inside a span is its child)."""
    t0, t1 = span
    covered, edge = 0.0, t0
    for a, b in sorted(o for o in others
                       if o != span and o[0] >= t0 and o[1] <= t1):
        a = max(a, edge)
        if b > a:
            covered += b - a
            edge = b
    return (t1 - t0) - covered


def program_span_total(ctx, p):
    """Seconds under the named spans in one part of the run: ``spans``
    summed whole, ``self_spans`` less their children, ``first_of`` only
    their first occurrence (of the whole run, wherever it fell)."""
    part = p.get("part", "setup")
    everything = [iv for spans in (ctx.get("program_spans") or {}).values()
                  for iv in spans[part]]
    total, found = 0.0, False
    for name in p.get("spans", ()):
        for t0, t1 in _intervals(ctx, name, part):
            total += t1 - t0
            found = True
    for name in p.get("self_spans", ()):
        for iv in _intervals(ctx, name, part):
            total += _self_seconds(iv, everything)
            found = True
    for name in p.get("first_of", ()):
        first = sorted(_intervals(ctx, name, "setup")
                       + _intervals(ctx, name, "window"))[:1]
        for t0, t1 in first:
            total += t1 - t0
            found = True
    return total * p.get("scale", 1.0) if found else None


def program_span_median(ctx, p):
    """Median length of the named span over one part of the run."""
    vals = [t1 - t0 for t0, t1 in _intervals(ctx, p["span"],
                                             p.get("part", "window"))]
    return statistics.median(vals) * p.get("scale", 1.0) if vals else None


def routes_logged(ctx, p):
    """Entries of the route log whose route matches ``route_regex``."""
    routes = ctx.get("routes")
    if routes is None:
        return None
    pat = re.compile(p["route_regex"])
    return float(sum(1 for r in routes if pat.search(r["route"])))


READERS = {
    "program_span_total": program_span_total,
    "program_span_median": program_span_median,
    "routes_logged": routes_logged,
}
