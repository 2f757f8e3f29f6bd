"""Reader ``span_event_median``: the median length (``t1 - t0``), over the
program's span events of one name in one part of the run, of the events
whose fields equal ``where``.

Parameters: ``span`` (the event's name), ``part`` (``setup`` / ``window`` /
``after``, default ``window``), ``where`` (``{field: value}``, default
none) and ``scale``. Reads
``ctx["program_span_events"]`` (``lib/program_spans.collect_events``: each
span event whole); a program that records no such span (a parent commit)
leaves nothing there and the reader returns ``None``.
"""

from __future__ import annotations

import statistics


def read(ctx, p):
    events = (ctx.get("program_span_events") or {}).get(
        p["span"], {}).get(p.get("part", "window"), [])
    where = p.get("where", {})
    vals = [e["t1"] - e["t0"] for e in events
            if all(e.get(k) == v for k, v in where.items())]
    return statistics.median(vals) * p.get("scale", 1.0) if vals else None
