"""Reader ``span_event_share``: what share of the extent of a family of
the program's spans a field of theirs adds up to.

Parameters: ``prefix`` (every span event whose name starts with it counts,
all names together), ``part`` (default ``window``), ``field`` (seconds on
the event, e.g. ``starved_s``), ``ends_at`` (the field holding the time
those seconds END at, e.g. ``t_enqueued``: the seconds are the interval
``[ends_at - field, ends_at]``) and ``scale`` (100 for a percentage). The
extent is the first ``t0`` to the last ``t1`` of the spans taken; an
interval is counted only as far as it lies inside the extent, so what
precedes the first span (the idle time before a run's first unit) is not
in the share. Reads ``ctx["program_span_events"]``; with no such span there
(a parent commit) the reader returns ``None``.
"""

from __future__ import annotations


def read(ctx, p):
    part = p.get("part", "window")
    events = [e for name, parts in
              (ctx.get("program_span_events") or {}).items()
              if name.startswith(p["prefix"]) for e in parts.get(part, [])]
    if not events:
        return None
    first = min(e["t0"] for e in events)
    extent = max(e["t1"] for e in events) - first
    if extent <= 0:
        return None
    total = 0.0
    for e in events:
        end = e.get(p["ends_at"])
        if end is not None:
            total += max(0.0, end - max(first, end - e.get(p["field"], 0.0)))
    return total / extent * p.get("scale", 1.0)
