"""Reader ``span_field_share``: over the program's span events of one name
in one part of the run, the sum of one count over the sum of another, both
read from a field that holds ``{key: {count: number}}`` (a unit's own sums
a table: ``device.run_indexed``'s ``hot_tier``).

Parameters: ``span`` (the event's name), ``part`` (default ``window``),
``field`` (the event's field holding the sums), ``of`` and ``over`` (the
counts divided, each summed over every key and every event) and ``scale``
(100 for a percentage). Reads ``ctx["program_span_events"]``; a program
that sets no such field (a parent commit, a configuration without the
tier) leaves nothing to read, as does a denominator of zero, and the reader
returns ``None``.
"""

from __future__ import annotations


def read(ctx, p):
    events = (ctx.get("program_span_events") or {}).get(
        p["span"], {}).get(p.get("part", "window"), [])
    of = over = 0.0
    for e in events:
        sums = e.get(p["field"])
        if isinstance(sums, dict):
            for counts in sums.values():
                of += float(counts.get(p["of"], 0.0))
                over += float(counts.get(p["over"], 0.0))
    return of / over * p.get("scale", 1.0) if over > 0 else None
