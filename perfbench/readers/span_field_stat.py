"""Reader ``span_field_stat``: one statistic of one number the program's
span events carry, over the events of one or several names in one part of
the run.

Parameters: ``span`` (an event's name, or a list of names, all together;
a name ending in ``*`` takes every name that starts with what precedes
it: ``device.*``), ``part`` (``setup`` / ``window`` / ``after``, default
``window``), then what is read of each event, either ``field`` (a number
on the event) or ``minus: [a, b]`` (field ``a`` less field ``b``, e.g.
``["hbm_close", "hbm_open"]``), ``where`` (``{field: value}``: of the
events that CARRY the field only those whose value equals it count; an
event without the field is kept, so one metric can pick ``solve ==
"user"`` among ``als.half_epoch`` spans and still read the root spans of
the other entries, which have no ``solve``), ``stat`` (``first``: the
earliest event by ``t0``; ``median``; ``max``) and ``scale``.

Reads ``ctx["program_span_events"]`` (``lib/program_spans.collect_events``:
each span event whole, in the order of its ``t0``). An event that lacks the
number (a backend that counts no memory, a parent commit whose spans carry
no bytes) is passed over; with none left the reader returns ``None`` and
the metric is left out of the line.
"""

from __future__ import annotations

import statistics

_STATS = {"first": lambda vals: vals[0], "median": statistics.median,
          "max": max}


def _named(name: str, wanted) -> bool:
    return any(name.startswith(w[:-1]) if w.endswith("*") else name == w
               for w in wanted)


def _number(event: dict, p: dict):
    if "minus" in p:
        a, b = (event.get(k) for k in p["minus"])
        ok = all(isinstance(v, (int, float)) for v in (a, b))
        return a - b if ok else None
    v = event.get(p["field"])
    return v if isinstance(v, (int, float)) else None


def read(ctx, p):
    wanted = [p["span"]] if isinstance(p["span"], str) else list(p["span"])
    part = p.get("part", "window")
    where = p.get("where", {})
    events = sorted(
        (e for name, parts in (ctx.get("program_span_events") or {}).items()
         if _named(name, wanted) for e in parts.get(part, [])
         if all(e[k] == v for k, v in where.items() if k in e)),
        key=lambda e: (e["t0"], e["t1"]))
    vals = [v for v in (_number(e, p) for e in events) if v is not None]
    if not vals:
        return None
    return _STATS[p.get("stat", "median")](vals) * p.get("scale", 1.0)
