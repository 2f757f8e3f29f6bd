"""Per-layer metric readers: one small function per KIND of source.

A metric's file (``perfbench/metrics/<name>.json``) names its reader and
gives the reader's parameters (the scope, the op-name pattern, the host
span or the counter it reads). A reader gets the run's context and returns
a number, or ``None`` when it finds nothing to read — the harness then
leaves the metric out of the line. Adding a metric that reads a kind of
source listed in :data:`READERS` is a file and an entry; a new kind of
source is a file too: ``perfbench/readers/<name>.py`` holding
``read(ctx, params)``, found by the name a metric's file gives
(:func:`reader`, ``resolve.py``), never an edit here (README.md).

Context (``ctx``): ``ops`` (the reduced trace, or None), ``spans`` (name ->
list of host-clock seconds, taken by the runner), ``counters`` (name ->
number, counted by the runner), ``program_spans``, ``program_span_events``
and ``routes`` (the program's own host spans, each span event's other
fields, and its route log: ``program_spans.py``, which holds their
readers), ``config`` (the configuration file), ``workers`` and
``peaks`` (this device's row of peaks.json).
"""

from __future__ import annotations

import re
import statistics

from perfbench.lib import program_spans, resolve
from perfbench.lib import trace_reduce as tr


def host_span_median(ctx, p):
    vals = ctx["spans"].get(p["span"])
    if not vals:
        return None
    return statistics.median(vals) * p.get("scale", 1.0)


def counter(ctx, p):
    v = ctx["counters"].get(p["counter"])
    return None if v is None else v * p.get("scale", 1.0)


def _steps(ctx):
    return tr.steps_traced(ctx["ops"]) if ctx.get("ops") else 0.0


def scope_time_per_step(ctx, p):
    steps = _steps(ctx)
    if not steps:
        return None
    t = tr.time_where(ctx["ops"], lambda o: tr.in_scope(o, p["scopes"]))
    return t / steps * p.get("scale", 1.0) if t > 0 else None


def op_time_per_step(ctx, p):
    steps = _steps(ctx)
    if not steps:
        return None
    pat = re.compile(p["name_regex"])
    t = sum(tr.time_where(ctx["ops"], lambda o: bool(pat.search(o.name)),
                          line) for line in p.get("lines", ["XLA Ops"]))
    return t / steps * p.get("scale", 1.0) if t > 0 else None


def _rowop(ctx, p):
    """``(measured seconds per step, rows per worker step, least bytes per
    worker step)``. Measured is ALL the leaf-op time under ``p["scopes"]``
    (the store's pull and push and every routed op, wherever it lies), each
    leaf once, whatever primitive did the work: a sort, a scan or a matrix
    product that forms a push's sums counts as the scatter it replaced.
    The bytes are the configuration's (``rowops``): the least ANY
    implementation of the step must move, ``bytes_per_worker_step`` where
    one ``row_bytes`` cannot express the mix."""
    steps = _steps(ctx)
    spec = ctx["config"].get("rowops")
    if not steps or not spec:
        return None
    t = tr.time_where(ctx["ops"], lambda o: tr.in_scope(o, p["scopes"]))
    if t <= 0:
        return None
    rows = float(spec["rows_per_worker_step"])
    least_bytes = float(spec.get("bytes_per_worker_step",
                                 rows * spec["row_bytes"]))
    return t / steps, rows, least_bytes


def rowop_ns_per_row(ctx, p):
    r = _rowop(ctx, p)
    return None if r is None else r[0] / r[1] * 1e9


def rowop_roofline_percent(ctx, p):
    r = _rowop(ctx, p)
    if r is None:
        return None
    least = tr.rowop_least_seconds(r[2], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / r[0]


def device_idle_percent(ctx, p):
    if not ctx.get("ops"):
        return None
    busy, window, _ = tr.busy_and_window(ctx["ops"])
    return 100.0 * (1.0 - busy / window) if window > 0 else None


READERS = {
    "host_span_median": host_span_median,
    "counter": counter,
    "scope_time_per_step": scope_time_per_step,
    "op_time_per_step": op_time_per_step,
    "rowop_ns_per_row": rowop_ns_per_row,
    "rowop_roofline_percent": rowop_roofline_percent,
    "device_idle_percent": device_idle_percent,
    **program_spans.READERS,
}


def reader(name: str):
    """The reader called ``name``: one of :data:`READERS`, else the
    ``read`` of ``perfbench/readers/<name>.py`` (:class:`resolve.SpecError`
    naming the file to add where there is neither)."""
    return READERS.get(name) or resolve.load("reader", name).read


def read_all(readers: dict, ctx: dict) -> dict:
    """name -> value for every metric whose reader found something."""
    out = {}
    for name, spec in readers.items():
        value = reader(spec["reader"])(ctx, spec.get("params", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out
