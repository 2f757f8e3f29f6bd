"""From a profiler trace to numbers: the reduction, kept with the benchmark.

Input is the trace-viewer file the JAX profiler writes beside its xplane
(``*.trace.json.gz``): complete events with ``ts``/``dur`` in
microseconds, device and host on one clock. Of each event the reducer
keeps what it needs (:class:`Op`), so a recorded trace can be kept small
for the tests (``tests/data``) in the same form (:func:`ops_to_json`).

A device op carries ``tf_op``, the path of named scopes and the primitive
that made it (``jit(run)/while/body/closed_call/fps.compute/scatter-add:``)
— that is where the program's ``fps.pull`` / ``fps.compute`` / ``fps.push``
scopes are read from — and its output shape. Control-flow ops (``while``)
contain their body's ops on the same line; only LEAVES count as time in
which an operation ran.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
import statistics

_SANITIZE = re.compile(r"[^A-Za-z0-9_.\-/\[\],:]")


@dataclasses.dataclass
class Op:
    device: int          # index of the device plane; -1 for host events
    line: str            # "XLA Ops", "Async XLA Ops", ...; host thread name
    name: str            # "fusion.51"; host: the annotation's name
    start: float         # seconds
    dur: float           # seconds
    tf_op: str = ""      # scope path and primitive
    shape: str = ""      # "f32[480189,10]"
    category: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_trace(path: str, host_prefix: str | tuple = "bench.") -> list:
    """Device ops of every TPU plane and the host events whose name starts
    with ``host_prefix`` (one prefix or a tuple of them), from a
    ``*.trace.json.gz``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    planes, lines = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            lines[(e["pid"], e["tid"])] = e["args"]["name"]
    device_pids = sorted(p for p, n in planes.items()
                         if n.startswith("/device:TPU:"))
    index = {p: i for i, p in enumerate(device_pids)}
    ops = []
    for e in events:
        if e.get("ph") != "X":
            continue
        pid, line = e["pid"], lines.get((e["pid"], e.get("tid")), "")
        if pid in index:
            a = e.get("args", {})
            ops.append(Op(index[pid], line, e["name"], e["ts"] * 1e-6,
                          e["dur"] * 1e-6, a.get("tf_op", ""),
                          a.get("shape_with_layout", "").split("{")[0],
                          a.get("hlo_category", "")))
        elif e["name"].startswith(host_prefix):
            ops.append(Op(-1, line, e["name"], e["ts"] * 1e-6,
                          e["dur"] * 1e-6))
    return ops


def ops_to_json(ops) -> list:
    return [dataclasses.astuple(o) for o in ops]


def ops_from_json(rows) -> list:
    return [Op(*r) for r in rows]


def device_count(ops) -> int:
    return 1 + max((o.device for o in ops), default=-1)


def leaves(ops, device: int, line: str = "XLA Ops") -> list:
    """Ops of one device line that contain no other op, in start order.
    Two nanoseconds of overlap are rounding, not nesting, and an op of no
    length nests in nothing."""
    evs = sorted((o for o in ops if o.device == device and o.line == line),
                 key=lambda o: (o.start, -o.dur))
    out, stack = [], []
    for o in evs:
        if o.dur <= 0:  # a marker (a zero-length custom call)
            out.append(o)
            continue
        while stack and stack[-1][0].end <= o.start + 2e-9:
            top, parent = stack.pop()
            if not parent:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([o, False])
    out.extend(top for top, parent in stack if not parent)
    out.sort(key=lambda o: o.start)
    return out


def busy_and_window(ops) -> tuple:
    """``(busy_s, window_s, gaps)`` averaged over the devices: the union
    of the leaf ops' intervals, the span from a device's first op to its
    last, and device 0's idle gaps as ``(start, end)`` pairs."""
    n = device_count(ops)
    busy = window = 0.0
    gaps = []
    for d in range(n):
        lv = leaves(ops, d)
        if not lv:
            continue
        cur_s, cur_e = lv[0].start, lv[0].end
        b = 0.0
        for o in lv[1:]:
            if o.start > cur_e:
                b += cur_e - cur_s
                if d == 0:
                    gaps.append((cur_e, o.start))
                cur_s, cur_e = o.start, o.end
            else:
                cur_e = max(cur_e, o.end)
        b += cur_e - cur_s
        busy += b
        window += cur_e - lv[0].start
    return (busy / max(n, 1), window / max(n, 1), gaps)


def steps_traced(ops, scope_marker: str = "/fps.") -> float:
    """Steps of the compiled loop inside the trace: every op of the loop
    body runs once a step, so the median count over the distinct scoped
    ops of device 0 is the number of steps (edges cost at most one)."""
    counts = {}
    for o in leaves(ops, 0):
        if scope_marker in o.tf_op:
            counts[o.name] = counts.get(o.name, 0) + 1
    return float(statistics.median(counts.values())) if counts else 0.0


def time_where(ops, pred, line: str = "XLA Ops") -> float:
    """Seconds of leaf ops satisfying ``pred``, averaged over devices."""
    n = device_count(ops)
    total = sum(o.dur for d in range(n) for o in leaves(ops, d, line)
                if pred(o))
    return total / max(n, 1)


def in_scope(op: Op, scopes) -> bool:
    return any(f"/{s}/" in op.tf_op for s in scopes)


def stable_name(op: Op) -> str:
    """Scope, primitive and shape: the same op under the same name after a
    recompile renumbers the fusions."""
    path = op.tf_op.rstrip(":").split("/")
    scope = next((p for p in path if p.startswith("fps.")), "-")
    prim = path[-1] if op.tf_op else op.name.split(".")[0]
    return _SANITIZE.sub("_", f"{scope}/{prim}:{op.shape}")


def breakdown(ops, top: int = 10) -> dict:
    """The device ops that took most time, under stable names, and the
    longest idle gaps of device 0 by what the host was doing (the
    shortest host span loaded that covers the gap's middle)."""
    n = device_count(ops)
    by_name = {}
    for d in range(n):
        for o in leaves(ops, d):
            k = stable_name(o)
            by_name[k] = by_name.get(k, 0.0) + o.dur / n
    host = [o for o in ops if o.device < 0]
    _, _, gaps = busy_and_window(ops)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        cover = [h for h in host if h.start <= mid <= h.end]
        who = min(cover, key=lambda h: h.dur).name if cover \
            else "host.unattributed"
        named.append([who, e - s])
    return {
        "device_ops": [[k, v] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }


# -- the arithmetic of the row-operation roofline -------------------------

def rowop_least_seconds(least_bytes: float,
                        hbm_bytes_per_s: float) -> float:
    """The least time a step's row operations can take on a chip bound by
    HBM bandwidth. ``least_bytes`` is what the ALGORITHM must move, rows x
    row bytes (a configuration's ``rowops``), and each such byte crosses
    HBM twice: a gather reads the table row and writes the batch row, a
    scatter-add reads the delta and writes the table row, a per-id sum is
    written once and read once by what consumes it. The read-modify-write
    of a destination is NOT counted, nor are indices, repeats of an id
    within a step where the configuration says so, or anything a
    particular program moves besides (a row per addend, a relayout, a
    sort's passes): so the count is the algorithm's least and a share
    cannot pass 100% while the measured time holds every op that moves
    those bytes."""
    return least_bytes * 2.0 / hbm_bytes_per_s
