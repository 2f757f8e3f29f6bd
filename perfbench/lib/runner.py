"""The body of a run, after the look for a chip: everything between the
parsed command line and the result object. ``run.py`` calls
:func:`run_cell` on the chip; the rehearsal test calls it on virtual CPU
devices at a tiny size (and never prints a rate from there).
"""

from __future__ import annotations

import contextlib
import glob
import json
import math
import os
import shutil
import statistics
import time

import numpy as np

from perfbench.lib import check, program_spans, readers, resolve, window
from perfbench.lib import trace_reduce as tr

_HERE = os.path.dirname(os.path.abspath(__file__))


@contextlib.contextmanager
def count_pallas_kernels(counts: list):
    """Append ``(kernel, interpret)`` for every Pallas kernel entry point
    TRACED inside the block (``chip_smoke.record_kernel_traces``, copied:
    ``fps_tpu.ops`` resolves the kernels from their module at each call, so
    wrapping the module attributes sees what the routed program holds)."""
    from fps_tpu.ops import pallas_kernels as pk

    names = [n for n in dir(pk) if n.endswith("_pallas")
             and callable(getattr(pk, n))]
    saved = {n: getattr(pk, n) for n in names}

    def wrap(name, fn):
        def counting(*args, **kw):
            counts.append((name, bool(kw.get("interpret", False))))
            return fn(*args, **kw)
        return counting

    for n, fn in saved.items():
        setattr(pk, n, wrap(n, fn))
    try:
        yield
    finally:
        for n, fn in saved.items():
            setattr(pk, n, fn)


class CompileCounter:
    """Counts, through JAX's monitoring events, the programs lowered
    (``n``: each new shape or function, compile-cache hit or not) and the
    persistent compile cache's hits and misses."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.n = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._lowered)
        jax.monitoring.register_event_listener(self._cache)

    def _lowered(self, event, duration, **kw):
        if event == self.LOWERED:
            self.n += 1

    def _cache(self, event, **kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


def examples_to_target(per_step_sum, per_step_n, quality: dict,
                       trailing: int):
    """Examples consumed from fresh state until the quality over the last
    ``trailing`` steps first meets the target; None if it never does."""
    s = np.cumsum(np.asarray(per_step_sum, np.float64))
    n = np.cumsum(np.asarray(per_step_n, np.float64))
    k = min(trailing, len(s))
    ws = s[k - 1:] - np.concatenate([[0.0], s[:-k]])
    wn = n[k - 1:] - np.concatenate([[0.0], n[:-k]])
    q = ws / np.maximum(wn, 1.0)
    if quality.get("root"):
        q = np.sqrt(q)
    hit = np.flatnonzero((q <= quality["target"]) & (wn > 0))
    return None if len(hit) == 0 else float(n[hit[0] + k - 1])


def _finite(host_metrics) -> bool:
    return all(np.isfinite(np.asarray(v)).all()
               for m in host_metrics for v in m.values())


def _peaks(kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json; add its "
                       "published peaks with their source")
    return table[kind]


def run_cell(loaded: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, emit, out_dir: str) -> dict:
    """One run of one cell. Returns the result object of the last line."""
    import jax

    cfg, traffic = loaded["config"], loaded["traffic"]
    devs = jax.devices()
    lowerings = CompileCounter()
    t_devices = time.perf_counter() - t_start

    # A traced run takes the program's own host spans (a memory-only
    # recorder, installed before anything of the program is built) and its
    # route log; an untraced run installs and reads nothing.
    spans_sink = program_spans.install_recorder() if trace else None

    # -- set-up: data, system, seeded state, warm-up ---------------------
    t0 = time.perf_counter()
    data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
    jax.block_until_ready(data)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    del data
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    init = resolve.reference(cfg).init_tables(seed, cfg)
    jax.block_until_ready(init)
    t_init = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = system.place(init)
    jax.block_until_ready(state)
    t_place = time.perf_counter() - t0

    kernels = []
    t0 = time.perf_counter()
    if trace:
        program_spans.clear_routes()
    with count_pallas_kernels(kernels):
        state, warm = window.queue_call(system, state)
    routes = program_spans.routes_traced() if trace else None
    # The state the warm-up call leaves is what the reference is compared
    # with; the next call donates it, so keep a copy on the device.
    after_warm = jax.tree.map(lambda x: x.copy(), state)
    state, first = window.queue_call(system, state)

    lowered_before = lowerings.n
    state, t_open, done = window.run_window(system, state, warm, first,
                                            seconds)
    lowered_in_window = lowerings.n - lowered_before
    setup_s = t_open - t_start
    t_warm = t_open - t0
    wall = done[-1].done_at - t_open
    jax.block_until_ready(state)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs)
    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                 for d in devs)

    # A traced run profiles ``trace_seconds`` of further calls, after the
    # window has closed and its memory has been read: the profiler's start
    # and stop touch no timed call.
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        state = window.run_traced(system, state, traffic["trace_seconds"],
                                  wall / len(done), trace_dir)
        jax.block_until_ready(state)
    del state

    # -- the window's numbers ----------------------------------------------
    rates = window.readings(t_open, done)
    total_examples = sum(float(np.sum(m["n"], dtype=np.float64))
                         for c in done for m in c.host)
    calls = [warm] + done
    failed = sum(0 if _finite(c.host) else 1 for c in calls)
    # The end-to-end rate: all the window's examples over all its time.
    rate = total_examples / wall
    emit("readings", n=len(rates), examples_per_s=rates,
         window_wall_s=wall, window_examples=total_examples,
         window_examples_per_s=rate,
         dispatch_ms=[c.dispatch_s * 1e3 for c in done])
    emit("setup", setup_s=setup_s, to_devices_s=t_devices, data_s=t_data,
         build_s=t_build, init_tables_s=t_init, place_s=t_place,
         warmup_s=t_warm, compile_cache_hits=lowerings.hits,
         compile_cache_misses=lowerings.misses,
         programs_lowered_in_window=lowered_in_window,
         bytes_in_use_after_window=in_use, peak_bytes=peak_bytes)

    quality = cfg.get("quality")
    to_target = None
    if quality:
        ksum, kn = quality["sum"], quality["count"]
        to_target = examples_to_target(
            np.concatenate([m[ksum] for c in calls for m in c.host]),
            np.concatenate([m[kn] for c in calls for m in c.host]),
            quality, int(traffic["quality_trailing_steps"]))
        emit("quality", target=quality["target"],
             examples_to_target=to_target,
             trailing_steps=traffic["quality_trailing_steps"])

    # -- the comparison with the plain reference (outside window, set-up) --
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.reference"):
        program = system.export(*after_warm)
        del after_warm
        numbers, (_, ref_loss, _) = check.compare_call(
            system, cfg, init, program, warm.host, data_sum)
    numbers["programs_lowered_in_window"] = float(lowered_in_window)
    limits = dict(cfg["limits"], programs_lowered_in_window=0)
    within, rows = check.judge(numbers, limits)
    for row in rows:
        emit("compared", **row)
    # Last in the result's line: every number compared beside its limit.
    compared = {r["number"]: {"value": r["value"], "limit": r["limit"]}
                for r in rows}
    emit("reference", seconds=time.perf_counter() - t0,
         steps=len(ref_loss), name=cfg["reference"])

    reports = {m["name"] for m in loaded["end_to_end"]}
    correct = bool(within and failed == 0)
    if "time_to_target_s" in reports:
        correct = correct and to_target is not None

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": correct, "attempted": len(calls), "failed": failed}
    if not trace:
        values = {"setup_s": setup_s, "examples_per_s": rate}
        if to_target is not None:
            values["time_to_target_s"] = to_target / rate
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in loaded["end_to_end"] if m["name"] in values}
        result["device"] = device
        result["compared"] = compared
        return result

    # -- traced run: per-layer metrics from the trace, spans and counters --
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        raise RuntimeError(f"the profiler left no trace under {trace_dir}")
    # An idle gap is named by the shortest host span covering its middle:
    # the program's own (fps.host.*) where one does, else the runner's.
    ops = tr.load_trace(paths[-1], host_prefix=("bench.", "fps.host."))
    busy_s, window_s, _ = tr.busy_and_window(ops)
    opened_at = program_spans.epoch_of(t_open)
    closed_at = program_spans.epoch_of(done[-1].done_at)
    ctx = {
        "ops": ops,
        "spans": {"bench.dispatch": [c.dispatch_s for c in done]},
        "program_spans": program_spans.collect(spans_sink, opened_at,
                                               closed_at),
        "program_span_events": program_spans.collect_events(
            spans_sink, opened_at, closed_at),
        "routes": routes,
        "counters": {
            "median_call_examples_per_s": statistics.median(rates),
            "examples_to_target": to_target,
            "pallas_kernels_in_program": float(
                sum(1 for _, interp in kernels if not interp)),
            "pallas_routes_in_program":
                program_spans.pallas_routes_in_program(routes),
            "peak_hbm_gb": peak_bytes / 1e9,
        },
        "config": cfg, "workers": system.W,
        "peaks": _peaks(devs[0].device_kind),
    }
    emit("kernels_traced", kernels=sorted(set(kernels)),
         steps_traced=tr.steps_traced(ops), trace_file=paths[-1])
    emit("program", routes=routes, spans={
        part: program_spans.totals(ctx["program_spans"], part)
        for part in ("setup", "window")})
    result["metrics"] = readers.read_all(loaded["readers"], ctx)
    # What the cell lists and no reader found: a route or a scope the
    # program no longer has shows here, in the run that lost it.
    emit("silent", metrics=sorted(set(loaded["readers"])
                                  - set(result["metrics"])))
    result["device"] = dict(device, busy_s=busy_s, window_s=window_s)
    result["breakdown"] = tr.breakdown(ops)
    result["compared"] = compared
    if not (busy_s > 0 and math.isfinite(busy_s)):
        raise RuntimeError("no operation ran on the device in the trace")
    return result
