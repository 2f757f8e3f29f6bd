"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that checks the files it is driven by, refuses any platform
but ``tpu`` (and fewer or more chips than the cell asks for), makes the
cell's data from ``--seed`` on the device, builds the system through the
API a user calls, warms up, measures for ``--seconds``, compares the first
call with the configuration's plain reference, and prints one JSON object
as its LAST line: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``compared``: every
number compared beside its limit, which are also the last lines on
standard error. Everything else worth keeping
— every reading, every number compared beside its limit, the counters —
goes on earlier lines, each one JSON object with an ``"event"`` key.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window, then profiles a few seconds of further calls after it has
closed, and reports the cell's per-layer metrics.
Nothing is written to disk but the compile cache and, when traced, the
profiler's own files under ``perfbench_out/`` inside the checkout.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def emit(event: str, **fields) -> None:
    print(json.dumps(dict(event=event, **fields)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)
    try:
        import fps_tpu  # noqa: F401  (the system under test must be here)
    except ImportError as e:
        print(f"perfbench: the system under test is not in this checkout: "
              f"{e}", file=sys.stderr)
        return 1

    t_imported = time.perf_counter() - _T_START
    import jax

    devs = jax.devices()
    t_devices = time.perf_counter() - _T_START
    chips = loaded["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) != chips:
        print(f"perfbench: cell {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 1

    from fps_tpu.utils.hostenv import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    # Where the environment places the cache the helper sets nothing in
    # code; every program of a cell must be cached all the same.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    emit("start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace, compile_cache=cache_dir,
         imported_s=t_imported, devices_s=t_devices)

    from perfbench.lib.runner import run_cell

    result = run_cell(loaded, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=_T_START, emit=emit,
                      out_dir=os.path.join(ROOT, "perfbench_out"))
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
