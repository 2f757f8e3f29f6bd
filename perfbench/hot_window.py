"""The readings that hold a two-tier cell to its reconcile window, at the
cell's own size, in one process.

    python3 perfbench/hot_window.py --workload <name> --seeds 1,2,... --windows 1,16 [--sound 1]

For every seed: the cell's data and system as a run builds them, the
first call of the timed entry from the seeded tables, and the plain
reference over the same call once for every window length given
(``model.hot_sync_every`` replaced): what ``check.compare`` would read if
the program had reconciled its replicas MORE often than the configuration
says (a smaller value; 1 = every step reads what the step before left,
the synchronous program) or LESS often. Each such replay must pass a
limit of the configuration, or a program that broke the window either way
would still be ``correct``. With ``--sound 1`` also as the configuration
states it (the SOUND reading, ``control.py``'s; off by default: a replay
and its comparison take minutes at this size, and every run of the cell
gives that reading). One JSON line per reading on stdout and in
``chiprun_out/hot_window.<workload>.jsonl``; the last line gives, per
value and number, the smallest reading over the seeds and its factor over
the configuration's limit, and ``unheld``: every (seed, value) whose
replay passed EVERY limit. Exits 1 if there is one (the limits do not
hold the window), 0 otherwise. Needs no measured window; the benchmark's
own runs never call this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# The verdict is staleness.py's, by value: the values other than the
# configured one whose replay passed EVERY limit.
from perfbench.staleness import unheld  # noqa: E402


def replays(loaded: dict, seed: int, values) -> dict:
    """``{hot_sync_every: numbers}`` for each of ``values``: the program's
    first call against the reference replayed under that window."""
    from perfbench.lib import check, resolve, window

    cfg, traffic = loaded["config"], loaded["traffic"]
    data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    del data
    init = resolve.reference(cfg).init_tables(seed, cfg)
    state, warm = window.queue_call(system, system.place(init))
    warm.wait()
    program = system.export(*state)
    del state
    out = {}
    for s in dict.fromkeys(values):
        replay = copy.deepcopy(cfg)
        replay["model"]["hot_sync_every"] = s
        out[s], _ = check.compare_call(system, replay, init, program,
                                       warm.host, data_sum)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--windows", required=True)
    ap.add_argument("--sound", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != loaded["cell"]["chips"]:
        print(f"hot_window: cell {args.workload} needs "
              f"{loaded['cell']['chips']} TPU chip(s); found {len(devs)} x "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    configured = loaded["config"]["model"]["hot_sync_every"]
    values = [int(s) for s in args.windows.split(",") if s]
    if args.sound:
        values.insert(0, configured)
    limits = loaded["config"]["limits"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    smallest, passed = {}, []
    with open(os.path.join(out_dir, f"hot_window.{args.workload}.jsonl"),
              "a") as f:
        def put(**row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for seed in [int(s) for s in args.seeds.split(",") if s]:
            t0 = time.perf_counter()
            readings = replays(loaded, seed, values)
            for s, numbers in readings.items():
                put(workload=args.workload, seed=seed, hot_sync_every=s,
                    numbers=numbers, seconds=time.perf_counter() - t0)
                low = smallest.setdefault(s, {})
                for k, v in numbers.items():
                    low[k] = min(low.get(k, float("inf")), v)
            passed += [[seed, s] for s in unheld(readings, limits,
                                                 configured)]
        put(workload=args.workload, kind="summary", smallest=smallest,
            over_limit={s: {k: v / limits[k] for k, v in low.items()
                            if limits.get(k)}
                        for s, low in smallest.items()},
            unheld=passed)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
