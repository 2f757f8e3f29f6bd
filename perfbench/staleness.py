"""The readings that hold a bounded-staleness cell to its bound, at the
cell's own size, in one process.

    python3 perfbench/staleness.py --workload <name> --seeds 1,2,... --sync-every 1,16

For every seed: the cell's data and system as a run builds them, the
first call of the timed entry from the seeded tables, and the plain
reference over the same call once as the configuration states it
(``model.sync_every``: the SOUND reading, ``control.py``'s) and once for
every other value given: what ``check.compare`` would read if the program
had read FRESHER (a smaller value; 1 = every step reads the live table)
or STALER than it says. Each such replay must pass a limit of the
configuration, or a program that broke the bound either way would still
be ``correct``. One JSON line per reading on stdout and in
``chiprun_out/staleness.<workload>.jsonl``; the last line gives, per
value and number, the smallest reading over the seeds and its factor
over the configuration's limit, and ``unheld``: every (seed, value) whose
replay passed EVERY limit. Exits 1 if there is one (the limits do not
hold the bound), 0 otherwise. Needs no measured window; the benchmark's
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


def replays(loaded: dict, seed: int, values) -> dict:
    """``{sync_every: numbers}`` for the configured value and each of
    ``values``: the program's first call against the reference replayed
    under that round length."""
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
    for s in dict.fromkeys([cfg["model"]["sync_every"], *values]):
        replay = copy.deepcopy(cfg)
        replay["model"]["sync_every"] = s
        out[s], _ = check.compare_call(system, replay, init, program,
                                       warm.host, data_sum)
    return out


def unheld(readings: dict, limits: dict, configured: int) -> list:
    """The round lengths other than ``configured`` whose replay passed
    EVERY limit: a program that read that fresh or that stale would be
    ``correct``."""
    return [s for s, numbers in readings.items() if s != configured
            and all(numbers[k] <= limit for k, limit in limits.items())]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sync-every", required=True)
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != loaded["cell"]["chips"]:
        print(f"staleness: cell {args.workload} needs "
              f"{loaded['cell']['chips']} TPU chip(s); found {len(devs)} x "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    values = [int(s) for s in args.sync_every.split(",") if s]
    limits = loaded["config"]["limits"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    configured = loaded["config"]["model"]["sync_every"]
    smallest, passed = {}, []
    with open(os.path.join(out_dir, f"staleness.{args.workload}.jsonl"),
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
                put(workload=args.workload, seed=seed, sync_every=s,
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
