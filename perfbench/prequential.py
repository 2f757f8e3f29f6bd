"""The readings that hold a rank-then-learn cell to its order, at the
cell's own size, in one process.

    python3 perfbench/prequential.py --workload <name> --seeds 1,2,...

For every seed: the cell's data and system as a run builds them, the
first call of the timed entry from the seeded tables, and the plain
reference over the same call once as the configuration states it
(``model.topk_rank = "before_update"``: the SOUND reading,
``control.py``'s) and once ranking AFTER each step's update: what
``check.compare`` would read if the program had answered an event's list
from a model that has already trained on the event. That replay must fail
a limit of the configuration, or a program that leaks the event into its
own list would still be ``correct``. One JSON line per reading on stdout
and in ``chiprun_out/prequential.<workload>.jsonl``; the last line gives,
per number, the smallest leaking reading over the seeds and its factor
over the configuration's limit, and ``unheld``: every seed whose leaking
replay passed EVERY limit. Exits 1 if there is one (the limits do not hold
the order), 0 otherwise. Needs no measured window; the benchmark's own
runs never call this.
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

SOUND, LEAK = "before_update", "after_update"


def replays(loaded: dict, seed: int) -> dict:
    """``{topk_rank: numbers}`` for both orders: the program's first call
    against the reference ranking before, and after, each step's update."""
    from perfbench.lib import check, resolve, window

    cfg, traffic = loaded["config"], loaded["traffic"]
    if cfg["model"].get("topk_rank") != SOUND:
        raise ValueError(f"{cfg['name']} states no model.topk_rank = "
                         f"{SOUND!r}: not a rank-then-learn configuration")
    data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    del data
    init = resolve.reference(cfg).init_tables(seed, cfg)
    state, warm = window.queue_call(system, system.place(init))
    warm.wait()
    program = system.export(*state)
    del state
    out = {}
    for order in (SOUND, LEAK):
        replay = copy.deepcopy(cfg)
        replay["model"]["topk_rank"] = order
        out[order], _ = check.compare_call(system, replay, init, program,
                                           warm.host, data_sum)
    return out


def held(numbers: dict, limits: dict) -> bool:
    """Whether a leaking replay's numbers fail at least one limit."""
    return any(numbers[k] > limit for k, limit in limits.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != loaded["cell"]["chips"]:
        print(f"prequential: cell {args.workload} needs "
              f"{loaded['cell']['chips']} TPU chip(s); found {len(devs)} x "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    limits = loaded["config"]["limits"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    smallest, unheld = {}, []
    with open(os.path.join(out_dir, f"prequential.{args.workload}.jsonl"),
              "a") as f:
        def put(**row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for seed in [int(s) for s in args.seeds.split(",") if s]:
            t0 = time.perf_counter()
            for order, numbers in replays(loaded, seed).items():
                put(workload=args.workload, seed=seed, topk_rank=order,
                    numbers=numbers, seconds=time.perf_counter() - t0)
                if order == LEAK:
                    for k, v in numbers.items():
                        smallest[k] = min(smallest.get(k, float("inf")), v)
                    if not held(numbers, limits):
                        unheld.append(seed)
        put(workload=args.workload, kind="summary", smallest_leak=smallest,
            over_limit={k: v / limits[k] for k, v in smallest.items()
                        if limits.get(k)},
            unheld=unheld)
    return 1 if unheld else 0


if __name__ == "__main__":
    sys.exit(main())
