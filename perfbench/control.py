"""The readings a limit is set from, at a cell's own size, in one process.

    python3 perfbench/control.py --workload <name> --seeds 1,2,... [--control-seeds 1,2,3]

For every seed: the cell's data and system as a run builds them, the
first call of the timed entry from the seeded tables, the plain reference
over the same call, and the numbers ``check.compare`` gives (the SOUND
readings). For every control seed also the control: the reference put in
the program's place in bfloat16, compared the same way. One JSON line per
reading on stdout and in ``chiprun_out/control.<workload>.jsonl``; the
last line gives, per number, the largest sound and the smallest control
reading. A limit goes between the two (PERF.md). Needs no measured window;
the benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(loaded: dict, seed: int, control: bool):
    """(sound numbers, control numbers or None, examples to target)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import check, resolve, runner, window

    cfg, traffic = loaded["config"], loaded["traffic"]
    data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    del data
    init = resolve.reference(cfg).init_tables(seed, cfg)
    state, warm = window.queue_call(system, system.place(init))
    warm.wait()
    program = system.export(*state)
    del state
    sound, (ref, ref_loss, ref_n) = check.compare_call(
        system, cfg, init, program, warm.host, data_sum)
    to_target = None
    if cfg.get("quality"):
        q = cfg["quality"]
        to_target = runner.examples_to_target(
            np.concatenate([m[q["sum"]] for m in warm.host]),
            np.concatenate([m[q["count"]] for m in warm.host]), q,
            int(traffic["quality_trailing_steps"]))
    low = None
    if control:
        lt, low_loss, low_n, low_feed = check.run_reference(
            system, cfg, init, dtype=jnp.bfloat16)
        low = check.compare(
            {k: np.asarray(v, np.float32) for k, v in lt.items()}, ref, init,
            low_loss, low_n, ref_loss, ref_n, low_feed,
            check.call_checksum(system, data_sum), system.examples_per_call)
    del system, program, ref
    gc.collect()
    jax.clear_caches()
    return sound, low, to_target


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != loaded["cell"]["chips"]:
        print(f"control: cell {args.workload} needs "
              f"{loaded['cell']['chips']} TPU chip(s); found {len(devs)} x "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    largest, smallest = {}, {}
    with open(os.path.join(out_dir, f"control.{args.workload}.jsonl"),
              "a") as f:
        def put(**row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for seed in sorted(set(seeds) | controls):
            t0 = time.perf_counter()
            sound, low, to_target = readings(loaded, seed, seed in controls)
            put(workload=args.workload, seed=seed, kind="sound",
                numbers=sound, examples_to_target=to_target,
                seconds=time.perf_counter() - t0)
            for k, v in sound.items():
                largest[k] = max(largest.get(k, 0.0), v)
            if low is not None:
                put(workload=args.workload, seed=seed, kind="control",
                    numbers=low)
                for k, v in low.items():
                    smallest[k] = min(smallest.get(k, float("inf")), v)
        put(workload=args.workload, kind="summary", largest_sound=largest,
            smallest_control=smallest)
    return 0


if __name__ == "__main__":
    sys.exit(main())
