"""The readings that hold ``dlrm-criteo``'s limits, at the cell's own size,
in one process.

    python3 perfbench/dlrm_controls.py --workload dlrm-criteo.epochs --seeds 1,2,... [--control-seeds 1,2] [--controls bf16,default_precision,drop_dense,mean_fold] [--examples-resident N]

For every seed: the cell's data and system as a run builds them, the
first call of the timed entry from the seeded tables, the plain reference
over the same call, and the numbers ``check.compare`` gives (the SOUND
readings, ``control.py``'s). For every control seed also each control:
the reference put in the program's place with ONE thing wrong, compared
the same way against the sound reference:

* ``bf16``: tables and arithmetic in bfloat16 (``control.py``'s control);
* ``default_precision``: float32 tables, every matrix product at the
  chip's DEFAULT precision (one bfloat16 pass) where the configuration
  states ``highest``;
* ``drop_dense``: the dense gradients dropped, the MLPs never move;
* ``mean_fold``: a touched row takes the MEAN of its pushes, not the sum.

Each must fail a limit of the configuration, or a program that computed
that would still be ``correct``. One JSON line per reading on stdout and
in ``chiprun_out/dlrm_controls.<workload>.jsonl``; the last line gives,
per number, the largest sound reading and, per control, the smallest
reading, its factor over the limit, and ``unheld``: every (seed, control)
that passed EVERY limit. Exits 1 if there is one. Every reading also
carries ``loss_gap_by_step``: the widest relative gap of a step's loss
over the call's first 1, 2, 4, ... steps (how the gap grows with the
call's length, which a limit on the whole call cannot show).
``--examples-resident N`` reads all of it at another length of call.
Needs no measured window; the benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = {
    "bf16": {},
    "default_precision": {"matmul_precision": "default"},
    "drop_dense": {"control": "drop_dense"},
    "mean_fold": {"control": "mean_fold"},
}


def gap_by_step(loss, ref_loss) -> dict:
    """``{K: widest relative gap over the first K steps}``, K = 1, 2, 4,
    ... and the whole call."""
    import numpy as np

    loss, ref = np.asarray(loss, np.float64), np.asarray(ref_loss, np.float64)
    gap = np.where(ref > 0, np.abs(loss - ref) / np.where(ref > 0, ref, 1), 0)
    worst = np.maximum.accumulate(gap)
    ks = sorted({2 ** i for i in range(len(gap).bit_length())} | {len(gap)})
    return {int(k): float(worst[k - 1]) for k in ks}


def readings(loaded: dict, seed: int, controls) -> tuple[dict, dict, dict]:
    """``(sound numbers, {control: numbers}, {reading: loss gap by
    step})`` of one seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

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
    sound, (ref, ref_loss, ref_n) = check.compare_call(
        system, cfg, init, program, warm.host, data_sum)
    del program
    by_step = {"sound": gap_by_step(
        np.concatenate([m[system.loss_key] for m in warm.host]), ref_loss)}
    out = {}
    for name in controls:
        wrong = copy.deepcopy(cfg)
        wrong["model"].update(CONTROLS[name])
        tables, loss, n, feed = check.run_reference(
            system, wrong, init,
            dtype=jnp.bfloat16 if name == "bf16" else None)
        out[name] = check.compare(
            {k: np.asarray(v, np.float32) for k, v in tables.items()}, ref,
            init, loss, n, ref_loss, ref_n, feed,
            check.call_checksum(system, data_sum), system.examples_per_call)
        by_step[name] = gap_by_step(loss, ref_loss)
        del tables
    del system, ref
    gc.collect()
    jax.clear_caches()
    return sound, out, by_step


def passes_every_limit(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in limits.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--examples-resident", type=int, default=None)
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)
    if args.examples_resident:
        loaded["config"]["data"]["examples_resident"] = args.examples_resident

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != loaded["cell"]["chips"]:
        print(f"dlrm_controls: cell {args.workload} needs "
              f"{loaded['cell']['chips']} TPU chip(s); found {len(devs)} x "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    controls = [c for c in args.controls.split(",") if c]
    limits = loaded["config"]["limits"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    largest, smallest, unheld = {}, {}, []
    with open(os.path.join(out_dir, f"dlrm_controls.{args.workload}.jsonl"),
              "a") as f:
        def put(**row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for seed in sorted(set(seeds) | control_seeds):
            t0 = time.perf_counter()
            sound, wrong, by_step = readings(
                loaded, seed, controls if seed in control_seeds else [])
            put(workload=args.workload, seed=seed, kind="sound",
                numbers=sound, within=passes_every_limit(sound, limits),
                loss_gap_by_step=by_step["sound"],
                examples_resident=loaded["config"]["data"][
                    "examples_resident"],
                seconds=time.perf_counter() - t0)
            for k, v in sound.items():
                largest[k] = max(largest.get(k, 0.0), v)
            for name, numbers in wrong.items():
                put(workload=args.workload, seed=seed, kind=name,
                    numbers=numbers, loss_gap_by_step=by_step[name])
                low = smallest.setdefault(name, {})
                for k, v in numbers.items():
                    low[k] = min(low.get(k, float("inf")), v)
                if passes_every_limit(numbers, limits):
                    unheld.append([seed, name])
        put(workload=args.workload, kind="summary", largest_sound=largest,
            smallest_control=smallest,
            over_limit={name: {k: v / limits[k] for k, v in low.items()
                               if limits.get(k)}
                        for name, low in smallest.items()},
            unheld=unheld)
    return 1 if unheld else 0


if __name__ == "__main__":
    sys.exit(main())
