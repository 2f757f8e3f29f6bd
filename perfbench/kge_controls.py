"""The readings that hold ``kge-wikidata5m``'s limits, at the cell's own
size, in one process (``dlrm_controls.py``'s method).

    python3 perfbench/kge_controls.py --workload kge-wikidata5m.epochs --seeds 1,2,... [--control-seeds 1,2] [--controls bf16,drop_state]

For every seed: the cell's data and system as a run builds them, the
first call of the timed entry from the seeded tables, the plain reference
over the same call, and the numbers ``check.compare`` gives (the SOUND
readings). For every control seed also each control: the reference put in
the program's place with ONE thing wrong, compared the same way against
the sound reference:

* ``bf16``: tables, optimizer state and arithmetic in bfloat16;
* ``drop_state``: the fold's state dropped: plain SGD, ``theta -= lr g``,
  the accumulators never written.

Each must fail a limit of the configuration, or a program that computed
that would still be ``correct``. And for every control seed the
configuration's float32 FLOOR, which must PASS every limit:

* ``reversed``: the reference itself on each step's positives in the
  opposite order: the same sums, their addends in another order. A sound
  program differs from the reference by as much, so a limit this reading
  breaks refuses sound programs (from a ZERO accumulator it reads as the
  program does: 0.2 - 0.5 on the rows' ``table_gap``, percents on the
  accumulators' hottest coordinates one seed in ten or twenty: PERF.md,
  PR 51).

One JSON line per reading on stdout and
in ``chiprun_out/kge_controls.<workload>.jsonl``; the last line gives, per
number, the largest sound reading and, per control, the smallest reading
(per floor the largest),
its factor over the limit, and ``unheld``: every (seed, control) that
passed EVERY limit and every (seed, floor) that did not. Exits 1 if there
is one. The sound reference's tables
wait on the HOST while a control runs: two more copies of 3.2 GB of rows
and state beside a control's own do not fit the device. Needs no measured
window; the benchmark's own runs never call this.
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

from perfbench.dlrm_controls import passes_every_limit  # noqa: E402

CONTROLS = {"bf16": {}, "drop_state": {"control": "drop_state"}}
FLOORS = {"reversed": {"control": "reversed"}}


def readings(loaded: dict, seed: int, controls) -> tuple[dict, dict]:
    """``(sound numbers, {control: numbers})`` of one seed."""
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
    ref = {k: np.asarray(v) for k, v in ref.items()}
    out = {}
    for name in controls:
        wrong = copy.deepcopy(cfg)
        wrong["model"].update({**CONTROLS, **FLOORS}[name])
        tables, loss, n, feed = check.run_reference(
            system, wrong, init,
            dtype=jnp.bfloat16 if name == "bf16" else None)
        out[name] = check.compare(
            {k: np.asarray(v, np.float32) for k, v in tables.items()}, ref,
            init, loss, n, ref_loss, ref_n, feed,
            check.call_checksum(system, data_sum), system.examples_per_call)
        del tables
    del system, ref
    gc.collect()
    jax.clear_caches()
    return sound, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default=",".join({**CONTROLS, **FLOORS}))
    args = ap.parse_args(argv)

    from perfbench.lib import spec

    bench = spec.load_benchmark()
    spec.validate(bench)
    loaded = spec.load_cell(bench, args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != loaded["cell"]["chips"]:
        print(f"kge_controls: cell {args.workload} needs "
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
    with open(os.path.join(out_dir, f"kge_controls.{args.workload}.jsonl"),
              "a") as f:
        def put(**row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for seed in sorted(set(seeds) | control_seeds):
            t0 = time.perf_counter()
            sound, wrong = readings(
                loaded, seed, controls if seed in control_seeds else [])
            put(workload=args.workload, seed=seed, kind="sound",
                numbers=sound, within=passes_every_limit(sound, limits),
                seconds=time.perf_counter() - t0)
            for k, v in sound.items():
                largest[k] = max(largest.get(k, 0.0), v)
            for name, numbers in wrong.items():
                put(workload=args.workload, seed=seed, kind=name,
                    numbers=numbers)
                # A control's smallest reading, a floor's largest.
                keep = max if name in FLOORS else min
                low = smallest.setdefault(name, {})
                for k, v in numbers.items():
                    low[k] = keep(low.get(k, v), v)
                if passes_every_limit(numbers, limits) != (name in FLOORS):
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
