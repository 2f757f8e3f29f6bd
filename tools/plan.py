"""Print an auto-tiering plan and its predicted collective-byte budget.

The CLI face of ``fps_tpu.tiering.planner`` (docs/performance.md
"Adaptive tiering"): given per-table geometries and an id-density
estimate — a synthetic Zipf profile (``--alpha``) or measured counts
from an ``.npz`` (``--counts``, arrays keyed by table name; e.g. the
per-id estimates a tracker sidecar's decayed sketch yields) — run
:func:`plan_tables` and print the per-table decision rows
(``hot_tier`` / ``hot_sync_every`` / dense route, with the planner's
reason strings).

Unless ``--no-lower``, the tool then LOWERS the plan: a generic
pull/push probe workload (:mod:`fps_tpu.tiering.probe`) is built over
the planned table specs on the 8-device CPU mesh, the exact per-chunk
program the driver would dispatch is lowered, and
``fps_tpu.analysis.collective_profile`` measures its collective count
and payload bytes — the predicted budget is a MEASURED program, not a
cost model. The untiered baseline program is profiled alongside so the
plan's collective savings are visible in one output.

Usage:
  python tools/plan.py --table item_factors:4096:16 --table users:100000:16 \
      [--alpha 1.2 | --counts COUNTS.npz] [--batch-rows 1024] \
      [--coverage 0.9] [--replica-budget-mb 64] [--max-sync-every 8] \
      [--shards 8] [--no-lower] [--json]

Like bench/audit_programs, re-execs itself into a cleaned 8-CPU-device
environment when lowering is requested and the current process cannot
see 8 devices.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def _parse_table(s: str):
    parts = s.split(":")
    if len(parts) != 3:
        raise argparse.ArgumentTypeError(
            f"--table wants name:num_ids:dim, got {s!r}")
    return parts[0], int(parts[1]), int(parts[2])


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="auto-tiering planner CLI (fps_tpu.tiering)")
    ap.add_argument("--table", action="append", required=True,
                    type=_parse_table, metavar="NAME:NUM_IDS:DIM",
                    help="one parameter table's geometry (repeatable)")
    ap.add_argument("--alpha", type=float, default=1.2,
                    help="synthetic Zipf skew for the density estimate "
                         "(ignored with --counts)")
    ap.add_argument("--counts", default=None, metavar="NPZ",
                    help="measured per-id counts, one array per table "
                         "name (overrides --alpha)")
    ap.add_argument("--batch-rows", type=int, default=1024,
                    help="pulled rows per step per table (the planner's "
                         "traffic unit)")
    ap.add_argument("--coverage", type=float, default=0.9,
                    help="traffic fraction a partial head must cover")
    ap.add_argument("--replica-budget-mb", type=float, default=64.0,
                    help="per-device replica memory budget per table")
    ap.add_argument("--max-sync-every", type=int, default=8,
                    help="reconcile-cadence ceiling (staleness bound)")
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--workers", type=int, default=8,
                    help="total worker devices (sizes the per-worker "
                         "compacted cold lane, planner.choose_cold_budget)")
    ap.add_argument("--no-lower", action="store_true",
                    help="plan only — skip lowering the probe program "
                         "(no jax devices needed)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output only")
    return ap


def _reexec_if_needed() -> None:
    spec = importlib.util.spec_from_file_location(
        "_fps_hostenv", os.path.join(_ROOT, "fps_tpu", "utils",
                                     "hostenv.py"))
    hostenv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hostenv)
    if hostenv.in_reexec():
        return
    env = hostenv.cpu_mesh_env(8)
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if not args.no_lower and argv is None:
        # Only the real CLI re-execs (importers own their device mesh).
        _reexec_if_needed()

    import numpy as np

    from fps_tpu.tiering.planner import TableDensity, plan_tables

    counts_by_name = {}
    if args.counts:
        with np.load(args.counts) as z:
            counts_by_name = {k: z[k].copy() for k in z.files}
    densities = []
    for name, num_ids, dim in args.table:
        if name in counts_by_name:
            c = np.asarray(counts_by_name[name], np.float64)
            if c.shape != (num_ids,):
                raise SystemExit(
                    f"--counts[{name}] shape {c.shape} != ({num_ids},)")
        else:
            c = 1.0 / np.arange(1, num_ids + 1) ** args.alpha
        densities.append(TableDensity(name, num_ids, dim, c))
    plans = plan_tables(
        densities,
        batch_rows_per_step=args.batch_rows,
        replica_budget_bytes=int(args.replica_budget_mb * (1 << 20)),
        coverage_target=args.coverage,
        max_sync_every=args.max_sync_every,
        num_shards=args.shards,
        num_workers=args.workers,
    )

    from fps_tpu.tiering.planner import global_sync_every

    out = {"plan": {n: p.to_json() for n, p in sorted(plans.items())},
           "hot_sync_every": global_sync_every(plans)}
    if not args.json:
        for name, p in sorted(plans.items()):
            print(f"{name}: hot_tier={p.hot_tier} "
                  f"hot_sync_every={p.hot_sync_every} dense={p.dense} "
                  f"cold_budget={p.cold_budget} "
                  f"coverage={p.coverage:.3f}\n    [{p.reason}]",
                  file=sys.stderr)

    if not args.no_lower:
        import jax

        from fps_tpu.analysis import collective_profile
        from fps_tpu.core.store import TableSpec
        from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
        from fps_tpu.tiering.probe import lowered_plan_text

        devs = jax.devices()
        nd, ns = default_mesh_shape(min(len(devs), 8))
        mesh = make_ps_mesh(num_shards=ns, num_data=nd,
                            devices=devs[:nd * ns])
        specs = {name: TableSpec(name, num_ids, dim)
                 for name, num_ids, dim in args.table}

        def profile(plans_arg, E):
            text = lowered_plan_text(mesh, specs, plans_arg,
                                     hot_sync_every=E)
            prof = collective_profile(text)
            return {"collectives": len(prof),
                    "bytes": sum(c.payload_bytes for c in prof)}

        out["predicted"] = profile(plans, global_sync_every(plans))
        out["untiered_baseline"] = profile({}, 1)
        out["mesh"] = dict(mesh.shape)
        if not args.json:
            print(f"predicted per-chunk collective budget: "
                  f"{out['predicted']['collectives']} collectives, "
                  f"{out['predicted']['bytes']} bytes "
                  f"(untiered baseline: "
                  f"{out['untiered_baseline']['collectives']} / "
                  f"{out['untiered_baseline']['bytes']})",
                  file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
