"""On-chip smoke: the training path, end to end, on the TPU that is here.

    python chip_smoke.py

One process drives every chip JAX reports (one chip belongs to one
process; nothing here starts a child). It refuses any platform other than
``tpu`` — there is no CPU mode — then runs, at the full width of the
repo's headline configurations and through the package-root API a user
calls:

* **mf** — online MF at ML-20M width (138,493 x 26,744, rank 10,
  20,000,263 synthetic ratings from a seed), two ``run_indexed`` epochs
  over a ``make_ps_mesh()`` of every device; the compiled epoch program
  is certified by the repo's own auditor (donation, no host transfer) and,
  on more than one device, must hold cross-shard collectives and tables
  sharded over every device.
* **pa** — binary PA-I at RCV1 width (47,236 features, 64 nnz, 800,000
  docs), the one headline that stands on Pallas under the default
  ``auto`` backend: one epoch whose Pallas routes are read from the
  program's own route log (``fps_tpu.ops.routes_traced``; they must have
  been traced COMPILED on a TPU), then the same epoch under
  ``ops.set_backend("xla")``, and the two results compared.
* **kernels** — both Pallas kernels compiled and run once at their
  production shapes against exact float64 host references.

Any stage that fails raises, which ends the run non-zero with a
traceback and no result line. On success stdout holds two JSON lines: the
full report (``{"report": {"versions": ..., "stages": ..., ...}}``:
per-stage status, kernels traced, set-up timings), then, as the LAST line,
the verdict and nothing else::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Progress goes to stderr. Times in the
report are set-up facts of this run (compile included where said), not
benchmark rates.

Tolerances (the documented hi+lo bf16 contract,
``fps_tpu/ops/pallas_kernels.py``): the dim-1 kernels carry each f32
as a truncated-bf16 ``hi`` plus a round-to-nearest bf16 ``lo`` of the exact
remainder, so every value read or pushed is off by at most
``2**-16`` relative (``|x - hi| < 2**-7 |x|``, and ``lo`` rounds that to 8
significant bits); sums accumulate in f32.

* kernel vs reference, per output element: ``PAIR_EPS = 2**-15`` (one
  ``2**-16`` for the split, one for f32 accumulation order) times the
  sum of ``|terms|`` that element accumulates.
* PA pallas-vs-xla weights after ``T`` steps: each step does one kernel
  read and one kernel push of every touched weight, and PA-I's step
  ``tau = min(C, loss/|x|^2)`` is continuous in the weights, so the two
  runs drift apart at most linearly: ``max|dw| <= 2 T 2**-16 max|w|``.
  The mistake count is NOT continuous (a sign flips when a margin crosses
  zero); only examples whose margin lies within the weight drift of zero
  can flip, a band ~``sqrt(nnz)`` weight-drifts wide against a margin
  spread of the same ``sqrt(nnz)`` scale, so the mistake RATE gets ten
  weight tolerances of slack.

The body is importable: :func:`run_smoke` takes a mesh and a
:class:`Sizes`, and tests/test_chip_smoke.py drives it tiny on the
8-virtual-device CPU mesh (kernels interpreted under the forced
``pallas`` backend). The TPU check lives in :func:`main` alone.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import numpy as np

PAIR_EPS = 2.0 ** -15

_KERNELS = ("gather_rows_dim1_pallas", "scatter_add_dim1_pallas")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs at. The defaults are the full widths; a test
    passes a tiny instance."""

    mf_scale: str = "20m"  # load_movielens(None, scale)
    mf_rank: int = 10
    mf_local_batch: int = 32768
    pa_features: int = 47_236
    pa_nnz: int = 64
    pa_examples: int = 800_000
    pa_head: int = 2048
    pa_local_batch: int = 16384
    # (kernel, table rows, table dim, ids per call): the PA table and its
    # head slice at one PA step's 2^20 ids.
    kernel_cases: tuple = (
        ("scatter_add_dim1_pallas", 47_236, 1, 1 << 20),
        ("gather_rows_dim1_pallas", 47_236, 1, 1 << 20),
        ("scatter_add_dim1_pallas", 2048, 1, 1 << 20),
        ("gather_rows_dim1_pallas", 2048, 1, 1 << 20),
    )


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def require(cond, msg: str) -> None:
    # Not ``assert``: the checks are the product and must survive ``-O``.
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def device_identity() -> dict:
    import jax
    import jaxlib

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a fact to report, not a failure
        libtpu = None
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def _on_tpu(mesh) -> bool:
    return mesh.devices.flat[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Stage: kernels against exact host references
# ---------------------------------------------------------------------------

def _kernel_case(name, R, D, B, interpret, rng):
    import jax.numpy as jnp

    from fps_tpu.ops import pallas_kernels as pk

    # Zipf-skewed ids (duplicates on the head) plus both kinds of dropped
    # id: the -1 pad sentinel and ids past the table.
    p = 1.0 / np.arange(1, R + 1) ** 0.8
    ids = rng.choice(R, B, p=p / p.sum()).astype(np.int32)
    ids[rng.random(B) < 0.02] = -1
    ids[rng.random(B) < 0.02] = R + 7
    keep = (ids >= 0) & (ids < R)
    table = rng.normal(0, 1, (R, D)).astype(np.float32)
    fn = getattr(pk, name)
    if name.startswith("gather"):
        got = np.asarray(fn(jnp.asarray(table), jnp.asarray(ids),
                            interpret=interpret))
        ref = np.where(keep[:, None], table[np.where(keep, ids, 0)], 0.0)
        bound = PAIR_EPS * np.abs(ref)
    else:
        deltas = rng.normal(0, 1, (B, D)).astype(np.float32)
        got = np.asarray(fn(jnp.asarray(table), jnp.asarray(ids),
                            jnp.asarray(deltas), interpret=interpret))
        ref = table.astype(np.float64)
        np.add.at(ref, ids[keep], deltas[keep].astype(np.float64))
        mass = np.abs(table).astype(np.float64)
        np.add.at(mass, ids[keep], np.abs(deltas[keep]).astype(np.float64))
        bound = PAIR_EPS * mass
    require(got.shape == ref.shape, f"{name} {R}x{D}: shape {got.shape}")
    require(np.isfinite(got).all(), f"{name} {R}x{D}: non-finite output")
    err = np.abs(got - ref)
    worst = float((err / np.maximum(bound, 1e-30)).max())
    require(worst <= 1.0,
            f"{name} {R}x{D} B={B}: error {worst:.3g}x its bound "
            f"(eps {PAIR_EPS:.3g}, max abs err {err.max():.3g})")
    return {"kernel": name, "rows": R, "dim": D, "ids": B,
            "interpret": interpret, "err_over_bound": round(worst, 4)}


def stage_kernels(mesh, sizes: Sizes) -> dict:
    interpret = not _on_tpu(mesh)
    rng = np.random.default_rng(7)
    cases = []
    for name, R, D, B in sizes.kernel_cases:
        t0 = time.perf_counter()
        cases.append(_kernel_case(name, R, D, B, interpret, rng))
        log(f"kernel {name} {R}x{D} B={B}: err/bound "
            f"{cases[-1]['err_over_bound']} "
            f"({time.perf_counter() - t0:.1f}s incl. compile)")
    require({c["kernel"] for c in cases} == set(_KERNELS),
            "kernel_cases must cover both kernels")
    return {"cases": cases}


# ---------------------------------------------------------------------------
# Stage: MF main path
# ---------------------------------------------------------------------------

def _check_placement(mesh, tables) -> dict:
    """Every table: shards on every device of the mesh, a row slice each."""
    from fps_tpu.parallel.mesh import SHARD_AXIS

    n_dev, n_shard = mesh.devices.size, mesh.shape[SHARD_AXIS]
    out = {}
    for name, arr in sorted(tables.items()):
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        require(len(devices) == n_dev,
                f"table {name}: shards on {len(devices)} devices, mesh has "
                f"{n_dev}")
        want_rows = arr.shape[0] // n_shard
        rows = {s.data.shape[0] for s in shards}
        require(rows == {want_rows},
                f"table {name}: shard rows {rows}, want {want_rows} "
                f"({arr.shape[0]} over {n_shard})")
        starts = {s.index[0].start or 0 for s in shards}
        require(len(starts) == n_shard,
                f"table {name}: {len(starts)} distinct row slices, want "
                f"{n_shard}")
        out[name] = {"devices": len(devices), "row_slices": len(starts),
                     "rows_per_shard": want_rows}
    in_use = []
    for d in mesh.devices.flat:
        stats = d.memory_stats()  # None where the backend reports none (CPU)
        if stats is not None:
            require(stats["bytes_in_use"] > 0,
                    f"device {d}: nothing resident after init_state")
            in_use.append(int(stats["bytes_in_use"]))
    out["bytes_in_use"] = in_use or "not reported by this backend"
    return out


def stage_mf(mesh, sizes: Sizes) -> dict:
    import jax

    from fps_tpu import DeviceDataset, DeviceEpochPlan, num_workers_of
    from fps_tpu.analysis import ProgramAuditor
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.utils.datasets import load_movielens

    t0 = time.perf_counter()
    data, nu, ni = load_movielens(None, sizes.mf_scale)
    nr = len(data["rating"])
    log(f"mf: {nu} users x {ni} items, {nr} ratings "
        f"(generated in {time.perf_counter() - t0:.1f}s)")
    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=nu, num_items=ni, rank=sizes.mf_rank,
                   learning_rate=0.1, reg=0.01)
    trainer, store = online_mf(mesh, cfg, combine="mean")
    # The repo's own compile-time certification: the epoch program is
    # lowered once more on its first call and audited (donation, host
    # transfers, dtype drift); the certificate carries its collectives.
    trainer.audit = auditor = ProgramAuditor()
    tables, local_state = trainer.init_state(jax.random.key(0))
    jax.block_until_ready((tables, local_state))
    placement = _check_placement(mesh, tables)
    dataset = DeviceDataset(mesh, data)
    plan = DeviceEpochPlan(dataset, num_workers=W,
                           local_batch=sizes.mf_local_batch,
                           route_key="user", seed=1)

    rmse, epoch_s = [], []
    for e in range(2):
        t0 = time.perf_counter()
        tables, local_state, metrics = trainer.run_indexed(
            tables, local_state, plan, jax.random.key(1), epochs=1,
            start_epoch=e)
        jax.block_until_ready((tables, local_state))
        epoch_s.append(time.perf_counter() - t0)
        m = metrics[0]  # host numpy: run_indexed read the metrics back
        require(all(np.isfinite(v).all() for v in jax.tree.leaves(m)),
                f"mf epoch {e + 1}: non-finite metrics")
        # Per-step counts are exact in f32; their sum past 2^24 is not.
        n = float(np.sum(m["n"], dtype=np.float64))
        require(n == nr, f"mf epoch {e + 1}: processed {n} != {nr} ratings")
        rmse.append(float(np.sqrt(np.sum(m["se"], dtype=np.float64) / n)))
        log(f"mf epoch {e + 1}: train RMSE {rmse[-1]:.4f} in "
            f"{epoch_s[-1]:.2f}s ({plan.steps_per_epoch} steps"
            f"{', compile included' if e == 0 else ''})")
    # Learning evidence: better than predicting 0 for every rating, and
    # still improving.
    zero_model = float(np.sqrt(np.mean(np.square(data["rating"],
                                                 dtype=np.float64))))
    require(rmse[1] < rmse[0] < zero_model,
            f"mf: want epoch-2 RMSE {rmse[1]:.4f} < epoch-1 {rmse[0]:.4f} "
            f"< zero-model {zero_model:.4f}")

    ids, rows = store.dump_model("item_factors")
    require(rows.shape == (ni, sizes.mf_rank) and len(ids) == ni,
            f"mf: dumped item table {rows.shape}, want {(ni, sizes.mf_rank)}")
    require(np.isfinite(rows).all(), "mf: non-finite item factors")

    require(len(auditor.certificates) == 1,
            f"mf: {len(auditor.certificates)} programs compiled, want the "
            "one epoch program")
    cert = auditor.certificates[0]
    require(cert.ok, f"mf: epoch program violates its contract: "
                     f"{[v.summary for v in cert.violations]}")
    if mesh.devices.size > 1:
        require(cert.collective_count > 0,
                "mf: no cross-shard collective in the epoch program on a "
                f"{dict(mesh.shape)} mesh")
    return {"mesh": dict(mesh.shape), "ratings": nr,
            "steps_per_epoch": int(plan.steps_per_epoch),
            "train_rmse": [round(r, 5) for r in rmse],
            "first_epoch_s_incl_compile": round(epoch_s[0], 2),
            "second_epoch_s": round(epoch_s[1], 2),
            "collectives": cert.per_kind(), "placement": placement}


# ---------------------------------------------------------------------------
# Stage: PA kernel path
# ---------------------------------------------------------------------------

def _pa_epoch(mesh, sizes: Sizes, data, q):
    """One PA-I epoch from zero weights under the ops backend in force;
    returns (weights (F,), mistake rate, steps, Pallas routes traced,
    seconds). The routes are the program's own log (``fps_tpu.ops.
    routes_traced``): ``(route, interpret, table rows, ids)`` for every
    Pallas call the routed epoch program holds."""
    import jax

    from fps_tpu import DeviceDataset, DeviceEpochPlan, num_workers_of, ops
    from fps_tpu.models.passive_aggressive import (
        PAConfig, passive_aggressive,
    )

    cfg = PAConfig(num_features=sizes.pa_features, variant="PA-I", C=1.0,
                   hot_features=sizes.pa_head if q else 0,
                   head_prefix_cols=q)
    trainer, store = passive_aggressive(mesh, cfg, max_steps_per_call=256)
    tables, local_state = trainer.init_state(jax.random.key(0))
    plan = DeviceEpochPlan(DeviceDataset(mesh, data),
                           num_workers=num_workers_of(mesh),
                           local_batch=sizes.pa_local_batch, seed=1)
    t0 = time.perf_counter()
    ops.clear_routes()
    tables, local_state, metrics = trainer.run_indexed(
        tables, local_state, plan, jax.random.key(1))
    jax.block_until_ready(tables)
    traced = [(r.route, r.interpret, r.rows, r.ids)
              for r in ops.routes_traced() if r.route in ops.PALLAS_ROUTES]
    secs = time.perf_counter() - t0
    m = metrics[0]
    require(all(np.isfinite(v).all() for v in jax.tree.leaves(m)),
            "pa: non-finite metrics")
    n = float(np.sum(m["n"], dtype=np.float64))
    require(n == sizes.pa_examples,
            f"pa: processed {n} != {sizes.pa_examples} examples")
    _, w = store.dump_model("weights")
    require(w.shape == (sizes.pa_features, 1) and np.isfinite(w).all(),
            f"pa: weights {w.shape} or non-finite")
    # Learning evidence: online mistake rate of the last step that held
    # examples, against chance (step 0 scores 1.0: zero weights, sign 0).
    last = np.flatnonzero(m["n"] > 0)[-1]
    last_rate = float(m["mistakes"][last] / m["n"][last])
    require(last_rate < 0.5,
            f"pa: last-step mistake rate {last_rate:.4f} is no better than "
            "chance")
    return (w[:, 0], float(np.sum(m["mistakes"], dtype=np.float64) / n),
            int(plan.steps_per_epoch), traced, secs)


def stage_pa(mesh, sizes: Sizes) -> dict:
    from fps_tpu import ops
    from fps_tpu.utils.datasets import (
        head_sort_slots, synthetic_sparse_classification,
    )

    t0 = time.perf_counter()
    data = synthetic_sparse_classification(
        sizes.pa_examples, sizes.pa_features, sizes.pa_nnz, seed=3,
        noise=0.05)
    q = 0
    if mesh.devices.size == 1:
        # Head-prefix routing is specified on one device only; wider
        # meshes take the dense collective route.
        data, q = head_sort_slots(data, sizes.pa_head)
        require(q > 0, "pa: head_sort_slots found no guaranteed head column")
    log(f"pa: {sizes.pa_examples} x {sizes.pa_nnz} nnz over "
        f"{sizes.pa_features} features, head prefix cols {q} "
        f"(generated in {time.perf_counter() - t0:.1f}s)")

    backend = ops.get_backend()
    require(backend != "xla", "pa: the kernel arm needs the auto or pallas "
                              "backend")
    w_k, mist_k, steps, traced, secs_k = _pa_epoch(mesh, sizes, data, q)
    kernels = sorted(set(traced))
    log(f"pa [{backend}]: mistake rate {mist_k:.4f}, {steps} steps in "
        f"{secs_k:.1f}s incl. compile; kernels traced: {kernels}")
    require(kernels, "pa: no Pallas kernel was traced — the run took the "
                     "XLA route")
    want_interpret = not _on_tpu(mesh)
    require(all(k[1] == want_interpret for k in kernels),
            f"pa: kernels traced with interpret != {want_interpret}: "
            f"{kernels}")
    names = {k[0] for k in kernels}
    require({"gather.dim1", "scatter_add.dim1"} <= names,
            f"pa: dim-1 routes missing from {names}")
    if q:
        head = {k[0] for k in kernels if k[2] == sizes.pa_head}
        full = {k[0] for k in kernels if k[2] != sizes.pa_head}
        require(head == {"gather.dim1_head", "scatter_add.dim1_head"}
                and {"gather.dim1", "scatter_add.dim1"} <= full,
                f"pa: want head-prefix AND full-table dim-1 routes, got "
                f"head {head} full {full}")

    ops.set_backend("xla")
    try:
        w_x, mist_x, _, traced_x, secs_x = _pa_epoch(mesh, sizes, data, q)
    finally:
        ops.set_backend(backend)
    require(not traced_x, f"pa [xla]: kernels traced: {traced_x}")
    log(f"pa [xla]: mistake rate {mist_x:.4f} in {secs_x:.1f}s incl. "
        "compile")

    w_tol = 2 * steps * 2.0 ** -16
    scale = float(np.abs(w_x).max())
    require(scale > 0, "pa: the xla run learned nothing")
    w_err = float(np.abs(w_k - w_x).max()) / scale
    require(w_err <= w_tol,
            f"pa: kernel and xla weights differ by {w_err:.3g} of max|w|, "
            f"tolerance {w_tol:.3g}")
    require(abs(mist_k - mist_x) <= 10 * w_tol,
            f"pa: mistake rates {mist_k:.5f} vs {mist_x:.5f} differ by more "
            f"than {10 * w_tol:.3g}")
    return {"mesh": dict(mesh.shape), "steps": steps, "head_prefix_cols": q,
            "kernels_traced": [list(k) for k in kernels],
            "mistake_rate": {"kernels": round(mist_k, 5),
                             "xla": round(mist_x, 5)},
            "weight_err_over_max": float(f"{w_err:.3g}"),
            "weight_tol": float(f"{w_tol:.3g}"),
            "epoch_s_incl_compile": {"kernels": round(secs_k, 2),
                                     "xla": round(secs_x, 2)}}


# ---------------------------------------------------------------------------

def run_smoke(mesh, sizes: Sizes) -> dict:
    """Run every stage on ``mesh``; returns the per-stage reports. A stage
    that fails raises — nothing is caught here."""
    stages = {}
    for name, stage in (("kernels", stage_kernels), ("mf", stage_mf),
                        ("pa", stage_pa)):
        log(f"--- stage {name} ---")
        t0 = time.perf_counter()
        stages[name] = dict(stage(mesh, sizes), status="passed",
                            wall_s=round(time.perf_counter() - t0, 1))
    return stages


def main() -> int:
    t_start = time.perf_counter()
    device = device_identity()
    log(f"device: {json.dumps(device)}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {device['platform']!r} "
              f"({device['kind']}, {device['count']} device(s)), not 'tpu' "
              "— this smoke runs on the accelerator only",
              file=sys.stderr)
        return 1

    from fps_tpu import make_ps_mesh
    from fps_tpu.utils.hostenv import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    log(f"compile cache: {cache_dir}")
    stages = run_smoke(make_ps_mesh(), Sizes())
    print(json.dumps({"report": {
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "stages": stages,
    }}), flush=True)
    # The verdict line: exactly these keys, the device as JAX reports it.
    print(json.dumps({
        "ok": True,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
