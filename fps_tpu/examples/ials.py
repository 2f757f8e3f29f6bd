"""Implicit-feedback iALS entrypoint (MovieLens-20M implicit workload).

BASELINE.json workload "Implicit-feedback iALS (MovieLens-20M)" — an
extension beyond the reference's algorithm set (SURVEY.md §6 flags it as
required-but-likely-absent upstream). Alternating sharded normal-equation
solves; see ``fps_tpu.models.ials``.
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np

from fps_tpu.examples.common import (
    apply_hot_tier,
    attach_obs,
    base_parser,
    emit,
    finish,
    make_mesh,
    make_watchdog,
    maybe_checkpointer,
    maybe_profile,
    maybe_serve,
    maybe_warm_start,
)


def main(argv=None) -> int:
    ap = base_parser("Implicit-feedback iALS on the TPU PS")
    ap.add_argument("--num-users", type=int, default=2_000)
    ap.add_argument("--num-items", type=int, default=1_000)
    ap.add_argument("--per-user", type=int, default=20)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--alpha", type=float, default=40.0)
    ap.add_argument("--reg", type=float, default=0.1)
    ap.add_argument("--topk", type=int, default=10)
    args = ap.parse_args(argv)
    args.num_data = 1  # iALS uses the shard axis only

    from fps_tpu.models.ials import (
        IALSConfig,
        IALSSolver,
        recall_at_k,
    )
    from fps_tpu.utils.datasets import synthetic_implicit, train_test_split

    if args.input:
        from fps_tpu.utils.datasets import load_movielens

        data, nu, ni = load_movielens(args.input, "20m")
        data["rating"] = np.maximum(data["rating"], 0.0)
    else:
        nu, ni = args.num_users, args.num_items
        data = synthetic_implicit(nu, ni, args.per_user, seed=args.seed)
    train, test = train_test_split(data, test_frac=0.1, seed=args.seed + 1)

    mesh = make_mesh(args)
    emit({"event": "start", "workload": "ials", "num_users": nu,
          "num_items": ni, "mesh": dict(mesh.shape)})

    solver = IALSSolver(mesh, IALSConfig(num_users=nu, num_items=ni,
                                         rank=args.rank, alpha=args.alpha,
                                         reg=args.reg))
    # --prefetch: overlapped assembly+placement of the interaction chunks
    # (the solver drives its own loop, so the knob lands on it directly —
    # same validation as the Trainer CLIs' apply_host_pipeline).
    if args.prefetch < 0:
        raise SystemExit(f"--prefetch must be >= 0, got {args.prefetch}")
    solver.prefetch = args.prefetch
    # --hot-tier: accepted-and-reported (no pull/push Trainer to tier —
    # the half-epoch normal-equation solves already read/write whole
    # factor blocks, not Zipf-skewed id streams).
    apply_hot_tier(args, None)
    solver.init(jax.random.key(args.seed))
    # iALS drives its own solver loop (no Trainer) — the recorder still
    # journals the run and catches checkpoint events via the process
    # default; epoch boundaries are emitted below.
    rec = attach_obs(args, workload="ials")
    maybe_warm_start(args, solver.store, None)
    ckpt = maybe_checkpointer(args)

    from fps_tpu.examples.common import make_epoch_source

    # iALS has no worker-local state to route for; the interaction stream
    # splits over ALL devices (the source's default worker count) and is
    # consumed twice per epoch (one pass per side).
    source = make_epoch_source(args, mesh, train)

    wd = make_watchdog(args, rec)
    with maybe_serve(args, rec):
        for epoch in range(args.epochs):
            # --profile traces the first epoch only (one epoch is
            # representative and keeps the trace small).
            cm = (maybe_profile(args) if epoch == 0
                  else contextlib.nullcontext())
            wcm = (wd.watch("epoch", epoch) if wd is not None
                   else contextlib.nullcontext())
            with cm, wcm:
                _, item_sweep = solver.epoch(lambda _e=epoch: source(_e, 1))
            # The sweep's own loss: the observed term sum c (1 - x.y)^2
            # under the tables the item sweep READ (this epoch's users,
            # the movies as it found them), summed on the device step by
            # step; no dump of both tables to the host. This read is the
            # epoch's one wait for the device.
            loss = float(np.sum(np.asarray(item_sweep["loss"]),
                                dtype=np.float64))
            emit({"event": "epoch", "epoch": epoch, "weighted_loss": loss})
            if rec is not None:
                rec.inc("driver.epochs")
                rec.event("epoch", index=epoch, weighted_loss=float(loss))
            if ckpt is not None and (epoch + 1) % args.checkpoint_every == 0:
                ckpt.save(epoch + 1, solver.store)
        if ckpt is not None:
            # iALS drives its own loop, so IT owns the durability barrier
            # the Trainer drivers provide: an async writer's last snapshot
            # must be on disk before the run reports done.
            ckpt.flush()

    r = recall_at_k(solver, test["user"][:2000], test["item"][:2000],
                    k=args.topk, exclude=(train["user"], train["item"]))
    emit({"event": "done", f"recall_at_{args.topk}": r})

    finish(args, solver.store, recorder=rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
