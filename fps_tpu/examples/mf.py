"""Online matrix factorization entrypoint (MovieLens-style ratings).

The analog of the reference's MF example job (upstream a ``main`` next to
``PSOnlineMatrixFactorization``, SURVEY.md §3.3): parse CLI args, build the
pipeline, train, emit metrics and the final model. ``--topk K`` additionally
prints top-K recommendations for a few users — the reference's
``...AndTopK`` variant.
"""

from __future__ import annotations

import jax
import numpy as np

from fps_tpu.examples.common import (
    apply_host_pipeline,
    apply_hot_tier,
    attach_obs,
    base_parser,
    make_guard,
    emit,
    finish,
    make_chunks,
    make_rollback,
    make_watchdog,
    make_mesh,
    maybe_checkpointer,
    maybe_profile,
    maybe_serve,
    maybe_warm_start,
)


def main(argv=None) -> int:
    ap = base_parser("Online MF (SGD) on the TPU parameter server")
    ap.add_argument("--scale", default="100k", choices=["100k", "1m", "20m"],
                    help="synthetic size when no --input is given")
    ap.add_argument("--rank", type=int, default=10)
    ap.add_argument("--learning-rate", type=float, default=0.05)
    ap.add_argument("--reg", type=float, default=0.01)
    ap.add_argument("--topk", type=int, default=0,
                    help="after training, print top-K items for sample users")
    ap.add_argument("--topk-every", type=int, default=0,
                    help="emit top-K (per worker, for users being trained) "
                         "every N steps FROM INSIDE the compiled loop — the "
                         "reference's streaming ...AndTopK shape; requires "
                         "--topk")
    ap.add_argument("--topk-queries", type=int, default=2,
                    help="with --topk-every: how many rating events of a "
                         "worker's step are answered with a list (the first "
                         "N rows of its batch; the reference answers every "
                         "event)")
    ap.add_argument("--negative-samples", type=int, default=0,
                    help="sample this many unrated items per rating as "
                         "weighted pseudo-negatives (implicit feedback)")
    ap.add_argument("--negative-weight", type=float, default=0.5)
    args = ap.parse_args(argv)
    if args.topk_every and not args.topk:
        raise SystemExit("--topk-every requires --topk")
    if args.topk_every and not 1 <= args.topk_queries <= args.local_batch:
        raise SystemExit("--topk-queries must lie between 1 and "
                         "--local-batch")

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.matrix_factorization import (
        MFConfig,
        online_mf,
        predict_host,
        rmse,
    )
    from fps_tpu.utils.datasets import load_movielens, train_test_split

    data, nu, ni = load_movielens(args.input, args.scale)
    train, test = train_test_split(data, test_frac=0.1, seed=args.seed + 1)
    mesh = make_mesh(args)
    W = num_workers_of(mesh)
    emit({"event": "start", "workload": "mf", "num_users": nu, "num_items": ni,
          "num_ratings": len(data["user"]), "mesh": dict(mesh.shape)})

    cfg = MFConfig(num_users=nu, num_items=ni, rank=args.rank,
                   learning_rate=args.learning_rate, reg=args.reg,
                   negative_samples=args.negative_samples,
                   negative_weight=args.negative_weight)
    trainer, store = online_mf(mesh, cfg, sync_every=args.sync_every,
                               guard=make_guard(args))
    if args.topk_every:
        import dataclasses

        from fps_tpu.models.recommendation import (
            make_online_topk_tap,
            mf_topk_query_fn,
        )

        trainer.config = dataclasses.replace(
            trainer.config,
            step_tap=make_online_topk_tap(
                store, "item_factors", args.topk, every=args.topk_every,
                query_fn=mf_topk_query_fn(W, num_queries=args.topk_queries),
            ),
        )
    apply_hot_tier(args, trainer)
    apply_host_pipeline(args, trainer)
    rec = attach_obs(args, trainer, workload="mf")
    tables, local_state = trainer.init_state(jax.random.key(args.seed))
    maybe_warm_start(args, store, None)

    chunks = make_chunks(args, mesh, train, route_key="user")

    def report(i, m):
        se, n = np.sum(m["se"]), max(1.0, np.sum(m["n"]))
        emit({"event": "chunk", "i": i, "train_rmse": float(np.sqrt(se / n)),
              "examples": float(n)})
        if "tap" in m:
            # Streaming AndTopK records: one event per emission step.
            users = np.asarray(m["tap"]["topk_query"])  # (T, W, q)
            items = np.asarray(m["tap"]["topk_ids"])  # (T, W, q, k)
            for t in np.flatnonzero((users >= 0).any(axis=(1, 2))):
                emit({"event": "topk_online", "chunk": i, "step": int(t),
                      "users": users[t].reshape(-1),
                      "items": items[t].reshape(users[t].size, -1)})

    with maybe_profile(args), maybe_serve(args, rec):
        tables, local_state, _ = trainer.fit_stream(
            tables, local_state, chunks, jax.random.key(args.seed),
            checkpointer=maybe_checkpointer(args),
            checkpoint_every=args.checkpoint_every,
            on_chunk=report,
            rollback=make_rollback(args),
            watchdog=make_watchdog(args, rec),
        )

    uf = np.asarray(local_state)
    pred = predict_host(store, uf, W, test["user"], test["item"])
    emit({"event": "done", "test_rmse": rmse(pred, test["rating"])})

    if args.topk:
        from fps_tpu.models.recommendation import mf_user_vectors, recommend_topk

        users = np.unique(test["user"])[:8]
        q = mf_user_vectors(uf, W, users)
        ids, scores = recommend_topk(store, "item_factors", q, args.topk)
        for u, row_i, row_s in zip(users, ids, scores):
            emit({"event": "topk", "user": int(u), "items": row_i,
                  "scores": np.round(row_s, 4)})

    finish(args, store, recorder=rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
