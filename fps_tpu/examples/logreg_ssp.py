"""Bounded-staleness logistic regression entrypoint (Criteo CTR style).

The "async bounded-staleness SGD, multi-worker data-parallel" workload from
BASELINE.json's configs — the canonical SSP exerciser. ``--sync-every s``
bounds how stale a worker's parameter snapshot may get (the framework analog
of the reference's free-running asynchrony + pull limiter; SURVEY.md §2.2).
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
    make_chunks,
    make_rollback,
    make_watchdog,
    maybe_profile,
    emit,
    finish,
    make_mesh,
    maybe_checkpointer,
    maybe_serve,
    maybe_warm_start,
)
from fps_tpu.utils.datasets import CRITEO_NUM_FEATURES


def main(argv=None) -> int:
    ap = base_parser("SSP logistic regression on the TPU PS")
    ap.add_argument("--num-features", type=int, default=CRITEO_NUM_FEATURES,
                    help="hashed feature space size (default: the Criteo "
                         "loader's own, 1,000,000)")
    ap.add_argument("--num-examples", type=int, default=100_000)
    ap.add_argument("--nnz", type=int, default=32)
    ap.add_argument("--learning-rate", type=float, default=0.1)
    ap.add_argument("--l2", type=float, default=0.0)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adagrad"],
                    help="adagrad keeps per-coordinate state in the sharded "
                         "table — strongly recommended with --input files "
                         "whose dense columns (e.g. Criteo numerics) make "
                         "plain SGD oscillate under SSP staleness")
    ap.add_argument("--input-format", default="auto",
                    choices=["auto", "svmlight", "criteo"],
                    help="--input file format (Criteo TSV or RCV1 svmlight)")
    ap.add_argument("--nnz-cap", type=int, default=None,
                    help="svmlight rows keep at most this many features "
                         "(default: the file's max row length)")
    args = ap.parse_args(argv)
    if args.sync_every is None:
        args.sync_every = 8  # this entrypoint exists to exercise SSP

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.logistic_regression import (
        LogRegConfig,
        logistic_regression,
        predict_proba_host,
    )
    from fps_tpu.utils.datasets import (
        load_sparse,
        sniff_sparse_format,
        synthetic_sparse_classification,
        train_test_split,
    )

    dense = 0
    if args.input:
        # Real dataset (Criteo TSV with hashed categoricals, or svmlight).
        fmt = args.input_format
        if fmt == "auto":
            fmt = sniff_sparse_format(args.input)
        data, args.num_features = load_sparse(
            args.input, fmt=fmt,
            num_features=args.num_features if fmt == "criteo" else None,
            nnz_cap=args.nnz_cap,
        )
        if fmt == "criteo":
            # The Criteo loader's fixed-slot layout (numeric column j at
            # slot j) lets the worker handle those 13 weights densely —
            # one static pull + one combined push per step instead of 13
            # scatter rows per example (LogRegConfig.dense_features).
            dense = 13
    else:
        data = synthetic_sparse_classification(
            args.num_examples, args.num_features, args.nnz, seed=args.seed
        )
    data["label"] = (data["label"] > 0).astype(np.float32)  # {0,1}
    train, test = train_test_split(data, test_frac=0.1, seed=args.seed + 1)

    mesh = make_mesh(args)
    W = num_workers_of(mesh)
    emit({"event": "start", "workload": "logreg_ssp",
          "sync_every": args.sync_every, "mesh": dict(mesh.shape)})

    cfg = LogRegConfig(num_features=args.num_features,
                       learning_rate=args.learning_rate, l2=args.l2,
                       optimizer=args.optimizer, dense_features=dense)
    trainer, store = logistic_regression(
        mesh, cfg, sync_every=args.sync_every, guard=make_guard(args))
    apply_hot_tier(args, trainer)
    apply_host_pipeline(args, trainer)
    rec = attach_obs(args, trainer, workload="logreg_ssp")
    tables, local_state = trainer.init_state(jax.random.key(args.seed))
    maybe_warm_start(args, store, None)

    chunks = make_chunks(args, mesh, train)
    def report(i, m):
        n = max(1.0, float(np.sum(m["n"])))
        emit({"event": "chunk", "i": i,
              "logloss": float(np.sum(m["logloss"]) / n),
              "error_rate": float(np.sum(m["mistakes"]) / n)})

    with maybe_profile(args), maybe_serve(args, rec):
        tables, local_state, _ = trainer.fit_stream(
            tables, local_state, chunks, jax.random.key(args.seed),
            checkpointer=maybe_checkpointer(args),
            checkpoint_every=args.checkpoint_every,
            on_chunk=report,
            rollback=make_rollback(args),
            watchdog=make_watchdog(args, rec),
        )

    p = predict_proba_host(store, test["feat_ids"], test["feat_vals"])
    acc = float(np.mean((p > 0.5) == (test["label"] > 0.5)))
    emit({"event": "done", "test_accuracy": acc})
    finish(args, store, recorder=rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
