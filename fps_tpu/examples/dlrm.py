"""DLRM click-through entrypoint: the hybrid parameter-server job.

The embedding fields are one table on the parameter server, pulled and
pushed by row; the two MLPs are the worker logic's DENSE parameters,
replicated, their gradients summed over the workers and folded by the
trainer's dense route (``fps_tpu.core.api.DenseLogic``; Parallax's hybrid,
arXiv:1808.02621). Shapes default to the published ones for the Criteo
Kaggle log (``facebookresearch/dlrm`` ``bench/dlrm_s_criteo_kaggle.sh``);
``--field-rows`` defaults to a small layout, ``--field-rows kaggle`` to
the log's own 26 cardinalities (33,762,577 rows of 16 floats, 2.16 GB).
Rows are synthetic in the Kaggle layout (one raw token a field, 13
``log1p`` counts, a click).
"""

from __future__ import annotations

import jax
import numpy as np

from fps_tpu.examples.common import (
    apply_host_pipeline,
    attach_obs,
    base_parser,
    emit,
    finish,
    make_chunks,
    make_mesh,
    make_watchdog,
    maybe_checkpointer,
    maybe_profile,
    maybe_serve,
    maybe_warm_start,
)


def _widths(text: str) -> tuple[int, ...]:
    return tuple(int(n) for n in text.split("-"))


def main(argv=None) -> int:
    ap = base_parser("DLRM (embedding fields on the PS, MLPs on the dense "
                     "route) on the TPU PS")
    ap.add_argument("--num-examples", type=int, default=100_000)
    ap.add_argument("--field-rows", default="5000-300-40-3-1200-17",
                    help="rows of each categorical field, dash-separated, "
                         "or 'kaggle' for the Criteo Kaggle log's 26")
    ap.add_argument("--arch-sparse-feature-size", type=int, default=16)
    ap.add_argument("--arch-mlp-bot", default="13-512-256-64-16",
                    help="numeric inputs, then each bottom layer's width")
    ap.add_argument("--arch-mlp-top", default="512-256-1",
                    help="each top layer's width (its input is what the "
                         "dot interaction leaves)")
    ap.add_argument("--learning-rate", type=float, default=0.1)
    args = ap.parse_args(argv)

    from fps_tpu.models.dlrm import DLRMConfig, dlrm, predict_proba_host
    from fps_tpu.utils.datasets import (
        CRITEO_KAGGLE_FIELD_ROWS,
        synthetic_click_fields,
        train_test_split,
    )

    if args.input:
        raise SystemExit("--input: no loader for the raw Kaggle TSV's "
                         "unhashed tokens yet; rows are synthetic")
    rows = (CRITEO_KAGGLE_FIELD_ROWS if args.field_rows == "kaggle"
            else _widths(args.field_rows))
    bot = _widths(args.arch_mlp_bot)
    cfg = DLRMConfig(field_rows=rows,
                     embed_dim=args.arch_sparse_feature_size,
                     numeric=bot[0], bottom_mlp=bot[1:],
                     top_mlp=_widths(args.arch_mlp_top),
                     learning_rate=args.learning_rate)
    data = synthetic_click_fields(args.num_examples, rows,
                                  numeric=cfg.numeric, seed=args.seed)
    train, test = train_test_split(data, test_frac=0.1, seed=args.seed + 1)

    mesh = make_mesh(args)
    emit({"event": "start", "workload": "dlrm", "rows": cfg.num_rows,
          "mesh": dict(mesh.shape)})
    # The dense route runs under none of the tier, SSP, guard or tap
    # modes: Trainer refuses them at construction, naming the parameters.
    if args.sync_every is not None:
        raise SystemExit("--sync-every: the dense route runs fully "
                         "synchronous only")
    trainer, store = dlrm(mesh, cfg)
    apply_host_pipeline(args, trainer)
    rec = attach_obs(args, trainer, workload="dlrm")
    tables, local_state = trainer.init_state(jax.random.key(args.seed))
    maybe_warm_start(args, store, None)

    def report(i, m):
        n = max(1.0, float(np.sum(m["n"])))
        emit({"event": "chunk", "i": i,
              "logloss": float(np.sum(m["logloss"]) / n),
              "error_rate": float(np.sum(m["mistakes"]) / n)})

    with maybe_profile(args), maybe_serve(args, rec):
        tables, local_state, _ = trainer.fit_stream(
            tables, local_state, make_chunks(args, mesh, train),
            jax.random.key(args.seed),
            checkpointer=maybe_checkpointer(args),
            checkpoint_every=args.checkpoint_every,
            on_chunk=report,
            watchdog=make_watchdog(args, rec),
        )

    p = predict_proba_host(cfg, store, test["tokens"], test["counts"])
    acc = float(np.mean((p > 0.5) == (test["label"] > 0.5)))
    emit({"event": "done", "test_accuracy": acc})
    finish(args, store, recorder=rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
