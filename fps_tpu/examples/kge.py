"""Knowledge-graph embedding entrypoint: ComplEx with AdaGrad at the server.

The KGE task of Lapse and NuPS (arXiv:2104.00501): an ``entity`` and a
``relation`` table of complex rows, trained by negative sampling, each
table folded by an optimizer of its OWN (``ServerLogic.fold``, AdaGrad):
the trainer keeps one accumulator row an id beside the table, sharded by
owner like it and saved with it, and a step reads and writes the rows it
pushed, no others. The triples stay resident on the device and every
epoch is one compiled call (``Trainer.run_indexed``); corruptions are
drawn on the device. Triples are synthetic with planted structure (an
entity's cluster decides what it links to).
"""

from __future__ import annotations

import jax
import numpy as np

from fps_tpu.examples.common import (
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
    ap = base_parser("ComplEx knowledge-graph embeddings (AdaGrad at the "
                     "server, the table's own fold) on the TPU PS")
    ap.add_argument("--num-triples", type=int, default=100_000)
    ap.add_argument("--num-entities", type=int, default=20_000)
    ap.add_argument("--num-relations", type=int, default=8)
    ap.add_argument("--rank", type=int, default=32,
                    help="complex components a row (a row holds 2 x rank)")
    ap.add_argument("--negatives", type=int, default=10)
    ap.add_argument("--learning-rate", type=float, default=0.1)
    args = ap.parse_args(argv)

    from fps_tpu import DeviceDataset, DeviceEpochPlan, num_workers_of
    from fps_tpu.models.kge import KGEConfig, kge, score_host
    from fps_tpu.utils.datasets import synthetic_triples, train_test_split

    if args.input:
        raise SystemExit("--input: no loader for a triple file yet; triples "
                         "are synthetic")
    # A table's own fold lands every push in the step that made it:
    # Trainer refuses the other modes at construction, naming the table.
    if args.sync_every is not None:
        raise SystemExit("--sync-every: a table's own fold runs fully "
                         "synchronous only")
    cfg = KGEConfig(num_entities=args.num_entities,
                    num_relations=args.num_relations, rank=args.rank,
                    negatives=args.negatives,
                    learning_rate=args.learning_rate)
    data = synthetic_triples(args.num_triples, cfg.num_entities,
                             cfg.num_relations, seed=args.seed)
    train, test = train_test_split(data, test_frac=0.05, seed=args.seed + 1)

    mesh = make_mesh(args)
    emit({"event": "start", "workload": "kge", "entities": cfg.num_entities,
          "row_floats": cfg.dim, "mesh": dict(mesh.shape)})
    trainer, store = kge(mesh, cfg)
    rec = attach_obs(args, trainer, workload="kge")
    tables, local_state = trainer.init_state(jax.random.key(args.seed))
    maybe_warm_start(args, store, None)
    plan = DeviceEpochPlan(DeviceDataset(mesh, train),
                           num_workers=num_workers_of(mesh),
                           local_batch=args.local_batch, seed=args.seed)

    def report(i, m):
        n = max(1.0, float(np.sum(m["n"])))
        emit({"event": "chunk", "i": i, "loss": float(np.sum(m["loss"]) / n),
              "triples": n})

    with maybe_profile(args), maybe_serve(args, rec):
        tables, local_state, _ = trainer.run_indexed(
            tables, local_state, plan, jax.random.key(args.seed),
            epochs=args.epochs, on_epoch=report,
            checkpointer=maybe_checkpointer(args),
            checkpoint_every=1 if args.checkpoint_every > 0 else 0,
            watchdog=make_watchdog(args, rec),
        )

    # Held-out triples against one corruption of their object each.
    rng = np.random.default_rng(args.seed + 2)
    wrong = rng.integers(0, cfg.num_entities, len(test["o"]))
    ahead = (score_host(store, test["s"], test["r"], test["o"])
             > score_host(store, test["s"], test["r"], wrong))
    emit({"event": "done", "pairwise_accuracy": float(np.mean(ahead))})
    finish(args, store, recorder=rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
