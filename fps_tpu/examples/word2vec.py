"""word2vec SGNS entrypoint (text8-style token stream).

The analog of the reference's word2vec example job (SURVEY.md §2 #10;
BASELINE.json workload "word2vec skip-gram negative sampling (text8)").
Reports words/sec alongside the training loss — the BASELINE.json headline
unit for this workload — and prints nearest neighbors of a few frequent
words at the end as a qualitative check.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from fps_tpu.examples.common import (
    apply_host_pipeline,
    apply_hot_tier,
    attach_obs,
    base_parser,
    emit,
    finish,
    make_guard,
    make_mesh,
    make_rollback,
    make_watchdog,
    maybe_checkpointer,
    maybe_profile,
    maybe_serve,
    maybe_warm_start,
)


def main(argv=None) -> int:
    ap = base_parser("word2vec SGNS on the TPU PS")
    ap.add_argument("--vocab-size", type=int, default=50_000)
    ap.add_argument("--num-tokens", type=int, default=None,
                    help="truncate the corpus to this many tokens; sizes "
                         "the synthetic stream when no --input is given "
                         "(default: whole file / 2M synthetic)")
    ap.add_argument("--dim", type=int, default=100)
    ap.add_argument("--window", type=int, default=5)
    ap.add_argument("--negatives", type=int, default=5)
    ap.add_argument("--learning-rate", type=float, default=0.025)
    ap.add_argument("--sketch-words", type=int, default=0,
                    help="track the P most frequent words' co-occurrence "
                         "similarity with a tug-of-war sketch riding the "
                         "training loop (pair AND fused block paths; "
                         "0 = off)")
    args = ap.parse_args(argv)

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.word2vec import (
        W2VConfig,
        Word2VecDevicePlan,
        accumulate_sketch_taps,
        cooccurrence_sketch_tap,
        nearest_neighbors,
        sketch_similarity,
        skipgram_chunks,
        word2vec,
        word2vec_block,
    )
    from fps_tpu.utils.datasets import load_text8

    tokens, vocab, uni = load_text8(args.input, args.vocab_size,
                                    args.num_tokens, seed=args.seed)
    mesh = make_mesh(args)
    W = num_workers_of(mesh)
    emit({"event": "start", "workload": "word2vec", "vocab_size": vocab,
          "tokens": len(tokens), "mesh": dict(mesh.shape)})

    cfg = W2VConfig(vocab_size=vocab, dim=args.dim, window=args.window,
                    negatives=args.negatives, learning_rate=args.learning_rate)

    sketch_probe = None
    step_tap = None
    if args.sketch_words > 0:
        # Rides BOTH paths: the pair batches directly, and the fused block
        # path via id-only pair-stream reconstruction from the raw block
        # batch (models.word2vec.block_pair_stream).
        from fps_tpu.sketch import TugOfWarSpec

        sketch_probe = np.argsort(-uni)[: args.sketch_words].astype(np.int32)
        step_tap = cooccurrence_sketch_tap(
            TugOfWarSpec(depth=5, width=1024, seed=args.seed),
            sketch_probe,
        )

    block_len = max(64, args.local_batch // (2 * cfg.window))
    if args.ingest == "device":
        # Block-granularity worker: one pull/push row per block position
        # (~10x fewer sparse row transactions than per-pair pull/push).
        trainer, store = word2vec_block(
            mesh, cfg, uni, block_len, sync_every=args.sync_every,
            max_steps_per_call=256, step_tap=step_tap,
            guard=make_guard(args),
        )
    else:
        trainer, store = word2vec(mesh, cfg, uni, sync_every=args.sync_every,
                                  max_steps_per_call=256, step_tap=step_tap,
                                  guard=make_guard(args))
    apply_hot_tier(args, trainer)
    apply_host_pipeline(args, trainer)
    rec = attach_obs(args, trainer, workload="word2vec")
    tables, local_state = trainer.init_state(jax.random.key(args.seed))
    maybe_warm_start(args, store, None)

    total_pairs = 0.0
    sketch_sum = None

    def report(i, m):
        nonlocal total_pairs, sketch_sum
        n = max(1.0, float(np.sum(m["n"])))
        total_pairs += n
        if sketch_probe is not None and "tap" in m:
            part = accumulate_sketch_taps([m])
            sketch_sum = part if sketch_sum is None else sketch_sum + part
        emit({"event": "chunk", "i": i,
              "sgns_loss": float(np.sum(m["loss"]) / n)})

    t0 = time.perf_counter()
    with maybe_profile(args), maybe_serve(args, rec):
        if args.ingest == "device":
            # Fused path: tokens resident on device, subsampling/compaction
            # and pair generation inside the compiled epoch.
            from fps_tpu import DeviceDataset

            plan = Word2VecDevicePlan(
                DeviceDataset(mesh, {"token": np.asarray(tokens, np.int32)}),
                uni, cfg, mesh, num_workers=W, block_len=block_len,
                seed=args.seed, sync_every=args.sync_every, mode="block",
            )
            tables, local_state, _ = trainer.run_indexed(
                tables, local_state, plan, jax.random.key(args.seed),
                epochs=args.epochs, on_epoch=report,
                checkpointer=maybe_checkpointer(args),
                # --checkpoint-every counts chunks on the host path; the
                # fused path snapshots per epoch when it is enabled at all.
                checkpoint_every=1 if args.checkpoint_every > 0 else 0,
                rollback=make_rollback(args),
                watchdog=make_watchdog(args, rec),
            )
        else:
            def all_epochs():
                for epoch in range(args.epochs):
                    yield from skipgram_chunks(
                        tokens, uni, cfg, num_workers=W,
                        local_batch=args.local_batch,
                        steps_per_chunk=args.steps_per_chunk,
                        sync_every=args.sync_every, seed=args.seed + epoch,
                    )

            tables, local_state, _ = trainer.fit_stream(
                tables, local_state, all_epochs(), jax.random.key(args.seed),
                checkpointer=maybe_checkpointer(args),
                checkpoint_every=args.checkpoint_every,
                on_chunk=report,
                rollback=make_rollback(args),
                watchdog=make_watchdog(args, rec),
            )
    dt = time.perf_counter() - t0
    emit({"event": "done", "pairs_per_sec": total_pairs / max(dt, 1e-9),
          "words_per_sec": args.epochs * len(tokens) / max(dt, 1e-9),
          "seconds": dt})

    if sketch_sum is not None:
        sims = sketch_similarity(sketch_sum)
        emit({"event": "cooccurrence_similarity",
              "probe_words": sketch_probe,
              "inner_products": np.round(sims, 1)})

    # Qualitative: neighbors of a few frequent words (ids 1..4; 0 may be UNK).
    probes = np.arange(1, 5)
    nn_ids, nn_sims = nearest_neighbors(store, probes, k=5)
    for p, row_i, row_s in zip(probes, nn_ids, nn_sims):
        emit({"event": "neighbors", "word": int(p), "nearest": row_i,
              "sims": np.round(row_s, 3)})

    finish(args, store, recorder=rec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
