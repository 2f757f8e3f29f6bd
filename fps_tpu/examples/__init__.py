"""Per-algorithm CLI entrypoints (the reference's L5 example-job layer).

The reference ships a ``main()`` per algorithm that parses CLI args (input
path, parallelism, learning rate, rank, ...) and wires the pipeline
(SURVEY.md §1 L5; upstream these are ``*Example`` objects next to each
algorithm, launched with ``flink run``). Here each module is runnable as

    python -m fps_tpu.examples.mf --epochs 2 --rank 10 ...
    python -m fps_tpu.examples.passive_aggressive --variant PA-I ...
    python -m fps_tpu.examples.word2vec --dim 100 --negatives 5 ...
    python -m fps_tpu.examples.logreg_ssp --sync-every 8 ...
    python -m fps_tpu.examples.ials --rank 16 --alpha 40 ...
    python -m fps_tpu.examples.kge --rank 32 --negatives 10 ...

Every entrypoint falls back to a synthetic dataset with matched statistics
when no input path is given (this environment has no network egress), prints
per-chunk metrics as JSON lines (the reference's ``WOut`` observability
stream), and can export the final model (the reference's close()-time
``(id, param)`` stream) with ``--export model.npz``.
"""
