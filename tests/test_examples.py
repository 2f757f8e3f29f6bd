"""Smoke tests for the L5 CLI entrypoints (the reference's example jobs).

Each entrypoint runs in-process on a tiny synthetic workload and must emit a
"done" event with a sane quality metric — the analog of the reference's
example jobs being runnable end-to-end on the local mini-cluster.
"""

import json

import pytest


def run_main(module, argv, capsys):
    rc = module.main(argv)
    assert rc == 0
    events = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    by_event = {}
    for e in events:
        by_event.setdefault(e["event"], []).append(e)
    assert "done" in by_event, f"no done event in {events}"
    return by_event


TINY = ["--epochs", "1", "--local-batch", "32", "--steps-per-chunk", "4"]


def test_mf_entrypoint(devices8, capsys, tmp_path):
    from fps_tpu.examples import mf

    export = str(tmp_path / "mf.npz")
    ev = run_main(
        mf,
        TINY + ["--scale", "100k", "--rank", "4", "--topk", "3",
                "--export", export],
        capsys,
    )
    assert ev["done"][0]["test_rmse"] < 2.0
    assert len(ev["topk"][0]["items"]) == 3
    assert ev["export"][0]["path"] == export

    # Warm start from the exported model must load cleanly.
    ev2 = run_main(
        mf, TINY + ["--scale", "100k", "--rank", "4", "--warm-start", export],
        capsys,
    )
    assert "warm_start" in ev2


def test_pa_entrypoints(devices8, capsys):
    from fps_tpu.examples import passive_aggressive as pa

    ev = run_main(
        pa, TINY + ["--num-examples", "4000", "--num-features", "500"], capsys
    )
    assert ev["done"][0]["test_accuracy"] > 0.6

    ev = run_main(
        pa,
        TINY + ["--num-examples", "4000", "--num-features", "500",
                "--num-classes", "4"],
        capsys,
    )
    assert ev["done"][0]["test_accuracy"] > 0.4


def test_word2vec_entrypoint(devices8, capsys):
    from fps_tpu.examples import word2vec as w2v

    ev = run_main(
        w2v,
        TINY + ["--vocab-size", "200", "--num-tokens", "20000", "--dim", "16"],
        capsys,
    )
    assert ev["done"][0]["pairs_per_sec"] > 0
    assert len(ev["neighbors"]) == 4


def test_logreg_entrypoint(devices8, capsys, tmp_path):
    from fps_tpu.examples import logreg_ssp

    ckdir = tmp_path / "ck"
    ev = run_main(
        logreg_ssp,
        TINY + ["--num-examples", "4000", "--num-features", "2000",
                "--sync-every", "2", "--checkpoint-dir", str(ckdir),
                "--checkpoint-every", "2"],
        capsys,
    )
    assert ev["done"][0]["test_accuracy"] > 0.6
    # --checkpoint-dir must actually produce snapshots (incl. end-of-stream).
    snaps = sorted(ckdir.glob("ckpt_*.npz"))
    assert snaps, "no checkpoints written despite --checkpoint-dir"


def test_dlrm_entrypoint(devices8, capsys, tmp_path):
    """The hybrid job on the 8-device mesh: embedding fields on the
    servers, the MLPs on the trainer's dense route; its snapshots carry
    the dense parameters."""
    import numpy as np

    from fps_tpu.examples import dlrm

    ckdir = tmp_path / "ck"
    ev = run_main(
        dlrm,
        TINY + ["--num-examples", "30000", "--epochs", "3",
                "--arch-sparse-feature-size", "8",
                "--arch-mlp-bot", "13-32-16-8", "--arch-mlp-top", "32-16-1",
                "--checkpoint-dir", str(ckdir), "--checkpoint-every", "4"],
        capsys,
    )
    assert ev["start"][0]["rows"] == 6560
    assert ev["done"][0]["test_accuracy"] > 0.6
    losses = [c["logloss"] for c in ev["chunk"]]
    assert losses[-1] < losses[0]
    snaps = sorted(ckdir.glob("ckpt_*.npz"))
    assert snaps, "no checkpoints written despite --checkpoint-dir"
    with np.load(snaps[-1]) as z:
        assert "dense::top_w0" in z.files and "table::emb" in z.files


def test_dlrm_entrypoint_refuses_ssp(devices8):
    from fps_tpu.examples import dlrm

    with pytest.raises(SystemExit, match="sync-every"):
        dlrm.main(TINY + ["--sync-every", "4"])


def test_ials_entrypoint(devices8, capsys):
    from fps_tpu.examples import ials

    ev = run_main(
        ials,
        TINY + ["--num-users", "64", "--num-items", "48", "--per-user", "10",
                "--rank", "4", "--epochs", "2"],
        capsys,
    )
    assert ev["done"][0]["recall_at_10"] > 0.0


def test_streaming_mf_entrypoint(devices8, capsys):
    from fps_tpu.examples import streaming_mf

    # bounded source: stops by exhaustion
    ev = run_main(
        streaming_mf,
        ["--local-batch", "32", "--steps-per-chunk", "4",
         "--num-users", "60", "--num-items", "40", "--rank", "4",
         "--max-records", "20000", "--source-batch", "1024"],
        capsys,
    )
    assert ev["done"][0]["stopped_by"] == "stream_exhausted"
    assert ev["done"][0]["records_seen"] == 20000.0
    # chunk RMSE falls over the stream
    rmses = [c["train_rmse"] for c in ev["chunk"]]
    assert rmses[-1] < rmses[0]

    # unbounded source: stops by convergence target
    ev = run_main(
        streaming_mf,
        ["--local-batch", "32", "--steps-per-chunk", "4",
         "--num-users", "60", "--num-items", "40", "--rank", "4",
         "--max-records", "0", "--target-rmse", "0.3",
         "--source-batch", "1024"],
        capsys,
    )
    assert ev["done"][0]["stopped_by"] == "target_rmse"


def test_pa_real_input_svmlight(devices8, capsys, tmp_path):
    """--input on a real svmlight file trains and evaluates: the flag is
    read, not accepted and ignored."""
    import numpy as np

    from fps_tpu.examples import passive_aggressive as pa

    rng = np.random.default_rng(0)
    NF, N = 60, 2000
    w = rng.normal(0, 1, NF)
    lines = []
    for _ in range(N):
        ids = np.sort(rng.choice(NF, 8, replace=False)) + 1
        vals = rng.normal(0, 1, 8)
        y = 1 if (w[ids - 1] @ vals) > 0 else -1
        lines.append(f"{y:+d} " + " ".join(
            f"{i}:{v:.4f}" for i, v in zip(ids, vals)))
    path = tmp_path / "rcv1.svm"
    path.write_text("\n".join(lines) + "\n")

    ev = run_main(
        pa, ["--epochs", "3", "--local-batch", "32", "--steps-per-chunk", "4",
             "--input", str(path)], capsys,
    )
    assert ev["done"][0]["test_accuracy"] > 0.8


def test_logreg_real_input_criteo(devices8, capsys, tmp_path):
    """--input on a Criteo-format TSV trains through the SSP path with the
    AdaGrad fold (dense numeric columns make plain SGD oscillate under
    staleness)."""
    import numpy as np

    from fps_tpu.examples import logreg_ssp

    rng = np.random.default_rng(0)
    lines = []
    for _ in range(2000):
        x = rng.integers(0, 100, 13)
        c0 = rng.choice(["aaaa", "bbbb", "cccc", "dddd"])
        label = int(c0 in ("aaaa", "bbbb")) if rng.random() > 0.05 else \
            int(rng.random() > 0.5)
        cats = [c0] + [format(int(v), "06x")
                       for v in rng.integers(0, 1000, 25)]
        lines.append("\t".join([str(label)] + [str(v) for v in x] + cats))
    path = tmp_path / "criteo.tsv"
    path.write_text("\n".join(lines) + "\n")

    ev = run_main(
        logreg_ssp,
        ["--epochs", "12", "--local-batch", "32", "--steps-per-chunk", "8",
         "--input", str(path), "--optimizer", "adagrad"],
        capsys,
    )
    assert ev["done"][0]["test_accuracy"] > 0.8


def test_kge_entrypoint(devices8, capsys, tmp_path):
    """ComplEx on the 8-device mesh, both tables under the table's own
    AdaGrad fold: held-out triples outrank a corruption, and the snapshots
    carry the optimizer state beside the tables."""
    import numpy as np

    from fps_tpu.examples import kge

    ckdir = tmp_path / "ck"
    ev = run_main(
        kge,
        ["--num-triples", "40000", "--num-entities", "2048", "--rank", "8",
         "--epochs", "3", "--local-batch", "64",
         "--checkpoint-dir", str(ckdir), "--checkpoint-every", "1"],
        capsys,
    )
    assert ev["start"][0]["row_floats"] == 16
    assert ev["done"][0]["pairwise_accuracy"] > 0.8
    losses = [c["loss"] for c in ev["chunk"]]
    assert len(losses) == 3 and losses[-1] < losses[0]
    snaps = sorted(ckdir.glob("ckpt_*.npz"))
    assert snaps, "no checkpoints written despite --checkpoint-dir"
    with np.load(snaps[-1]) as z:
        assert z["fold::entity"].shape == z["table::entity"].shape == (2048,
                                                                       16)
        assert np.any(z["fold::entity"] > 0)


def test_kge_entrypoint_refuses_ssp(devices8):
    from fps_tpu.examples import kge

    with pytest.raises(SystemExit, match="sync-every"):
        kge.main(["--sync-every", "4"])
