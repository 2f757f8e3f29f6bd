"""word2vec SGNS: loss falls during streaming training; negatives sampled
on-device; both tables updated through the collective pull/push path."""

import jax
import numpy as np

from fps_tpu.core.driver import num_workers_of
from fps_tpu.models.word2vec import (
    IN_TABLE,
    OUT_TABLE,
    W2VConfig,
    skipgram_chunks,
    word2vec,
)
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.utils.datasets import synthetic_corpus

V = 300


def train_w2v(mesh, sync_every=None, epochs=2, dim=16):
    tokens = synthetic_corpus(V, 60_000, num_topics=8, seed=0)
    uni = np.bincount(tokens, minlength=V).astype(np.float64)
    cfg = W2VConfig(vocab_size=V, dim=dim, window=3, negatives=4,
                    learning_rate=0.05, subsample_t=None)
    trainer, store = word2vec(mesh, cfg, uni, sync_every=sync_every)
    tables, ls = trainer.init_state(jax.random.key(0))
    W = num_workers_of(mesh)
    all_m = []
    for e in range(epochs):
        chunks = skipgram_chunks(
            tokens, uni, cfg, num_workers=W, local_batch=64,
            steps_per_chunk=8, sync_every=sync_every, seed=e,
        )
        tables, ls, m = trainer.fit_stream(
            tables, ls, chunks, jax.random.fold_in(jax.random.key(1), e)
        )
        all_m.extend(m)
    loss = np.concatenate([m["loss"] for m in all_m])
    n = np.concatenate([m["n"] for m in all_m])
    return store, loss, n


def test_w2v_loss_decreases(devices8):
    mesh = make_ps_mesh(num_shards=8, num_data=1)
    store, loss, n = train_w2v(mesh)
    steps = len(loss)
    early = loss[: steps // 5].sum() / n[: steps // 5].sum()
    late = loss[-steps // 5 :].sum() / n[-steps // 5 :].sum()
    # Initial loss ~ (1+K)*log 2 ≈ 3.47 with K=4; must drop clearly.
    assert late < early * 0.8, (early, late)
    # Input table moved away from init; output table moved away from zero.
    in_emb = store.lookup_host(IN_TABLE, np.arange(V))
    out_emb = store.lookup_host(OUT_TABLE, np.arange(V))
    assert float(np.abs(out_emb).max()) > 0.01
    assert float(np.linalg.norm(in_emb, axis=1).max()) > 0.1


def test_w2v_ssp_matches_shape(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=2)
    store, loss, n = train_w2v(mesh, sync_every=4, epochs=1)
    assert len(loss) > 0 and np.all(np.isfinite(loss))
    early = loss[: len(loss) // 4].sum() / n[: len(loss) // 4].sum()
    late = loss[-len(loss) // 4 :].sum() / n[-len(loss) // 4 :].sum()
    assert late < early, (early, late)


def test_skipgram_chunks_static_shapes():
    tokens = synthetic_corpus(50, 5000, seed=1)
    uni = np.bincount(tokens, minlength=50).astype(np.float64)
    cfg = W2VConfig(vocab_size=50, window=2, subsample_t=None)
    shapes = set()
    total_w = 0.0
    for chunk in skipgram_chunks(tokens, uni, cfg, num_workers=4,
                                 local_batch=8, steps_per_chunk=4):
        shapes.add(chunk["center"].shape)
        assert chunk["center"].shape == chunk["context"].shape
        total_w += chunk["weight"].sum()
    assert len(shapes) == 1  # every chunk identical shape
    # pair count ≈ 2 * E[min(half,d) coverage] — just sanity-bound it.
    assert total_w > 2 * 0.9 * len(tokens)


def test_cooccurrence_sketch_tap_tracks_exact(devices8):
    """The tug-of-war step_tap riding the training loop must reproduce the
    exact co-occurrence inner products among probe words (computed from the
    identical pair stream) up to the sketch's variance: high rank agreement
    across probe pairs and bounded error on the diagonal (F2 norms)."""
    from fps_tpu.models.word2vec import (
        accumulate_sketch_taps,
        cooccurrence_sketch_tap,
        sketch_similarity,
    )
    from fps_tpu.sketch import TugOfWarSpec

    V2 = 80
    tokens = synthetic_corpus(V2, 20_000, num_topics=4, seed=5)
    uni = np.bincount(tokens, minlength=V2).astype(np.float64)
    cfg = W2VConfig(vocab_size=V2, dim=8, window=2, negatives=2,
                    subsample_t=None)
    probe = np.argsort(-uni)[:6].astype(np.int32)  # 6 most frequent words
    spec = TugOfWarSpec(depth=5, width=512, seed=7)

    mesh = make_ps_mesh(num_shards=4, num_data=2)
    W = num_workers_of(mesh)
    trainer, store = word2vec(
        mesh, cfg, uni, step_tap=cooccurrence_sketch_tap(spec, probe)
    )
    tables, ls = trainer.init_state(jax.random.key(0))
    chunk_args = dict(num_workers=W, local_batch=64, steps_per_chunk=4,
                      seed=3)
    tables, ls, m = trainer.fit_stream(
        tables, ls, skipgram_chunks(tokens, uni, cfg, **chunk_args),
        jax.random.key(1),
    )
    sketches = accumulate_sketch_taps(m)
    est = sketch_similarity(sketches)

    # Exact co-occurrence from the IDENTICAL (deterministic) pair stream.
    C = np.zeros((len(probe), V2), np.float64)
    for chunk in skipgram_chunks(tokens, uni, cfg, **chunk_args):
        c = chunk["center"].reshape(-1)
        x = chunk["context"].reshape(-1)
        w = chunk["weight"].reshape(-1)
        for p, pid in enumerate(probe):
            sel = (c == pid) & (w > 0)
            np.add.at(C[p], x[sel], w[sel])
    exact = C @ C.T

    # Diagonal (second-moment) estimates: unbiased, variance O(F2^2/width).
    rel = np.abs(np.diag(est) - np.diag(exact)) / np.maximum(
        np.diag(exact), 1.0
    )
    assert np.median(rel) < 0.15, (np.diag(est), np.diag(exact))
    # Off-diagonal similarity structure: strong rank agreement.
    iu = np.triu_indices(len(probe), k=1)
    r = np.corrcoef(est[iu], exact[iu])[0, 1]
    assert r > 0.9, (r, est[iu], exact[iu])


def test_w2v_push_delay_guardrail_warns(devices8):
    """docs/STALENESS.md finding #5: large push_delay (the measured collapse
    regime for SGNS under the lr-downscale recipe) must raise a runtime
    warning; small/zero push_delay must not."""
    import warnings

    import pytest

    tokens = synthetic_corpus(50, 2000, seed=0)
    uni = np.bincount(tokens, minlength=50).astype(np.float64)
    mesh = make_ps_mesh(num_shards=8, num_data=1)
    cfg_down = W2VConfig(vocab_size=50, dim=8, learning_rate=0.00625,
                         subsample_t=None)
    with pytest.warns(UserWarning, match="push_delay=16.*downscaled"):
        word2vec(mesh, cfg_down, uni, push_delay=16)
    with pytest.warns(UserWarning, match="push_delay=16"):
        word2vec(mesh, W2VConfig(vocab_size=50, dim=8, subsample_t=None),
                 uni, push_delay=16)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        word2vec(mesh, W2VConfig(vocab_size=50, dim=8, subsample_t=None),
                 uni, push_delay=4)
