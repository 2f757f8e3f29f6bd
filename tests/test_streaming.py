"""Unbounded streaming ingest + combinators + profiling tests."""

import numpy as np
import pytest

from fps_tpu.core.ingest import epoch_chunks, stream_chunks


def _source(n_batches, batch_n, seed=0, nnz=None):
    """Unbounded-style source: varying-size columnar batches."""
    rng = np.random.default_rng(seed)
    for b in range(n_batches):
        n = batch_n + (b % 3)  # varying lengths
        batch = {
            "user": rng.integers(0, 40, n).astype(np.int32),
            "item": rng.integers(0, 30, n).astype(np.int32),
            "rating": rng.normal(0, 1, n).astype(np.float32),
        }
        if nnz:
            batch["feat_ids"] = rng.integers(0, 100, (n, nnz)).astype(np.int32)
        yield batch


def _collect_real(chunks, key):
    """All real (weight 1) values of a column across chunks, any order."""
    vals = []
    for c in chunks:
        w = c["weight"].reshape(-1) > 0
        vals.append(c[key].reshape(-1, *c[key].shape[c["weight"].ndim:])[w])
    return np.concatenate(vals) if vals else np.array([])


def test_stream_chunks_conserves_examples_roundrobin():
    src = list(_source(10, 50))
    total = sum(len(b["user"]) for b in src)
    chunks = list(stream_chunks(iter(src), num_workers=4, local_batch=8,
                                steps_per_chunk=3))
    # Static shapes on every chunk.
    for c in chunks:
        assert c["user"].shape == (3, 32)
        assert c["weight"].shape == (3, 32)
    got = int(sum(c["weight"].sum() for c in chunks))
    assert got == total
    # Every rating value survives exactly once.
    want = np.sort(np.concatenate([b["rating"] for b in src]))
    have = np.sort(_collect_real(chunks, "rating"))
    np.testing.assert_allclose(have, want)


def test_stream_chunks_routing_and_multidim():
    src = list(_source(6, 40, seed=1, nnz=5))
    chunks = list(stream_chunks(iter(src), num_workers=4, local_batch=8,
                                steps_per_chunk=2, route_key="user"))
    W, LB = 4, 8
    for c in chunks:
        assert c["feat_ids"].shape == (2, 32, 5)
        # Routed: every real example sits in its owner's slot range.
        users = c["user"].reshape(2, W, LB)
        weight = c["weight"].reshape(2, W, LB)
        for w in range(W):
            real = weight[:, w, :] > 0
            assert np.all(users[:, w, :][real] % W == w)
    total = sum(len(b["user"]) for b in src)
    assert int(sum(c["weight"].sum() for c in chunks)) == total


def test_stream_chunks_ssp_shape():
    chunks = list(stream_chunks(_source(4, 64), num_workers=2, local_batch=4,
                                steps_per_chunk=4, sync_every=2))
    for c in chunks:
        assert c["user"].shape == (2, 2, 8)
    with pytest.raises(ValueError):
        next(stream_chunks(_source(1, 8), num_workers=2, local_batch=4,
                           steps_per_chunk=3, sync_every=2))


def test_stream_chunks_trains_mf(devices8):
    """stream_chunks output feeds the compiled driver directly."""
    import jax

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    W = num_workers_of(mesh)
    trainer, store = online_mf(mesh, MFConfig(32, 24, rank=4), donate=False)
    data = synthetic_ratings(32, 24, 2048, seed=2)

    def src():
        for s in range(0, 2048, 256):
            yield {k: v[s : s + 256] for k, v in data.items()}

    chunks = stream_chunks(src(), num_workers=W, local_batch=16,
                           steps_per_chunk=4, route_key="user")
    tables, ls = trainer.init_state(jax.random.key(0))
    tables, ls, metrics = trainer.fit_stream(tables, ls, chunks,
                                             jax.random.key(1))
    n = sum(float(np.sum(m["n"])) for m in metrics)
    assert n == 2048.0


def test_combinators(devices8):
    import jax

    from fps_tpu.core.combinators import clip_pushes, scale_pushes, tap_outputs
    from fps_tpu.core.driver import Trainer, num_workers_of
    from fps_tpu.models.matrix_factorization import (
        MatrixFactorizationWorker,
        MFConfig,
        make_store,
    )
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    W = num_workers_of(mesh)
    cfg = MFConfig(num_users=16, num_items=12, rank=4)
    data = synthetic_ratings(16, 12, 256, seed=3)

    def run(wrap):
        store = make_store(mesh, cfg)
        logic = wrap(MatrixFactorizationWorker(cfg, W))
        trainer = Trainer(mesh, store, logic)
        chunk = next(epoch_chunks(data, num_workers=W, local_batch=8,
                                  steps_per_chunk=2, route_key="user"))
        tables, ls = trainer.init_state(jax.random.key(0))
        tables, ls, m = trainer.run_chunk(tables, ls, chunk, jax.random.key(1))
        return store, jax.tree.map(np.asarray, m)

    # tap_outputs adds push statistics to the metrics stream.
    _, m = run(tap_outputs)
    assert "push_norm/item_factors" in m and "push_count/item_factors" in m
    assert np.all(m["push_count/item_factors"] > 0)

    # clip_pushes with a tiny max_norm shrinks the push norms.
    _, m_clip = run(lambda l: tap_outputs(clip_pushes(l, 1e-3)))
    assert np.sum(m_clip["push_norm/item_factors"]) < np.sum(
        m["push_norm/item_factors"]
    )

    # scale_pushes(0) must leave the item table at its initialization.
    s0, _ = run(lambda l: scale_pushes(l, 0.0))
    s1, _ = run(lambda l: l)
    init_store = make_store(mesh, cfg)
    init_store.init(jax.random.fold_in(jax.random.key(0), 0))
    np.testing.assert_allclose(
        s0.dump_model("item_factors")[1],
        init_store.dump_model("item_factors")[1],
        rtol=1e-6,
    )
    assert not np.allclose(
        s1.dump_model("item_factors")[1], init_store.dump_model("item_factors")[1]
    )


def test_every_chunk_of_a_stream_is_a_device_span(devices8):
    """What an ``on_chunk`` stopwatch measured from outside (and forced a
    host sync a chunk to measure), the program stamps from inside: every
    chunk is a ``device.fit_stream`` span under the journal's chunk index,
    whether the loop reads the chunk back or not."""
    import jax

    from fps_tpu import obs
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_ratings

    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    W = num_workers_of(mesh)
    trainer, _ = online_mf(mesh, MFConfig(16, 12, rank=4), donate=False)
    data = synthetic_ratings(16, 12, 512, seed=4)

    def run(on_chunk):
        sink = obs.MemorySink()
        rec = obs.Recorder(sinks=[sink])
        tables, ls = trainer.init_state(jax.random.key(0))
        trainer.fit_stream(
            tables, ls, epoch_chunks(data, num_workers=W, local_batch=8,
                                     steps_per_chunk=2, route_key="user"),
            jax.random.key(1), on_chunk=on_chunk, recorder=rec)
        rec.close()  # drains the watcher: the last chunk's span is in
        spans = [e for e in sink.events("span")
                 if e["span"] == "device.fit_stream"]
        return sink.events("chunk"), spans

    seen = []
    for on_chunk in (lambda step, metrics: seen.append(step), None):
        chunks, spans = run(on_chunk)
        assert len(chunks) == len(spans) == len(seen) >= 2
        assert [e["chunk"] for e in spans] == [e["index"] for e in chunks]
        assert all(e["steps"] == 2 for e in spans)
        assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))


def test_trace_writes_profile(tmp_path, devices8):
    import jax
    import jax.numpy as jnp

    from fps_tpu.utils import profiling

    with profiling.trace(str(tmp_path)):
        jnp.sum(jnp.arange(1000.0)).block_until_ready()
    produced = list(tmp_path.rglob("*"))
    assert produced, "no trace files written"
