"""Model kind ``word2vec_sgns``: skip-gram with negative sampling at
token-BLOCK granularity (``fps_tpu.models.word2vec.word2vec_block`` under
``Word2VecDevicePlan(mode="block")``: the entry ``fps_tpu/examples/
word2vec.py --ingest device`` takes). Both embedding tables are served
tables under the per-id mean; the worker holds no local state.

What the benchmark's comparison needs of this kind that the others get
from the data alone: an epoch's batches are DRAWN (subsampling, windows,
negatives), so :meth:`System.fed_chunks` takes them from the plan's and
the worker's own traced functions under the keys ``Trainer.run_indexed``
derives, and hands them to the reference as data; the count of examples
is the count of instances those batches define
(:func:`count_instances`); and the tokens an epoch's subsampling dropped
are fed as ``token`` rows in steps PAST the live ones, whose blocks are
empty (``valid_len`` 0), so that the feed checksums to the corpus once an
epoch: the kept stream plus (corpus less kept, by counts, never
negative).
"""

from __future__ import annotations

import numpy as np

from perfbench.datasets.token_stream import unigram_counts
from perfbench.lib import systems

IN, OUT = "in_embeddings", "out_embeddings"


def count_instances(half, valid_len) -> int:
    """Training instances of blocks with half-windows ``half (..., L)`` and
    valid lengths ``valid_len (...)``: position ``i`` pairs with ``i + d``
    for ``1 <= d <= half_i`` while ``i + d < valid_len``, both ways."""
    half = np.asarray(half, np.int64)
    room = np.asarray(valid_len, np.int64)[..., None] - 1 - np.arange(
        half.shape[-1])
    return int(2 * np.minimum(half, np.maximum(room, 0)).sum())


class System(systems.System):
    loss_key = "loss"

    def build(self, data, dataset):
        from fps_tpu.models.word2vec import (
            W2VConfig,
            Word2VecDevicePlan,
            word2vec_block,
        )

        if not hasattr(Word2VecDevicePlan, "TOKEN"):
            raise RuntimeError(
                "this checkout's Word2VecDevicePlan takes host tokens, not "
                "a DeviceDataset of one `token` column: the cell cannot run")
        m = self.cfg["model"]
        wcfg = W2VConfig(
            vocab_size=m["vocab_size"], dim=m["dim"], window=m["window"],
            negatives=m["negatives"], learning_rate=m["learning_rate"],
            subsample_t=m["subsample_t"])
        counts = unigram_counts(self.cfg["data"])
        self.trainer, self.store = word2vec_block(
            self.mesh, wcfg, counts, m["block_len"])
        self.plan = Word2VecDevicePlan(
            dataset, counts, wcfg, self.mesh, num_workers=self.W,
            block_len=m["block_len"], seed=self.seed & 0x7FFFFFFF,
            mode="block")
        self._instances = {}    # call index -> instances of its batches
        self._builders = {}     # steps a chunk -> jitted chunk builder

    def place(self, init):
        """The host tables go up FLAT (a 2-D host array is tiled on the
        way at a fraction of the link's speed) and take the program's
        layout on the device."""
        import jax.numpy as jnp

        tables, local_state = self._shells()
        for name in (IN, OUT):
            logical = jnp.asarray(init[name].reshape(-1)).reshape(
                init[name].shape)
            tables[name] = systems.to_physical(
                logical, self.store.num_shards, tables[name])
        return tables, local_state

    def export(self, tables, local_state):
        """Both tables in logical id order, on the host. Read back through
        the program's layout helper and FLAT: a 2-D device array is
        un-tiled on the host at a fraction of the link's speed
        (``datagen.make_and_fetch``), and these are 1.3 GB each."""
        import jax.numpy as jnp

        from fps_tpu.core.store import id_to_phys, rows_per_shard

        V, S = self.cfg["model"]["vocab_size"], self.store.num_shards
        phys = id_to_phys(jnp.arange(V, dtype=jnp.int32), S,
                          rows_per_shard(V, S))
        out = {}
        for name in (IN, OUT):
            rows = jnp.take(tables[name], phys, axis=0)
            out[name] = np.asarray(rows.reshape(-1)).reshape(rows.shape)
        return out

    # -- the call's draws, as data for the reference ------------------------

    @property
    def examples_per_call(self) -> int:
        """The instances call 0's batches define: drawn, so counted from
        the fed batches on the host, not a constant of the data."""
        if 0 not in self._instances:
            for _ in self.fed_chunks(0, 64):
                pass
        return self._instances[0]

    def _chunk_builder(self, steps: int):
        """Jitted ``(epoch args, call key, first step) -> (chunk, counts)``:
        ``steps`` global batches and how often each word is among their
        live ``token`` rows. The plan's ``local_batch_at`` and the worker's
        ``prepare`` under the key chain of the compiled epoch
        (``Trainer._build_indexed_fn``: the call's key folded with the
        worker index, split once a step, ``_compute_step`` splitting the
        step's key again for ``prepare``). Steps at or past
        ``steps_per_epoch`` come out empty."""
        import jax
        import jax.numpy as jnp

        if steps in self._builders:
            return self._builders[steps]
        plan, logic = self.plan, self.trainer.logic
        W, T, L = self.W, int(plan.steps_per_epoch), plan.block_len
        V = self.cfg["model"]["vocab_size"]

        def prep_keys(ckey):
            def worker(w):
                def body(k, _):
                    k, sub = jax.random.split(k)
                    return k, jax.random.split(sub)[1]
                return jax.lax.scan(body, jax.random.fold_in(ckey, w),
                                    None, length=T)[1]
            return jax.vmap(worker)(jnp.arange(W, dtype=jnp.int32))

        def build(args, ckey, start):
            keys = prep_keys(ckey)                       # (W, T)

            def one(t, w):
                tt = jnp.minimum(t, T - 1)
                b = plan.local_batch_at(args, w, tt)
                b = logic.prepare(b, keys[w, tt])
                v = jnp.where(t < T, b["valid_len"], 0)
                return {"block": b["block"], "half": b["half"],
                        "valid_len": v, "negatives": b["negatives"],
                        "token": b["block"][:L],
                        "weight": (jnp.arange(L) < v).astype(jnp.float32)}

            ts = start + jnp.arange(steps, dtype=jnp.int32)
            ws = jnp.arange(W, dtype=jnp.int32)
            chunk = jax.vmap(lambda t: jax.vmap(lambda w: one(t, w))(ws))(ts)
            for k in ("token", "weight"):        # rows: (steps, W * L)
                chunk[k] = chunk[k].reshape(steps, W * L)
            counts = jnp.zeros(V, jnp.int32).at[chunk["token"]].add(
                (chunk["weight"] > 0).astype(jnp.int32))
            return chunk, counts

        self._builders[steps] = jax.jit(build)
        return self._builders[steps]

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        import jax

        if (self.epochs_per_call != 1
                or self.trainer.config.max_steps_per_call is not None):
            raise NotImplementedError(
                "one epoch a call in one compiled call: the key chain below "
                "is that one's")
        plan = self.plan
        T, S = int(plan.steps_per_epoch), steps_per_chunk
        W, L = self.W, plan.block_len
        V = self.cfg["model"]["vocab_size"]
        e = call_index
        args = plan.epoch_args(e)
        ckey = jax.random.fold_in(jax.random.fold_in(self.key, e), 0)
        build = self._chunk_builder(S)

        left = np.bincount(np.asarray(plan.dataset.columns[plan.TOKEN]),
                           minlength=V).astype(np.int64)
        instances = 0
        for start in range(0, T, S):
            chunk, counts = build(args, ckey, np.int32(start))
            live = min(S, T - start)
            left -= np.asarray(counts)
            instances += count_instances(np.asarray(chunk["half"])[:live],
                                         np.asarray(chunk["valid_len"])[:live])
            yield chunk, live
        self._instances[call_index] = instances

        # What the subsampling dropped, by counts: never negative (a kept
        # token the corpus lacks stays missing, and the checksum shows it).
        dropped = np.repeat(np.arange(V, dtype=np.int32), np.maximum(left, 0))
        rows = S * W * L
        empty = {k: np.zeros(v.shape, v.dtype) for k, v in chunk.items()}
        empty["half"] += 1
        for lo in range(0, len(dropped), rows):
            part = dropped[lo:lo + rows]
            tok = np.zeros(rows, np.int32)
            tok[:len(part)] = part
            wt = (np.arange(rows) < len(part)).astype(np.float32)
            yield jax.device_put(dict(
                empty, token=tok.reshape(S, W * L),
                weight=wt.reshape(S, W * L))), 0
