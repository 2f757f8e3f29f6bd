"""Model kind ``kge``: ComplEx with AdaGrad at the server
(``fps_tpu.models.kge``, the entry ``fps_tpu/examples/kge.py`` takes): the
``entity`` and ``relation`` tables each folded by the table's own
optimizer (``ServerLogic.fold``), its state beside the table under
``<table>::fold``; no local state.

What this kind needs that the others get from the base: a step's
corruptions are DRAWN in the worker's ``prepare``, so
:meth:`System.fed_chunks` takes the batches from the plan's and the
worker's own traced functions under the keys ``Trainer.run_indexed``
derives and hands them to the reference as data (as kind
``word2vec_sgns`` does); and the entity table and its optimizer state are
placed from and exported to the reference's ``export_blocks`` ranges of
ids, so that no array compared on the host is the whole 1.6 GB.
"""

from __future__ import annotations

import numpy as np

from perfbench.lib import systems

ENTITY, RELATION = "entity", "relation"


class System(systems.System):
    loss_key = "loss"

    def build(self, data, dataset):
        from fps_tpu.models.kge import KGEConfig, kge

        m = self.cfg["model"]
        self.mcfg = KGEConfig(
            num_entities=m["entities"], num_relations=m["relations"],
            rank=m["rank"], negatives=m["negatives"], l2=m["l2"],
            learning_rate=m["learning_rate"], eps=m["eps"],
            initial_accumulator=m["initial_accumulator"],
            init_std=m["init_std"])
        self.trainer, self.store = kge(self.mesh, self.mcfg)
        self.plan = self._plan(dataset, m["local_batch"], None)
        n = int(m["export_blocks"])
        self.blocks = [f"{ENTITY}_{b:02d}" for b in range(n)]
        self.acc_blocks = [f"{ENTITY}_acc_{b:02d}" for b in range(n)]
        self._builders = {}     # steps a chunk -> jitted chunk builder

    def place(self, init):
        """The host blocks go up FLAT, a block at a time (a 2-D host array
        is tiled on the way at a fraction of the link's speed), and take
        the program's layout on the device."""
        import jax
        import jax.numpy as jnp

        from fps_tpu.core.store import fold_key, padded_rows

        S = self.store.num_shards

        def laid(names):
            logical = jnp.concatenate([
                jnp.asarray(init[n].reshape(-1)).reshape(init[n].shape)
                for n in names])
            if S == 1:  # the physical layout is the logical one
                return jax.device_put(logical, self.store.sharding)
            return systems.to_physical(logical, S, jax.ShapeDtypeStruct(
                (padded_rows(len(logical), S),) + logical.shape[1:],
                logical.dtype, sharding=self.store.sharding))

        return {
            ENTITY: laid(self.blocks),
            fold_key(ENTITY): laid(self.acc_blocks),
            RELATION: laid([RELATION]),
            fold_key(RELATION): laid([RELATION + "_acc"]),
        }, ()

    def export(self, tables, local_state):
        """Tables and optimizer state in logical id order on the host, the
        entity's two cut into the reference's blocks, each fetched flat."""
        import jax
        import jax.numpy as jnp

        from fps_tpu.core.store import fold_key, id_to_phys, rows_per_shard

        S = self.store.num_shards
        rows = self.mcfg.num_entities // len(self.blocks)

        def logical(name, arr):
            if S == 1:
                return arr
            n = self.store.specs[name].num_ids
            return jnp.take(arr, id_to_phys(jnp.arange(n, dtype=jnp.int32),
                                            S, rows_per_shard(n, S)), axis=0)

        cut = jax.jit(lambda t: [t[b * rows:(b + 1) * rows].reshape(-1)
                                 for b in range(len(self.blocks))])
        out = {}
        for names, key in ((self.blocks, ENTITY),
                           (self.acc_blocks, fold_key(ENTITY))):
            for name, flat in zip(names, cut(logical(ENTITY, tables[key]))):
                out[name] = np.asarray(flat).reshape(rows, -1)
        for name, key in ((RELATION, RELATION),
                          (RELATION + "_acc", fold_key(RELATION))):
            t = logical(RELATION, tables[key])
            out[name] = np.asarray(t.reshape(-1)).reshape(t.shape)
        return out

    # -- the call's draws, as data for the reference ------------------------

    def _chunk_builder(self, steps: int):
        """Jitted ``(epoch args, call key, first step) -> chunk``:
        ``steps`` global batches, the plan's ``local_batch_at`` and the
        worker's ``prepare`` under the key chain of the compiled epoch
        (``Trainer._build_indexed_fn``: the call's key folded with the
        worker index, split once a step, ``_compute_step`` splitting the
        step's key again for ``prepare``). Steps at or past
        ``steps_per_epoch`` come out with weight 0."""
        import jax
        import jax.numpy as jnp

        if steps in self._builders:
            return self._builders[steps]
        plan, logic = self.plan, self.trainer.logic
        W, T = self.W, int(plan.steps_per_epoch)

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
                b = logic.prepare(plan.local_batch_at(args, w, tt),
                                  keys[w, tt])
                return dict(b, weight=jnp.where(t < T, b["weight"], 0))

            ts = start + jnp.arange(steps, dtype=jnp.int32)
            ws = jnp.arange(W, dtype=jnp.int32)
            chunk = jax.vmap(lambda t: jax.vmap(lambda w: one(t, w))(ws))(ts)
            # (steps, W, B, ...) -> (steps, W * B, ...): every worker's rows
            return {k: v.reshape((steps, -1) + v.shape[3:])
                    for k, v in chunk.items()}

        self._builders[steps] = jax.jit(build)
        return self._builders[steps]

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        import jax

        if (self.epochs_per_call != 1
                or self.trainer.config.max_steps_per_call is not None):
            raise NotImplementedError(
                "one epoch a call in one compiled call: the key chain below "
                "is that one's")
        T, S = int(self.plan.steps_per_epoch), steps_per_chunk
        e = call_index
        args = self.plan.epoch_args(e)
        ckey = jax.random.fold_in(jax.random.fold_in(self.key, e), 0)
        build = self._chunk_builder(S)
        for start in range(0, T, S):
            yield build(args, ckey, np.int32(start)), min(S, T - start)
