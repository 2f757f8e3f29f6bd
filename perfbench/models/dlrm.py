"""Model kind ``dlrm``: DLRM's hybrid job (``fps_tpu.models.dlrm``, the
entry ``fps_tpu/examples/dlrm.py`` takes): the embedding fields as ONE
served table ``emb`` keyed ``offset[f] + token``, the two MLPs as the
worker logic's dense parameters on the trainer's dense route, no local
state.

What this kind needs that the others get from the base: the reference
holds the fields as separate tables, so ``place`` lays them end to end
into the one key space and ``export`` cuts them apart again at the same
offsets; and the MLPs' parameters ride the program's tables dict under
``<name>::dense`` (``fps_tpu.core.store.dense_key``), which both map to
the reference's names. The trainer's surface did not grow: ``call`` is
the base's (``Trainer.run_indexed`` over the tables dict).
"""

from __future__ import annotations

import numpy as np

from perfbench.lib import systems

EMB = "emb"


class System(systems.System):
    loss_key = "logloss"

    def build(self, data, dataset):
        from fps_tpu.models.dlrm import DLRMConfig, dlrm

        m = self.cfg["model"]
        self.mcfg = DLRMConfig(
            field_rows=self.cfg["data"]["categorical_cardinalities"],
            embed_dim=m["embed_dim"], numeric=m["numeric"],
            bottom_mlp=m["bottom_mlp"], top_mlp=m["top_mlp"],
            learning_rate=m["learning_rate"])
        if list(self.mcfg.field_offsets) != list(m["field_offsets"]):
            raise ValueError("model.field_offsets are not the running sums "
                             "of data.categorical_cardinalities")
        self.trainer, self.store = dlrm(self.mesh, self.mcfg)
        self.plan = self._plan(dataset, m["local_batch"], None)
        self.fields = [f"emb_{f:02d}" for f in range(len(m["field_offsets"]))]

    def place(self, init):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from fps_tpu.core.store import dense_key, padded_rows

        S = self.store.num_shards
        emb = jnp.concatenate([init[name] for name in self.fields])
        if S == 1:  # the physical layout is the logical one
            emb = jax.device_put(emb, self.store.sharding)
        else:
            emb = systems.to_physical(emb, S, jax.ShapeDtypeStruct(
                (padded_rows(self.mcfg.num_rows, S), self.mcfg.embed_dim),
                emb.dtype, sharding=self.store.sharding))
        everywhere = NamedSharding(self.mesh, PartitionSpec())
        tables = {EMB: emb}
        for name in self.mcfg.layer_shapes():
            # A copy: the call donates its tables, the reference keeps init.
            tables[dense_key(name)] = jax.device_put(
                jnp.array(init[name]), everywhere)
        return tables, ()

    def export(self, tables, local_state):
        """The fields cut out of the one key space (each fetched flat: a
        2-D device array is un-tiled on the host far below the link's
        speed) and the MLPs' parameters, under the reference's names."""
        import jax

        from fps_tpu.core.store import dense_key

        D = self.mcfg.embed_dim
        cuts = list(zip(self.fields, self.mcfg.field_offsets,
                        self.mcfg.field_rows))
        if self.store.num_shards == 1:  # physical rows are logical rows
            flat = jax.jit(lambda t: [t[lo:lo + n].reshape(-1)
                                      for _, lo, n in cuts])(tables[EMB])
            out = {name: np.asarray(f).reshape(n, D)
                   for (name, _, n), f in zip(cuts, flat)}
        else:
            self.store.tables = dict(tables)
            rows = self.store.dump_model(EMB)[1]
            out = {name: rows[lo:lo + n] for name, lo, n in cuts}
        for name in self.mcfg.layer_shapes():
            out[name] = np.asarray(tables[dense_key(name)])
        return out
