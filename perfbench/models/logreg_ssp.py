"""Model kind ``logreg_ssp``: sparse logistic regression under bounded
staleness (``fps_tpu.models.logistic_regression`` with ``sync_every``:
the entry ``fps_tpu/examples/logreg_ssp.py`` takes), one served table of
``[weight, AdaGrad accumulator]`` rows, no local state.

What this kind needs that the others get from the base: its plan is built
WITH ``sync_every`` (an epoch is a whole number of rounds, and
``Trainer.run_indexed`` refuses a plan that disagrees with the trainer);
the reference carries the round's snapshot as a second table, which after
a whole number of rounds IS the weights (``export`` answers both names
from the program's one table); and a step's place in its round reaches
the reference as data (``fed_chunks`` adds ``step`` beside the columns).
"""

from __future__ import annotations

from perfbench.lib import systems

WEIGHTS, SNAPSHOT = "weights", "snapshot"


class System(systems.System):
    loss_key = "logloss"

    def build(self, data, dataset):
        from fps_tpu import DeviceEpochPlan
        from fps_tpu.models.logistic_regression import (
            LogRegConfig, logistic_regression,
        )

        m = self.cfg["model"]
        lcfg = LogRegConfig(
            num_features=m["num_features"], learning_rate=m["learning_rate"],
            l2=m["l2"], batch_average=m["batch_average"],
            optimizer=m["optimizer"], adagrad_eps=m["adagrad_eps"],
            dense_features=m["dense_features"])
        if lcfg.table_width != m["table_width"]:
            raise ValueError(f"optimizer {m['optimizer']!r} keeps rows of "
                             f"{lcfg.table_width}, the configuration states "
                             f"{m['table_width']}")
        self.trainer, self.store = logistic_regression(
            self.mesh, lcfg, sync_every=m["sync_every"])
        self.plan = DeviceEpochPlan(
            dataset, num_workers=self.W, local_batch=m["local_batch"],
            seed=self.seed & 0x7FFFFFFF, sync_every=m["sync_every"])

    def place(self, init):
        tables, local_state = self._shells()
        tables = dict(tables, **{WEIGHTS: systems.to_physical(
            init[WEIGHTS], self.store.num_shards, tables[WEIGHTS])})
        return tables, local_state

    def export(self, tables, local_state):
        """The table in logical id order under both of the reference's
        names: a call ends on a round's edge, where the next round's
        snapshot is the table itself."""
        self.store.tables = dict(tables)
        rows = self.store.dump_model(WEIGHTS)[1]
        return {WEIGHTS: rows, SNAPSHOT: rows}

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        """The base's chunks with the rounds' axis folded back into steps
        (a plan with ``sync_every`` yields ``(rounds, s, rows)``) and each
        step's index in the call beside the columns."""
        import jax.numpy as jnp

        done = 0
        for chunk, live in super().fed_chunks(call_index, steps_per_chunk):
            chunk = {k: v.reshape((steps_per_chunk,) + v.shape[2:])
                     for k, v in chunk.items()}
            chunk["step"] = done + jnp.arange(steps_per_chunk,
                                              dtype=jnp.int32)
            yield chunk, live
            done += steps_per_chunk
