"""Model kind ``passive_aggressive``: the binary passive-aggressive
classifier (``fps_tpu.models.passive_aggressive``), one served table of
weights, no local state."""

from __future__ import annotations

from perfbench.lib import systems


class System(systems.System):
    loss_key = "loss"

    def build(self, data, dataset):
        from fps_tpu.models.passive_aggressive import (
            PAConfig, passive_aggressive,
        )

        m = self.cfg["model"]
        # Head-prefix routing is specified on one device only (bench.py
        # run_pa); wider meshes take the dense collective route.
        q = m["head_prefix_cols"] if self.mesh.devices.size == 1 else 0
        self.trainer, self.store = passive_aggressive(
            self.mesh,
            PAConfig(num_features=m["num_features"], variant=m["variant"],
                     C=m["C"], hot_features=m["head_features"] if q else 0,
                     head_prefix_cols=q),
            max_steps_per_call=m.get("max_steps_per_call"))
        self.plan = self._plan(dataset, m["local_batch"], None)

    def place(self, init):
        tables, local_state = self._shells()
        tables = dict(tables, weights=systems.to_physical(
            init["weights"], self.store.num_shards, tables["weights"]))
        return tables, local_state

    def export(self, tables, local_state):
        self.store.tables = dict(tables)
        return {"weights": self.store.dump_model("weights")[1][:, 0]}
