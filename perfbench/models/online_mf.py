"""Model kind ``online_mf``: online matrix factorization by SGD
(``fps_tpu.models.matrix_factorization.online_mf``). Movie factors are the
served table, user factors the worker's local state."""

from __future__ import annotations

from perfbench.lib import systems


class System(systems.System):
    loss_key = "se"

    def build(self, data, dataset):
        from fps_tpu.models.matrix_factorization import MFConfig, online_mf

        m = self.cfg["model"]
        self.trainer, self.store = online_mf(
            self.mesh,
            MFConfig(num_users=m["num_users"], num_items=m["num_items"],
                     rank=m["rank"], learning_rate=m["learning_rate"],
                     reg=m["reg"], init_min=m["init_min"],
                     init_max=m["init_max"]),
            combine=m["combine"])
        self.plan = self._plan(dataset, m["local_batch"], m["route_key"])

    def place(self, init):
        tables, local_state = self._shells()
        tables = dict(tables, item_factors=systems.to_physical(
            init["item_factors"], self.store.num_shards,
            tables["item_factors"]))
        local_state = systems.to_physical(init["user_factors"], self.W,
                                          local_state)
        return tables, local_state

    def export(self, tables, local_state):
        self.store.tables = dict(tables)
        return {
            "item_factors": self.store.dump_model("item_factors")[1],
            "user_factors": self.trainer.logic.export_local_state(
                local_state),
        }
