"""Model kind ``ials``: implicit-feedback ALS by sharded normal equations
(``fps_tpu.models.ials.IALSSolver``, the entry
``fps_tpu/examples/ials.py --ingest device`` takes): two factor tables, no
worker-local state, and no ``Trainer``: the solver drives its own loop.

What this kind needs that the others get from the base:

* its entry is ``als_epoch``: ``call`` queues the harness's "epochs" as
  SWEEPS of ``IALSSolver.half_epoch`` over ``device_epoch_chunks`` of the
  plan, sweep ``e`` solving the users when ``e`` is even and the movies
  when it is odd (traffic ``sweeps``: two to a call, one ALS epoch), each
  under epoch ``e``'s shuffle, and returns each sweep's own per-step
  device metrics cut to the plan's live steps;
* the reference is a scan of stateless steps, so ``fed_chunks`` puts
  beside the columns which side a step's sweep solves (``solve_item``)
  and whether the step is its sweep's last (``last``), and the sums of
  the normal equations are tables of the reference's
  (``ials_normal_eq``): zero before and after every sweep, which is what
  ``export`` answers for them, as views that hold no memory.
"""

from __future__ import annotations

from perfbench.lib import systems
from perfbench.lib.resolve import SpecError

USERS, ITEMS = "user_factors", "item_factors"
LHS, RHS = "normal_lhs", "normal_rhs"


class System(systems.System):
    entry = "als_epoch"
    loss_key = "loss"

    def build(self, data, dataset):
        import jax.numpy as jnp

        from fps_tpu.models.ials import IALSConfig, IALSSolver
        from fps_tpu.obs import timing

        if "als.solve" not in timing.ONCE_SCOPES:
            raise SpecError(
                "this checkout's IALSSolver.half_epoch returns no per-step "
                "metrics and names no als.* scope (fps_tpu/models/ials.py, "
                "fps_tpu/obs/timing.py): the cell cannot run on it")
        m = self.cfg["model"]
        self.solver = IALSSolver(self.mesh, IALSConfig(
            num_users=m["num_users"], num_items=m["num_items"],
            rank=m["rank"], alpha=m["alpha"], reg=m["reg"],
            init_scale=m["init_scale"], dtype=jnp.float32))
        self.store = self.solver.store
        self.plan = self._plan(dataset, m["local_batch"], None)
        self.steps_per_chunk = int(m["steps_per_chunk"])

    def _shells(self):
        import jax

        return self.solver.init(jax.random.key(0)), ()

    def place(self, init):
        tables, local_state = self._shells()
        return {name: systems.to_physical(init[name], self.store.num_shards,
                                          tables[name])
                for name in (USERS, ITEMS)}, local_state

    def export(self, tables, local_state):
        import numpy as np

        m = self.cfg["model"]
        self.store.tables = dict(tables)
        rows, k = max(m["num_users"], m["num_items"]), m["rank"]
        return {USERS: self.store.dump_model(USERS)[1],
                ITEMS: self.store.dump_model(ITEMS)[1],
                LHS: np.broadcast_to(np.float32(0), (rows, k * k)),
                RHS: np.broadcast_to(np.float32(0), (rows, k))}

    def call(self, tables, local_state):
        """Queue the call's sweeps behind whatever is queued; returns at
        once with the tables the last solve will leave and one dict of
        per-step device metrics a sweep."""
        from fps_tpu.core.device_ingest import device_epoch_chunks

        self.store.tables = dict(tables)
        E, T = self.epochs_per_call, int(self.plan.steps_per_epoch)
        sweeps = []
        for e in range(self.calls * E, (self.calls + 1) * E):
            metrics = self.solver.half_epoch(
                "item" if e % 2 else "user",
                device_epoch_chunks(
                    self.plan.dataset, num_workers=self.W,
                    local_batch=self.plan.local_batch,
                    steps_per_chunk=self.steps_per_chunk, plan=self.plan,
                    start_epoch=e))
            # Cut to the plan's live steps (a last chunk is padded with
            # steps of weight 0), where there is anything to cut.
            sweeps.append({k: v if v.shape[0] == T else v[:T]
                           for k, v in metrics.items()})
        self.calls += 1
        return dict(self.store.tables), local_state, sweeps

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        """The base's chunks (sweep ``e`` is the plan's epoch ``e``) with
        the side the step's sweep solves and the sweep's last step marked."""
        import jax.numpy as jnp

        T = int(self.plan.steps_per_epoch)
        per_sweep = -(-T // steps_per_chunk)
        first = call_index * self.epochs_per_call
        for j, (chunk, live) in enumerate(
                super().fed_chunks(call_index, steps_per_chunk)):
            sweep, at = first + j // per_sweep, j % per_sweep
            step = at * steps_per_chunk + jnp.arange(steps_per_chunk,
                                                     dtype=jnp.int32)
            yield dict(
                chunk,
                solve_item=jnp.full(steps_per_chunk, sweep % 2, jnp.int32),
                last=(step == per_sweep * steps_per_chunk - 1).astype(
                    jnp.int32)), live
