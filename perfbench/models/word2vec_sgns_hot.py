"""Model kind ``word2vec_sgns_hot``: ``word2vec_sgns``'s job under the
two-tier storage (``TableSpec.hot_tier`` / ``TrainerConfig.hot_sync_every``:
what ``fps_tpu/examples/word2vec.py --ingest device --hot-tier H
--hot-sync-every E`` builds). The ``H`` most frequent words of both tables
are replicated on every chip and reconciled once a window of ``E`` steps;
the tier engages on a mesh of more than one device only, so the kind has
no one-chip cell.

What this kind needs beside its parent's: the tier set through the user's
path (``examples.common.apply_hot_tier``) before the first call; a step's
place in its call beside the drawn batches (``fed_chunks`` adds ``step``
and ``last``, as ``logreg_ssp.System`` adds ``step``); the tables dict a
call returns carries the replicas under ``<table>::hot`` (``place`` hands
the canonical tables alone, as a user who loaded them would, and the first
call derives the replicas); the per-step metrics carry the tier's counters
as a nested ``hot_tier`` channel, flattened here so that the runner's
checks (finite, fetched) see plain arrays; and ``export`` answers the
reference's carried tables: ``hot_*`` from the program's own replicas,
``pending_*`` as replica MINUS the table's head, exact on the device,
which the guarantee "each replica equals its table's head bit for bit at a
call boundary" says is zero (the program's own pending buffers live inside
a compiled call and never reach a boundary).
"""

from __future__ import annotations

import argparse

import numpy as np

from perfbench.models import word2vec_sgns

IN, OUT = word2vec_sgns.IN, word2vec_sgns.OUT
HOT = {IN: "hot_in", OUT: "hot_out"}
PENDING = {IN: "pending_in", OUT: "pending_out"}


class System(word2vec_sgns.System):

    def build(self, data, dataset):
        from fps_tpu.examples.common import apply_hot_tier

        super().build(data, dataset)
        m = self.cfg["model"]
        self.H, self.E = int(m["hot_tier"]), int(m["hot_sync_every"])
        apply_hot_tier(
            argparse.Namespace(hot_tier=self.H, hot_sync_every=self.E,
                               cold_budget=int(m["cold_budget"])),
            self.trainer, self.store)
        specs, config = self.store.specs, self.trainer.config
        if (self.W < 2 or config.hot_sync_every != self.E
                or any(specs[n].hot_tier != self.H for n in (IN, OUT))):
            raise RuntimeError(
                f"the two-tier storage did not engage (hot_tier {self.H}, "
                f"hot_sync_every {self.E}, {self.W} device(s)): it needs a "
                "mesh of more than one device, and this kind measures "
                "nothing else")

    def call(self, tables, local_state):
        """The parent's call with the tier's counters flattened into the
        per-step metrics (``hot_tier.<table>.<counter>``)."""
        tables, local_state, metrics = super().call(tables, local_state)
        flat = []
        for m in metrics:
            m = dict(m)
            for table, counters in sorted(m.pop("hot_tier").items()):
                for k, v in sorted(counters.items()):
                    m[f"hot_tier.{table}.{k}"] = v
            flat.append(m)
        return tables, local_state, flat

    def export(self, tables, local_state):
        import jax.numpy as jnp

        from fps_tpu.core.store import hot_key

        out = super().export(tables, local_state)
        for name in (IN, OUT):
            replica = tables[hot_key(name)]
            head = jnp.asarray(out[name][:self.H])
            out[HOT[name]] = np.asarray(replica)
            out[PENDING[name]] = np.asarray(jnp.pad(
                replica - head, ((0, 0), (0, 1))))
        return out

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        """The parent's chunks with each step's index in the call and
        whether it is the call's last (the reference reconciles after
        every ``E``-th step and after the last)."""
        import jax.numpy as jnp

        T, done = int(self.plan.steps_per_epoch), 0
        for chunk, live in super().fed_chunks(call_index, steps_per_chunk):
            step = done + jnp.arange(steps_per_chunk, dtype=jnp.int32)
            yield dict(chunk, step=step, last=step == T - 1), live
            done += steps_per_chunk
