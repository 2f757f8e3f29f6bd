"""Model kind ``online_mf_topk``: online matrix factorization that answers
a top-K list per rating event and then trains on it
(``fps_tpu.models.matrix_factorization.online_mf`` with
``fps_tpu.models.recommendation.make_online_topk_tap`` as the trainer's
``step_tap``: the trainer ``fps_tpu/examples/mf.py --topk K --topk-every 1
--topk-queries Q`` builds).

What this kind adds to ``online_mf``'s adapter: the tap; a ``call`` whose
metrics carry the lists flat beside the step's sums (``topk_ids``,
``topk_scores``, ``topk_query``, ``topk_padding``: the window fetches
them with every call's metrics, as a consumer of the lists would); and
the lists of the WARM-UP call, the one the reference replays, answered by
``export`` as tables and handed to the reference as data
(``lib/reference/mf_sgd_topk.py`` says what each table holds). The
warm-up call's lists stay on the device until the comparison reads them
(0.6 GB at 256 queries a step; it is in the cell's peak memory).

It needs the program's PREQUENTIAL tap (a tap that sees the step's
pre-update view and counts its padding queries). A checkout without it
cannot run this kind and says so as the system is built, before anything
is placed on the device.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from perfbench.lib import resolve
from perfbench.models import online_mf

TABLE = "item_factors"


class System(online_mf.System):

    def __init__(self, cfg, traffic, data, seed):
        from fps_tpu.core.driver import Trainer
        from fps_tpu.models import recommendation

        if not (hasattr(Trainer, "_tap_step")
                and hasattr(recommendation, "topk_journal")):
            raise resolve.SpecError(
                "model kind online_mf_topk needs the program's prequential "
                "top-K tap (Trainer._tap_step, recommendation.topk_journal)"
                ": this checkout's fps_tpu has none")
        self._lists = None
        super().__init__(cfg, traffic, data, seed)

    def build(self, data, dataset):
        from fps_tpu.models.recommendation import (
            make_online_topk_tap, mf_topk_query_fn,
        )

        super().build(data, dataset)
        m = self.cfg["model"]
        self.trainer.config = dataclasses.replace(
            self.trainer.config,
            step_tap=make_online_topk_tap(
                self.store, TABLE, m["topk"], every=m["topk_every"],
                query_fn=mf_topk_query_fn(
                    self.W, num_queries=m["queries_per_step"])))
        steps = m["topk_steps_per_call"]
        if (self.W, int(self.plan.steps_per_epoch)
                * int(self.traffic["epochs_per_call"])) != (1, steps):
            raise resolve.SpecError(
                f"{self.cfg['name']}: the reference holds a call's lists "
                f"for one worker and {steps} steps; this plan has "
                f"{self.W} worker(s) and {self.plan.steps_per_epoch} steps "
                "an epoch")

    def call(self, tables, local_state):
        first = self.calls == 0
        tables, local_state, metrics = super().call(tables, local_state)
        flat = [{**{k: v for k, v in m.items() if k != "tap"}, **m["tap"]}
                for m in metrics]
        if first:
            self._lists = flat[0]
        return tables, local_state, flat

    def lists(self) -> dict:
        """The warm-up call's lists on the host, ``(steps, Q, ...)`` with
        ``Q`` every worker's queries of a step side by side."""
        import jax

        if not isinstance(self._lists["topk_ids"], np.ndarray):
            got = jax.device_get({k: self._lists[k] for k in (
                "topk_ids", "topk_scores", "topk_query", "topk_padding")})
            # (steps, W, q, ...) -> (steps, W * q, ...); padding (steps, W).
            self._lists = {k: v.reshape((len(v), -1) + v.shape[3:])
                           for k, v in got.items()}
        return self._lists

    def export(self, tables, local_state):
        out = super().export(tables, local_state)
        lists = self.lists()
        ids, query = lists["topk_ids"], lists["topk_query"]
        live = query >= 0
        scores = np.where(ids >= 0, lists["topk_scores"], np.float32(0))
        ranked = np.sort(ids, axis=-1)
        distinct = (ranked[..., :1] >= 0).astype(np.int64).sum(-1) + (
            (ranked[..., 1:] != ranked[..., :-1])
            & (ranked[..., 1:] >= 0)).sum(-1)
        in_range = (ids < self.cfg["model"]["num_items"]).all(-1)
        stray = ((ids != -1).sum(-1) * ~live).sum(-1) + np.abs(
            lists["topk_padding"].sum(-1) - (~live).sum(-1))
        out.update(
            topk_scores=scores, topk_id_scores=scores,
            topk_query=query.astype(np.float32),
            topk_counts=np.stack(
                [live.sum(-1), (distinct * (live & in_range)).sum(-1),
                 stray], axis=-1).astype(np.float32))
        return out

    def fed_chunks(self, call_index: int, steps_per_chunk: int):
        """The base's chunks with each step's index in the call and the
        ids the program answered at that step (the reference scores them
        by its own tables) beside the columns."""
        import jax.numpy as jnp

        if call_index != 0:
            raise ValueError("only the warm-up call's lists are kept")
        ids = self.lists()["topk_ids"]
        done = 0
        for chunk, live in super().fed_chunks(call_index, steps_per_chunk):
            block = np.full((steps_per_chunk,) + ids.shape[1:], -1, np.int32)
            block[:live] = ids[done:done + live]
            yield dict(chunk, topk_ids=jnp.asarray(block),
                       step=done + jnp.arange(steps_per_chunk,
                                              dtype=jnp.int32)), live
            done += steps_per_chunk
