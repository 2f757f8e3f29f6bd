"""Plain reference: ``sgns_block``'s step under NuPS's parameter management,
as the configuration ``w2v-1bw-hot`` states it: the ``H`` most frequent
words of both tables are read from a REPLICA that is refreshed once a
window of ``E`` steps, their updates wait in a PENDING sum until then, and
every other word is read and written at once.

Straightforward ``jax.numpy``, float32, nothing imported from the program
(nor from ``sgns_block``: the step's arithmetic below is a copy of its
own). The program's draws are data here, and so is a step's place in its
call (``batch["step"]``, ``batch["last"]``). ``run_reference`` scans a
stateless ``step(tables, batch)`` and ``compare`` walks every table
returned, so what the management carries from step to step rides as
TABLES beside the two embeddings (the seam ``logreg_ssp_adagrad`` and
``ials_normal_eq`` use):

* ``hot_in`` / ``hot_out`` ``[H, dim]``: ``R``, rows ``[0, H)`` of a table
  as the last reconcile left them;
* ``pending_in`` / ``pending_out`` ``[H, dim + 1]``: the window's sums
  ``P`` and, in the last column, its counts ``c``.

One step ``t`` of a call (``H = model.hot_tier``, ``E =
model.hot_sync_every``):

* a row ``i < H`` is read from ``R``, a row ``i >= H`` from the table;
* the arithmetic is ``sgns_block``'s: instances, ``dv``, ``du``, ``dn``,
  each divided by ``max(inst_p, 1)``, pushed for ``inst_p > 0``;
* pushed rows with ``i >= H``: the table adds to every such id the MEAN of
  the live rows pushed to it in the step;
* pushed rows with ``i < H``: added into ``P[i]``, counted in ``c[i]``;
* after the step, if ``(t + 1) % E == 0`` or the step is the call's last:
  ``table[i] += P[i] / max(c[i], 1)`` for every ``i < H``, ``R`` = the new
  head, ``P = c = 0``.

After a reconcile the replicas equal the heads and the pending tables are
zero, so at a call's end ``hot_*`` repeat the embeddings' first ``H`` rows
and ``pending_*`` are zeros: what the program answers for them
(``models/word2vec_sgns_hot.py``: its own replicas, and replica MINUS head)
is compared against that.

With ``H = 0`` this is ``sgns_block`` op for op. ``dtype`` is float32 for
the reference proper; the control runs it in bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

IN, OUT = "in_embeddings", "out_embeddings"
HOT_IN, HOT_OUT = "hot_in", "hot_out"
PENDING_IN, PENDING_OUT = "pending_in", "pending_out"


def init_tables(seed: int, cfg: dict) -> dict:
    """``sgns_block``'s start (``in_embeddings`` uniform in +-0.5/dim from
    the configuration's ``init_salt``, ``out_embeddings`` zeros; host
    arrays), the replicas equal to the heads, nothing pending."""
    del seed
    m = cfg["model"]
    V, D, H = m["vocab_size"], m["dim"], int(m["hot_tier"])
    half = 0.5 / D
    flat = jax.random.uniform(jax.random.key(m["init_salt"] & 0xFFFFFFFF),
                              (V * D,), jnp.float32, -half, half)
    v = np.asarray(flat).reshape(V, D)
    return {IN: v, OUT: np.zeros((V, D), np.float32),
            HOT_IN: v[:H].copy(), HOT_OUT: np.zeros((H, D), np.float32),
            PENDING_IN: np.zeros((H, D + 1), np.float32),
            PENDING_OUT: np.zeros((H, D + 1), np.float32)}


def _mean_push(table, ids, rows, live):
    """Add to every id the mean of the live rows pushed to it."""
    dtype = table.dtype
    acc = jnp.zeros(table.shape, dtype).at[ids].add(
        jnp.where(live[:, None], rows, 0).astype(dtype))
    cnt = jnp.zeros(table.shape[0], dtype).at[ids].add(live.astype(dtype))
    return table + acc / jnp.maximum(cnt, 1)[:, None]


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    del workers  # the batch carries one block a worker
    m = cfg["model"]
    lr, H, E = m["learning_rate"], int(m["hot_tier"]), int(
        m["hot_sync_every"])

    def read(table, replica, ids):
        if not H:
            return table[ids]
        hot = ids < H
        return jnp.where(hot[..., None], replica[jnp.where(hot, ids, 0)],
                         table[ids])

    def push(table, pending, ids, rows, live):
        if not H:
            return _mean_push(table, ids, rows, live), pending
        hot = live & (ids < H)
        table = _mean_push(table, ids, rows, live & (ids >= H))
        counted = jnp.concatenate(
            [jnp.where(hot[:, None], rows, 0), hot[:, None]],
            axis=1).astype(dtype)
        return table, pending.at[jnp.where(hot, ids, 0)].add(counted)

    def reconcile(table, pending):
        D = table.shape[1]
        head = table[:H] + pending[:, :D] / jnp.maximum(pending[:, D:], 1)
        return table.at[:H].set(head), head, jnp.zeros_like(pending)

    def step(tables, batch):
        Vt, Ut = tables[IN], tables[OUT]
        Rv, Ru = tables[HOT_IN], tables[HOT_OUT]
        block = batch["block"]                      # (Wk, L+W) word ids
        half, vlen = batch["half"], batch["valid_len"]   # (Wk, L), (Wk,)
        negs = batch["negatives"]                   # (Wk, L+W, K)
        Wk, LW = block.shape
        L = half.shape[1]
        Wd, K, D = LW - L, negs.shape[2], Vt.shape[1]

        # Every candidate pair, one by one: worker, position i, offset d,
        # orientation. Positions are numbered across workers (w * LW + p).
        w = jnp.arange(Wk)[:, None, None]
        i = jnp.arange(L)[None, :, None]
        d = jnp.arange(1, Wd + 1)[None, None, :]
        ok = (d <= half[:, :, None]) & (i + d < vlen[:, None, None])
        lo = jnp.broadcast_to(w * LW + i, ok.shape).reshape(-1)
        hi = jnp.broadcast_to(w * LW + i + d, ok.shape).reshape(-1)
        centre = jnp.concatenate([lo, hi])
        context = jnp.concatenate([hi, lo])
        live = jnp.concatenate([ok.reshape(-1)] * 2)
        word = block.reshape(-1)                    # word at a position
        P = Wk * LW

        lw = live.astype(dtype)
        inst = jnp.zeros(P, dtype).at[centre].add(lw)
        vc = read(Vt, Rv, word[centre])
        ux = read(Ut, Ru, word[context])
        l = jnp.sum(vc * ux, axis=-1)
        g = (jax.nn.sigmoid(l) - 1) * lw
        dv = jnp.zeros((P, D), dtype).at[centre].add(-lr * g[:, None] * ux)
        du = jnp.zeros((P, D), dtype).at[context].add(-lr * g[:, None] * vc)
        loss = jnp.sum(-jax.nn.log_sigmoid(l).astype(jnp.float32)
                       * live.astype(jnp.float32))

        vp = read(Vt, Rv, word)                     # (P, D)
        un = read(Ut, Ru, negs.reshape(P, K))       # (P, K, D)
        ln = jnp.sum(vp[:, None, :] * un, axis=-1)  # (P, K)
        s = inst[:, None] * jax.nn.sigmoid(ln)
        dv = dv + jnp.sum(-lr * s[:, :, None] * un, axis=1)
        dn = -lr * s[:, :, None] * vp[:, None, :]   # (P, K, D)
        loss = loss + jnp.sum(
            -jax.nn.log_sigmoid(-ln).astype(jnp.float32)
            * inst.astype(jnp.float32)[:, None])

        per = 1 / jnp.maximum(inst, 1)
        dv, du = dv * per[:, None], du * per[:, None]
        dn = dn * per[:, None, None]
        pushed = inst > 0
        Vt, Pv = push(Vt, tables[PENDING_IN], word, dv, pushed)
        Ut, Pu = push(
            Ut, tables[PENDING_OUT],
            jnp.concatenate([word, negs.reshape(-1)]),
            jnp.concatenate([du, dn.reshape(P * K, D)]),
            jnp.concatenate([pushed, jnp.repeat(pushed, K)]))
        if H:
            # The window's end (and the call's): the pending means land.
            Vt, Rv, Pv, Ut, Ru, Pu = jax.lax.cond(
                ((batch["step"] + 1) % E == 0) | batch["last"],
                lambda: reconcile(Vt, Pv) + reconcile(Ut, Pu),
                lambda: (Vt, Rv, Pv, Ut, Ru, Pu))
        out = {"loss": loss,
               "n": jnp.sum(live.astype(jnp.float32))}
        return {IN: Vt, OUT: Ut, HOT_IN: Rv, HOT_OUT: Ru,
                PENDING_IN: Pv, PENDING_OUT: Pu}, out

    return step


LOSS_KEY = "loss"  # the program's per-step metric this loss mirrors
