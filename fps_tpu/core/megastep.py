"""Device-resident megastep: K chunks in ONE compiled program.

The per-chunk drivers (``Trainer.fit_stream`` / ``Trainer.run_indexed``)
pay a full host round-trip between compiled calls: Python dispatch, key
folding, metric bookkeeping, and (with syncing consumers) a blocking
device→host transfer sit between every chunk. After PR 10/12 made the
data plane payload-proportional, that host serialization is the last
per-chunk overhead left in the hot loop (ROADMAP: "a fully
device-resident megastep"; automatic cross-replica sharding of weight
updates — arXiv:2004.13336 — shows the win of keeping the whole update
loop on-device).

Here K chunk *segments* run under one ``lax.scan`` step driver inside a
single jitted program with donated table buffers, consuming batches via
the device-side ingest path (:class:`fps_tpu.core.device_ingest.
DeviceEpochPlan`), and the work the host loop used to do at chunk
boundaries happens **in-graph**:

* **reconcile ticks** — every segment ends with the same flush
  reconcile a per-chunk compiled call ends with, so segment boundaries
  hold one canonical table and the megastep is bit-identical to K
  per-chunk ``run_indexed`` dispatches (tested);
* **sketch folds** — each segment's count-min window accumulator is
  psum-merged into the running window at the segment boundary, exactly
  the per-call merge of old;
* **tier ticks** (:class:`fps_tpu.tiering.MegastepTick`) — every
  ``check_every`` segments the merged window folds into a device-
  resident decayed count-min, the head re-ranks by (decayed count desc,
  id asc), and the replica / slot-map / gid arrays are re-derived from
  the canonical table — the host Retierer's boundary contract, traced;
* **overflow VOTE** — the gap PR 10 explicitly left: batches
  materialize inside the jit, so there is no host id stream to certify
  the compacted cold routes against. Before each segment runs, a cheap
  in-graph pre-pass re-reads the segment's raw id columns
  (``WorkerLogic.pulled_ids_traced``), counts every (step, worker)
  slice's cold ids against ``TableSpec.cold_budget`` exactly like the
  host certifier, and psums the verdict so every device agrees; the
  segment then ``lax.cond``-dispatches the compacted branch or the
  bit-identical static-route branch.

Collective cost stays O(traffic): the per-step collectives live inside
the scan body (one static occurrence however large K is) and the
boundary ticks move O(window) bytes per window — the contract auditor
pins the census as K-independent (``tools/audit_programs.py``
``mf_megastep`` rows).

Checkpoints land at megastep boundaries (the only host-visible
boundaries left); resume restores the snapshot and continues at
``start_megastep`` with the same per-(epoch, chunk) PRNG/shuffle
derivation, so a SIGKILL mid-megastep replays bit-identically (the
``megastep_kill`` chaos scenario).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fps_tpu.core import resilience
from fps_tpu.core.store import (
    device_slot_map,
    lookup_hot_slots,
    replica_from_shard,
    sketch_key,
    split_tiering,
    watch_routed,
)
from fps_tpu.obs.timing import PhaseTimer, host_span, watch_device
from fps_tpu.parallel.mesh import (
    DATA_AXIS,
    SHARD_AXIS,
    key_to_replicated,
)

_log = logging.getLogger("fps_tpu.megastep")


def _psum_workers(x):
    return lax.psum(lax.psum(x, SHARD_AXIS), DATA_AXIS)


def vote_certifiable_tables(trainer, plan) -> frozenset:
    """Which compacted tables the device-side vote can certify: the
    logic's :meth:`~fps_tpu.core.api.WorkerLogic.pulled_ids_traced`
    stream (probed by abstract evaluation — no device work) must cover
    them. A compacted table the stream misses can never certify, so the
    megastep lowers the static routes for every table (mirrors the host
    certifier's "uncertifiable chunk reports every compacted table")."""
    compact = trainer._cold_compact_map()
    if not compact:
        return frozenset()
    cols = {
        k: jax.ShapeDtypeStruct((plan.local_batch,) + tuple(v.shape[1:]),
                                v.dtype)
        for k, v in plan.dataset.columns.items()
    }
    cols["weight"] = jax.ShapeDtypeStruct((plan.local_batch,), jnp.float32)

    def probe(batch):
        ids = trainer.logic.pulled_ids_traced(batch)
        return dict(ids) if ids is not None else {}

    try:
        covered = set(jax.eval_shape(probe, cols))
    except Exception:
        _log.warning("pulled_ids_traced probe failed; megastep cold "
                     "routes stay static", exc_info=True)
        return frozenset()
    if not set(compact) <= covered:
        return frozenset()
    return frozenset(compact)


def build_megastep_fn(trainer, plan, mode: str, K: int, tick=None):
    """One jitted program running K chunk segments of ``plan``.

    Signature of the returned callable::

        (tables, local_state, iargs, start_ci, key, tick_ops)
            -> (tables, local_state, outs, aux)

    ``start_ci`` is the epoch-relative index of the first chunk segment
    (the megastep's segments cover ``[start_ci, start_ci + K)``);
    ``key`` is the epoch key (``fold_in(run_key, epoch)``, replicated) —
    each segment folds its own chunk index in-graph, reproducing
    ``run_indexed``'s per-call key derivation bit-for-bit. ``outs``
    leaves carry ``K * steps_per_call`` leading rows; ``aux`` holds the
    per-segment overflow votes, the tier tick's updated decayed state /
    fold counter, and per-tick churn / re-rank telemetry (all
    replicated)."""
    from jax.sharding import PartitionSpec as P

    from fps_tpu.core.driver import worker_index
    from fps_tpu.core.store import fold_key, hot_key, ids_key, map_key

    if trainer.logic.dense is not None:
        raise ValueError(
            "the worker logic declares dense parameters "
            f"{sorted(trainer._dense_like)}; the megastep does not "
            "carry them (its segments thread tables and tier state only): "
            "drive run_indexed or fit_stream")
    if trainer._row_fold_map():
        raise ValueError(
            f"tables {sorted(trainer._row_fold_map())} declare an "
            "optimizer of their own (ServerLogic.fold); the megastep does "
            "not carry its state: drive run_indexed or fit_stream")
    T = trainer._indexed_call_steps(plan)
    s = trainer.config.sync_every
    tier = trainer._hot_tier_map()
    mapped = trainer._mapped_tables()
    track = trainer._track_specs()
    folds_on = trainer._hot_fold_map()
    E = trainer.config.hot_sync_every
    certifiable = vote_certifiable_tables(trainer, plan)
    compact = {
        name: C for name, C in sorted(trainer._cold_compact_map().items())
        if name in certifiable
    }
    if tick is not None:
        c_tick = tick.check_every
        if K % c_tick:
            # run_megastep validates this too; direct builders
            # (lowered_megastep_text) must fail the same way instead of
            # silently truncating the dispatch to fewer segments.
            raise ValueError(
                f"chunks_per_dispatch={K} must be a multiple of "
                f"tick.check_every={c_tick}")
        tick_tables = sorted(track)
        groups, c_seg = K // c_tick, c_tick
    else:
        tick_tables = []
        groups, c_seg = 1, K

    def mega_device(tables, local_state, iargs, start_ci, key, tick_ops):
        widx = worker_index()
        (tables, hot, maps, gids, sketches,
         fstates) = split_tiering(tables)

        def run_segment(carry, ci, compact_map):
            (tables, hot, maps, gids, sketches, fstates,
             local_state) = carry[:7]
            tick_rest = carry[7:]
            # run_indexed derives fold_in(fold_in(key, e), ci) on host;
            # fold_in is the same function traced, so the megastep's
            # in-graph derivation reproduces the stream bit-for-bit.
            ckey = jax.random.fold_in(key, ci)
            kk = jax.random.fold_in(ckey, widx)
            delta = trainer._init_hot_deltas(tables, tier)
            sk0 = {name: jnp.zeros_like(sketches[name])
                   for name in sorted(track)}

            def step_t(c, t, snapshot=None):
                (tables, hot, delta, fstates, sk, local_state, kk) = c
                kk, sub = jax.random.split(kk)
                with jax.named_scope("fps.ingest"):
                    batch = plan.local_batch_at(iargs, widx, t)
                tapped = trainer._tap_step(tables, batch, local_state, t)
                with watch_routed() as routed, \
                        trainer._watch_counts() as counted:
                    (pushes, local_state, out, hp, hcounts,
                     sk, _) = trainer._compute_step(
                        tables, snapshot, local_state, batch, sub,
                        hot=hot, tier=tier, maps=maps, track=track, sk=sk,
                        compact=compact_map,
                    )
                    dropped = {}
                    if tier:
                        tables, delta, dropped = trainer._apply_hot_split(
                            tables, delta, pushes, tier, hp, maps,
                            compact_map)
                    else:
                        tables = trainer._apply_pushes(tables, pushes, hp)
                out = trainer._mount_hot_channel(out, hcounts, delta,
                                                 tier, dropped, routed)
                out = trainer._mount_counts(out, counted)
                with jax.named_scope("fps.metrics"):
                    out = jax.tree.map(_psum_workers, out)
                out = trainer._mount_tap(out, tapped)
                return (tables, hot, delta, fstates, sk, local_state,
                        kk), out

            c0 = (tables, hot, delta, fstates, sk0, local_state, kk)
            start = ci * T
            if mode == "sync":
                if not tier:
                    c1, outs = lax.scan(
                        step_t, c0, start + jnp.arange(T, dtype=jnp.int32))
                else:
                    R, rem = divmod(T, E)
                    c1, outs = trainer._windowed_scan(
                        step_t, c0, tier,
                        head=(start + jnp.arange(R * E, dtype=jnp.int32)
                              .reshape(R, E)) if R else None,
                        tail=(start + R * E
                              + jnp.arange(rem, dtype=jnp.int32))
                        if rem else None,
                        gids=gids,
                    )
            else:
                c1, outs = lax.scan(
                    lambda c, r: trainer._ssp_round(
                        step_t, c,
                        start + r * s + jnp.arange(s, dtype=jnp.int32),
                        tier, gids),
                    c0, jnp.arange(T // s, dtype=jnp.int32))
                outs = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), outs)
            (tables, hot, delta, fstates, sk, local_state, _) = c1
            # Per-segment sketch merge — the exact end-of-call psum merge
            # the per-chunk driver does, so K segments accumulate the
            # identical window a K-dispatch host loop would.
            new_sketches = dict(sketches)
            if sk:
                with jax.named_scope("fps.sketch_merge"):
                    for name in sorted(sk):
                        new_sketches[name] = (sketches[name]
                                              + _psum_workers(sk[name]))
            return (tables, hot, maps, gids, new_sketches, fstates,
                    local_state) + tick_rest, outs

        def group_votes(maps, gci0):
            """Device-side uniform overflow votes for one tick group's
            segments ``[gci0, gci0 + c_seg)``: every (step, worker)
            slice of every compacted table must fit its ``cold_budget``
            lane — the host certifier's rule, counted in-graph from the
            raw id columns (padding positions count like real ids,
            exactly as the compaction sees them). Hot membership is
            constant within a group (ticks land only at group
            boundaries), so the whole group votes in one pre-pass and
            ONE ``(c_seg,)`` psum makes the verdicts uniform across
            devices — K scalar collectives would otherwise dominate the
            dispatch-overhead win this driver exists for."""

            def body(ok, t):
                batch = plan.local_batch_at(iargs, widx, t)
                ids = trainer.logic.pulled_ids_traced(batch)
                fit = jnp.bool_(True)
                for name in sorted(compact):
                    tids = ids[name].reshape(-1).astype(jnp.int32)
                    if name in mapped:
                        slot = lookup_hot_slots(maps[name], tids)
                        cold = (tids >= 0) & (slot < 0)
                    else:
                        cold = tids >= tier[name]
                    fit = fit & (jnp.sum(cold.astype(jnp.int32))
                                 <= compact[name])
                return ok, fit

            with jax.named_scope("fps.megastep_vote"):
                start = gci0 * T
                _, fits = lax.scan(
                    body, jnp.int32(0),
                    start + jnp.arange(c_seg * T, dtype=jnp.int32))
                seg_ok = jnp.all(fits.reshape(c_seg, T), axis=1)
                bad = _psum_workers((~seg_ok).astype(jnp.int32))
            return (bad == 0).astype(jnp.int32)

        def seg_step(carry, ci, vote):
            if not compact:
                carry, outs = run_segment(carry, ci, {})
                return carry, (outs, jnp.int32(1))

            def compacted(c):
                return run_segment(c, ci, compact)

            def static(c):
                c2, outs = run_segment(c, ci, {})
                # The compacted branch's out channel carries a
                # cold_dropped counter per compacted table (the device
                # observability net); pad the static branch to the same
                # structure so lax.cond's branches agree.
                ht = dict(outs[resilience.HOT_TIER_KEY])
                for name in sorted(compact):
                    entry = dict(ht[name])
                    entry["cold_dropped"] = jnp.zeros((T,), jnp.int32)
                    ht[name] = entry
                outs = dict(outs,
                            **{resilience.HOT_TIER_KEY: ht})
                return c2, outs

            carry, outs = lax.cond(vote > 0, compacted, static, carry)
            return carry, (outs, vote)

        def apply_tick(carry):
            """In-graph tier tick (``MegastepTick``): fold the merged
            window into the decayed count-min, re-rank the head by
            (decayed count desc, id asc), and re-derive replica /
            slot-map / gid arrays from the canonical table — valid at
            the boundary because every segment ended with a flush
            reconcile. Pure data flow: the program never recompiles on
            a re-rank, exactly like the host Retierer."""
            from fps_tpu import sketch as sklib
            from fps_tpu.tiering.tick import device_top_ids

            (tables, hot, maps, gids, sketches, fstates, local_state,
             dcm, tct) = carry
            hot, maps, gids = dict(hot), dict(maps), dict(gids)
            sketches, dcm = dict(sketches), dict(dcm)
            extras = {}
            with jax.named_scope("fps.megastep_tick"):
                for name in tick_tables:
                    spec = trainer.store.specs[name]
                    H = mapped[name]
                    st = sklib.dcm_fold_traced(
                        tick.spec, dcm[name], sketches[name], tct)
                    dcm[name] = st
                    sketches[name] = jnp.zeros_like(sketches[name])
                    est = sklib.cm_query(
                        tick._table_cm(name), st,
                        jnp.arange(spec.num_ids, dtype=jnp.int32))
                    cand = device_top_ids(est, H)
                    cur = lookup_hot_slots(maps[name], cand)
                    promoted = H - jnp.sum((cur >= 0).astype(jnp.int32))
                    churn = promoted.astype(jnp.float32) / H
                    # The host Retierer's rule exactly: re-rank only when
                    # churn exceeds the threshold AND something was
                    # actually promoted (an identical set must keep its
                    # slot order).
                    swap = (churn > tick.churn_threshold) & (promoted > 0)
                    sel = jnp.where(swap, cand, gids[name])
                    gids[name] = sel
                    maps[name] = device_slot_map(spec.num_ids, sel)
                    hot[name] = replica_from_shard(
                        tables[name], sel,
                        num_shards=trainer.num_shards)
                    extras[name] = {"churn": churn,
                                    "re_ranked": swap.astype(jnp.int32)}
            return (tables, hot, maps, gids, sketches, fstates,
                    local_state, dcm, tct + 1), extras

        carry0 = (tables, hot, maps, gids, sketches, fstates, local_state)
        if tick is not None:
            carry0 = carry0 + (dict(tick_ops["dcm"]),
                               jnp.asarray(tick_ops["tick"], jnp.int32))

        def group_body(carry, g):
            gci0 = start_ci + g * c_seg
            group_fit = (group_votes(carry[2], gci0) if compact
                         else jnp.ones((c_seg,), jnp.int32))

            def seg_at(c, j):
                return seg_step(c, gci0 + j, group_fit[j])

            carry, (outs, votes) = lax.scan(
                seg_at, carry, jnp.arange(c_seg, dtype=jnp.int32))
            extras = {}
            if tick is not None:
                carry, extras = apply_tick(carry)
            return carry, (outs, votes, extras)

        carry, (outs, votes, extras) = lax.scan(
            group_body, carry0, jnp.arange(groups, dtype=jnp.int32))
        # (groups, c_seg, T, ...) -> (K * T, ...)
        outs = jax.tree.map(
            lambda x: x.reshape((groups * c_seg * T,) + x.shape[3:]), outs)
        votes = votes.reshape(-1)
        (tables, hot, maps, gids, sketches, fstates,
         local_state) = carry[:7]
        aux = {"votes": votes, "tick": {}, "extras": extras}
        if tick is not None:
            aux["tick"] = {"dcm": carry[7], "tick": carry[8]}
        tables = {**tables,
                  **{hot_key(n): v for n, v in sorted(hot.items())},
                  **{map_key(n): v for n, v in sorted(maps.items())},
                  **{ids_key(n): v for n, v in sorted(gids.items())},
                  **{fold_key(n): v for n, v in sorted(fstates.items())},
                  **{sketch_key(n): v
                     for n, v in sorted(sketches.items())}}
        return tables, local_state, outs, aux

    table_specs = {name: P(SHARD_AXIS, None) for name in trainer.store.specs}
    table_specs.update({hot_key(name): P() for name in tier})
    table_specs.update({map_key(name): P() for name in sorted(mapped)})
    table_specs.update({ids_key(name): P() for name in sorted(mapped)})
    table_specs.update({sketch_key(name): P() for name in sorted(track)})
    table_specs.update({fold_key(name): P(SHARD_AXIS, None)
                        for name in sorted(folds_on)})
    ls_spec = P((DATA_AXIS, SHARD_AXIS))

    def run(tables, local_state, iargs, start_ci, key, tick_ops):
        shmapped = jax.shard_map(
            mega_device,
            mesh=trainer.mesh,
            in_specs=(
                table_specs,
                jax.tree.map(lambda _: ls_spec, local_state),
                jax.tree.map(lambda _: P(), iargs),
                P(),
                P(),
                jax.tree.map(lambda _: P(), tick_ops),
            ),
            out_specs=(
                table_specs,
                jax.tree.map(lambda _: ls_spec, local_state),
                P(),
                P(),
            ),
            check_vma=False,
        )
        return shmapped(tables, local_state, iargs, start_ci, key,
                        tick_ops)

    donate = (0, 1) if trainer.config.donate else ()
    return jax.jit(run, donate_argnums=donate)


@host_span("run_megastep", call=True)
def run_megastep(trainer, tables, local_state, plan, key, *,
                 epochs: int = 1, chunks_per_dispatch: int = 4,
                 on_megastep=None, checkpointer=None,
                 checkpoint_every: int = 0, start_megastep: int = 0,
                 as_numpy: bool = True, rollback=None, recorder=None,
                 health=None, watchdog=None, tick=None):
    """Drive ``epochs`` passes of ``plan`` in K-chunk megasteps.

    Each dispatch runs ``chunks_per_dispatch`` chunk segments of
    ``trainer._indexed_call_steps(plan)`` steps each — bit-identical to
    the same number of per-chunk ``run_indexed`` dispatches (tables,
    metrics, and checkpoints; tested), but with per-chunk Python
    dispatch, host sync, and transfer overhead out of the hot loop.

    ``chunks_per_dispatch="auto"`` replaces the flag with measurement:
    a short calibration window (:mod:`fps_tpu.core.autok`) times one-
    and two-cadence-block dispatches on throwaway copies, models the
    host-serial share as ``h / (h + K*c)``, and picks the smallest K
    that clears the target share — rounded to the tick cadence, capped
    at one epoch's calls. The chosen K (``megastep.auto_k`` gauge) then
    drives a run bit-identical to passing it explicitly. Resuming a
    run (``start_megastep > 0``) should pass the original chosen K
    explicitly — megastep indices are counted in units of K.

    Checkpoints land every ``checkpoint_every`` megasteps under the
    GLOBAL megastep index (``start_megastep`` resumes there — shuffles
    and PRNG keys derive from the (epoch, chunk) pair, so a restart
    replays bit-identically). ``rollback`` / ``health`` / ``watchdog``
    adjudicate at megastep granularity: a poisoned megastep restores
    the pre-dispatch state and quarantines its index (the per-segment
    attribution rides the quarantine event via
    :func:`fps_tpu.core.resilience.health_by_segment`).

    ``tick`` (a :class:`fps_tpu.tiering.MegastepTick`) runs the
    adaptive-tiering boundary in-graph every ``tick.check_every``
    segments; ``chunks_per_dispatch`` must be a multiple of that
    cadence. The decayed sketch state round-trips between dispatches as
    device arrays (no forced host sync); host mirrors update lazily at
    checkpoint boundaries and end of run.

    Returns ``(tables, local_state, per-megastep metrics list)`` with
    each entry trimmed to the epoch's real steps (phantom weight-0
    trailing segments dropped, like ``run_indexed``).
    """
    from fps_tpu.core.driver import (
        _beat,
        _find_heartbeat,
        _phase,
        _watch,
    )

    cfg = trainer.config
    auto_k = isinstance(chunks_per_dispatch, str)
    if auto_k:
        if chunks_per_dispatch != "auto":
            raise ValueError(
                f"chunks_per_dispatch must be an int >= 1 or 'auto', "
                f"got {chunks_per_dispatch!r}")
        K = None  # resolved by the calibration window below
    else:
        K = int(chunks_per_dispatch)
        if K < 1:
            raise ValueError(
                f"chunks_per_dispatch must be >= 1, got "
                f"{chunks_per_dispatch}")
    if cfg.push_delay:
        raise ValueError(
            "run_megastep does not support push_delay: the in-flight ring "
            "buffer would need a per-segment flush that reorders delivery "
            "against the in-graph boundary ticks — use fit_stream / "
            "run_indexed for delayed-push emulation")
    if cfg.auto_tier:
        raise ValueError(
            "run_megastep does not support auto_tier: the planner's "
            "mid-run recompile has no boundary to land on inside one "
            "compiled program — plan first (tools/plan.py), then attach "
            "a MegastepTick for in-graph re-ranking")
    trainer._check_rollback(rollback)
    trainer._check_health(health)
    mode = "sync" if cfg.sync_every is None else "ssp"
    if (cfg.sync_every or None) != (plan.sync_every or None):
        raise ValueError("plan.sync_every must match TrainerConfig")
    if tick is not None:
        from fps_tpu.tiering.tick import MegastepTick

        if not isinstance(tick, MegastepTick):
            raise TypeError(
                f"tick must be a fps_tpu.tiering.MegastepTick, got "
                f"{type(tick).__name__}")
        if trainer.retierer is not None and trainer.retierer is not tick:
            raise ValueError(
                "trainer already has a retierer attached — run_megastep "
                "drives tier boundaries in-graph via its own MegastepTick")
        if not auto_k and K % tick.check_every:
            raise ValueError(
                f"chunks_per_dispatch={K} must be a multiple of "
                f"tick.check_every={tick.check_every} so every tick "
                "lands on a static in-graph boundary")
        # Attach-then-validate, restoring on failure: a rejected call
        # must not leave the tick installed as the trainer's retierer
        # (the mapped-tier resolution needs it attached to be checked
        # at all, so the attach cannot simply move below the check).
        prev_retierer = trainer.retierer
        trainer.retierer = tick
        if not trainer._mapped_tables():
            trainer.retierer = prev_retierer
            raise ValueError(
                "MegastepTick attached but no table resolves onto the "
                "mapped tier (needs a partial hot_tier, hot_sync_every "
                "> 1, and a multi-device mesh)")
    elif trainer.retierer is not None:
        raise ValueError(
            "run_megastep runs tier boundaries in-graph: attach a "
            "fps_tpu.tiering.MegastepTick (tick=...), not a host "
            "Retierer")
    rec = recorder if recorder is not None else trainer.recorder
    timer = PhaseTimer(rec) if rec is not None else None
    hb = _find_heartbeat(rec)
    quarantine = (rollback if rollback is not None and
                  resilience.as_guard(cfg.guard) is not None else None)
    sync_each = (quarantine is not None or health is not None
                 or watchdog is not None)
    from fps_tpu.core.driver import calls_per_epoch_of

    T_call = trainer._indexed_call_steps(plan)
    n_calls = calls_per_epoch_of(plan, T_call)
    T = plan.steps_per_epoch
    with _phase(timer, "attach_hot"):
        tables = trainer._attach_hot(tables, timer)
    if auto_k:
        from fps_tpu.core.autok import calibrate_chunks_per_dispatch

        K, overhead_s, per_chunk_s = calibrate_chunks_per_dispatch(
            trainer, tables, local_state, plan, key, mode=mode,
            tick=tick, n_calls=n_calls)
        if rec is not None:
            rec.set("megastep.auto_k", K)
            rec.event("megastep_auto_k", chosen_k=K,
                      overhead_s=round(overhead_s, 6),
                      per_chunk_s=round(per_chunk_s, 6))
    M = -(-n_calls // K)
    compact_cfg = trainer._cold_compact_map()
    vote_on = bool(compact_cfg) and bool(
        vote_certifiable_tables(trainer, plan))
    fn = trainer._get_megastep_fn(plan, mode, K, tick, timer)
    if rec is not None:
        rec.set("megastep.chunks_per_dispatch", K)
    all_metrics = []
    deferred_votes = []  # device vote arrays, converted lazily
    deferred_ticks = []  # device per-tick churn/re-rank telemetry
    saved_at = None
    tick_dev = None  # device-resident {dcm, tick} round-tripping dispatches
    iargs, cur_epoch = None, None
    end = epochs * M

    def tick_host_sync(tables):
        """Lazy host-mirror sync (+ sidecar) for the in-graph tick: only
        checkpoint boundaries and end-of-run pay the device→host read."""
        if tick is None or tick_dev is None:
            return
        tick.absorb(trainer, tick_dev, tables)

    def fold_votes(rec):
        if rec is None or not compact_cfg:
            return
        for votes, real in deferred_votes:
            # Weight the fold by REAL segments: a trimmed final
            # dispatch still runs K in-graph segments, but its trailing
            # weight-0 phantoms did no work — counting them would make
            # megastep.windows (and the vote counters) disagree with
            # the dispatched-work totals the bench asserts on. Phantom
            # segments are always the trailing ones, so the first
            # ``real`` votes are exactly the real windows' verdicts.
            if votes is None:
                # Uncertifiable dispatch: every real segment fell back
                # to the static routes. The verdict is ONE AND-ed bit
                # per window over every compacted table — per-table
                # attribution would multiply-count it, so the counter
                # is unlabeled.
                rec.inc("cold_route.vote_overflow_windows", real)
                continue
            v = np.asarray(votes).reshape(-1)[:real]
            ok = int((v != 0).sum())
            if ok:
                rec.inc("cold_route.vote_compact_windows", ok)
            if ok < v.size:
                rec.inc("cold_route.vote_overflow_windows",
                        int(v.size) - ok)
        deferred_votes.clear()

    def fold_ticks(rec):
        if rec is None:
            return
        for extras in deferred_ticks:
            for t in sorted(extras):
                rr = np.asarray(extras[t]["re_ranked"]).reshape(-1)
                ch = np.asarray(extras[t]["churn"]).reshape(-1)
                if int(rr.sum()):
                    rec.inc("tiering.re_ranks", int(rr.sum()), table=t)
                if ch.size:
                    rec.set("tiering.churn", float(ch[-1]), table=t)
        deferred_ticks.clear()

    try:
        for g in range(start_megastep, end):
            e, m = divmod(g, M)
            if rollback is not None and g in rollback.preset:
                rollback.skip(g)
                if rec is not None:
                    rec.inc("rollback.preset_skipped")
                    rec.flush()
                continue
            if cur_epoch != e:
                with _phase(timer, "ingest"):
                    iargs = plan.epoch_args(e)
                cur_epoch = e
            ekey = key_to_replicated(jax.random.fold_in(key, e),
                                     trainer.mesh)
            if quarantine is not None:
                last_good = (resilience.tree_copy(tables),
                             resilience.tree_copy(local_state))
                tick_good = (resilience.tree_copy(tick_dev)
                             if tick_dev is not None else None)
            if tick is not None:
                tick_ops = (tick_dev if tick_dev is not None
                            else tick.tick_ops(trainer))
            else:
                tick_ops = {}
            _beat(hb, g, "dispatch")
            restored = None
            with _watch(watchdog, "megastep", g):
                with _phase(timer, "megastep"), _phase(timer, "enqueue"):
                    tables, local_state, metrics, aux = fn(
                        tables, local_state, iargs, np.int32(m * K),
                        ekey, tick_ops)
                # Trim phantom weight-0 trailing rows so the epoch's
                # concatenated metrics match run_indexed's exactly.
                keep = max(0, min(K * T_call, T - m * K * T_call))
                # Real (non-phantom) chunk segments of this dispatch —
                # the unit megastep.windows and the vote fold count in.
                real_segs = min(K, -(-keep // T_call)) if T_call else K
                if keep < K * T_call:
                    metrics = jax.tree.map(lambda x: x[:keep], metrics)
                # The megastep is queued: its completion is the watcher's
                # to stamp (a None test with no recorder).
                watch_device("device.run_megastep", metrics, timer,
                             epoch=e, chunk=m * K, steps=keep)
                if quarantine is not None:
                    with _phase(timer, "host_sync"):
                        metrics, restored = trainer._maybe_quarantine(
                            quarantine, last_good, metrics, g, "megastep")
                elif sync_each:
                    with _phase(timer, "host_sync"):
                        metrics = jax.tree.map(np.asarray, metrics)
            if tick is not None:
                tick_dev = dict(aux["tick"])
            if compact_cfg:
                # Votes count at dispatch time even for a later-
                # quarantined megastep — the same convention as the host
                # certifier's cold_route.compact_chunks, which run_chunk
                # increments before adjudication. Each entry carries the
                # dispatch's REAL segment count so the fold can drop
                # trailing phantom windows.
                deferred_votes.append(
                    (aux["votes"] if vote_on else None, real_segs))
            ev = {"index": g} if rec is not None else None
            poison = 0
            if sync_each and (rec is not None or health is not None):
                poison = trainer._fold_metrics_accounting(rec, metrics, ev)
            if rec is not None:
                rec.inc("megastep.windows", real_segs)
                if restored is not None:
                    rec.inc("rollback.quarantined")
                    ev["quarantined"] = True
                    # Per-segment attribution: which in-graph chunk first
                    # reported poison (global chunk index within epoch).
                    seg = resilience.health_by_segment(metrics, K, T_call)
                    bad = [m * K + i for i, p in enumerate(seg) if p]
                    ev["poisoned_chunks_in_graph"] = bad
            trainer._apply_health_decision(health, rec, g, poison,
                                           "megastep")
            if restored is not None:
                if rec is not None:
                    rec.event("megastep", phases=timer.chunk_summary(),
                              **ev)
                    rec.flush()
                tables, local_state = restored
                if tick is not None:
                    tick_dev = tick_good
                continue
            if tick is not None and aux["extras"]:
                # Tick telemetry only for SURVIVING dispatches: a
                # quarantined megastep's re-ranks rolled back with its
                # state (the host path never counts re-ranks for
                # quarantined chunks either).
                deferred_ticks.append(aux["extras"])
            all_metrics.append(metrics)
            trainer.store.tables = dict(tables)
            if on_megastep is not None:
                with _phase(timer, "host_sync"):
                    host = jax.tree.map(np.asarray, metrics)
                if rec is not None and not sync_each:
                    trainer._fold_metrics_accounting(rec, host, ev)
                all_metrics[-1] = host
                with _phase(timer, "callback"):
                    on_megastep(g, host)
            if (checkpointer is not None and checkpoint_every > 0
                    and (g + 1) % checkpoint_every == 0):
                with _phase(timer, "checkpoint"):
                    tick_host_sync(tables)
                    trainer._save_checkpoint(checkpointer, g + 1,
                                             local_state)
                    if tick is not None and tick.state_dir is not None:
                        tick.save_boundary(g + 1, tables)
                saved_at = g + 1
            if rec is not None:
                rec.event("megastep", phases=timer.chunk_summary(), **ev)
                rec.flush()
        trainer.store.tables = dict(tables)
        tick_host_sync(tables)
        if (checkpointer is not None and end > start_megastep
                and saved_at != end):
            with _phase(timer, "checkpoint"):
                trainer._save_checkpoint(checkpointer, end, local_state,
                                         final=True)
                if tick is not None and tick.state_dir is not None:
                    tick.save_boundary(end, tables)
    finally:
        fold_votes(rec)
        fold_ticks(rec)
        if checkpointer is not None:
            with _phase(timer, "checkpoint"):
                checkpointer.flush()
    if on_megastep is None and as_numpy:
        with _phase(timer, "host_sync"):
            all_metrics = [jax.tree.map(np.asarray, mtree)
                           for mtree in all_metrics]
        if rec is not None and not sync_each:
            for mtree in all_metrics:
                trainer._fold_metrics_accounting(rec, mtree)
    if rec is not None:
        rec.flush()
    return tables, local_state, all_metrics
