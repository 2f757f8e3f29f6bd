"""Adaptive tiering (fps_tpu.tiering): online hot-set re-ranking + the
auto-tiering planner.

The contracts under test, per docs/performance.md "Adaptive tiering":

* **mapped == static on the identity ranking** — the adaptive tier with
  hot set ``[0, H)`` trains to PR 5's static head's values, to the few
  ulps the compiler's own rounding differs by (the slot-map machinery
  changes routing representation, not semantics);
* **re-ranks NEVER recompile** — the hot membership rides as replicated
  slot-map/gid DATA; the compile cache is keyed on H only (asserted on
  the cache itself AND on the program-build count);
* **the flush-reconcile invariant survives re-ranks** — at any boundary
  the replica is a pure projection of the canonical table's CURRENT hot
  ids, and checkpoints stay canonical (one table per spec, restorable
  by an untiered trainer);
* **sidecar resume is bit-identical** — a run resumed from checkpoint +
  tracker sidecar replays the straight run's re-rank decisions exactly;
* the planner derives (H, E, dense) from densities, and the fold
  resolution REPORTS (warns) instead of silently disengaging.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fps_tpu.core.api import ServerLogic
from fps_tpu.core.checkpoint import Checkpointer
from fps_tpu.core.driver import num_workers_of
from fps_tpu.core.store import (
    hot_key,
    hot_slot_map,
    lookup_hot_slots,
    sketch_key,
)
from fps_tpu.models.logistic_regression import (
    LogRegConfig,
    logistic_regression,
)
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.testing.workloads import (
    NF,
    logreg_chunks,
    logreg_data,
    weights,
)
from fps_tpu.tiering import Retierer, TableDensity, plan_tables
from fps_tpu import sketch as sk


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _make_trainer(mesh, *, hot_tier=0, hot_sync_every=1, retierer=None,
                  **cfg_over):
    trainer, store = logistic_regression(
        mesh, LogRegConfig(num_features=NF, learning_rate=0.5))
    if hot_tier:
        for name, spec in store.specs.items():
            store.specs[name] = dataclasses.replace(
                spec, hot_tier=min(hot_tier, spec.num_ids))
    trainer.config = dataclasses.replace(
        trainer.config, hot_sync_every=hot_sync_every, **cfg_over)
    trainer.retierer = retierer
    return trainer, store


def _fit(trainer, chunks, **kw):
    tables, ls = trainer.init_state(jax.random.key(0))
    return trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1),
                              **kw)


# ---------------------------------------------------------------------------
# Mapped tier semantics.
# ---------------------------------------------------------------------------

def test_mapped_identity_ranking_matches_static_head(devices8):
    """The adaptive (slot-mapped) tier with hot set [0, H) must train to
    the same values as the static id<H tier — the mapped routing is a
    representation change, not a semantics change.

    To a few ulps of the largest weight, not bit for bit, and in cold rows
    as in hot ones: every store op adds in the same order on both sides
    (running the mapped trainer with ``reconcile_hot`` in place of
    ``reconcile_hot_mapped``, or ``split_hot_push`` in place of
    ``split_hot_push_slots``, changes no bit), but the mapped PULL decides
    membership by a gather from the slot map where the static one
    compares ``id < H``, and XLA's CPU pipeline compiles the worker's
    float arithmetic downstream of a gather differently from downstream
    of a compare (``lookup_hot_slots`` answered by the compare: bit
    equality, PR 29). Which float op it re-rounds is the compiler's
    choice; what is held exactly is every count, and the replica."""
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=2)

    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=3)
    _, _, m_static = _fit(trainer, chunks)
    w_static = weights(store)

    # check_every > len(chunks): the Retierer engages the mapped routes
    # but never re-ranks, so the hot set stays the identity head.
    rt = Retierer(check_every=100)
    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=3,
                                   retierer=rt)
    tables, _, m_mapped = _fit(trainer, chunks)
    w_mapped = weights(store)
    # Measured: half an ulp of the largest weight after the 64 steps.
    np.testing.assert_allclose(
        w_mapped, w_static, rtol=0,
        atol=8 * np.spacing(np.abs(w_static).max()))
    for a, b in zip(m_static, m_mapped, strict=True):
        for k in ("n", "mistakes"):
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    # Boundary invariant, mapped flavor: replica == canonical rows of
    # the CURRENT hot ids.
    gids = rt.hot_ids_for("weights", 64)
    assert np.array_equal(np.asarray(tables[hot_key("weights")]),
                          store.lookup_host("weights", gids))


def test_retierer_on_disengaged_tier_lowers_untiered_program(devices8):
    """Attaching a Retierer must not perturb programs whose tier the
    resolution disengages: exact mode (hot_sync_every=1) and
    untiered specs both lower BYTE-IDENTICAL text to the plain untiered
    trainer — tracking is gated on the RESOLVED tier, not the raw spec,
    so no orphan sketch ops ride a program nothing will consume."""
    from fps_tpu.parallel.mesh import key_to_replicated

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=1)

    def lowered(**kw):
        trainer, _ = _make_trainer(mesh, **kw)
        tables, ls = trainer.init_state(jax.random.key(0))
        tables = trainer._attach_hot(tables)
        batches = trainer._place_chunk(chunks[0], "sync")
        key = key_to_replicated(jax.random.key(1), mesh)
        return trainer._get_compiled("sync").lower(
            tables, ls, batches, key).as_text()

    base = lowered()
    assert lowered(hot_tier=64, hot_sync_every=1,
                   retierer=Retierer(check_every=2)) == base
    assert lowered(retierer=Retierer(check_every=2)) == base


def test_rerank_zero_recompiles_and_boundary_invariant(devices8):
    """Forced re-ranks must (a) actually fire, (b) hit the SAME compiled
    program — zero recompiles, counted on both the compile cache and the
    program-build calls — and (c) keep the replica a projection of the
    canonical rows of whatever ids are currently hot. Two identical runs
    stay bit-identical (the re-rank schedule is deterministic)."""
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=2)
    results = []
    for _ in range(2):
        rt = Retierer(check_every=2, churn_threshold=-1.0)
        trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=3,
                                       retierer=rt)
        builds = []
        orig = type(trainer)._build_chunk_fn

        def counting(self, mode, *args, _orig=orig, _b=builds, **kw):
            _b.append(mode)
            return _orig(self, mode, *args, **kw)

        trainer._build_chunk_fn = counting.__get__(trainer)
        tables, _, m = _fit(trainer, chunks)
        assert rt.re_ranks >= 1
        assert len(trainer._compiled) == 1, "re-rank recompiled"
        assert builds == ["sync"], f"program rebuilt: {builds}"
        gids = rt.hot_ids_for("weights", 64)
        assert np.array_equal(np.asarray(tables[hot_key("weights")]),
                              store.lookup_host("weights", gids))
        results.append((weights(store), m, gids.copy()))
    assert np.array_equal(results[0][0], results[1][0])
    assert np.array_equal(results[0][2], results[1][2])
    assert _tree_equal(results[0][1], results[1][1])


def test_rerank_checkpoints_stay_canonical(tmp_path, devices8):
    """A checkpoint written by a re-ranked run is one canonical table in
    logical id order — no aux entries, restorable by a plain UNTIERED
    trainer, equal to the run's own host view."""
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=2)
    rt = Retierer(check_every=2, churn_threshold=-1.0)
    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=3,
                                   retierer=rt)
    d = str(tmp_path / "ck")
    with Checkpointer(d) as ckpt:
        _fit(trainer, chunks, checkpointer=ckpt, checkpoint_every=1)
        assert rt.re_ranks >= 1
        want = weights(store)

        untiered, ustore = _make_trainer(mesh)
        tables, ls = untiered.init_state(jax.random.key(0))
        tables, ls, step = untiered.restore_checkpoint(ckpt, ls)
        assert not any("::" in k for k in tables)
        assert np.array_equal(weights(ustore), want)


def test_sidecar_resume_bit_identical(tmp_path, devices8):
    """Kill-free, in-process version of the retier_kill chaos scenario:
    a run resumed from (checkpoint, tracker sidecar) replays the
    straight adaptive run's re-rank decisions and final weights
    bit-for-bit."""
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=2)
    stop_at = 3

    def adaptive_trainer(state_dir):
        rt = Retierer(check_every=2, churn_threshold=-1.0,
                      state_dir=state_dir)
        return _make_trainer(mesh, hot_tier=64, hot_sync_every=3,
                             retierer=rt)

    d1 = str(tmp_path / "straight")
    trainer, store = adaptive_trainer(d1)
    _fit(trainer, chunks)
    want = weights(store)
    want_gids = trainer.retierer.hot_ids_for("weights", 64).copy()

    class Stop(Exception):
        pass

    def stop(i, _m):
        if i == stop_at:
            raise Stop

    d2 = str(tmp_path / "resumed")
    trainer, store = adaptive_trainer(d2)
    tables, ls = trainer.init_state(jax.random.key(0))
    with Checkpointer(d2) as ckpt:
        with pytest.raises(Stop):
            trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1),
                               checkpointer=ckpt, checkpoint_every=1,
                               on_chunk=stop)
        # Fresh trainer + fresh Retierer, like a restarted process.
        trainer, store = adaptive_trainer(d2)
        tables, ls = trainer.init_state(jax.random.key(0))
        tables, ls, start = trainer.restore_checkpoint(ckpt, ls)
        assert trainer.retierer.restore(start) is True
        trainer.fit_stream(tables, ls, iter(chunks[start:]),
                           jax.random.key(1), start_step=start)
    assert np.array_equal(weights(store), want)
    assert np.array_equal(trainer.retierer.hot_ids_for("weights", 64),
                          want_gids)


def test_device_tracking_matches_host_counts(devices8):
    """The device-side window sketch (updated inside the compiled step,
    psum-merged across the mesh) must equal a HOST cm_update over the
    chunk's live pulled ids under the SAME per-table hashing spec — the
    seed-agreement contract between tracker halves."""
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=1)
    rt = Retierer(check_every=100)  # never folds: window keeps raw sums
    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=3,
                                   retierer=rt)
    tables, ls = trainer.init_state(jax.random.key(0))
    tables, ls, _ = trainer.fit_stream(tables, ls, iter(chunks[:1]),
                                       jax.random.key(1))
    win = np.asarray(tables[sketch_key("weights")])
    spec = rt._table_cm("weights")
    ids = chunks[0]["feat_ids"].reshape(-1)
    live = (np.repeat(chunks[0]["weight"].reshape(-1),
                      chunks[0]["feat_ids"].shape[-1]) > 0)
    host = sk.cm_update(spec, sk.cm_init(spec),
                        jnp.asarray(np.where(live, ids, -1).astype(
                            np.int32)))
    np.testing.assert_allclose(win, np.asarray(host))


# ---------------------------------------------------------------------------
# Store-level mapped primitives.
# ---------------------------------------------------------------------------

def test_hot_slot_map_contract():
    m = hot_slot_map(10, np.array([7, 2, 9]))
    assert m.shape == (11,)
    assert m[7] == 0 and m[2] == 1 and m[9] == 2
    assert m[10] == -1 and m[0] == -1
    slots = np.asarray(lookup_hot_slots(
        jnp.asarray(m), jnp.asarray(np.array([2, -1, 0, 9], np.int32))))
    assert slots.tolist() == [1, -1, -1, 2]
    with pytest.raises(ValueError, match="duplicates"):
        hot_slot_map(10, np.array([1, 1]))
    with pytest.raises(ValueError, match="outside"):
        hot_slot_map(10, np.array([10]))


def test_rows_replica_requires_valid_ids(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    _, store = _make_trainer(mesh)
    store.init(jax.random.key(0))
    rep = np.asarray(store.rows_replica("weights", np.array([5, 3, 380])))
    assert np.array_equal(rep,
                          store.lookup_host("weights",
                                            np.array([5, 3, 380])))
    with pytest.raises(ValueError, match="subset"):
        store.rows_replica("weights", np.array([NF]))
    with pytest.raises(ValueError, match="subset"):
        store.rows_replica("weights", np.array([], np.int64))


# ---------------------------------------------------------------------------
# Resolution policy: the fold gap reports instead of silently falling back.
# ---------------------------------------------------------------------------

def test_fold_resolution_warns_not_silent(devices8):
    # PR 10 moved max/min onto the tier (windowed extremum buffer), so
    # the demotion — and its warning — is down to the per-push folds:
    # a callable combine and apply_fn.
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=4)
    trainer.server_logic["weights"] = ServerLogic(
        combine=lambda summed, counts: summed)
    with pytest.warns(UserWarning, match="gathered route"):
        assert trainer._resolve_hot_tier(store.specs["weights"]) == 0
    # Once per table per trainer — resolution runs per compile AND per
    # chunk via _attach_hot, so a repeat must stay silent.
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        assert trainer._resolve_hot_tier(store.specs["weights"]) == 0

    # max/min no longer demote: the tier engages.
    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=4)
    trainer.server_logic["weights"] = ServerLogic(combine="max")
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert trainer._resolve_hot_tier(store.specs["weights"]) == 64

    # apply_fn trips the same report.
    trainer, store = _make_trainer(mesh, hot_tier=64, hot_sync_every=4)
    trainer.server_logic["weights"] = ServerLogic(
        apply_fn=lambda cur, d: cur + d)
    with pytest.warns(UserWarning, match="apply_fn"):
        assert trainer._resolve_hot_tier(store.specs["weights"]) == 0


# ---------------------------------------------------------------------------
# Planner.
# ---------------------------------------------------------------------------

def _zipf_density(name, num_ids, dim, alpha=1.2):
    return TableDensity(name, num_ids, dim,
                        1.0 / np.arange(1, num_ids + 1) ** alpha)


def test_planner_full_replication_under_budget():
    plans = plan_tables([_zipf_density("t", 1024, 8)],
                        batch_rows_per_step=256)
    p = plans["t"]
    assert p.hot_tier == 1024 and p.hot_sync_every >= 2
    assert "full replication" in p.reason


def test_planner_partial_head_respects_budget_and_coverage():
    # 1M ids x dim 16 x 4B = 64MB > a 1MB budget -> partial head.
    plans = plan_tables([_zipf_density("t", 1 << 20, 16, alpha=1.4)],
                        batch_rows_per_step=4096,
                        replica_budget_bytes=1 << 20)
    p = plans["t"]
    budget_rows = (1 << 20) // (16 * 4)
    assert 0 < p.hot_tier <= budget_rows
    assert 2 <= p.hot_sync_every <= 8
    assert p.coverage >= 0.5


def test_planner_flat_distribution_stays_untiered():
    flat = TableDensity("t", 1 << 16, 16, np.ones(1 << 16))
    plans = plan_tables([flat], batch_rows_per_step=4096,
                        replica_budget_bytes=1 << 18)
    assert plans["t"].hot_tier == 0 and plans["t"].hot_sync_every == 1
    assert "flat" in plans["t"].reason


def test_planner_no_evidence_stays_untiered_and_global_e():
    from fps_tpu.tiering import global_sync_every

    empty = TableDensity("a", 64, 4, np.zeros(64))
    hot = _zipf_density("b", 64, 4)
    plans = plan_tables([empty, hot], batch_rows_per_step=64)
    assert plans["a"].hot_tier == 0
    assert plans["b"].hot_tier == 64
    assert global_sync_every(plans) == plans["b"].hot_sync_every
    assert global_sync_every({"a": plans["a"]}) == 1


def test_planner_cold_budget_for_partial_heads():
    from fps_tpu.tiering.planner import choose_cold_budget

    # Partial head on a non-dense table: the plan carries a compacted
    # cold lane sized to the UNCOVERED traffic (margined, multiple of 8).
    plans = plan_tables([_zipf_density("t", 1 << 20, 16, alpha=1.4)],
                        batch_rows_per_step=4096,
                        replica_budget_bytes=1 << 20,
                        num_workers=8)
    p = plans["t"]
    assert 0 < p.hot_tier < (1 << 20)
    assert p.cold_budget == choose_cold_budget(
        p.coverage, 4096, num_workers=8)
    assert p.cold_budget % 8 == 0
    assert "compacted cold lane" in p.reason
    # Full replication: no cold route, no lane.
    plans = plan_tables([_zipf_density("t", 1024, 8)],
                        batch_rows_per_step=256, num_workers=8)
    assert plans["t"].cold_budget == 0
    # Low coverage: a lane as wide as the batch buys nothing -> 0.
    assert choose_cold_budget(0.1, 4096, num_workers=8) == 0
    # knobs() compares the compile-affecting fields only.
    a = plans["t"]
    b = dataclasses.replace(a, coverage=0.123, reason="different")
    assert a.knobs() == b.knobs()
    assert a.knobs() != dataclasses.replace(a, cold_budget=8).knobs()


def test_replan_unchanged_noop_changed_recompiles_once(devices8):
    """Periodic RE-planning (Retierer.replan_every): an unchanged plan
    is a strict no-op — zero recompiles, counted on the compile cache
    AND the program-build calls; a changed plan (here: the replica
    budget collapses, forcing full replication -> partial head)
    recompiles exactly once."""
    from fps_tpu import obs

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=4)
    rt = Retierer(auto_plan=True, warmup_checks=1, check_every=1,
                  replan_every=1)
    trainer, store = _make_trainer(mesh, retierer=rt)
    builds = []
    orig = type(trainer)._build_chunk_fn

    def counting(self, mode, *args, _orig=orig, _b=builds, **kw):
        _b.append(mode)
        return _orig(self, mode, *args, **kw)

    trainer._build_chunk_fn = counting.__get__(trainer)
    rec = obs.Recorder(sinks=[])
    trainer.recorder = rec

    # Phase 1: warmup program + the planned program = 2 builds; every
    # boundary after the plan re-plans with UNCHANGED knobs (stationary
    # stream) — zero further builds.
    _fit(trainer, chunks)
    assert rt.planned
    n_initial = len(builds)
    assert n_initial == 2, builds
    assert rec.counter_value("tiering.replans", changed="false") >= 1
    assert rec.counter_value("tiering.replans", changed="true") == 0
    plan_before = {n: p.knobs() for n, p in rt.plans.items()}

    # Phase 2: collapse the replica budget — the next re-plan must land
    # a DIFFERENT plan (partial head) with exactly one recompile.
    rt.plan_kwargs["replica_budget_bytes"] = 64 * 4  # 64 rows of dim 1
    tables, ls = trainer.init_state(jax.random.key(0))
    trainer.fit_stream(tables, ls, iter(chunks[:2]), jax.random.key(2))
    assert rec.counter_value("tiering.replans", changed="true") == 1
    assert {n: p.knobs() for n, p in rt.plans.items()} != plan_before
    assert store.specs["weights"].hot_tier < NF
    assert len(builds) == n_initial + 1, builds

    # Phase 3: further boundaries with the (new) stationary plan are
    # no-ops again.
    n_after = len(builds)
    trainer.fit_stream(trainer.store.tables, ls, iter(chunks[2:4]),
                       jax.random.key(3), start_step=2)
    assert len(builds) == n_after, builds
    assert np.isfinite(weights(store)).all()


def test_plan_application_preserves_fold_state(devices8):
    """Applying (or re-applying) a plan strips the DERIVABLE aux entries
    (replica, slot maps, sketches — re-split from the canonical table)
    but must KEEP ::fold optimizer state: it is not a projection of the
    canonical table, and zeroing a live Adagrad accumulator mid-run
    would silently change step sizes."""
    from fps_tpu.tiering.planner import TierPlan

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    trainer, store = _make_trainer(mesh, hot_tier=NF, hot_sync_every=3)
    trainer.server_logic["weights"] = dataclasses.replace(
        trainer.server_logic["weights"], hot_fold="adagrad")
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=1)
    tables, _, _ = _fit(trainer, chunks)
    state_before = np.asarray(tables["weights::fold"])
    assert np.any(state_before != 0)  # the run really accumulated state

    # Install a plan that keeps the table's knobs (full replication,
    # same E): the strip must preserve the live fold state verbatim —
    # a dropped entry would be re-derived as ZEROS by _attach_hot.
    rt = Retierer()
    trainer.retierer = rt
    plans = {"weights": TierPlan(NF, 3, False, 1.0, "test")}
    out = rt._install_plans(trainer, dict(tables), plans, {}, None,
                            what="test")
    assert "weights::fold" in out
    assert np.array_equal(np.asarray(out["weights::fold"]), state_before)
    # The derivable kinds were genuinely stripped + re-derived (the
    # replica is a projection, so re-derivation is value-identical).
    assert hot_key("weights") in out


def test_planner_validates_density():
    with pytest.raises(ValueError, match="shape"):
        TableDensity("t", 8, 4, np.zeros(9))
    with pytest.raises(ValueError, match="negative"):
        TableDensity("t", 2, 4, np.array([-1.0, 1.0]))


def test_top_ids_matches_full_sort_with_ties():
    from fps_tpu.tiering.retier import top_ids

    rng = np.random.default_rng(0)
    # Heavy ties: small integer counts force the tie-break to matter.
    est = rng.integers(0, 5, 1000).astype(np.float64)
    for H in (1, 7, 64, 999, 1000, 1500):
        full = np.lexsort((np.arange(len(est)), -est))[:min(H, len(est))]
        np.testing.assert_array_equal(top_ids(est, H), full)


def test_sidecar_sweep_keeps_checkpointed_steps(tmp_path):
    from fps_tpu.core import snapshot_format as fmt

    rt = Retierer(state_dir=str(tmp_path), keep=2)
    # A published snapshot at step 2: its sidecar must survive the sweep
    # even once newer sidecars push it past `keep` — that is the step a
    # supervised resume will restore.
    open(fmt.snapshot_path(str(tmp_path), 2), "wb").close()
    for step in range(1, 7):
        rt._save_sidecar(step, {})
    from fps_tpu.tiering import sidecar_path

    import os

    left = sorted(f for f in os.listdir(tmp_path)
                  if f.startswith("tiering-"))
    assert os.path.basename(sidecar_path(str(tmp_path), 2)) in left
    assert os.path.basename(sidecar_path(str(tmp_path), 6)) in left
    assert os.path.basename(sidecar_path(str(tmp_path), 5)) in left
    assert len(left) == 3  # newest 2 + the checkpointed step


def test_auto_tier_push_delay_rejected_at_run_entry(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=1)
    trainer, _ = _make_trainer(mesh, auto_tier=True, push_delay=2)
    tables, ls = trainer.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="auto_tier and push_delay"):
        trainer.fit_stream(tables, ls, iter(chunks), jax.random.key(1))


# ---------------------------------------------------------------------------
# Auto-tier end to end + probe lowering.
# ---------------------------------------------------------------------------

def test_auto_tier_plans_and_trains(devices8):
    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    train, _ = logreg_data()
    chunks = logreg_chunks(train, num_workers_of(mesh), epochs=2)
    trainer, store = _make_trainer(mesh, auto_tier=True)
    _fit(trainer, chunks)
    rt = trainer.retierer
    assert rt is not None and rt.planned
    assert "weights" in rt.plans
    # The plan landed on the live spec/config.
    assert store.specs["weights"].hot_tier == rt.plans["weights"].hot_tier
    assert np.isfinite(weights(store)).all()


def test_probe_plan_lowering_and_rerank_identity(devices8):
    """The probe program lowers with the plan's routes, and two
    different hot id sets lower BYTE-IDENTICAL text (the unit-level
    recompile-freedom check; tools/audit_programs.py pins the same
    claim on the MF workload)."""
    from fps_tpu.analysis import collective_profile
    from fps_tpu.core.store import TableSpec
    from fps_tpu.tiering import TierPlan, lowered_plan_text

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    specs = {"t": TableSpec("t", 256, 8)}
    plans = {"t": TierPlan(64, 2, False, 0.9, "test")}
    rt1 = Retierer()
    text1 = lowered_plan_text(mesh, specs, plans, hot_sync_every=2,
                              retierer=rt1)
    assert collective_profile(text1, 0)
    rt2 = Retierer()
    rt2.hot_ids["t"] = np.arange(64, 128, dtype=np.int64)
    text2 = lowered_plan_text(mesh, specs, plans, hot_sync_every=2,
                              retierer=rt2)
    assert text1 == text2


# ---------------------------------------------------------------------------
# Chaos: SIGKILL between re-rank and re-split (slow; shared with the sweep).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_retier_kill_resumes_bit_identical(tmp_path):
    from fps_tpu.testing.supervised_demo import run_retier_kill_scenario

    ok, detail = run_retier_kill_scenario(str(tmp_path))
    assert ok, detail
