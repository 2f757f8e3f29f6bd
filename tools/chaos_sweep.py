"""Chaos sweep: run the fault-injector matrix end-to-end and print a
one-line survival digest (compact JSON).

Scenarios (all deterministic — fps_tpu.testing.chaos; the training
harness is shared with tests/test_resilience.py via
fps_tpu.testing.workloads):

* ``nan_mask`` / ``inf_mask``  — NaN/Inf-poisoned chunk under guard="mask":
  survives iff every table stays finite, the health channel fired, and
  test accuracy stays within tolerance of the clean run.
* ``huge_norm_mask``           — finite norm-exploded deltas under a
  norm_limit guard: survives iff the norm tier fired and quality holds.
* ``observe_rollback``         — guard="observe" + RollbackPolicy:
  survives iff exactly the poisoned chunk is quarantined and the tables
  stay finite.
* ``ckpt_truncate`` / ``ckpt_bitflip`` — corrupt the newest of two
  snapshots: survives iff restore falls back to the older one.
* ``tmp_sweep``                — stale mid-write tmp file: survives iff a
  fresh Checkpointer sweeps it and restores normally.
* ``supervised``               — a SIGSTOP-wedged child under
  ``tools/supervise.py``: survives iff the supervisor deadline-aborts
  (SIGTERM→SIGKILL), restarts with backoff, the resumed run restores
  ``latest_valid_step`` (at most one chunk of lost work), no corrupt
  snapshot is ever selected, and the final weights are BIT-IDENTICAL to
  an unsupervised straight run.
* ``prefetch_kill``            — SIGKILL while the overlapped host
  pipeline's worker thread is assembling a chunk several indices ahead
  of the dispatch point (``--prefetch 2``): survives iff the supervisor
  restarts the child once, nothing is quarantined (one crash is not
  determinism evidence), and the resumed pipeline-on run reproduces a
  straight pipeline-on run bit-for-bit.
* ``serve_while_train``        — a concurrent ``fps_tpu.serve``
  ReadServer polls the supervised child's checkpoint dir while the child
  is SIGKILLed mid-run and a torn full-named snapshot candidate is
  planted: survives iff readers never observe a torn, CRC-failing, or
  backward-moving table, the torn candidate is rejected, the reader
  converges on the newest valid snapshot byte-for-byte, and a post-run
  quarantine of the served snapshot swaps the reader BACKWARD
  (``docs/serving.md``).
* ``hot_tier_kill``            — SIGKILL between hot-tier reconciles
  under the supervisor (two-tier storage on, ``--hot-tier``/
  ``--hot-sync-every``): survives iff the restart restores from the
  last reconciled snapshot (one canonical table — the flush-reconcile
  boundary invariant), re-splits the hot replica, replays exactly one
  chunk, quarantines nothing, and reproduces a straight tiered run's
  final weights bit-for-bit.
* ``retier_kill``              — SIGKILL between a hot-set re-rank and
  the next checkpoint with the ADAPTIVE tier on (``fps_tpu.tiering``:
  mapped hot set, device-side tracking, forced re-rank cadence,
  tracker sidecars): survives iff the restart restores the last
  reconciled snapshot AND the matching tracker sidecar, re-derives the
  replica/slot-map from both, quarantines nothing, and replays to
  final weights bit-identical to a straight adaptive run (i.e. the
  resumed re-rank decisions are the straight run's).
* ``reconcile_shard_kill``     — SIGKILL between a sharded
  (reduce-scatter) reconcile window and the next checkpoint, with a
  stateful Adagrad hot-tier fold on (``--hot-fold adagrad``: per-row
  optimizer state sharded over the replica axis, persisted as
  ``fold::`` checkpoint arrays): survives iff the restart restores the
  canonical tables AND the matching fold state (fold arrays present in
  the snapshot, canonical table bytes untouched), quarantines nothing,
  and replays to final weights bit-identical to a straight run — a
  zero-restarted Adagrad accumulator would diverge.

* ``delta_chain_kill``         — delta-snapshot chains
  (``Checkpointer(delta=DeltaPolicy(...))``): a supervised child
  publishing one full + per-chunk deltas is SIGKILLed mid-chain, and a
  compaction victim is SIGKILLed at EVERY fold phase (pre-rename /
  pre-sweep / mid-sweep): survives iff every crash recovers to the last
  verified chain link (resume bit-identical; the delta encoding itself
  bit-identical to full snapshots) and a rerun compaction completes.
* ``fleet_fence``              — step-fenced serving fleet
  (``fps_tpu.serve.fleet``): N readers under quorum fencing over a
  SIGKILLed+restarted delta-publishing child, with one READER killed
  and restarted mid-swap: survives iff the fence stays forward-monotone,
  no reader ever answers a superseded step (restart included), delta
  chains hot-swap incrementally, and the fleet converges byte-identical
  to the resolved chain.

* ``pod_kill_one_host``        — pod of 3 member agents
  (``fps_tpu.supervise.pod``) over one shared pod dir; ONE member's
  child is SIGKILLed: survives iff the leader makes one pod-wide
  decision (coordinated abort + restart of ALL members from the common
  ``latest_valid_step``), nothing is quarantined or evicted, and every
  member finishes bit-identical to an uninterrupted run.
* ``pod_partition_coordinator`` — the lease HOLDER's member agent is
  SIGSTOPped: survives iff a follower seizes the expired lease (fencing
  epoch bump), fences every member dir, restarts the pod, the stale
  leader's orphan child is REFUSED by the fence when it next publishes
  (StaleEpochError in its log; no epoch-stale snapshot postdates the
  fence), and the released leader rejoins to a bit-identical finish.
* ``pod_flapping_member``      — one member's child crashes at the same
  chunk on every attempt: survives iff two coordinated restarts converge
  on a POD-WIDE quarantine of that chunk, EVERY member skips it (no host
  re-dispatches a chunk another host proved poisonous), and all members
  match a straight run carrying the same quarantine preset.
* ``pod_elastic_resize``       — a whole host dies (member agent + child
  SIGKILLed) and later returns: survives iff the leader evicts it (the
  pod re-plans at W-1), the survivors continue, the returning member is
  re-admitted at the next boundary from a SYNCED canonical snapshot, and
  every member finishes byte-identical to a straight W-host run — with
  zero torn or epoch-stale checkpoints published.

* ``storage_brownout``         — deterministic I/O faults
  (``fps_tpu.testing.faultfs``: transient EIO writes, slow fsyncs, a
  torn rename, EIO/stale/ENOENT reads, flaky scans) against a live
  training run + 2-reader quorum fleet: survives iff training never
  crashes and finishes BIT-identical to the fault-free run, at least
  one publish degrades (backlog raised, drained after recovery), the
  fleet serves last-good throughout with zero fence violations, and
  the read plane's degradation is counted (poll_errors), never a
  frozen reader.
* ``storage_blackout_recover`` — every snapshot write fails for a
  window covering three publishes' full retry budgets: survives iff
  training continues with a BOUNDED publish backlog (exactly the
  blacked-out publishes), the first landed publish drains it, the
  recovered directory's newest snapshot is bit-identical to the clean
  run's, and a fresh process resumes from it.
* ``enospc_compaction``        — ENOSPC through the LSM fold's whole
  retry budget: survives iff the fold aborts with the delta chain
  INTACT (still resolvable), ``storage.compaction_aborts`` counts it,
  and the next publish after recovery re-triggers a compaction that
  completes bit-exactly.
* ``slow_lease_near_ttl``      — the pod lease holder's renewal writes
  are slowed past TTL/2: survives iff the leader steps down CLEANLY
  before its record expires, stops renewing so the record lapses, a
  follower seizes with a strictly-higher fencing epoch, and the
  deposed leader stays out.

* ``tenant_poison_isolation``  — two tenants under one
  ``fps_tpu.tenancy.TenantManager``; tenant a's child poison-crashes at
  the same chunk every attempt: survives iff a's OWN supervisor
  quarantines it (2 restarts, chunk skipped) while tenant b finishes
  with zero restarts, BIT-IDENTICAL to its solo run, both fencing
  epochs untouched, and the post-run namespace audit clean.
* ``tenant_enospc_brownout``   — an ENOSPC faultfs schedule carried in
  tenant a's spec env (the only injection channel — per-tenant by
  construction) fails a run of its snapshot writes: survives iff a
  degrades (publishes skipped + counted in a's own telemetry) without
  restarting and still matches the fault-free solo weights, b sees zero
  degraded publishes and stays bit-identical, audit clean.
* ``tenant_reader_wedge``      — each tenant namespace runs its own
  heartbeating serving reader; a's reader is SIGSTOPped, detected
  wedged via a's own beacons, and restarted: survives iff b's reader
  never reads as wedged, b's serve fence bytes are untouched by the
  whole episode, the restarted reader catches up
  (``time_to_recovered_s``), both tenants' weights stay bit-identical
  to the clean run, audit clean.
* ``tenant_noisy_neighbor``    — a's flat access profile demands more
  replica budget than its weighted share; ``plan_tenants`` must grant
  b its FULL demand (plan knobs identical to b's solo plan) while only
  a's hot tier shrinks, then real children train at the arbitrated
  knobs: survives iff b is bit-identical to its solo run at those
  knobs and a still finishes cleanly, audit clean.

The digest also carries the clean run's program CERTIFICATE
(``fps_tpu.analysis``, ``docs/analysis.md``): the compiled logreg step
is audited against its derived contract, so a regression in collective
structure / donation / host-transfer freedom fails the sweep even when
every scenario still survives.

The pod scenarios additionally export their CAUSAL TRACE
(``tools/trace_export.py``: one merged Chrome/Perfetto span tree per pod
dir — ``pod_kill_one_host`` and ``pod_partition_coordinator`` fail
unless the coordinated restart is a single parent span whose per-host
attempt children all carry the fencing epoch) and a FLEET rollup + SLO
burn section (``fps_tpu.obs.fleet`` over the member obs dirs), lifted
into the digest's top-level ``fleet`` field.

``--only SCENARIO[,SCENARIO...]`` (repeatable; entries may be fnmatch
globs like ``tenant_*``) runs a subset so CI can shard the sweep; a red
run exits nonzero and names the failing scenarios on stderr (and in the
digest's ``failed`` list).

Run (CPU mesh, like the test suite):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=/root/repo python tools/chaos_sweep.py
"""

import glob
import json
import os
import sys

import numpy as np

import jax

from fps_tpu.core.checkpoint import Checkpointer
from fps_tpu.core.driver import num_workers_of
from fps_tpu.core.resilience import GuardConfig, RollbackPolicy
from fps_tpu.models.logistic_regression import (
    LogRegConfig,
    logistic_regression,
)
from fps_tpu.parallel.mesh import make_ps_mesh
from fps_tpu.testing import chaos
from fps_tpu.testing.workloads import (
    NF,
    accuracy,
    health_sum,
    logreg_chunks,
    logreg_data,
    run_logreg,
    weights,
)


# -- time-to-recovered SLOs ------------------------------------------------
# Seconds from the fault landing to the injected plane demonstrably
# recovered (the scenarios' own ``time_to_recovered_s`` measurement).
# A scenario that RECOVERS but recovers late is a failure: surviving a
# brownout by spending three minutes down is an outage with extra
# steps. The default is deliberately generous — CPU CI pays compiles
# and subprocess spawns a TPU pod never would — and per-scenario
# overrides loosen it further where recovery legitimately includes
# multi-child restarts or whole-tenant replays. ``--recovery-slo-s``
# rescales the default without touching the override ratios.
RECOVERY_SLO_DEFAULT_S = 60.0
RECOVERY_SLO_OVERRIDES_S = {
    # Pod-coordinated restarts: leader re-election + every member
    # replaying from the common verified step (N children, N compiles).
    "pod_kill_one_host": 120.0,
    "pod_partition_coordinator": 120.0,
    # Tenant scenarios restart/replay a whole tenant namespace (its own
    # supervisor, checkpoints, and serving reader) beside a healthy one.
    "tenant_enospc_brownout": 120.0,
    "tenant_reader_wedge": 120.0,
}


def recovery_slo_for(name: str, default_s: float | None = None) -> float:
    base = (RECOVERY_SLO_DEFAULT_S if default_s is None
            else float(default_s))
    scale = base / RECOVERY_SLO_DEFAULT_S
    return RECOVERY_SLO_OVERRIDES_S.get(name, RECOVERY_SLO_DEFAULT_S) * scale


def _finite(store):
    return bool(np.all(np.isfinite(weights(store))))


def program_certificate(trainer, chunks) -> dict:
    """Certify the exact compiled program the sweep's scenarios dispatch
    (fps_tpu.analysis) and return the certificate JSON for the digest —
    a regression in collective structure (an extra psum, a lost
    donation, a stray host callback) shows up here next to the survival
    booleans, even when every scenario still survives."""
    import dataclasses

    from fps_tpu.analysis import certify, contract_for_trainer

    hlo = trainer.lowered_chunk_text(chunks[0], "sync")
    # Pin the sweep program's collective structure exactly (counts, not
    # bytes — payload scales with the harness): the gathered logreg
    # route is one pull all_gather + one routed-push all_to_all, so an
    # extra psum (or a lost route) fails the sweep, as promised above.
    contract = dataclasses.replace(
        contract_for_trainer(trainer, "sync"),
        max_collectives=2,
        per_kind_max={"all_gather": 1, "all_to_all": 1},
        exact_collectives=True,
    )
    cert = certify(hlo, contract, program="chaos/logreg")
    return cert.to_json()


def _health_totals(metrics, tables=("weights",)):
    """Per-table health-counter totals over a run's metrics list — the
    digest's evidence that the guard actually saw the poison."""
    return {
        t: {kind: health_sum(metrics, t, kind)
            for kind in ("nonfinite", "norm", "masked")}
        for t in tables
    }


def poison_scenario(mesh, chunks, test, acc_clean, kind):
    poisoned = list(chaos.poison_chunks(iter(chunks), chunk_index=1,
                                        column="feat_vals", kind=kind,
                                        frac=0.5, seed=1))
    guard = (GuardConfig(mode="mask", norm_limit=100.0)
             if kind == "huge" else GuardConfig(mode="mask"))
    _, store, m = run_logreg(mesh, poisoned, guard=guard)
    tier = "norm" if kind == "huge" else "nonfinite"
    ok = (_finite(store) and health_sum(m, "weights", tier) > 0
          and abs(accuracy(store, test) - acc_clean) < 0.05)
    return ok, {"health": _health_totals(m)}


def rollback_scenario(mesh, chunks):
    poisoned = list(chaos.poison_chunks(iter(chunks), chunk_index=1,
                                        column="feat_vals", kind="nan",
                                        frac=0.5, seed=1))
    policy = RollbackPolicy()
    _, store, m = run_logreg(mesh, poisoned, guard="observe",
                             rollback=policy)
    ok = _finite(store) and policy.quarantined == [1]
    # Quarantined chunks contribute no metrics entry, so the health totals
    # here cover only the SURVIVING chunks (expected all-zero under
    # observe+rollback — the poison was dropped whole).
    return ok, {"health": _health_totals(m),
                "quarantined": list(policy.quarantined),
                "rollback_budget": policy.max_rollbacks}


def ckpt_scenario(tmpdir, mesh, chunks, mode):
    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, store = logistic_regression(mesh, cfg)
    tables, ls = trainer.init_state(jax.random.key(0))
    ckpt = Checkpointer(tmpdir, keep=2)
    for i, c in enumerate(chunks[:2]):
        tables, ls, _ = trainer.run_chunk(tables, ls, c, jax.random.key(i))
        ckpt.save(i + 1, store, None)
    want = weights(store).copy()
    if mode == "tmp_sweep":
        import time

        torn = os.path.join(tmpdir, "torn.tmp.npz")
        open(torn, "wb").write(b"PK\x03\x04x")
        past = time.time() - 2 * Checkpointer.TMP_SWEEP_AGE_S
        os.utime(torn, (past, past))  # crash leftover, not a live writer
        ckpt2 = Checkpointer(tmpdir, keep=2)
        _, step = ckpt2.restore_tables(store)
        return (step == 2 and not glob.glob(tmpdir + "/*.tmp.npz")
                and np.array_equal(weights(store), want))
    chaos.corrupt_latest_snapshot(tmpdir, mode)
    ok = Checkpointer(tmpdir, keep=2).latest_valid_step() == 1
    _, step = ckpt.restore_tables(store)
    return ok and step == 1 and _finite(store)


def supervised_scenario(tmpdir):
    """End-to-end supervisor survival: wedge a real training child with
    SIGSTOP mid-run; the supervisor must abort + restart it and the
    resumed run must reproduce the straight run bit-for-bit. One shared
    implementation with the slow test in tests/test_supervise.py
    (fps_tpu.testing.supervised_demo.run_supervised_scenario) so the two
    cannot drift."""
    from fps_tpu.testing.supervised_demo import run_supervised_scenario

    return run_supervised_scenario(tmpdir)


def _subprocess_scenario(fn_name,
                         module="fps_tpu.testing.supervised_demo"):
    """A scenario that lives in a testing module (supervised_demo by
    default; the multi-tenant ones in fps_tpu.testing.tenant_demo) and
    runs whole child processes — imported lazily, executed in a fresh
    tempdir."""
    import tempfile

    def run(_harness):
        import importlib

        demo = importlib.import_module(module)
        with tempfile.TemporaryDirectory() as d:
            return getattr(demo, fn_name)(d)

    return run


def _harness_scenarios():
    """Scenario registry: name -> callable(harness) -> (ok, detail|None).
    The in-process scenarios share one lazily-built logreg harness; the
    subprocess ones (supervised / pod) need none of it."""
    import tempfile

    def ckpt(mode):
        def run(h):
            with tempfile.TemporaryDirectory() as d:
                return ckpt_scenario(d, h["mesh"], h["chunks"], mode), None

        return run

    return {
        "nan_mask": lambda h: poison_scenario(
            h["mesh"], h["chunks"], h["test"], h["acc_clean"], "nan"),
        "inf_mask": lambda h: poison_scenario(
            h["mesh"], h["chunks"], h["test"], h["acc_clean"], "inf"),
        "huge_norm_mask": lambda h: poison_scenario(
            h["mesh"], h["chunks"], h["test"], h["acc_clean"], "huge"),
        "observe_rollback": lambda h: rollback_scenario(
            h["mesh"], h["chunks"]),
        "ckpt_truncate": ckpt("truncate"),
        "ckpt_bitflip": ckpt("bitflip"),
        "tmp_sweep": ckpt("tmp_sweep"),
        "supervised": lambda h: supervised_scenario_tmp(),
        "prefetch_kill": _subprocess_scenario("run_prefetch_kill_scenario"),
        "hot_tier_kill": _subprocess_scenario("run_hot_tier_kill_scenario"),
        "retier_kill": _subprocess_scenario("run_retier_kill_scenario"),
        "megastep_kill": _subprocess_scenario("run_megastep_kill_scenario"),
        "reconcile_shard_kill": _subprocess_scenario(
            "run_reconcile_shard_kill_scenario"),
        "serve_while_train": _subprocess_scenario(
            "run_serve_while_train_scenario"),
        # Delta-snapshot chains + the step-fenced serving fleet
        # (ISSUE 14; docs/resilience.md failure model rows, docs/
        # serving.md fleet sections).
        "delta_chain_kill": _subprocess_scenario(
            "run_delta_chain_kill_scenario"),
        "fleet_fence": _subprocess_scenario(
            "run_fleet_fence_scenario"),
        # Pod-level scenarios (fps_tpu.supervise.pod): N member agents
        # over one shared pod dir — one failure domain.
        "pod_kill_one_host": _subprocess_scenario(
            "run_pod_kill_one_host_scenario"),
        "pod_partition_coordinator": _subprocess_scenario(
            "run_pod_partition_coordinator_scenario"),
        "pod_flapping_member": _subprocess_scenario(
            "run_pod_flapping_member_scenario"),
        "pod_elastic_resize": _subprocess_scenario(
            "run_pod_elastic_resize_scenario"),
        # Hostile-filesystem scenarios (fps_tpu.testing.faultfs +
        # fps_tpu/core/retry.py; docs/resilience.md "Hostile
        # filesystem"): deterministic I/O fault injection against the
        # framework's own storage seams — ENOSPC/EIO/latency/torn
        # renames/stale reads — with training, compaction, the serving
        # fleet, and the pod lease all required to DEGRADE (retry,
        # skip, step down, serve last-good) instead of crashing or
        # wedging, and to recover bit-identically.
        "storage_brownout": _subprocess_scenario(
            "run_storage_brownout_scenario"),
        "storage_blackout_recover": _subprocess_scenario(
            "run_storage_blackout_recover_scenario"),
        "enospc_compaction": _subprocess_scenario(
            "run_enospc_compaction_scenario"),
        "slow_lease_near_ttl": _subprocess_scenario(
            "run_slow_lease_near_ttl_scenario"),
        # Hostile-network scenarios (fps_tpu.serve.wire +
        # fps_tpu.testing.faultnet; docs/resilience.md "Hostile
        # network"): deterministic wire-fault schedules against the
        # framed TCP plane — no torn frame is ever decoded, reconnects
        # dedupe through the replay cache (zero duplicate applies),
        # slow peers cost latency never integrity, deadlines bound
        # every request, and a SIGSTOPped reader becomes a
        # reader_wedged incident within the liveness timeout.
        "net_torn_frames": _subprocess_scenario(
            "run_net_torn_frames_scenario"),
        "net_reconnect_storm": _subprocess_scenario(
            "run_net_reconnect_storm_scenario"),
        "net_slow_peer": _subprocess_scenario(
            "run_net_slow_peer_scenario"),
        "net_partition_reader": _subprocess_scenario(
            "run_net_partition_reader_scenario"),
        # Batched read-plane scenarios (ISSUE 19: multi-lookup wire op
        # + admission control + the fleet autoscaler): a torn multi
        # frame is never partially applied (exactly-once across the
        # storm, batched == unbatched == binary bit-identical, BUSY
        # sheds whole batches retryably), and reader churn under the
        # autoscaler — scale-up, wedged-reader replacement, scale-down
        # — keeps the step fence monotone and the answers exact.
        "serve_batch_storm": _subprocess_scenario(
            "run_serve_batch_storm_scenario"),
        "autoscale_reader_churn": _subprocess_scenario(
            "run_autoscale_reader_churn_scenario"),
        # Multi-tenant blast-radius scenarios (fps_tpu.tenancy +
        # fps_tpu.testing.tenant_demo; docs/resilience.md "Multi-tenant
        # blast radius"): one tenant is faulted, and every NON-injected
        # tenant must finish bit-identical to its solo run with a clean
        # post-run namespace audit (zero cross-tenant writes) — the
        # per-scenario time_to_recovered_s and audit verdicts are lifted
        # into the digest's top-level maps.
        "tenant_poison_isolation": _subprocess_scenario(
            "run_tenant_poison_isolation_scenario",
            module="fps_tpu.testing.tenant_demo"),
        "tenant_enospc_brownout": _subprocess_scenario(
            "run_tenant_enospc_brownout_scenario",
            module="fps_tpu.testing.tenant_demo"),
        "tenant_reader_wedge": _subprocess_scenario(
            "run_tenant_reader_wedge_scenario",
            module="fps_tpu.testing.tenant_demo"),
        "tenant_noisy_neighbor": _subprocess_scenario(
            "run_tenant_noisy_neighbor_scenario",
            module="fps_tpu.testing.tenant_demo"),
    }


def supervised_scenario_tmp():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        return supervised_scenario(d)


# Scenarios that need the shared in-process logreg harness (mesh, chunk
# stream, clean-run accuracy); everything else runs pure subprocesses.
_NEEDS_HARNESS = ("nan_mask", "inf_mask", "huge_norm_mask",
                  "observe_rollback", "ckpt_truncate", "ckpt_bitflip",
                  "tmp_sweep")


class _ScenarioTimeout(BaseException):
    """A scenario overran --timeout-s (raised from the SIGALRM handler
    so even a blocked subprocess wait unwinds). BaseException — the
    KeyboardInterrupt pattern — so a scenario's own broad `except
    Exception` recovery paths cannot swallow the timeout and leave the
    sweep unbounded with a disarmed timer."""


def _run_bounded(fn, harness, timeout_s: float):
    """Run one scenario under a wall-clock bound. SIGALRM (not a
    thread) so a scenario wedged inside a blocking syscall — the exact
    failure mode the flag exists for — is interrupted; 0 disables.
    Children a timed-out scenario leaks are the price of failing
    loudly instead of hanging CI."""
    if timeout_s <= 0:
        return fn(harness)
    import signal

    def on_alarm(_sig, _frame):
        raise _ScenarioTimeout()

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, timeout_s)
    try:
        return fn(harness)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


def main(argv=None):
    import argparse

    scenarios = _harness_scenarios()
    ap = argparse.ArgumentParser(
        description="fps_tpu chaos sweep: run the fault-injector matrix "
                    "and print a one-line survival digest")
    ap.add_argument("--only", action="append", default=[],
                    metavar="SCENARIO[,SCENARIO...]",
                    help="run only these scenarios (repeatable / "
                         "comma-separated; fnmatch globs like "
                         "'tenant_*' work) — lets CI shard the sweep; "
                         f"known: {', '.join(scenarios)}")
    ap.add_argument("--list", action="store_true",
                    help="print registered scenario names (one per "
                         "line) and exit — CI shards build their "
                         "--only sets from this instead of hardcoding")
    ap.add_argument("--timeout-s", type=float, default=0.0,
                    help="per-scenario wall-clock bound (0 = none): a "
                         "wedged scenario fails LOUDLY under its own "
                         "name instead of hanging the whole sweep "
                         "(SIGALRM-interrupted, so even a blocked "
                         "subprocess wait is bounded)")
    ap.add_argument("--recovery-slo-s", type=float, default=None,
                    metavar="S",
                    help="rescale the time-to-recovered SLO default "
                         f"(normally {RECOVERY_SLO_DEFAULT_S:.0f}s; "
                         "per-scenario overrides scale with it; 0 "
                         "disables SLO enforcement): a scenario that "
                         "recovers but recovers LATE fails the sweep "
                         "under its own name")
    ap.add_argument("--shard", default=None, metavar="K/N",
                    help="run shard K of N (1-based) over the --list "
                         "order, after --only filtering — CI splits "
                         "the sweep across jobs without hardcoding "
                         "scenario names")
    args = ap.parse_args(argv)
    if args.list:
        for name in scenarios:
            print(name)
        return 0
    selected = [s for arg in args.only for s in arg.split(",") if s]
    # Each --only entry may be an exact name or an fnmatch glob
    # (e.g. 'tenant_*', 'pod_*') — a pattern matching nothing is a
    # typo and fails loudly, same as an unknown exact name.
    import fnmatch

    unknown = sorted(pat for pat in selected
                     if not fnmatch.filter(scenarios, pat))
    if unknown:
        ap.error(f"unknown scenario(s)/pattern(s) {unknown}; "
                 f"known: {sorted(scenarios)}")
    names = [n for n in scenarios
             if not selected
             or any(fnmatch.fnmatch(n, pat) for pat in selected)]
    if args.shard:
        try:
            k, n_shards = (int(x) for x in args.shard.split("/"))
        except ValueError:
            ap.error(f"--shard wants K/N (e.g. 2/4), got {args.shard!r}")
        if not 1 <= k <= n_shards:
            ap.error(f"--shard K must be in [1, N], got {args.shard!r}")
        names = [nm for i, nm in enumerate(names)
                 if i % n_shards == k - 1]

    harness = None
    certificate = None
    if any(n in _NEEDS_HARNESS for n in names) or (not selected
                                                   and not args.shard):
        mesh = make_ps_mesh()
        train, test = logreg_data()
        chunks = logreg_chunks(train, num_workers_of(mesh), epochs=2)
        trainer_clean, store_clean, _ = run_logreg(mesh, chunks)
        harness = {"mesh": mesh, "test": test, "chunks": chunks,
                   "acc_clean": accuracy(store_clean, test)}
        # The certificate rides the full sweep (and any shard that
        # builds the harness anyway): a collective-structure regression
        # fails the sweep even when every scenario survives.
        certificate = program_certificate(trainer_clean, chunks)

    results = {}
    detail = {}
    for name in names:
        try:
            out = _run_bounded(scenarios[name], harness, args.timeout_s)
        except _ScenarioTimeout:
            # The loud-failure contract: the wedged scenario is NAMED
            # in the digest and on stderr; the sweep moves on.
            print(f"chaos_sweep: scenario {name} timed out after "
                  f"{args.timeout_s}s", file=sys.stderr, flush=True)
            results[name] = False
            detail[name] = {"error": "timeout",
                            "timeout_s": args.timeout_s}
            continue
        ok, d = out if isinstance(out, tuple) else (out, None)
        results[name] = bool(ok)
        if d is not None:
            detail[name] = d

    # Time-to-recovered SLO: a scenario whose measured recovery latency
    # overruns its bound fails even though it recovered — late recovery
    # is an outage with extra steps. Enforced here (not inside the
    # scenarios) so the bounds stay in one place and obs_report can
    # read breaches off the digest.
    slo_enforced = (args.recovery_slo_s is None
                    or args.recovery_slo_s > 0)
    slo_breaches = {}
    if slo_enforced:
        for n, d in detail.items():
            t = (d.get("time_to_recovered_s")
                 if isinstance(d, dict) else None)
            if t is None:
                continue
            bound = recovery_slo_for(n, args.recovery_slo_s)
            if float(t) > bound:
                slo_breaches[n] = {"time_to_recovered_s": float(t),
                                   "slo_s": bound}
                results[n] = False
                print(f"chaos_sweep: scenario {n} recovered in "
                      f"{float(t):.1f}s, over its {bound:.1f}s SLO",
                      file=sys.stderr, flush=True)

    failed = sorted(n for n, ok in results.items() if not ok)
    cert_ok = certificate is None or certificate["ok"]
    digest = {
        "chaos_sweep": results,
        "survived": sum(results.values()),
        "total": len(results),
        # The names CI wants on a red run — also printed to stderr.
        "failed": failed,
        # Per-scenario evidence: per-table health-counter totals and the
        # rollback/quarantine record (survival booleans alone said WHETHER
        # we lived, not WHAT the defenses saw).
        "detail": detail,
        # The compiled program's contract certificate (fps_tpu.analysis):
        # collective structure regressions surface next to survival.
        "program_certificate": certificate,
        # Fleet rollup + SLO burn over the pod scenario's member obs
        # dirs (fps_tpu.obs.fleet, computed inside the scenario before
        # its tempdir is collected): the sweep's fleet-level telemetry
        # evidence — throughput, cold-route certification rate, restart
        # counts, and burn-rate verdicts ride the digest.
        "fleet": (detail.get("pod_kill_one_host") or {}).get("fleet"),
        # Per-scenario recovery latency (seconds from the fault landing
        # to the injected plane demonstrably recovered; null where the
        # scenario degrades in place instead of restarting) and the
        # multi-tenant scenarios' post-run namespace-audit verdicts —
        # obs_report's incident view and CI both read these off the
        # digest without digging through detail.
        "time_to_recovered_s": {
            n: d.get("time_to_recovered_s")
            for n, d in detail.items()
            if isinstance(d, dict) and "time_to_recovered_s" in d},
        # The SLO verdicts next to the measurements: the bound every
        # recovering scenario was held to and the ones that overran it
        # (breaches also flip the scenario into `failed`).
        "recovery_slo": {
            "default_s": (args.recovery_slo_s
                          if slo_enforced and args.recovery_slo_s
                          else RECOVERY_SLO_DEFAULT_S),
            "enforced": slo_enforced,
            "bounds_s": {
                n: recovery_slo_for(
                    n, args.recovery_slo_s if slo_enforced else None)
                for n, d in detail.items()
                if isinstance(d, dict) and "time_to_recovered_s" in d},
            "breaches": slo_breaches,
        },
        "namespace_audit": {
            n: d.get("namespace_audit")
            for n, d in detail.items()
            if isinstance(d, dict) and "namespace_audit" in d},
        "clean_test_acc": (round(harness["acc_clean"], 4)
                           if harness else None),
    }
    if harness:
        digest["mesh"] = dict(harness["mesh"].shape)
    print(json.dumps(digest), flush=True)
    if failed or not cert_ok:
        blame = list(failed) + ([] if cert_ok else ["program_certificate"])
        print(f"chaos_sweep: FAILED scenarios: {', '.join(blame)}",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
