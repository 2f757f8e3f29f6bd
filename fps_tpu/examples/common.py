"""Shared CLI plumbing for the example entrypoints."""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np


def base_parser(description: str) -> argparse.ArgumentParser:
    """Common flags: mesh shape (the reference's workerParallelism /
    psParallelism pair), batching, execution mode, persistence."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--input", default=None, help="dataset path (default: synthetic)")
    ap.add_argument("--num-shards", type=int, default=None,
                    help="parameter-shard axis size (reference: psParallelism); "
                         "default: all devices")
    ap.add_argument("--num-data", type=int, default=1,
                    help="replicated data-parallel axis size")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--local-batch", type=int, default=256,
                    help="examples per worker per step")
    ap.add_argument("--steps-per-chunk", type=int, default=16,
                    help="microbatch steps per compiled call")
    ap.add_argument("--sync-every", type=int, default=None,
                    help="SSP staleness bound s (default: fully synchronous)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--export", default=None, help="write final model to this .npz")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot every N chunks (requires --checkpoint-dir)")
    ap.add_argument("--checkpoint-async", action="store_true",
                    help="double-buffered background checkpoint writer "
                         "(fps_tpu.core.checkpoint.AsyncCheckpointer): "
                         "save() returns before serialize+fsync; the "
                         "driver's end-of-run flush is the durability "
                         "barrier")
    ap.add_argument("--warm-start", default=None,
                    help="initialize tables from a saved model .npz "
                         "(reference: transformWithModelLoad)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler device trace of the training "
                         "region under DIR (view with XProf/Perfetto)")
    ap.add_argument("--ingest", default="device", choices=["device", "host"],
                    help="'device' keeps the dataset resident on the mesh "
                         "and builds chunks with on-device gathers (fast "
                         "path on TPU VMs); 'host' regenerates and uploads "
                         "every chunk (the unbounded-stream shape)")
    ap.add_argument("--prefetch", type=int, default=0, metavar="N",
                    help="overlapped host pipeline depth "
                         "(fps_tpu.core.prefetch): chunk assembly and "
                         "host->device placement run up to N chunks "
                         "ahead on a background thread, so the device "
                         "never idles on host ingest; 0 = synchronous "
                         "host loop. Numerics are bit-identical either "
                         "way; 2 is the recommended depth")
    ap.add_argument("--hot-tier", type=int, default=0, metavar="H",
                    help="two-tier parameter storage "
                         "(fps_tpu.core.store.TableSpec.hot_tier): "
                         "replicate the leading H ids of every PS table "
                         "across the shard axis — hot reads become "
                         "collective-free local gathers, hot pushes "
                         "accumulate locally and reconcile by one psum "
                         "every --hot-sync-every steps. Ids must be "
                         "frequency-ranked (hottest first; the shipped "
                         "loaders are). Engages on multi-device meshes "
                         "with --hot-sync-every > 1 and an additive/mean "
                         "server fold; otherwise the exact untiered "
                         "program runs")
    ap.add_argument("--hot-sync-every", type=int, default=1, metavar="E",
                    help="hot-tier reconcile cadence in steps "
                         "(TrainerConfig.hot_sync_every): the SSP "
                         "staleness bound applied to the parameter "
                         "plane. 1 (default) = exact mode, bit-identical "
                         "to the untiered path")
    ap.add_argument("--cold-budget", type=int, default=0, metavar="C",
                    help="payload-proportional cold routing "
                         "(TableSpec.cold_budget): with a PARTIAL hot "
                         "head, compact each batch's cold ids into a "
                         "C-wide per-worker lane so the cold collective "
                         "routes carry O(cold traffic) payload instead "
                         "of O(batch). Host-certified per chunk (like "
                         "head_prefix); overflowing chunks fall back to "
                         "the static routes bit-identically with a "
                         "cold_route.overflow_chunks counter. Requires "
                         "--hot-tier with H < num_ids on a non-dense "
                         "route; 0 = static cold routes")
    ap.add_argument("--hot-fold", default=None,
                    choices=["adagrad", "adam"],
                    help="stateful hot-tier server optimizer "
                         "(ServerLogic.hot_fold): per-row Adagrad/Adam "
                         "state sharded over the replica axis by the "
                         "sharded reconcile (reduce-scatter -> apply "
                         "the owned 1/S slice -> all-gather). Requires "
                         "a FULLY-replicated hot tier (--hot-tier >= "
                         "num_ids) with --hot-sync-every > 1; state "
                         "rides checkpoints as fold:: arrays, canonical "
                         "table bytes unchanged")
    ap.add_argument("--auto-tier", action="store_true",
                    help="adaptive tiering (fps_tpu.tiering, "
                         "docs/performance.md): track pulled-id "
                         "frequencies online (device-side count-min, "
                         "psum-merged), derive per-table hot_tier / "
                         "hot_sync_every / dense route from the "
                         "sketched densities after a warmup (replacing "
                         "the hand-tuned --hot-tier/--hot-sync-every "
                         "knobs), and re-rank the hot set on drift — "
                         "re-ranks swap replicated data, never "
                         "recompile. Explicit --hot-tier/"
                         "--hot-sync-every still apply until the "
                         "planner's first decision")
    ap.add_argument("--guard", default=None, choices=["observe", "mask"],
                    help="on-device push-delta health guard "
                         "(fps_tpu.core.resilience): 'mask' drops "
                         "non-finite / norm-exploded update rows in-step, "
                         "'observe' only counts them onto the metrics "
                         "stream; default off (zero-cost)")
    ap.add_argument("--guard-norm-limit", type=float, default=None,
                    help="per-row L2 norm ceiling for push deltas "
                         "(requires --guard)")
    ap.add_argument("--guard-local", action="store_true",
                    help="extend the guard to worker-LOCAL state updates "
                         "(e.g. MF user factors): poisoned local rows are "
                         "counted — and in mask mode reverted — like "
                         "poisoned pushes (requires --guard)")
    ap.add_argument("--rollback-budget", type=int, default=None,
                    help="quarantine poisoned chunks via a host-loop "
                         "RollbackPolicy with this budget (requires "
                         "--guard); under a supervisor, indices "
                         "quarantined by previous attempts are always "
                         "carried in, budget flag or not")
    ap.add_argument("--heartbeat", default=None, metavar="PATH",
                    help="touch this progress-beacon file on every "
                         "chunk/epoch boundary (default: the "
                         "FPS_TPU_HEARTBEAT env var, set automatically "
                         "by tools/supervise.py)")
    ap.add_argument("--serve-port", type=int, default=None, metavar="PORT",
                    help="publish this run's snapshots to query traffic "
                         "WHILE training (fps_tpu.serve, docs/serving.md): "
                         "a SnapshotWatcher hot-swaps each new checkpoint "
                         "into a line-JSON TCP ReadServer on "
                         "127.0.0.1:PORT (0 = ephemeral; the bound port "
                         "is emitted). Requires --checkpoint-dir and "
                         "--checkpoint-every")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="telemetry output (fps_tpu.obs): JSONL event log, "
                         "per-process run journal, and Prometheus text "
                         "exposition under DIR; render with "
                         "tools/obs_report.py")
    ap.add_argument("--obs-watchdog-s", type=float, default=None,
                    help="flag any chunk whose dispatch+sync exceeds this "
                         "many seconds (stalled dispatch / hung multi-host "
                         "peer); forces a per-chunk metrics sync")
    return ap


def _make_heartbeat(args):
    """--heartbeat / the supervisor's FPS_TPU_HEARTBEAT env contract →
    a Heartbeat, or None when this run is unsupervised."""
    from fps_tpu.supervise import child

    path = getattr(args, "heartbeat", None)
    if path:
        return child.Heartbeat(path)
    return child.from_env()


def attach_obs(args, trainer=None, *, workload: str | None = None):
    """Resolve --obs-dir (and the supervised-heartbeat contract) into an
    installed recorder (or None).

    Opens the standard on-disk telemetry set under ``--obs-dir``
    (``fps_tpu.obs.open_run``), stamps the run journal with the CLI args
    as the config digest, installs it as the process-default recorder
    (checkpoint/rollback events flow automatically), and attaches it to
    ``trainer`` when given. Close via :func:`finish`.

    When the run is supervised (``--heartbeat`` or the supervisor's
    ``FPS_TPU_HEARTBEAT`` env var), a HeartbeatSink rides the recorder so
    every chunk/epoch journal event doubles as the supervisor's liveness
    signal; with no ``--obs-dir`` a minimal heartbeat-only recorder is
    returned instead — attaching one never changes training behavior.
    """
    hb = _make_heartbeat(args)
    if getattr(args, "obs_dir", None) is None:
        if getattr(args, "obs_watchdog_s", None) is not None:
            raise SystemExit("--obs-watchdog-s requires --obs-dir")
        if hb is None:
            return None
        from fps_tpu.obs import Recorder
        from fps_tpu.supervise import child

        rec = Recorder(sinks=[child.HeartbeatSink(hb)])
        if trainer is not None:
            trainer.recorder = rec
        return rec
    from fps_tpu import obs

    rec = obs.open_run(args.obs_dir, config=vars(args),
                       meta={"workload": workload} if workload else None)
    if hb is not None:
        from fps_tpu.supervise import child

        rec.sinks.append(child.HeartbeatSink(hb))
    if trainer is not None:
        trainer.recorder = rec
    emit({"event": "obs", "dir": args.obs_dir, "run_id": rec.run_id})
    return rec


def apply_host_pipeline(args, trainer):
    """Fold the host-pipeline CLI knobs (--prefetch) into the trainer's
    config. Host-side only — the compiled program is unchanged — so this
    is a plain config replace, no factory plumbing."""
    if getattr(args, "prefetch", 0):
        import dataclasses

        if args.prefetch < 0:
            raise SystemExit(f"--prefetch must be >= 0, got {args.prefetch}")
        trainer.config = dataclasses.replace(trainer.config,
                                             prefetch=args.prefetch)
    return trainer


def apply_hot_tier(args, trainer, store=None):
    """Fold the two-tier storage CLI knobs (--hot-tier/--hot-sync-every)
    into the trainer's store specs and config. Must run before the first
    compiled call (the tier resolution is part of the compile key).

    ``trainer=None`` (iALS: half-epoch normal-equation solves, no
    pull/push Trainer to tier) accepts-and-reports the flag instead of
    failing, so the CLI surface stays uniform across the six examples.
    """
    H = getattr(args, "hot_tier", 0)
    E = getattr(args, "hot_sync_every", 1)
    auto = getattr(args, "auto_tier", False)
    cold = getattr(args, "cold_budget", 0)
    fold = getattr(args, "hot_fold", None)
    if E < 1:
        raise SystemExit(f"--hot-sync-every must be >= 1, got {E}")
    if H < 0:
        raise SystemExit(f"--hot-tier must be >= 0, got {H}")
    if cold < 0:
        raise SystemExit(f"--cold-budget must be >= 0, got {cold}")
    if cold and not (H or auto):
        raise SystemExit("--cold-budget needs a hot tier: pass "
                         "--hot-tier H (partial head) or --auto-tier")
    if fold and not H:
        raise SystemExit("--hot-fold needs --hot-tier (fully-replicated: "
                         "H >= num_ids) and --hot-sync-every > 1")
    if not H and E == 1 and not auto:
        return trainer
    if trainer is None:
        emit({"event": "hot_tier_ignored",
              "reason": "this workload has no pull/push trainer "
                        "(iALS half-epoch solves)"})
        return None
    import dataclasses

    store = store or trainer.store
    if H:
        for name, spec in store.specs.items():
            store.specs[name] = dataclasses.replace(
                spec, hot_tier=min(H, spec.num_ids),
                cold_budget=cold)
    if fold:
        for name, sl in trainer.server_logic.items():
            trainer.server_logic[name] = dataclasses.replace(
                sl, hot_fold=fold)
    trainer.config = dataclasses.replace(trainer.config, hot_sync_every=E,
                                         auto_tier=auto)
    tiered = sorted(trainer._hot_tier_map())  # also validates vs push_delay
    emit({"event": "hot_tier", "hot_tier": H, "hot_sync_every": E,
          "auto_tier": auto, "tiered_tables": tiered,
          "cold_budget": cold, "hot_fold": fold,
          "compacted_tables": sorted(trainer._cold_compact_map()),
          "exact_mode": (E == 1 or not tiered) and not auto})
    return trainer


def maybe_serve(args, recorder=None):
    """Resolve ``--serve-port`` into a serve-while-train context manager.

    Inside the ``with`` block a background thread polls
    ``--checkpoint-dir`` (and the ``--obs-dir`` journal, when set — the
    ``checkpoint_saved`` events carry path/step/bytes so no directory
    re-stat is needed) and hot-swaps every new verified snapshot into a
    TCP ``ReadServer``; exit stops the watcher, closes the socket, and
    emits the serve stats. Returns a no-op context when the flag is off,
    so call sites wrap the training region unconditionally.
    """
    if getattr(args, "serve_port", None) is None:
        return contextlib.nullcontext()
    if not (args.checkpoint_dir and args.checkpoint_every > 0):
        raise SystemExit("--serve-port requires --checkpoint-dir and "
                         "--checkpoint-every (serving reads published "
                         "snapshots)")
    import threading

    from fps_tpu.serve import ReadServer, TcpServe

    server, watcher = ReadServer.over(
        args.checkpoint_dir, journal=getattr(args, "obs_dir", None),
        recorder=recorder)
    tcp = TcpServe(server, port=args.serve_port).start()
    stop = threading.Event()
    thread = threading.Thread(
        target=watcher.run, kwargs={"interval_s": 0.5, "stop": stop},
        name="fps-serve-watcher", daemon=True)
    thread.start()
    emit({"event": "serving", "host": tcp.host, "port": tcp.port,
          "ckpt_dir": args.checkpoint_dir})

    @contextlib.contextmanager
    def running():
        try:
            yield server
        finally:
            stop.set()
            thread.join(timeout=10.0)
            tcp.close()
            if not thread.is_alive():
                # Final swap: the end-of-run flush's snapshot. Skipped
                # if the watcher thread outlived the join timeout (a
                # multi-GB verify can) — poll() is single-threaded by
                # contract and must not run concurrently with it.
                watcher.poll()
            stats = server.stats()
            stats.update(swaps=dict(watcher.swaps),
                         rejected=watcher.rejected,
                         write_to_servable_s=watcher.write_to_servable_s)
            emit({"event": "served", **stats})

    return running()


def make_watchdog(args, recorder):
    """--obs-watchdog-s into a StepWatchdog bound to the run's recorder."""
    if getattr(args, "obs_watchdog_s", None) is None:
        return None
    from fps_tpu.obs import StepWatchdog

    return StepWatchdog(args.obs_watchdog_s, recorder=recorder)


def make_guard(args):
    """Resolve the --guard flags into a TrainerConfig.guard value."""
    if args.guard is None:
        if args.guard_norm_limit is not None:
            raise SystemExit("--guard-norm-limit requires --guard")
        if getattr(args, "guard_local", False):
            raise SystemExit("--guard-local requires --guard")
        return None
    from fps_tpu.core.resilience import GuardConfig

    return GuardConfig(mode=args.guard, norm_limit=args.guard_norm_limit,
                       local=getattr(args, "guard_local", False))


def make_rollback(args):
    """--rollback-budget plus any supervisor-carried quarantine set into a
    RollbackPolicy (or None). The preset alone (no budget flag, no guard)
    is legal: a supervised restart must honor quarantine decisions even
    when the operator never asked for health-based rollback."""
    from fps_tpu.core.resilience import RollbackPolicy
    from fps_tpu.supervise import child

    preset = child.quarantined_from_env()
    budget = getattr(args, "rollback_budget", None)
    if budget is None and not preset:
        return None
    if budget is not None and args.guard is None:
        raise SystemExit("--rollback-budget requires --guard")
    policy = RollbackPolicy(preset=preset)
    if budget is not None:
        policy.max_rollbacks = budget
    if preset:
        emit({"event": "quarantine_carried", "indices": sorted(preset)})
    return policy


def make_epoch_source(args, mesh, data, *, route_key=None, num_workers=None):
    """Restartable chunk source honoring --ingest and the batching flags.

    Returns ``source(start_epoch=0, epochs=1) -> chunk iterator``. The
    device path builds the dataset and epoch plan ONCE, so repeated calls
    (e.g. iALS consuming the stream twice per epoch) reuse the compiled
    chunk builder instead of retracing it.
    """
    from fps_tpu.core.driver import num_workers_of

    W = num_workers_of(mesh) if num_workers is None else num_workers
    if args.ingest == "device":
        from fps_tpu.core.device_ingest import (
            DeviceDataset,
            DeviceEpochPlan,
            device_epoch_chunks,
        )

        ds = DeviceDataset(mesh, data)
        plan = DeviceEpochPlan(
            ds, num_workers=W, local_batch=args.local_batch,
            route_key=route_key, sync_every=args.sync_every, seed=args.seed,
        )

        def source(start_epoch=0, epochs=1):
            return device_epoch_chunks(
                ds, num_workers=W, local_batch=args.local_batch,
                steps_per_chunk=args.steps_per_chunk, route_key=route_key,
                sync_every=args.sync_every, seed=args.seed,
                start_epoch=start_epoch, epochs=epochs, plan=plan,
            )
    else:
        from fps_tpu.core.ingest import epoch_chunks

        def source(start_epoch=0, epochs=1):
            def it():
                for e in range(start_epoch, start_epoch + epochs):
                    yield from epoch_chunks(
                        data, num_workers=W, local_batch=args.local_batch,
                        steps_per_chunk=args.steps_per_chunk,
                        route_key=route_key, sync_every=args.sync_every,
                        seed=None if args.seed is None else args.seed + e,
                    )

            return it()

    return source


def make_chunks(args, mesh, data, *, route_key=None):
    """Chunk iterator over --epochs passes (one-shot form of
    :func:`make_epoch_source`)."""
    return make_epoch_source(args, mesh, data, route_key=route_key)(
        0, args.epochs
    )


def make_mesh(args):
    """The run's mesh. Every example CLI builds it before its first
    compile, so this is also where the persistent compilation cache is
    switched on."""
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()
    return make_ps_mesh(num_shards=args.num_shards, num_data=args.num_data)


def emit(record: dict) -> None:
    """One JSON line per event — the WOut metrics stream."""
    json.dump({k: _py(v) for k, v in record.items()}, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()


def _py(v):
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    return v


def finish(args, store, trainer=None, local_state=None, recorder=None):
    """Handle --export and close the --obs-dir telemetry at end of run."""
    if args.export:
        from fps_tpu.core.checkpoint import export_model

        export_model(store, args.export)
        emit({"event": "export", "path": args.export})
    if recorder is not None:
        recorder.close()  # run_end journal record + final flush


def maybe_checkpointer(args):
    if args.checkpoint_dir and args.checkpoint_every > 0:
        from fps_tpu.core.checkpoint import AsyncCheckpointer, Checkpointer

        cls = (AsyncCheckpointer if getattr(args, "checkpoint_async", False)
               else Checkpointer)
        return cls(args.checkpoint_dir)
    if getattr(args, "checkpoint_async", False):
        raise SystemExit("--checkpoint-async requires --checkpoint-dir "
                         "and --checkpoint-every")
    return None


def maybe_warm_start(args, store, key) -> None:
    """Apply --warm-start after store init (tables must exist first)."""
    if args.warm_start:
        from fps_tpu.core.checkpoint import load_model

        load_model(store, args.warm_start)
        emit({"event": "warm_start", "path": args.warm_start})


def maybe_profile(args):
    """Context manager tracing the training region when --profile is set."""
    if getattr(args, "profile", None):
        from fps_tpu.obs import trace

        emit({"event": "profile", "dir": args.profile})
        return trace(args.profile)
    return contextlib.nullcontext()
