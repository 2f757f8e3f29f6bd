"""Headline benchmarks vs a MEASURED sequential-baseline, on the real TPU.

BASELINE.json metric: "MovieLens-20M MF epoch time; text8 word2vec
words/sec/chip". The reference publishes no numbers (``"published": {}``)
and its Flink/JVM stack cannot run in this image, so every ``vs_baseline``
here is computed against a *measured, compiled* stand-in rather than a
guessed constant: ``fps_tpu/native/src/fps_native.cc`` implements the
reference's sequential per-record parameter-server hot loops (MF
pull→SGD→push, per-pair SGNS, per-feature sparse logreg) in C++ in two
modes, both strictly generous to the reference:

* ``ps``    — every pull request / pull response / push delta pays a real
  message hop (noinline memcpy through a bounded ring), the cheapest
  possible model of the reference's Flink operator hops (no JVM, no
  serialization framework, no network). ``vs_baseline`` is measured
  against THIS mode: same architecture, zero framework overhead.
* ``ideal`` — the fused sequential loop with direct array access, a floor
  no real deployment reaches. Reported alongside (``baseline`` field) for
  full honesty; on transaction-bound single-chip workloads (rank-10 MF,
  scalar-table logreg) it is genuinely competitive — see BASELINE.md's
  roofline discussion.

Default (no args) runs ALL workloads and prints one JSON line per
workload — w2v, logreg, ials first, the headline MF line LAST:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
vs_baseline > 1 means this framework is faster than the measured baseline.

* ``mf``     — ML-20M-scale MF **wall-clock to train-RMSE <= 0.12**
  (planted-structure noise floor ~0.1) vs the native loop's OWN measured
  time-to-the-same-target (it converges in fewer epochs — sequential SGD
  is the per-epoch gold standard — and pays that credit honestly).
* ``w2v``    — text8-scale SGNS words/sec/chip vs the native per-pair
  loop's words/sec on the same pair distribution.
* ``logreg`` — Criteo-scale SSP logreg examples/sec/chip vs the native
  per-example fan-out loop.
* ``ials``   — planted-implicit time to recall@20 >= 0.35 (plateau ~0.39;
  no reference baseline exists: iALS is a required extension the
  reference lacks).

Compile time is excluded everywhere via a warm-up pass on throwaway
state; each workload also prints a learning-evidence line on stderr
(NaN/flat = diverged — treat as failure regardless of speed).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def attach_phase_recorder(trainer):
    """Sink-less obs recorder on the trainer for the TIMED region: the
    per-workload JSON gains a ``phases`` breakdown (dispatch / host_sync /
    checkpoint seconds+counts), so a BENCH regression is attributable to
    a phase instead of one opaque wall-clock number. Aggregates-only (no
    sinks, no extra host syncs) — the recorder never changes the driver's
    sync behavior, so the measured numbers are unaffected."""
    from fps_tpu import obs

    rec = obs.Recorder(sinks=[])
    trainer.recorder = rec
    return rec


def phase_summary(rec):
    return {ph: {"s": round(v["s"], 4), "n": v["n"]}
            for ph, v in sorted(rec.phase_totals().items())}


# Driver-thread phases that serialize against dispatch — the host work
# the overlapped pipeline (fps_tpu.core.prefetch) moves off the critical
# path. 'prefetch' itself is worker-thread time and deliberately NOT in
# this sum: it overlaps the phases below. 'reconcile' is the two-tier
# re-split at run entry (once per run, host-side).
HOST_SERIAL_PHASES = ("ingest", "place", "host_sync", "checkpoint",
                      "callback", "reconcile")


# ---------------------------------------------------------------------------
# Cross-shard collective accounting (two-tier A/B evidence).
#
# The implementation grew into the static-analysis subsystem
# (fps_tpu.analysis — HloProgram model + contract pass suite);
# count_collectives is re-exported here for backward compatibility, and
# collective_profile is its structured form: one (kind, payload_bytes,
# replica_groups) entry per qualifying collective, so the A/B can report
# payload BYTES moved per chunk alongside the op count.
# ---------------------------------------------------------------------------

from fps_tpu.analysis import (  # noqa: F401  (count_collectives: re-export)
    collective_profile,
    count_collectives,
)


def host_pipeline_ab(trainer, init_state, make_chunks, *, depth=2):
    """A/B the fit_stream host pipeline on one workload.

    Runs the SAME chunk stream twice — background prefetch+place pipeline
    off, then on (fresh state each arm, shared compiled program) — and
    reports wall-clock, the per-phase breakdown, and the host-serial
    share of wall-clock for both arms, plus per-phase and overall overlap
    ratios. The BENCH trajectory's acceptance signal: host_serial_share
    must strictly drop from ``off`` to ``on`` (the chunks are
    bit-identical either way, so nothing else may move)."""
    import dataclasses

    import jax

    from fps_tpu import obs

    out = {"prefetch_depth": depth}
    base, base_rec = trainer.config, trainer.recorder
    try:
        for label, pf in (("off", 0), ("on", depth)):
            trainer.config = dataclasses.replace(base, prefetch=pf)
            rec = obs.Recorder(sinks=[])
            trainer.recorder = rec
            tables, ls = init_state()
            t0 = time.perf_counter()
            trainer.fit_stream(tables, ls, make_chunks(), jax.random.key(1))
            wall = time.perf_counter() - t0
            phases = {ph: round(v["s"], 4)
                      for ph, v in sorted(rec.phase_totals().items())}
            serial = sum(phases.get(ph, 0.0) for ph in HOST_SERIAL_PHASES)
            out[label] = {
                "wall_s": round(wall, 4),
                "host_serial_s": round(serial, 4),
                "host_serial_share": (round(serial / wall, 4) if wall
                                      else None),
                "phases": phases,
            }
    finally:
        trainer.config = base
        trainer.recorder = base_rec
    off, on = out["off"], out["on"]
    out["overlap_ratio"] = (
        round(1.0 - on["host_serial_s"] / off["host_serial_s"], 4)
        if off["host_serial_s"] > 0 else None)
    out["phase_overlap"] = {
        ph: round(1.0 - on["phases"].get(ph, 0.0) / v, 4)
        for ph, v in off["phases"].items()
        if ph in HOST_SERIAL_PHASES and v > 1e-9
    }
    out["speedup"] = (round(off["wall_s"] / on["wall_s"], 3)
                      if on["wall_s"] else None)
    return out


def first_last_real_step(metrics, key):
    """Per-example metric value at the first and last non-padding step of
    one epoch's metrics dict (trailing steps are weight-0 padding)."""
    vals = np.asarray(metrics[key])
    counts = np.asarray(metrics["n"])
    real = np.flatnonzero(counts > 0)
    if len(real) == 0:  # degenerate shard: every step was padding
        return float("nan"), float("nan")
    return (vals[real[0]] / counts[real[0]],
            vals[real[-1]] / counts[real[-1]])


def _time_to_target(per_epoch_s, curve, target):
    """Baseline time-to-target: median epoch seconds x epochs needed.
    The median (not the raw cumsum) makes the BASELINE's number robust to
    transient host contention from the preceding TPU workload — raw first
    -epoch spikes would inflate the baseline and flatter ``vs_baseline``.
    (Our own side always reports its raw measured wall-clock.) Returns
    ``(seconds, epochs)`` or ``(None, None)`` if the target is never hit."""
    import statistics

    for e, v in enumerate(curve):
        if v <= target:
            return statistics.median(per_epoch_s) * (e + 1), e + 1
    return None, None


def _rate_baseline(base_by_mode, kind, unit, our_rate, quality_by_mode):
    """Assemble the JSON ``baseline`` dict + ``vs_baseline`` for a
    rate-metric workload (logreg, pa) from per-mode measured rates, and
    print the per-mode stderr lines. Shared so the baseline JSON shape and
    report format cannot drift between workloads."""
    baseline = {"kind": "unavailable"}
    vs = None
    for label, rate in base_by_mode.items():
        if label == "ps":
            baseline = {"kind": kind, f"ps_{unit}_per_s": round(rate, 1)}
            vs = round(our_rate / rate, 2)
        else:
            baseline[f"ideal_{unit}_per_s"] = round(rate, 1)
        print(f"native baseline [{label}]: {1e9 / rate:.0f} ns/{unit[:-1]} "
              f"({rate / 1e6:.2f}M {unit}/s), "
              f"{quality_by_mode[label]}", file=sys.stderr)
    return baseline, vs


def _measure_native_modes(thunk):
    """Yield ``(label, result)`` for the ``ps`` then ``ideal`` native
    baseline modes, best-of-2 each: transient host contention from the
    preceding TPU dispatch must not inflate the baseline (min = least
    -contended, i.e. most favorable to the reference). Stops silently if
    the native library is unavailable (result None)."""
    for label, ps_mode in (("ps", True), ("ideal", False)):
        res = min((thunk(ps_mode) for _ in range(2)),
                  key=lambda r: r[0] if r else float("inf"))
        if res is None:
            return
        yield label, res


# ---------------------------------------------------------------------------
# Matrix factorization (headline)
# ---------------------------------------------------------------------------

def run_mf(args):
    import statistics

    import jax

    from fps_tpu import native
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.utils.datasets import load_movielens

    data, nu, ni = load_movielens(args.movielens_path, args.scale)
    nr = len(data["user"])
    target = args.rmse_target
    LR, REG = 0.1, 0.01

    # MEASURED baseline FIRST, before any TPU work: the process is quiet
    # here, so the sequential loop gets its least-contended (most
    # favorable) timing window. The native loop runs the SAME ratings with
    # the SAME hyperparameters to the SAME target on its own online-RMSE
    # curve; per-epoch times are element-wise min'd over two runs
    # (host-contention noise on this shared VM swings single-run epochs by
    # ~1.5x). The baseline gets the same --max-epochs search budget as our
    # side — a stricter --rmse-target must not silently drop the
    # comparison by under-searching the baseline.
    baseline = {"kind": "unavailable"}
    base_tt = {}
    for label, ps_mode in (("ps", True), ("ideal", False)):
        # Early-stop schedule: at the shared lr the sequential loop reaches
        # the default target inside 3 epochs; only a stricter --rmse-target
        # pays for the full --max-epochs search (wall-clock matters — the
        # driver runs all five workloads in one bench invocation).
        for budget in (min(3, args.max_epochs), args.max_epochs):
            runs = [native.baseline_mf(
                data["user"], data["item"], data["rating"], nu, ni,
                rank=args.rank, lr=LR, reg=REG, seed=0,
                epochs=budget, ps_mode=ps_mode,
            ) for _ in range(2)]
            if any(r is None for r in runs):
                runs = None
                break
            curve = [m ** 0.5 for m in runs[0][1]]
            if any(r <= target for r in curve) or budget >= args.max_epochs:
                break
        if runs is None:
            break
        secs = [min(a, b) for a, b in zip(runs[0][0], runs[1][0])]
        tt, _ = _time_to_target(secs, curve, target)
        base_tt[label] = tt
        if label == "ps":
            baseline = {
                "kind": "measured native sequential PS loop (message-hop "
                        "mode); 'ideal' = fused-loop floor",
                "ps_time_to_target_s": round(tt, 3) if tt else None,
                "ps_epoch_s": round(float(np.median(secs)), 4),
            }
        else:
            baseline["ideal_time_to_target_s"] = round(tt, 3) if tt else None
            baseline["ideal_epoch_s"] = round(float(np.median(secs)), 4)
        print(f"native baseline [{label}]: epoch_s="
              f"{[round(s, 3) for s in secs]} rmse="
              f"{[round(r, 4) for r in curve]}", file=sys.stderr)

    devs = jax.devices()
    nd, ns = default_mesh_shape(len(devs))
    mesh = make_ps_mesh(num_shards=ns, num_data=nd)
    W = num_workers_of(mesh)

    # LR=0.1 is the shared operating point for BOTH systems (measured
    # sweep, round 3): at this noise floor it converges in 3 epochs for
    # ours AND the native sequential loop (vs 5 and 4 at the old 0.05),
    # stable across shuffle seeds; both sides always run the SAME
    # hyperparameters, so the comparison never rests on asymmetric tuning.
    cfg = MFConfig(num_users=nu, num_items=ni, rank=args.rank,
                   learning_rate=LR, reg=REG)
    # Per-id mean combine: at this batch size summed duplicate updates on
    # Zipfian-hot items diverge (the quality line below would show NaN);
    # mean-combine is the reference's combining-sender analog and learns
    # stably at any batch size.
    trainer, store = online_mf(mesh, cfg, combine="mean")
    dataset = DeviceDataset(mesh, data)  # one-time upload, outside the epoch
    plan = DeviceEpochPlan(
        dataset,
        num_workers=W,
        local_batch=args.local_batch,
        route_key="user",
        seed=1,
    )

    # Warm-up: compile + one full epoch on throwaway state (ingest is fused
    # into the jit, so the whole epoch — shuffle, batch gathers, training —
    # is ONE dispatch). The timed run below reuses the compiled program on
    # FRESH state: time-to-quality excludes one-time compilation.
    tables, local_state = trainer.init_state(jax.random.key(0))
    trainer.run_indexed(tables, local_state, plan, jax.random.key(9))

    tables, local_state = trainer.init_state(jax.random.key(0))
    rec = attach_phase_recorder(trainer)  # timed region only (post-warmup)
    epoch_times, rmse_curve = [], []
    # Speculative epoch pipelining: dispatch epoch e+1 BEFORE blocking on
    # epoch e's metrics, so the ~0.1-0.3 s per-epoch dispatch + sync round
    # trip overlaps device execution instead of serializing between
    # epochs. Epochs execute in order on the chip, so blocking on epoch
    # e's metrics returns exactly when e finishes — the recorded
    # time-to-target is unchanged in meaning, and the one speculative
    # epoch in flight at the stop point is simply discarded.
    t_start = time.perf_counter()
    t_prev = t_start
    pending = []  # device metrics dicts of not-yet-evaluated epochs

    def eval_oldest():
        """Block on the oldest pending epoch's (se, n) — ONE fetch round
        trip — and record its RMSE and wall time."""
        nonlocal t_prev
        md = pending.pop(0)
        se, n = jax.device_get((md["se"], md["n"]))
        rmse_e = float(np.sqrt(se.sum() / max(float(n.sum()), 1.0)))
        now = time.perf_counter()
        epoch_times.append(now - t_prev)
        t_prev = now
        rmse_curve.append(rmse_e)
        return rmse_e

    for e in range(args.max_epochs):
        tables, local_state, m = trainer.run_indexed(
            tables, local_state, plan, jax.random.key(1),
            epochs=1, start_epoch=e, as_numpy=False,
        )
        pending.append(m[0])
        if e == 0:
            continue  # keep one epoch in flight before evaluating
        if eval_oldest() <= target:
            break
    while pending and (not rmse_curve or rmse_curve[-1] > target):
        eval_oldest()
    total_s = sum(epoch_times)
    epochs = len(epoch_times)
    median_epoch = statistics.median(epoch_times)
    reached = rmse_curve[-1] <= target
    # Speculative pipelining: when the target is hit with an epoch still in
    # flight, that epoch's updates are already in `tables` — the post-loop
    # state reflects up to epochs+1 training passes, while timing/quality
    # cover exactly `epochs`. Only timing + rmse_curve are reported here;
    # anyone consuming the final state (export, extra eval) must account
    # for the extra pass — hence the explicit flag in the summary.
    state_extra_epochs = len(pending)

    vs = None
    if base_tt.get("ps") is not None and reached:
        vs = round(base_tt["ps"] / total_s, 2)

    # Host-pipeline A/B on the HOST-ingest path (fit_stream +
    # epoch_chunks): per-chunk numpy assembly + upload is exactly the
    # serial host work the overlapped pipeline hides, and the fused
    # run_indexed numbers above cannot show it. Bounded chunk budget so
    # the A/B stays a small fraction of the headline run.
    from itertools import islice

    from fps_tpu.core.ingest import epoch_chunks

    def ab_chunks(n=12):
        return islice(
            epoch_chunks(data, num_workers=W, local_batch=args.local_batch,
                         steps_per_chunk=8, route_key="user", seed=5),
            n)

    trainer.recorder = None  # keep the headline phases breakdown clean
    wt, wl = trainer.init_state(jax.random.key(7))
    trainer.fit_stream(wt, wl, ab_chunks(2), jax.random.key(8))  # compile
    host_pipeline = host_pipeline_ab(
        trainer, lambda: trainer.init_state(jax.random.key(0)), ab_chunks)

    print(
        "quality: per-epoch train RMSE "
        + " -> ".join(f"{r:.4f}" for r in rmse_curve)
        + (f" (reached <= {target})" if reached
           else f" (STOPPED at max_epochs={args.max_epochs} without "
                f"reaching {target})"),
        file=sys.stderr,
    )
    print(f"epoch times: {[round(t, 3) for t in epoch_times]} s "
          f"(median {median_epoch:.4f})", file=sys.stderr)

    return {
        "metric": f"ml{args.scale}_mf_time_to_rmse_{target}",
        "value": round(total_s, 4),
        "unit": "s",
        "vs_baseline": vs,
        "epochs": epochs,
        "median_epoch_s": round(median_epoch, 4),
        "final_train_rmse": round(rmse_curve[-1], 4),
        "reached": reached,
        "state_extra_epochs": state_extra_epochs,
        "phases": phase_summary(rec),
        "host_pipeline": host_pipeline,
        "baseline": baseline,
    }


# ---------------------------------------------------------------------------
# word2vec SGNS
# ---------------------------------------------------------------------------

def run_w2v(args):
    import jax

    from fps_tpu import native
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.word2vec import (
        W2VConfig, Word2VecDevicePlan, _keep_probs, word2vec_block,
    )
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.utils.datasets import load_text8

    tokens, V, uni = load_text8(
        args.text8_path, vocab_size=50_000, num_tokens=args.num_tokens
    )
    devs = jax.devices()
    nd, ns = default_mesh_shape(len(devs))
    mesh = make_ps_mesh(num_shards=ns, num_data=nd)
    W = num_workers_of(mesh)

    cfg = W2VConfig(vocab_size=V, dim=args.dim, window=5, negatives=5)
    # Block-granularity worker: each block position's IN/OUT row is pulled
    # and pushed once per step (sparse row ops are per-transaction bound on
    # TPU — this is ~10x fewer transactions than per-pair pull/push).
    # Cap each dispatch well under the TPU runtime's per-dispatch deadline.
    trainer, store = word2vec_block(
        mesh, cfg, uni, args.block_len, max_steps_per_call=256
    )
    tables, ls = trainer.init_state(jax.random.key(0))
    plan = Word2VecDevicePlan(
        tokens, uni, cfg, mesh, num_workers=W,
        block_len=args.block_len, seed=1, mode="block",
    )

    # MEASURED baseline FIRST (quiet pre-TPU window — host contention from
    # device dispatch must not inflate the baseline's per-pair cost):
    # native per-pair SGNS over a representative pair sample from the same
    # generator/distribution. Converted to words/s AFTER the epoch runs,
    # via the epoch's actual pair count.
    per_pair_ns = {}
    loss_by_mode = {}
    keep_p = _keep_probs(cfg, uni).astype(np.float32)
    sample = native.skipgram_pairs(
        np.ascontiguousarray(tokens[:2_000_000]), cfg.window, 3,
        keep_p=keep_p,
    )
    if sample is not None:
        c, x = sample
        m_pairs = min(len(c), 1_500_000)
        for label, (secs, loss) in _measure_native_modes(
            lambda m: native.baseline_w2v(
                c[:m_pairs], x[:m_pairs], uni, dim=cfg.dim,
                negatives=cfg.negatives, lr=cfg.learning_rate, ps_mode=m,
            )
        ):
            per_pair_ns[label] = secs / m_pairs
            loss_by_mode[label] = loss

    # Warm-up epoch: compiles the fused program.
    tables, ls, m = trainer.run_indexed(tables, ls, plan, jax.random.key(9))

    rec = attach_phase_recorder(trainer)  # timed region only (post-warmup)
    t0 = time.perf_counter()
    tables, ls, metrics = trainer.run_indexed(
        tables, ls, plan, jax.random.key(1)
    )
    epoch_s = time.perf_counter() - t0
    words_s = len(tokens) / epoch_s / len(devs)  # per chip

    per0, per1 = first_last_real_step(metrics[0], "loss")
    print(
        f"quality: SGNS loss/pair step0 {per0:.4f} -> last-real-step "
        f"{per1:.4f} (epoch 2; init loss = (1+K)*log2 = "
        f"{0.6931 * (1 + cfg.negatives):.3f})",
        file=sys.stderr,
    )

    # metrics "n" counts PAIRS (the quality line above compares loss/n to
    # the (1+K)*log2 per-pair init loss), so no (1+K) rescale here.
    pairs = float(metrics[0]["n"].sum())
    baseline = {"kind": "unavailable"}
    vs = None
    for label, per_pair in per_pair_ns.items():
        base_words_s = len(tokens) / (pairs * per_pair)
        if label == "ps":
            baseline = {
                "kind": "measured native sequential per-pair SGNS "
                        "(message-hop mode); 'ideal' = fused floor",
                "ps_words_per_s": round(base_words_s, 1),
            }
            vs = round(words_s / base_words_s, 2)
        else:
            baseline["ideal_words_per_s"] = round(base_words_s, 1)
        print(f"native baseline [{label}]: {per_pair * 1e9:.0f} ns/pair"
              f" ({base_words_s / 1e3:.0f}k words/s), loss "
              f"{loss_by_mode[label]:.4f}", file=sys.stderr)

    return {
        "metric": "text8_w2v_words_per_sec_per_chip",
        "value": round(words_s, 1),
        "unit": "words/s",
        "vs_baseline": vs,
        "epoch_s": round(epoch_s, 3),
        "phases": phase_summary(rec),
        "baseline": baseline,
    }


# ---------------------------------------------------------------------------
# SSP logistic regression
# ---------------------------------------------------------------------------

def run_logreg(args):
    """Criteo-style bounded-staleness (SSP) logistic regression throughput."""
    import jax

    from fps_tpu import native
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.logistic_regression import (
        LogRegConfig, logistic_regression,
    )
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.utils.datasets import (
        load_sparse, synthetic_sparse_classification,
    )

    NF, NNZ, NEX = 1_000_000, 39, 4_000_000  # Criteo-ish shape
    DENSE = 13  # Criteo's numeric columns, fixed-slot (id j at slot j)
    if args.input:
        from fps_tpu.utils.datasets import sniff_sparse_format

        fmt = sniff_sparse_format(args.input)  # sniff ONCE, pass through
        data, NF = load_sparse(args.input, fmt=fmt, num_features=NF)
        NEX, NNZ = data["feat_ids"].shape
        # Only the Criteo TSV loader guarantees the fixed-slot head.
        if fmt != "criteo":
            DENSE = 0
    else:
        data = synthetic_sparse_classification(NEX, NF, NNZ, seed=0,
                                               noise=0.05,
                                               dense_features=DENSE)
    data = dict(data, label=(data["label"] > 0).astype(np.float32))

    LR = 0.1
    # MEASURED baseline FIRST (quiet pre-TPU window): native per-example
    # fan-out loop on a sample of the same dataset (the reference pulls
    # and pushes each active feature individually — dense or not).
    m_ex = min(NEX, 500_000)
    base_ex_s = {}
    loss_by_mode = {}
    for label, (secs, loss) in _measure_native_modes(
        lambda m: native.baseline_logreg(
            data["feat_ids"][:m_ex], data["feat_vals"][:m_ex],
            data["label"][:m_ex], NF, lr=LR, ps_mode=m,
        )
    ):
        base_ex_s[label] = m_ex / secs
        loss_by_mode[label] = loss

    devs = jax.devices()
    nd, ns = default_mesh_shape(len(devs))
    mesh = make_ps_mesh(num_shards=ns, num_data=nd)
    W = num_workers_of(mesh)
    # dense_features: the 13 numeric weights ride one static pull and one
    # batch-combined push per step instead of 13 scatter rows per example
    # (the fixed-slot layout contract; see LogRegConfig).
    cfg = LogRegConfig(num_features=NF, learning_rate=LR,
                       dense_features=DENSE)
    trainer, store = logistic_regression(
        mesh, cfg, sync_every=8, max_steps_per_call=256
    )
    tables, ls = trainer.init_state(jax.random.key(0))
    ds = DeviceDataset(mesh, data)
    plan = DeviceEpochPlan(
        ds, num_workers=W, local_batch=16384, sync_every=8, seed=1
    )

    tables, ls, _ = trainer.run_indexed(tables, ls, plan, jax.random.key(9))
    rec = attach_phase_recorder(trainer)  # timed region only (post-warmup)
    # Steady-state throughput over E back-to-back epochs (see run_pa).
    E = 2
    t0 = time.perf_counter()
    tables, ls, metrics = trainer.run_indexed(
        tables, ls, plan, jax.random.key(1), epochs=E, as_numpy=False,
    )
    np.asarray(metrics[-1]["n"])
    epoch_s = (time.perf_counter() - t0) / E
    ex_s = NEX / epoch_s / len(devs)

    per0, _ = first_last_real_step(metrics[0], "logloss")
    _, per1 = first_last_real_step(metrics[-1], "logloss")
    print(
        f"quality: logloss step0 {per0:.4f} (epoch 2) -> last-real-step "
        f"{per1:.4f} (epoch {E + 1}; chance = 0.693)",
        file=sys.stderr,
    )

    # MEASURED baseline: native per-example fan-out loop on a sample of the
    # same dataset (the reference pulls/pushes each feature individually).
    baseline, vs = _rate_baseline(
        base_ex_s,
        "measured native sequential per-feature-fan-out logreg "
        "(message-hop mode); 'ideal' = fused floor",
        "examples", ex_s,
        {k: f"logloss {v:.4f}" for k, v in loss_by_mode.items()},
    )

    # Host-pipeline A/B on the host-ingest SSP path (see run_mf). A
    # smaller local batch keeps the per-chunk assembly cost (the thing
    # being overlapped) a sane fraction of each chunk.
    from itertools import islice

    from fps_tpu.core.ingest import epoch_chunks

    def ab_chunks(n=12):
        return islice(
            epoch_chunks(data, num_workers=W, local_batch=4096,
                         steps_per_chunk=8, sync_every=8, seed=5),
            n)

    trainer.recorder = None  # keep the headline phases breakdown clean
    wt, wl = trainer.init_state(jax.random.key(7))
    trainer.fit_stream(wt, wl, ab_chunks(2), jax.random.key(8))  # compile
    host_pipeline = host_pipeline_ab(
        trainer, lambda: trainer.init_state(jax.random.key(0)), ab_chunks)

    return {
        "metric": "criteo_ssp_logreg_examples_per_sec_per_chip",
        "value": round(ex_s, 1),
        "unit": "examples/s",
        "vs_baseline": vs,
        "epoch_s": round(epoch_s, 3),
        "steady_state_epochs": E,
        "phases": phase_summary(rec),
        "host_pipeline": host_pipeline,
        "baseline": baseline,
    }


# ---------------------------------------------------------------------------
# Passive-aggressive (RCV1-scale binary, PA-I)
# ---------------------------------------------------------------------------

def run_pa(args):
    """RCV1-scale binary passive-aggressive throughput (PA-I closed form)."""
    import jax

    from fps_tpu import native
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.passive_aggressive import (
        PAConfig, passive_aggressive,
    )
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.utils.datasets import (
        load_sparse, synthetic_sparse_classification,
    )

    # RCV1 shape: 47236 features, ~76 nonzeros/doc, ~800k docs.
    NF, NNZ, NEX = 47_236, 64, 800_000
    if args.input:
        data, NF = load_sparse(args.input, num_features=NF)
        NEX, NNZ = data["feat_ids"].shape
    else:
        data = synthetic_sparse_classification(NEX, NF, NNZ, seed=3,
                                               noise=0.05)
    # PA (model and native baseline alike) requires labels in {-1,+1};
    # svmlight files commonly carry 0/1, which would pin the hinge at 1.0
    # for negative rows. (run_logreg's analog maps to {0,1} instead —
    # logloss wants probabilities, hinge wants signs.)
    data = dict(data, label=np.where(data["label"] > 0, 1.0,
                                     -1.0).astype(np.float32))

    C = 1.0
    # MEASURED baseline FIRST (quiet pre-TPU window).
    m_ex = min(NEX, 400_000)
    base_ex_s = {}
    quality = {}
    for label, res in _measure_native_modes(
        lambda m: native.baseline_pa(
            data["feat_ids"][:m_ex], data["feat_vals"][:m_ex],
            data["label"][:m_ex], NF, C=C, variant="PA-I", ps_mode=m,
        )
    ):
        secs, hinge, mist = res
        base_ex_s[label] = m_ex / secs
        quality[label] = (hinge, mist)

    # Multiclass baseline in the same quiet pre-TPU window (the 20-class
    # sequential closed-form loop, fps_baseline_pa_mc) on the SAME data the
    # TPU multiclass run will train on.
    from fps_tpu.utils.datasets import synthetic_sparse_multiclass

    NCLS, NEX_MC = 20, 200_000
    mdata = synthetic_sparse_multiclass(NEX_MC, NF, NCLS, NNZ, seed=5)
    mc_base_ex_s = {}
    mc_quality = {}
    for label, res in _measure_native_modes(
        lambda m: native.baseline_pa_mc(
            mdata["feat_ids"], mdata["feat_vals"], mdata["label"], NF, NCLS,
            C=C, variant="PA-I", ps_mode=m,
        )
    ):
        secs, hinge, mist = res
        mc_base_ex_s[label] = NEX_MC / secs
        mc_quality[label] = (hinge, mist)

    devs = jax.devices()
    nd, ns = default_mesh_shape(len(devs))
    mesh = make_ps_mesh(num_shards=ns, num_data=nd)
    W = num_workers_of(mesh)
    # Head-prefix routing (single-device meshes): frequency-sort each
    # example's slots so the first q columns carry ids < H, and the
    # guaranteed prefix rides head-only kernels — measured at ~15% of
    # the end-to-end headline (BASELINE.md round-5: 4.53M ex/s with the
    # machinery off vs 5.36M with it on). Equality-tested in
    # tests/test_passive_aggressive.py.
    HEAD = 2048
    q = 0
    if len(devs) == 1:
        from fps_tpu.utils.datasets import head_sort_slots

        data, q = head_sort_slots(data, HEAD)
    cfg = PAConfig(num_features=NF, variant="PA-I", C=C,
                   hot_features=HEAD if q else 0, head_prefix_cols=q)
    trainer, store = passive_aggressive(mesh, cfg, max_steps_per_call=256)
    tables, ls = trainer.init_state(jax.random.key(0))
    ds = DeviceDataset(mesh, data)
    plan = DeviceEpochPlan(ds, num_workers=W, local_batch=16384, seed=1)

    tables, ls, _ = trainer.run_indexed(tables, ls, plan, jax.random.key(9))
    rec = attach_phase_recorder(trainer)  # timed region only (post-warmup)
    # Steady-state throughput: E back-to-back epochs in one call, blocking
    # only on the final epoch's metrics — epochs queue on-device with no
    # host round trip between them, the same zero-per-pass-overhead
    # semantics the native baseline's tight loop gets. (Single-epoch
    # timing charged ~0.2 s of dispatch + metric-sync against a ~0.25 s
    # device epoch — measured ~90% of the device floor at E=4.)
    E = 4
    t0 = time.perf_counter()
    tables, ls, metrics = trainer.run_indexed(
        tables, ls, plan, jax.random.key(1), epochs=E, as_numpy=False,
    )
    np.asarray(metrics[-1]["n"])  # fence on the last epoch
    epoch_s = (time.perf_counter() - t0) / E
    ex_s = NEX / epoch_s / len(devs)

    per0, _ = first_last_real_step(metrics[0], "mistakes")
    _, per1 = first_last_real_step(metrics[-1], "mistakes")
    print(
        f"quality: online mistake rate step0 {per0:.4f} (epoch 2) -> "
        f"last-real-step {per1:.4f} (epoch {E + 1}; chance = 0.5)",
        file=sys.stderr,
    )

    baseline, vs = _rate_baseline(
        base_ex_s,
        "measured native sequential per-feature-fan-out PA-I (message-hop "
        "mode); 'ideal' = fused floor. NOTE: at RCV1 scale the whole "
        "190 KB weight vector is L2-resident on the host core — the "
        "degenerate best case for the sequential loop",
        "examples", ex_s,
        {k: f"hinge {h:.4f}, mistakes {m:.4f}"
         for k, (h, m) in quality.items()},
    )

    # Multiclass PA (transformMulticlass parity, SURVEY §2 #9): a 20-class
    # RCV1-shaped run measured under the same roof, against its own
    # measured native sequential loop (fps_baseline_pa_mc, above).
    mcfg = PAConfig(num_features=NF, num_classes=NCLS, variant="PA-I", C=C)
    mtr, _ = passive_aggressive(mesh, mcfg, max_steps_per_call=256)
    mt, mls = mtr.init_state(jax.random.key(0))
    mds = DeviceDataset(mesh, mdata)
    mplan = DeviceEpochPlan(mds, num_workers=W, local_batch=16384, seed=1)
    mt, mls, _ = mtr.run_indexed(mt, mls, mplan, jax.random.key(9))
    E_MC = 2  # steady-state over 2 back-to-back epochs (as above)
    t0 = time.perf_counter()
    mt, mls, mm = mtr.run_indexed(mt, mls, mplan, jax.random.key(1),
                                  epochs=E_MC, as_numpy=False)
    np.asarray(mm[-1]["n"])
    mc_epoch_s = (time.perf_counter() - t0) / E_MC
    mc_ex_s = NEX_MC / mc_epoch_s / len(devs)
    m0, _ = first_last_real_step(mm[0], "mistakes")
    _, m1 = first_last_real_step(mm[-1], "mistakes")
    print(
        f"multiclass ({NCLS} classes): online mistake rate step0 {m0:.4f} "
        f"-> last-real-step {m1:.4f} (epoch {E_MC + 1}; "
        f"chance = {1 - 1 / NCLS:.2f})",
        file=sys.stderr,
    )
    mc_baseline, mc_vs = _rate_baseline(
        mc_base_ex_s,
        f"measured native sequential per-feature-fan-out {NCLS}-class PA-I "
        "(message-hop mode, num_classes-float row messages); 'ideal' = "
        "fused floor",
        "examples", mc_ex_s,
        {k: f"hinge {h:.4f}, mistakes {m:.4f}"
         for k, (h, m) in mc_quality.items()},
    )

    return {
        "metric": "rcv1_pa1_examples_per_sec_per_chip",
        "value": round(ex_s, 1),
        "unit": "examples/s",
        "vs_baseline": vs,
        "epoch_s": round(epoch_s, 3),
        "steady_state_epochs": E,
        "phases": phase_summary(rec),
        "baseline": baseline,
        "multiclass": {
            "num_classes": NCLS,
            "examples_per_sec_per_chip": round(mc_ex_s, 1),
            "epoch_s": round(mc_epoch_s, 3),
            "steady_state_epochs": E_MC,
            "mistake_rate_step0": round(float(m0), 4),
            "mistake_rate_last": round(float(m1), 4),
            "chance": round(1 - 1 / NCLS, 2),
            "baseline": mc_baseline,
            "vs_baseline": mc_vs,
        },
    }


# ---------------------------------------------------------------------------
# Two-tier storage A/B (zipf skew; replicated hot head vs sharded-only)
# ---------------------------------------------------------------------------

def _zipf_ratings(num_users, num_items, n, *, alpha=1.05, rank=3, seed=0):
    """Planted low-rank ratings whose ITEM stream is zipf-skewed with
    frequency-ranked ids (hottest first — the head convention every
    tier/hot_ids consumer assumes; real ML20M/text8/Criteo streams have
    exactly this shape)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, num_items + 1) ** alpha
    p /= p.sum()
    user = rng.integers(0, num_users, n).astype(np.int32)
    item = rng.choice(num_items, size=n, p=p).astype(np.int32)
    uf = rng.normal(0, 1.0 / rank ** 0.5, (num_users, rank))
    vf = rng.normal(0, 1.0 / rank ** 0.5, (num_items, rank))
    rating = ((uf[user] * vf[item]).sum(1)
              + rng.normal(0, 0.1, n)).astype(np.float32)
    return {"user": user, "item": item, "rating": rating}


def _reexec_workload_subprocess(workload: str):
    """Run ``--workload <name>`` in a cleaned 8-CPU-device subprocess
    (same pattern as ``__graft_entry__``'s dryrun re-exec): the tier
    A/Bs are specified over the 8-device mesh, and a single-chip TPU
    process cannot widen itself in-place."""
    import os
    import subprocess

    from fps_tpu.utils.hostenv import cpu_mesh_env, reexec_count

    if reexec_count() >= 8:
        raise RuntimeError(
            f"{workload} A/B needs 8 devices, still short after re-exec")
    root = os.path.dirname(os.path.abspath(__file__))
    env = cpu_mesh_env(8)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"),
         "--workload", workload],
        env=env, cwd=root, capture_output=True, text=True, timeout=1500,
    )
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise RuntimeError(
        f"{workload} re-exec produced no JSON; tail: "
        f"{(r.stdout + r.stderr)[-800:]}")


def _reexec_tiered_subprocess():
    return _reexec_workload_subprocess("tiered")


def split_route_bytes(profile, *, hot_rows, dim, num_shards,
                      counted=False, itemsize=4, sketch_bytes=0):
    """Attribute a tiered program's collective bytes per ROUTE: the
    window reconcile's reduce-scatter + all-gather pair (or the legacy /
    extremum all_reduce) is identified by its analytically-known payload
    (``ceil(H/S)*S`` padded head rows times the delta width — the count
    column under a counted combine), everything else is the cold
    pull/push routes. Separating the two makes the payload-proportional
    cold-routing win and the sharded-reconcile cost independently
    attributable in the A/B (one aggregate ratio conflates them)."""
    total = sum(c.payload_bytes for c in profile)
    tracking = 0
    if sketch_bytes:
        # The adaptive tier's end-of-call sketch-merge psum — its own
        # bucket (it is tracking overhead, neither a data route).
        for c in profile:
            if c.kind == "all_reduce" and c.payload_bytes == sketch_bytes:
                tracking += c.payload_bytes
                break
    if not hot_rows:
        return {"cold": total - tracking, "hot_reconcile": 0,
                "tracking": tracking}
    Hp = -(-hot_rows // num_shards) * num_shards
    dimp = dim + (1 if counted else 0)
    rs_bytes = Hp * dimp * itemsize
    ag_bytes = Hp * dim * itemsize
    # The data-axis psum of the owned slice (meshes with a data axis),
    # and the extremum pmax (full head + indicator column).
    slice_bytes = (Hp // num_shards) * dimp * itemsize
    ar_ok = (slice_bytes, Hp * (dim + 1) * itemsize)
    want = {"reduce_scatter": (rs_bytes,), "all_gather": (ag_bytes,),
            "all_reduce": ar_ok}
    reconcile = 0
    matched = {k: False for k in want}
    for c in profile:
        if (c.kind in want and not matched[c.kind]
                and c.payload_bytes in want[c.kind]):
            matched[c.kind] = True
            reconcile += c.payload_bytes
    return {"cold": total - reconcile - tracking,
            "hot_reconcile": reconcile, "tracking": tracking}


def run_tiered(args):
    """Zipf-skew two-tier A/B on the 8-device mesh: the same chunk
    stream trained four ways —

    * **off**  — untiered (per-step collective pull/push);
    * **on**   — full replication (the PR-5 headline: hot reads local,
      one sharded reconcile per ``hot_sync_every`` window);
    * **head** — PARTIAL hot head (H < num_ids) with the STATIC cold
      routes: the ROADMAP scaling cliff — even at a >0.9 hit rate the
      cold collectives still carry the full O(batch) payload;
    * **head_compact** — the same partial head with
      ``TableSpec.cold_budget``: cold ids compact into a bounded lane,
      so cold-route collective bytes track actual cold traffic.

    Reports per-chunk collective count and PER-ROUTE payload bytes (hot
    reconcile vs cold pull/push — :func:`split_route_bytes`) plus
    examples/s per arm, and an ``ssp`` sub-run: the ``head_compact``
    configuration under bounded staleness (``sync_every > 1``),
    measuring the compact/overflow certification rates there (the
    carried-over ROADMAP question; surfaced fleet-wide as
    ``cold_route_cert_rate`` in ``fps_tpu.obs.fleet`` rollups). Acceptance signals: strictly fewer collectives
    and no throughput regression for ``on`` vs ``off`` (PR 5), and a
    >= 3x cold-route byte reduction for ``head_compact`` vs ``head`` at
    a >= 0.9 hit rate (PR 10, pinned statically as the
    ``mf_tiered_compact`` audit budget)."""
    import dataclasses

    import jax

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh

    devs = jax.devices()
    if len(devs) < 8:
        return _reexec_tiered_subprocess()
    nd, ns = default_mesh_shape(8)
    mesh = make_ps_mesh(num_shards=ns, num_data=nd, devices=devs[:8])
    W = num_workers_of(mesh)

    NU, NI, RANK = 4096, 4096, 16
    E_SYNC = 4          # hot_sync_every: the parameter-plane SSP bound
    H_PART = 2048       # partial head: ~0.93 coverage at alpha 1.05
    COLD_BUDGET = 256   # per-worker cold lane (~3.5x expected cold rows)
    LOCAL_BATCH, SPC, CHUNKS = 1024, 8, 12
    data = _zipf_ratings(NU, NI, W * LOCAL_BATCH * SPC * CHUNKS, seed=0)

    def make_chunks(s=None):
        # s > 1 re-chunks the same stream for SSP mode (per-round batch
        # layout: extra leading rounds axis).
        return epoch_chunks(data, num_workers=W, local_batch=LOCAL_BATCH,
                            steps_per_chunk=SPC, route_key="user",
                            sync_every=s, seed=5)

    SSP_S = 2  # the ssp arm's bounded-staleness window (sync_every)
    out = {"hot_sync_every": E_SYNC, "hot_tier_rows": NI,
           "partial_head": H_PART, "cold_budget": COLD_BUDGET,
           "zipf_alpha": 1.05, "mesh": dict(mesh.shape)}
    rates = {}
    # (label, H, cold_budget, force_gathered, sync_every): the
    # partial-head arms force the gathered cold route
    # (dense_collectives=False) — the compaction story is about
    # embedding-scale tables whose cold route cannot afford table-sized
    # dense collectives; at this bench scale the item table would
    # otherwise auto-resolve dense. The "ssp" arm is the head_compact
    # configuration under BOUNDED STALENESS (the carried-over ROADMAP
    # question): the per-chunk host certification is mode-independent
    # (raw id streams, not staleness, decide the lane), and this arm
    # pins that with measured compact/overflow rates — surfaced
    # fleet-wide as cold_route_cert_rate in fps_tpu.obs.fleet rollups.
    arms = (("off", 0, 0, False, None), ("on", NI, 0, False, None),
            ("head", H_PART, 0, True, None),
            ("head_compact", H_PART, COLD_BUDGET, True, None),
            ("ssp", H_PART, COLD_BUDGET, True, SSP_S))
    for label, H, C, gathered, s in arms:
        cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                       learning_rate=0.05)
        # Per-id mean combine: zipf-hot duplicate ids need the averaged
        # step (run_mf's reasoning) — and it exercises the tier's
        # windowed count-normalized reconcile.
        trainer, store = online_mf(mesh, cfg, combine="mean",
                                   sync_every=s)
        if H:
            store.specs["item_factors"] = dataclasses.replace(
                store.specs["item_factors"], hot_tier=H, cold_budget=C,
                **({"dense_collectives": False} if gathered else {}))
            trainer.config = dataclasses.replace(
                trainer.config, hot_sync_every=E_SYNC)
        from fps_tpu import obs

        # Static collective profile of the per-chunk program, split per
        # route (mean combine carries the count column -> counted=True).
        mode = "sync" if s is None else "ssp"
        hlo = trainer.lowered_chunk_text(next(make_chunks(s)), mode)
        profile = collective_profile(hlo)
        colls = len(profile)
        coll_bytes = sum(c.payload_bytes for c in profile)
        routes = split_route_bytes(
            profile, hot_rows=H, dim=RANK, num_shards=ns, counted=True)

        # Warm-up (compile), then timed run on fresh state with a fresh
        # recorder — the hit-rate counters must scope the timed pass
        # only, not the warm-up traffic.
        from itertools import islice

        tables, ls = trainer.init_state(jax.random.key(0))
        trainer.fit_stream(tables, ls, islice(make_chunks(s), 2),
                           jax.random.key(9))
        rec = obs.Recorder(sinks=[])
        trainer.recorder = rec
        tables, ls = trainer.init_state(jax.random.key(0))
        t0 = time.perf_counter()
        tables, ls, m = trainer.fit_stream(
            tables, ls, make_chunks(s), jax.random.key(1))
        wall = time.perf_counter() - t0
        n_ex = float(sum(np.asarray(mm["n"]).sum() for mm in m))
        se = float(sum(np.asarray(mm["se"]).sum() for mm in m))
        rates[label] = n_ex / wall
        arm = {
            "collectives_per_chunk": colls,
            # Payload bytes those collectives move per chunk program —
            # the structured profile's sum (fps_tpu.analysis), split by
            # ROUTE so the reconcile-sharding and cold-compaction wins
            # are separately attributable (the partial-head scaling
            # cliff is a BYTES story the bare count can't show).
            "collective_bytes_per_chunk": coll_bytes,
            "cold_bytes_per_chunk": routes["cold"],
            "hot_reconcile_bytes_per_chunk": routes["hot_reconcile"],
            "examples_per_sec": round(n_ex / wall, 1),
            "wall_s": round(wall, 4),
            "train_rmse": round((se / max(n_ex, 1.0)) ** 0.5, 4),
        }
        if s is not None:
            arm["sync_every"] = s
        if H:
            hr = rec.counter_value("hot_tier.hot_rows",
                                   table="item_factors")
            pr = rec.counter_value("hot_tier.pulled_rows",
                                   table="item_factors")
            # None under SSP by design: reads come from the round
            # snapshot, not the replica, so no pull counters flow
            # (driver fold docs).
            arm["hot_hit_rate"] = round(hr / pr, 4) if pr else None
        if C:
            arm["compact_chunks"] = int(
                rec.counter_value("cold_route.compact_chunks"))
            arm["overflow_chunks"] = int(rec.counter_value(
                "cold_route.overflow_chunks", table="item_factors"))
            arm["cold_dropped"] = int(rec.counter_value(
                "hot_tier.cold_dropped", table="item_factors"))
            total = arm["compact_chunks"] + arm["overflow_chunks"]
            arm["certification_rate"] = (
                round(arm["compact_chunks"] / total, 4) if total
                else None)
        out[label] = arm

    off, on = out["off"], out["on"]
    head, compact = out["head"], out["head_compact"]
    out["collectives_fewer"] = (on["collectives_per_chunk"]
                                < off["collectives_per_chunk"])
    # PER-ROUTE ratios (PR 10): the cold ratio isolates the compaction
    # win at the same head; the reconcile share shows what the sharded
    # window exchange costs against the cold traffic it absorbs.
    out["collective_bytes_ratio"] = {
        "cold_compact_vs_static": (
            round(compact["cold_bytes_per_chunk"]
                  / head["cold_bytes_per_chunk"], 4)
            if head["cold_bytes_per_chunk"] else None),
        "cold_head_vs_off": (
            round(head["cold_bytes_per_chunk"]
                  / off["cold_bytes_per_chunk"], 4)
            if off["cold_bytes_per_chunk"] else None),
        "total_on_vs_off": (
            round(on["collective_bytes_per_chunk"]
                  / off["collective_bytes_per_chunk"], 4)
            if off["collective_bytes_per_chunk"] else None),
    }
    ratio = out["collective_bytes_ratio"]["cold_compact_vs_static"]
    out["cold_bytes_reduction_x"] = (
        round(1.0 / ratio, 2) if ratio else None)
    out["speedup"] = round(rates["on"] / rates["off"], 3)
    out["speedup_compact_vs_head"] = round(
        rates["head_compact"] / rates["head"], 3)
    print(
        f"tiered A/B: collectives/chunk {off['collectives_per_chunk']} -> "
        f"{on['collectives_per_chunk']} "
        f"({off['collective_bytes_per_chunk']} -> "
        f"{on['collective_bytes_per_chunk']} bytes), examples/s "
        f"{off['examples_per_sec']:.0f} -> {on['examples_per_sec']:.0f}, "
        f"hot hit rate {on.get('hot_hit_rate')}; partial head "
        f"hit rate {head.get('hot_hit_rate')}, cold bytes/chunk "
        f"{head['cold_bytes_per_chunk']} -> "
        f"{compact['cold_bytes_per_chunk']} "
        f"({out['cold_bytes_reduction_x']}x, overflow "
        f"{compact.get('overflow_chunks')}, dropped "
        f"{compact.get('cold_dropped')}); SSP s={SSP_S} cert rate "
        f"{out['ssp']['certification_rate']} (overflow "
        f"{out['ssp']['overflow_chunks']})", file=sys.stderr)
    return {
        "metric": "zipf_mf_two_tier_examples_per_sec",
        "value": on["examples_per_sec"],
        "unit": "examples/s",
        # The A/B's own ratio: tier-on throughput over tier-off on the
        # same mesh/stream (no native-loop analog exists for this one).
        "vs_baseline": out["speedup"],
        **out,
    }


def _drifting_zipf_ratings(num_users, num_items, n, *, alpha=1.2, rank=3,
                           rotate_frac=0.5, shift=None, seed=0):
    """Planted low-rank ratings whose ITEM popularity RANKING rotates
    mid-stream: the first ``rotate_frac`` of examples draw item ids with
    Zipf rank = id (frequency-ranked, hottest first — the convention a
    static tier is specified against); the rest draw with rank =
    ``(id - shift) mod num_items``, so the hot head MOVES to ids around
    ``shift``. Stream order is temporal (feed with ``seed=None`` chunking
    so the drift survives ingest)."""
    rng = np.random.default_rng(seed)
    shift = num_items // 2 if shift is None else shift
    p = 1.0 / np.arange(1, num_items + 1) ** alpha
    p /= p.sum()
    n1 = int(n * rotate_frac)
    user = rng.integers(0, num_users, n).astype(np.int32)
    item1 = rng.choice(num_items, size=n1, p=p).astype(np.int32)
    item2 = ((rng.choice(num_items, size=n - n1, p=p) + shift)
             % num_items).astype(np.int32)
    item = np.concatenate([item1, item2])
    uf = rng.normal(0, 1.0 / rank ** 0.5, (num_users, rank))
    vf = rng.normal(0, 1.0 / rank ** 0.5, (num_items, rank))
    rating = ((uf[user] * vf[item]).sum(1)
              + rng.normal(0, 0.1, n)).astype(np.float32)
    return {"user": user, "item": item, "rating": rating}


def run_tiered_drift(args):
    """Drifting-Zipf adaptive-tiering A/B (fps_tpu.tiering;
    docs/performance.md "Adaptive tiering") on the 8-device mesh: the
    SAME drifting MF stream (item hot set rotates mid-run) trained
    three ways —

    * **static-oracle**: the best static config full knowledge buys
      under the replica budget (full item-table replication, E=4 — the
      PR 5 proven-win arm; drift-immune by construction);
    * **static-stale**: the PR 5-style hand-tuned partial head a user
      would pin from phase-1 frequencies (H=512, E=4) — after the
      rotation its replica serves ~nothing, and the program pays the
      full per-step collective complement it was meant to avoid;
    * **adaptive**: ``TrainerConfig.auto_tier`` — online tracking + the
      planner derive the config instead (it finds the item table fits
      the budget and fully replicates), with the Retierer's checks
      riding the run.

    Acceptance (ISSUE 9 / ROADMAP): adaptive examples/s within ~10% of
    the oracle and strictly above static-stale. A second sub-experiment
    (``rerank_recovery``) forces a PARTIAL mapped head under a tight
    replica budget and shows the re-ranker recovering the hot-tier HIT
    RATE after the rotation (static-stale's collapses), with ZERO
    recompiles across re-ranks — the online half of the NuPS story,
    which throughput alone cannot show (cold-route payloads are static
    shapes; the count win needs full replication).
    """
    import dataclasses

    import jax

    from fps_tpu import obs
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.tiering import Retierer

    devs = jax.devices()
    if len(devs) < 8:
        return _reexec_workload_subprocess("tiered_drift")
    nd, ns = default_mesh_shape(8)
    mesh = make_ps_mesh(num_shards=ns, num_data=nd, devices=devs[:8])
    W = num_workers_of(mesh)

    NU, NI, RANK = 4096, 4096, 16
    E_SYNC, H_STALE = 4, 512
    LOCAL_BATCH, SPC, CHUNKS = 1024, 8, 12
    data = _drifting_zipf_ratings(
        NU, NI, W * LOCAL_BATCH * SPC * CHUNKS, alpha=1.2, seed=0)

    def make_chunks():
        # seed=None: stream order preserved — the drift IS the workload.
        return epoch_chunks(data, num_workers=W, local_batch=LOCAL_BATCH,
                            steps_per_chunk=SPC, route_key="user",
                            seed=None)

    def make_trainer(arm):
        cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                       learning_rate=0.05)
        trainer, store = online_mf(mesh, cfg, combine="mean")
        if arm == "oracle":
            store.specs["item_factors"] = dataclasses.replace(
                store.specs["item_factors"], hot_tier=NI)
            trainer.config = dataclasses.replace(
                trainer.config, hot_sync_every=E_SYNC)
        elif arm == "stale":
            store.specs["item_factors"] = dataclasses.replace(
                store.specs["item_factors"], hot_tier=H_STALE)
            trainer.config = dataclasses.replace(
                trainer.config, hot_sync_every=E_SYNC)
        else:  # adaptive: tracking + planner derive the knobs
            trainer.config = dataclasses.replace(
                trainer.config, auto_tier=True)
        return trainer, store

    out = {"mesh": dict(mesh.shape), "zipf_alpha": 1.2,
           "rotate_at_chunk": CHUNKS // 2, "hot_sync_every": E_SYNC,
           "stale_head": H_STALE, "num_items": NI}
    rates = {}
    from itertools import islice

    for arm in ("oracle", "stale", "adaptive"):
        trainer, store = make_trainer(arm)
        # Warm-up: compile — and for the adaptive arm, let the tracker
        # see enough traffic that the planner fires and its (one,
        # deliberate) recompile happens OUTSIDE the timed region; the
        # timed run then starts with the planned config via
        # on_run_entry, like any restarted production run would.
        tables, ls = trainer.init_state(jax.random.key(0))
        trainer.fit_stream(tables, ls, islice(make_chunks(), 6),
                           jax.random.key(9))
        hlo = trainer.lowered_chunk_text(next(make_chunks()), "sync")
        profile = collective_profile(hlo)
        rec = obs.Recorder(sinks=[])
        trainer.recorder = rec
        tables, ls = trainer.init_state(jax.random.key(0))
        t0 = time.perf_counter()
        tables, ls, m = trainer.fit_stream(
            tables, ls, make_chunks(), jax.random.key(1))
        wall = time.perf_counter() - t0
        n_ex = float(sum(np.asarray(mm["n"]).sum() for mm in m))
        se = float(sum(np.asarray(mm["se"]).sum() for mm in m))
        rates[arm] = n_ex / wall
        Hres = trainer._hot_tier_map().get("item_factors", 0)
        sketch_b = 0
        if trainer.retierer is not None:
            cm = trainer.retierer.spec
            sketch_b = cm.depth * cm.width * 4
        routes = split_route_bytes(
            profile, hot_rows=Hres, dim=RANK,
            num_shards=mesh.shape["shard"], counted=True,
            sketch_bytes=sketch_b)
        arm_out = {
            "collectives_per_chunk": len(profile),
            "collective_bytes_per_chunk": sum(
                c.payload_bytes for c in profile),
            # Per-route split (PR 10): cold pull/push vs the window
            # reconcile vs tracking overhead — the three optimizations
            # stay separately attributable.
            "cold_bytes_per_chunk": routes["cold"],
            "hot_reconcile_bytes_per_chunk": routes["hot_reconcile"],
            "tracking_bytes_per_chunk": routes["tracking"],
            "examples_per_sec": round(n_ex / wall, 1),
            "wall_s": round(wall, 4),
            "train_rmse": round((se / max(n_ex, 1.0)) ** 0.5, 4),
        }
        hr = rec.counter_value("hot_tier.hot_rows", table="item_factors")
        pr = rec.counter_value("hot_tier.pulled_rows",
                               table="item_factors")
        arm_out["hot_hit_rate"] = round(hr / pr, 4) if pr else None
        if arm == "adaptive":
            arm_out["planned"] = (
                {n: p.to_json() for n, p in
                 sorted(trainer.retierer.plans.items())}
                if trainer.retierer.plans else None)
        out[arm] = arm_out

    out["within_oracle"] = round(rates["adaptive"] / rates["oracle"], 4)
    out["above_stale"] = bool(rates["adaptive"] > rates["stale"])

    # -- re-rank recovery sub-experiment: tight replica budget forces a
    # PARTIAL mapped head; the hit rate around the rotation is the
    # online-management signal (throughput is program-identical between
    # these two arms — payload shapes are static).
    recovery = {}
    half = CHUNKS // 2
    for label in ("static", "adaptive"):
        cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                       learning_rate=0.05)
        trainer, store = online_mf(mesh, cfg, combine="mean")
        store.specs["item_factors"] = dataclasses.replace(
            store.specs["item_factors"], hot_tier=H_STALE)
        trainer.config = dataclasses.replace(
            trainer.config, hot_sync_every=E_SYNC)
        if label == "adaptive":
            trainer.retierer = Retierer(check_every=2,
                                        churn_threshold=0.1)
        tables, ls = trainer.init_state(jax.random.key(0))
        phases = {}
        chunks = list(make_chunks())
        for phase, sl in (("phase1", chunks[:half]),
                          ("phase2", chunks[half:])):
            rec = obs.Recorder(sinks=[])
            trainer.recorder = rec
            start = 0 if phase == "phase1" else half
            tables, ls, _ = trainer.fit_stream(
                tables, ls, iter(sl), jax.random.key(1),
                start_step=start)
            hr = rec.counter_value("hot_tier.hot_rows",
                                   table="item_factors")
            pr = rec.counter_value("hot_tier.pulled_rows",
                                   table="item_factors")
            phases[phase] = round(hr / pr, 4) if pr else None
        entry = {"hit_rate_phase1": phases["phase1"],
                 "hit_rate_phase2": phases["phase2"]}
        if label == "adaptive":
            entry["re_ranks"] = trainer.retierer.re_ranks
            # Exactly ONE program across both phases and every re-rank:
            # the no-recompile contract, visible in the bench evidence.
            entry["recompiles_after_first"] = len(trainer._compiled) - 1
        recovery[label] = entry
    out["rerank_recovery"] = recovery

    print(
        "tiered_drift: examples/s oracle "
        f"{out['oracle']['examples_per_sec']:.0f} / stale "
        f"{out['stale']['examples_per_sec']:.0f} / adaptive "
        f"{out['adaptive']['examples_per_sec']:.0f} "
        f"(within_oracle {out['within_oracle']}, above_stale "
        f"{out['above_stale']}); recovery hit-rate phase2 static "
        f"{recovery['static']['hit_rate_phase2']} -> adaptive "
        f"{recovery['adaptive']['hit_rate_phase2']} with "
        f"{recovery['adaptive']['re_ranks']} re-ranks, "
        f"{recovery['adaptive']['recompiles_after_first']} recompiles",
        file=sys.stderr)
    return {
        "metric": "drifting_zipf_adaptive_tiering_examples_per_sec",
        "value": out["adaptive"]["examples_per_sec"],
        "unit": "examples/s",
        # The A/B's own ratio: adaptive throughput over the
        # static-oracle arm on the same mesh/stream (1.0 = the planner
        # gave up nothing vs hand-tuned omniscience).
        "vs_baseline": out["within_oracle"],
        **out,
    }


def _serve_ab_one(label, trainer, init_state, make_chunks,
                  make_query, *, queries_hint):
    """One serve-while-train A/B arm pair: train the same stream twice —
    checkpointing both times (the A/B isolates SERVING overhead, not
    checkpoint cost) — first bare, then with a SnapshotWatcher hot-swap
    loop and a query-load thread hammering the in-process ReadServer.
    Returns the per-model dict (train rates, queries/s, p50/p99 lookup
    latency, write→servable lag)."""
    import tempfile
    import threading

    import jax

    from fps_tpu.core.checkpoint import AsyncCheckpointer
    from fps_tpu.serve import NoSnapshotError, ReadServer, SnapshotWatcher

    def timed_fit(ckpt_dir):
        tables, ls = init_state()
        ckpt = AsyncCheckpointer(ckpt_dir, keep=3)
        t0 = time.perf_counter()
        tables, ls, m = trainer.fit_stream(
            tables, ls, make_chunks(), jax.random.key(1),
            checkpointer=ckpt, checkpoint_every=1)
        wall = time.perf_counter() - t0
        ckpt.close()
        n_ex = float(sum(np.asarray(mm["n"]).sum() for mm in m))
        return n_ex, wall

    # Warm-up (compile) on throwaway state, outside every timed region.
    from itertools import islice

    tables, ls = init_state()
    with tempfile.TemporaryDirectory() as d:
        ckpt = AsyncCheckpointer(d, keep=2)
        trainer.fit_stream(tables, ls, islice(make_chunks(), 2),
                           jax.random.key(9), checkpointer=ckpt,
                           checkpoint_every=1)
        ckpt.close()

    with tempfile.TemporaryDirectory() as d:
        n_ex, wall_off = timed_fit(d)
    rate_off = n_ex / wall_off

    with tempfile.TemporaryDirectory() as d:
        server = ReadServer()
        lags = []

        def on_swap(snap, _direction):
            server.swap_to(snap)
            if watcher.write_to_servable_s is not None:
                lags.append(watcher.write_to_servable_s)

        watcher = SnapshotWatcher(d, on_swap=on_swap)
        stop = threading.Event()
        qcount = [0]

        qerr = []

        def query_load():
            rng = np.random.default_rng(0)
            while not stop.is_set():
                try:
                    make_query(server, rng)
                except NoSnapshotError:
                    time.sleep(0.005)
                    continue
                except Exception as e:  # noqa: BLE001 — re-raised below
                    # A dead load generator must fail the workload, not
                    # publish queries_per_sec≈0 as a measurement.
                    qerr.append(e)
                    return
                qcount[0] += 1

        threads = [
            threading.Thread(target=watcher.run,
                             kwargs={"interval_s": 0.05, "stop": stop},
                             name="bench-serve-watcher", daemon=True),
            threading.Thread(target=query_load, name="bench-serve-load",
                             daemon=True),
        ]
        for t in threads:
            t.start()
        n_ex, wall_on = timed_fit(d)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        if qerr:
            raise RuntimeError(
                f"serve[{label}] query load died mid-run") from qerr[0]
        if qcount[0] == 0:
            # BENCH_r14 class of bug: a load generator that never got a
            # query through must FAIL the workload — a reported
            # queries_per_sec of 0.0 is a dead reader, not a rate.
            raise RuntimeError(
                f"serve[{label}] reader_dead: query load finished with "
                "0 queries served")
        if not any(t.is_alive() for t in threads):
            # Pick up the end-of-run flush's final snapshot — unless a
            # thread outlived its join timeout: poll() is
            # single-threaded by contract.
            watcher.poll()
    rate_on = n_ex / wall_on

    lat = server.latency_s() or {}
    lag_steps = None
    if watcher.current is not None and watcher.max_written_step is not None:
        lag_steps = watcher.max_written_step - watcher.current.step
    arm = {
        "train_examples_per_sec_off": round(rate_off, 1),
        "train_examples_per_sec_serving": round(rate_on, 1),
        "train_retention": round(rate_on / rate_off, 4),
        "queries_per_sec": round(qcount[0] / wall_on, 1),
        "queries": qcount[0],
        "latency_p50_s": lat.get("p50"),
        "latency_p99_s": lat.get("p99"),
        "write_to_servable_s_mean": (round(float(np.mean(lags)), 4)
                                     if lags else None),
        "write_to_servable_s_max": (round(float(np.max(lags)), 4)
                                    if lags else None),
        "snapshot_lag_steps_final": lag_steps,
        "swaps": dict(watcher.swaps),
        "rejected_snapshots": watcher.rejected,
        "rows_served": server.rows_served,
    }
    print(f"serve[{label}]: {arm['queries_per_sec']:.0f} q/s "
          f"(hint >= {queries_hint}), p50 {lat.get('p50')}, p99 "
          f"{lat.get('p99')}, write->servable mean "
          f"{arm['write_to_servable_s_mean']}s, train retention "
          f"{arm['train_retention']}", file=sys.stderr)
    return arm


def run_serve(args):
    """Serve-while-train A/B (fps_tpu.serve, docs/serving.md): MF and
    logreg trained with per-chunk async checkpoints while a
    SnapshotWatcher + in-process ReadServer answer a saturating query
    load — reports queries/s, p50/p99 lookup latency, and the
    write→servable freshness lag ALONGSIDE training throughput with and
    without the serving plane attached."""
    import jax

    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.logistic_regression import (
        LogRegConfig,
        logistic_regression,
    )
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    mesh = make_ps_mesh()
    W = num_workers_of(mesh)
    out = {"mesh": dict(mesh.shape)}

    # -- MF: pull + user×item top-k against the exported user factors.
    NU, NI, RANK = 2048, 2048, 8
    LOCAL_BATCH, SPC, CHUNKS = 512, 8, 10
    mf_data = _zipf_ratings(NU, NI, W * LOCAL_BATCH * SPC * CHUNKS, seed=0)
    mf_cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                      learning_rate=0.05)
    mf_trainer, _mf_store = online_mf(mesh, mf_cfg)

    def mf_chunks():
        return epoch_chunks(mf_data, num_workers=W, local_batch=LOCAL_BATCH,
                            steps_per_chunk=SPC, route_key="user", seed=5)

    def mf_query(server, rng):
        if rng.integers(2):
            server.topk(rng.integers(0, NU, 8), k=10)
        else:
            server.pull("item_factors", rng.integers(0, NI, 256))

    out["mf"] = _serve_ab_one(
        "mf", mf_trainer,
        lambda: mf_trainer.init_state(jax.random.key(0)),
        mf_chunks, mf_query, queries_hint=100)

    # -- logreg: batched pull-by-id + sparse linear scoring.
    NF, NNZ = 1 << 14, 16
    lr_data = synthetic_sparse_classification(
        W * 256 * 8 * 10, NF, NNZ, seed=0)
    lr_data["label"] = (lr_data["label"] > 0).astype(np.float32)
    lr_cfg = LogRegConfig(num_features=NF, learning_rate=0.1)
    lr_trainer, _lr_store = logistic_regression(mesh, lr_cfg)

    def lr_chunks():
        return epoch_chunks(lr_data, num_workers=W, local_batch=256,
                            steps_per_chunk=8, seed=5)

    def lr_query(server, rng):
        if rng.integers(2):
            ids = rng.integers(0, NF, (64, NNZ))
            server.score_linear(ids, rng.normal(size=(64, NNZ)))
        else:
            server.pull("weights", rng.integers(0, NF, 256))

    out["logreg"] = _serve_ab_one(
        "logreg", lr_trainer,
        lambda: lr_trainer.init_state(jax.random.key(0)),
        lr_chunks, lr_query, queries_hint=100)

    qps = out["mf"]["queries_per_sec"] + out["logreg"]["queries_per_sec"]
    retention = min(out["mf"]["train_retention"],
                    out["logreg"]["train_retention"])
    return {
        "metric": "serve_while_train_queries_per_sec",
        "value": round(qps, 1),
        "unit": "queries/s",
        # The A/B's own ratio: training throughput retained while the
        # serving plane runs (1.0 = serving is free to the trainer).
        "vs_baseline": retention,
        **out,
    }


# ---------------------------------------------------------------------------
# iALS (required extension; no reference baseline exists)
# ---------------------------------------------------------------------------

def run_ials(args):
    import jax

    from fps_tpu.models.ials import (
        IALSConfig, IALSSolver, interaction_chunks, recall_at_k,
    )
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.utils.datasets import synthetic_implicit, train_test_split

    NU, NI, PER_USER, RANK = 32768, 16384, 64, 16
    TARGET = args.recall_target
    data = synthetic_implicit(NU, NI, PER_USER, rank=8, seed=0)
    train, test = train_test_split(data, test_frac=0.1, seed=1)

    devs = jax.devices()
    # iALS uses the shard axis only: fold ALL devices into it (a (ns, 1)
    # mesh over a subset would fail make_ps_mesh's full-cover check).
    mesh = make_ps_mesh(num_shards=len(devs), num_data=1)
    solver = IALSSolver(mesh, IALSConfig(num_users=NU, num_items=NI,
                                         rank=RANK, alpha=40.0, reg=0.1))

    def chunks():
        return interaction_chunks(train, num_workers=len(devs),
                                  local_batch=65536, steps_per_chunk=4,
                                  seed=0)

    # Warm-up epoch on throwaway state (compile), then re-init and time.
    solver.init(jax.random.key(99))
    solver.epoch(chunks)
    solver.init(jax.random.key(0))

    epoch_times, recalls = [], []
    for e in range(args.max_epochs):
        t0 = time.perf_counter()
        solver.epoch(chunks)
        epoch_times.append(time.perf_counter() - t0)
        r = recall_at_k(solver, test["user"][:2000], test["item"][:2000],
                        k=20, exclude=(train["user"], train["item"]))
        recalls.append(float(r))
        if r >= TARGET:
            break
    total_s = sum(epoch_times)
    reached = recalls[-1] >= TARGET

    print(
        "quality: per-epoch recall@20 "
        + " -> ".join(f"{r:.4f}" for r in recalls)
        + (f" (reached >= {TARGET})" if reached
           else f" (STOPPED at max_epochs={args.max_epochs})"),
        file=sys.stderr,
    )
    print(f"epoch times: {[round(t, 3) for t in epoch_times]} s",
          file=sys.stderr)

    return {
        "metric": f"implicit_ials_time_to_recall20_{TARGET}",
        "value": round(total_s, 4),
        "unit": "s",
        # iALS is a required extension BEYOND the reference's algorithm set
        # (SURVEY §6): there is no reference implementation to measure.
        "vs_baseline": None,
        "epochs": len(epoch_times),
        "final_recall_at_20": round(recalls[-1], 4),
        "reached": reached,
        "baseline": {"kind": "none — algorithm absent from the reference"},
    }


def run_megastep_ab(args):
    """Per-chunk dispatch vs K-chunk megastep on tiered MF (8-device
    mesh): the SAME tiered, cold-budgeted, device-ingested workload
    driven two ways —

    * **per_chunk** — ``run_indexed`` with ``max_steps_per_call`` = one
      chunk: every chunk pays Python dispatch, host key folding, and
      metric bookkeeping between compiled calls;
    * **megastep** — ``run_megastep`` fusing K of those chunks into ONE
      compiled program (``fps_tpu.core.megastep``): reconcile / sketch
      boundaries run in-graph and the device-side overflow VOTE selects
      the compacted cold routes per window (no host id stream exists on
      this path — the gap PR 10 left).

    Acceptance signals: megastep examples/s >= 1.3x per-chunk, final
    tables BIT-IDENTICAL across the two drivers, and the megastep
    program's collective census unchanged when K doubles (the
    O(traffic)-not-O(K) claim, also pinned statically by
    ``tools/audit_programs.py``'s ``mf_megastep`` rows)."""
    import dataclasses

    import jax

    from fps_tpu import obs
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh

    devs = jax.devices()
    if len(devs) < 8:
        return _reexec_workload_subprocess("megastep")
    nd, ns = default_mesh_shape(8)
    mesh = make_ps_mesh(num_shards=ns, num_data=nd, devices=devs[:8])
    W = num_workers_of(mesh)

    NU, NI, RANK = 4096, 4096, 16
    E_SYNC = 4
    H_PART = 2048
    COLD_BUDGET = 8  # ~3x the expected per-(step, worker) cold rows
    # Sized for the dispatch-bound regime the megastep targets: small
    # per-chunk compute (the TPU ratio — sub-ms steps behind a ~ms host
    # round-trip per dispatch), many chunks. The per-chunk arm then
    # pays ~CHUNKS host round-trips per epoch where the megastep pays
    # CHUNKS/K.
    LOCAL_BATCH, SPC, CHUNKS, K = 32, 2, 768, 16
    EPOCHS = 2
    data = _zipf_ratings(NU, NI, W * LOCAL_BATCH * SPC * CHUNKS, seed=0)

    def make_trainer():
        cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                       learning_rate=0.05)
        trainer, store = online_mf(mesh, cfg, combine="mean",
                                   max_steps_per_call=SPC)
        store.specs["item_factors"] = dataclasses.replace(
            store.specs["item_factors"], hot_tier=H_PART,
            cold_budget=COLD_BUDGET, dense_collectives=False)
        trainer.config = dataclasses.replace(
            trainer.config, hot_sync_every=E_SYNC)
        plan = DeviceEpochPlan(
            DeviceDataset(mesh, data), num_workers=W,
            local_batch=LOCAL_BATCH, route_key="user", seed=5)
        return trainer, store, plan

    out = {"chunks_per_dispatch": K, "steps_per_chunk": SPC,
           "partial_head": H_PART, "cold_budget": COLD_BUDGET,
           "hot_sync_every": E_SYNC, "epochs": EPOCHS,
           "mesh": dict(mesh.shape)}
    finals = {}
    # Third arm (ISSUE 20): chunks_per_dispatch="auto" — the calibrated
    # K must land in the explicit arm's dispatch-amortized regime
    # (host_serial_share <= explicit K's) while staying bit-identical.
    for label in ("per_chunk", "megastep", "auto"):
        trainer, store, plan = make_trainer()

        def go(t, ls, key, epochs, _tr=trainer, _p=plan, _label=label):
            if _label == "per_chunk":
                return _tr.run_indexed(t, ls, _p, key, epochs=epochs)
            return _tr.run_megastep(
                t, ls, _p, key, epochs=epochs,
                chunks_per_dispatch=K if _label == "megastep" else "auto")

        # Warm-up pass (compile) on throwaway state, then the timed run
        # on fresh state with a fresh aggregates-only recorder.
        t0s, l0s = trainer.init_state(jax.random.key(0))
        go(t0s, l0s, jax.random.key(9), 1)
        rec = obs.Recorder(sinks=[])
        trainer.recorder = rec
        tables, ls = trainer.init_state(jax.random.key(0))
        t0 = time.perf_counter()
        tables, ls, m = go(tables, ls, jax.random.key(1), EPOCHS)
        wall = time.perf_counter() - t0
        n_ex = float(sum(np.asarray(mm["n"]).sum() for mm in m))
        phases = {ph: round(v["s"], 4)
                  for ph, v in sorted(rec.phase_totals().items())}
        serial = sum(phases.get(ph, 0.0) for ph in HOST_SERIAL_PHASES)
        arm_k = K
        if label == "auto":
            arm_k = max(int(rec.snapshot()["gauges"]["megastep.auto_k"]),
                        1)
        arm = {
            "examples_per_sec": round(n_ex / wall, 1),
            "wall_s": round(wall, 4),
            "host_serial_s": round(serial, 4),
            "host_serial_share": (round(serial / wall, 4) if wall
                                  else None),
            "dispatches": int(
                plan.calls_per_epoch(SPC) * EPOCHS
                if label == "per_chunk" else
                -(-plan.calls_per_epoch(SPC) // arm_k) * EPOCHS),
            "phases": phases,
        }
        if label == "auto":
            arm["chosen_k"] = arm_k
        if label == "megastep":
            arm["vote_compact_windows"] = int(
                rec.counter_value("cold_route.vote_compact_windows"))
            # Unlabeled since the phantom-window fix: ONE AND-ed verdict
            # per window, weighted by real (non-weight-0) segments.
            arm["vote_overflow_windows"] = int(rec.counter_value(
                "cold_route.vote_overflow_windows"))
            arm["cold_dropped"] = int(rec.counter_value(
                "hot_tier.cold_dropped", table="item_factors"))
            arm["windows"] = int(rec.counter_value("megastep.windows"))
            # Phantom-window fix (PR-13 carried-over item): the counter
            # must equal the REAL dispatched chunk count — the same
            # number the per-chunk arm dispatches — not M * K.
            arm["windows_match_dispatched"] = (
                arm["windows"]
                == plan.calls_per_epoch(SPC) * EPOCHS)
        finals[label] = {k: np.asarray(v) for k, v in store.tables.items()
                        if "::" not in k}
        out[label] = arm

    out["numerics_bit_identical"] = all(
        np.array_equal(finals["per_chunk"][k], finals[other][k])
        for other in ("megastep", "auto")
        for k in finals["per_chunk"])
    # ISSUE 20 acceptance: the calibrated K buys at least the explicit
    # K's dispatch amortization (shares are noisy at the 4th decimal —
    # judge with a hair of slack).
    out["auto_share_le_explicit"] = bool(
        out["auto"]["host_serial_share"] is not None
        and out["megastep"]["host_serial_share"] is not None
        and out["auto"]["host_serial_share"]
        <= out["megastep"]["host_serial_share"] + 0.005)
    # The O(traffic)-not-O(K) claim, measured on the lowered programs:
    # doubling K must leave the collective census byte-identical (the
    # per-step collectives live inside the scan body; boundary ticks
    # move O(window) bytes per window).
    trainer, _, plan = make_trainer()
    prof_k = collective_profile(trainer.lowered_megastep_text(
        plan, chunks_per_dispatch=2))
    trainer2, _, plan2 = make_trainer()
    prof_2k = collective_profile(trainer2.lowered_megastep_text(
        plan2, chunks_per_dispatch=4))
    census = [(sum(1 for c in p), sum(c.payload_bytes for c in p))
              for p in (prof_k, prof_2k)]
    out["collective_census_k2"] = {"count": census[0][0],
                                   "bytes": census[0][1]}
    out["collective_census_k4"] = {"count": census[1][0],
                                   "bytes": census[1][1]}
    out["collective_bytes_k_independent"] = census[0] == census[1]
    ratio = (out["megastep"]["examples_per_sec"]
             / out["per_chunk"]["examples_per_sec"]
             if out["per_chunk"]["examples_per_sec"] else None)
    out["speedup"] = round(ratio, 3) if ratio else None
    print(
        f"megastep A/B: examples/s "
        f"{out['per_chunk']['examples_per_sec']:.0f} -> "
        f"{out['megastep']['examples_per_sec']:.0f} "
        f"({out['speedup']}x at K={K}) -> "
        f"{out['auto']['examples_per_sec']:.0f} "
        f"(auto K={out['auto']['chosen_k']}), host_serial_share "
        f"{out['per_chunk']['host_serial_share']} -> "
        f"{out['megastep']['host_serial_share']} -> "
        f"{out['auto']['host_serial_share']} (auto<=explicit "
        f"{out['auto_share_le_explicit']}), bit-identical "
        f"{out['numerics_bit_identical']}, census K-independent "
        f"{out['collective_bytes_k_independent']} (vote compact "
        f"{out['megastep']['vote_compact_windows']} / overflow "
        f"{out['megastep']['vote_overflow_windows']}, dropped "
        f"{out['megastep']['cold_dropped']})", file=sys.stderr)
    return {
        "metric": "megastep_vs_per_chunk_examples_per_sec_ratio",
        "value": out["megastep"]["examples_per_sec"],
        "unit": "examples/s",
        "vs_baseline": out["speedup"],
        **out,
    }


def run_delta(args):
    """Delta-snapshot + serving-fleet A/B (ISSUE 14; docs/serving.md,
    docs/resilience.md) on the tiered zipf-MF workload at a ~0.94 hot
    hit rate: the same stream trained twice with per-chunk async
    checkpoints —

    * **full**  — every publication rewrites whole tables (the PR-7
      baseline: publish bytes and write→servable lag are O(table));
    * **delta** — ``DeltaPolicy`` chains: one full + row-sparse deltas
      sourced from the driver's touched-rows tracker, so publish bytes
      track rows actually touched since the last publication.

    A SnapshotWatcher tails each arm for write→servable lag; the delta
    arm additionally runs the step-fenced SERVING FLEET (N >= 3
    ``FleetReader``s under quorum fencing) with a per-reader query load,
    reporting p50/p99 pull latency under concurrent training.

    Acceptance: >= 3x fewer publish bytes than full snapshots, states
    bit-identical, and the fleet converged on one fenced step."""
    import dataclasses
    import tempfile
    import threading

    import jax

    from fps_tpu.core.checkpoint import AsyncCheckpointer, DeltaPolicy
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import default_mesh_shape, make_ps_mesh
    from fps_tpu.serve import (
        NoSnapshotError,
        ServingFleet,
        SnapshotWatcher,
        scan_heartbeats,
    )

    devs = jax.devices()
    if len(devs) < 8:
        return _reexec_workload_subprocess("delta")
    nd, ns = default_mesh_shape(8)
    mesh = make_ps_mesh(num_shards=ns, num_data=nd, devices=devs[:8])
    W = num_workers_of(mesh)

    # Table large relative to per-chunk traffic (that is the regime the
    # delta encoding exists for); H = half the table gives the tiered
    # arm's ~0.94 hit rate at alpha 1.05 (run_tiered's coverage rule).
    NU, NI, RANK = 32768, 32768, 16
    H, E_SYNC = 12288, 4  # ~0.94 hot hit rate at alpha 1.05
    LOCAL_BATCH, SPC, CHUNKS = 256, 4, 10
    N_READERS = 3
    data = _zipf_ratings(NU, NI, W * LOCAL_BATCH * SPC * CHUNKS, seed=0)

    def make_chunks():
        return epoch_chunks(data, num_workers=W, local_batch=LOCAL_BATCH,
                            steps_per_chunk=SPC, route_key="user", seed=5)

    def make_trainer():
        from fps_tpu import obs

        cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK,
                       learning_rate=0.05)
        trainer, store = online_mf(mesh, cfg, combine="mean")
        store.specs["item_factors"] = dataclasses.replace(
            store.specs["item_factors"], hot_tier=H,
            dense_collectives=False)
        trainer.config = dataclasses.replace(trainer.config,
                                             hot_sync_every=E_SYNC)
        rec = obs.Recorder(sinks=[])
        trainer.recorder = rec
        return trainer, store, rec

    def run_arm(d, policy, *, fleet=None):
        trainer, store, rec = make_trainer()
        tables, ls = trainer.init_state(jax.random.key(0))
        ck = AsyncCheckpointer(d, keep=CHUNKS + 2, delta=policy)
        lags = []
        watcher = SnapshotWatcher(
            d, on_swap=lambda s, _dir: lags.append(
                watcher.write_to_servable_s))
        stop = threading.Event()
        threads = [threading.Thread(
            target=watcher.run, kwargs={"interval_s": 0.05, "stop": stop},
            name="bench-delta-watcher", daemon=True)]
        qcounts = [0] * (len(fleet.readers) if fleet is not None else 0)
        qerr = []
        if fleet is not None:
            fleet.start(interval_s=0.05)

            def load(idx, reader):
                rng = np.random.default_rng(idx)
                while not stop.is_set():
                    try:
                        reader.server.pull(
                            "item_factors", rng.integers(0, NI, 256))
                    except NoSnapshotError:
                        time.sleep(0.005)
                        continue
                    except Exception as e:  # noqa: BLE001 — re-raised
                        qerr.append(e)
                        return
                    qcounts[idx] += 1

            threads += [threading.Thread(
                target=load, args=(i, r), daemon=True,
                name=f"bench-delta-load-{i}")
                for i, r in enumerate(fleet.readers)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        tables, ls, m = trainer.fit_stream(
            tables, ls, make_chunks(), jax.random.key(1),
            checkpointer=ck, checkpoint_every=1)
        wall = time.perf_counter() - t0
        ck.close()
        stop.set()
        if fleet is not None:
            fleet.stop()
        for t in threads:
            t.join(timeout=10.0)
        if qerr:
            raise RuntimeError("delta fleet query load died") from qerr[0]
        n_ex = float(sum(np.asarray(mm["n"]).sum() for mm in m))
        hr = rec.counter_value("hot_tier.hot_rows", table="item_factors")
        pr = rec.counter_value("hot_tier.pulled_rows",
                               table="item_factors")
        pubs = ck.full_publishes + ck.delta_publishes
        arm = {
            "examples_per_sec": round(n_ex / wall, 1),
            "publish_bytes_total": ck.publish_bytes_total,
            "publish_bytes_per_publication": (
                round(ck.publish_bytes_total / pubs) if pubs else None),
            "publications": pubs,
            "delta_publishes": ck.delta_publishes,
            "full_publishes": ck.full_publishes,
            "hot_hit_rate": round(hr / pr, 4) if pr else None,
            "write_to_servable_s_mean": (round(float(np.mean(lags)), 4)
                                         if lags else None),
            "write_to_servable_s_max": (round(float(np.max(lags)), 4)
                                        if lags else None),
        }
        final = store.lookup_host("item_factors", np.arange(NI))
        return arm, final, (qcounts, wall)

    # Warm-up (compile) outside every timed region.
    from itertools import islice

    trainer, _store, _rec = make_trainer()
    tables, ls = trainer.init_state(jax.random.key(9))
    with tempfile.TemporaryDirectory() as d:
        ck = AsyncCheckpointer(d, keep=2)
        trainer.fit_stream(tables, ls, islice(make_chunks(), 2),
                           jax.random.key(9), checkpointer=ck,
                           checkpoint_every=1)
        ck.close()

    policy = DeltaPolicy(full_every=CHUNKS + 4)
    with tempfile.TemporaryDirectory() as d:
        full_arm, full_state, _ = run_arm(d, None)
    # The lag/throughput A/B runs WITHOUT the fleet attached (same
    # topology as the full arm: one watcher) so write->servable compares
    # the PUBLISH paths, not GIL contention from the load generators.
    with tempfile.TemporaryDirectory() as d:
        delta_arm, delta_state, _ = run_arm(d, policy)
    # Fleet pass: same delta-publishing stream with N fence-coordinated
    # readers + per-reader query load hammering them mid-train.
    with tempfile.TemporaryDirectory() as d:
        fleet = ServingFleet(d, N_READERS, quorum=2)
        fleet_arm, _fleet_state, (qcounts, wall) = run_arm(
            d, policy, fleet=fleet)
        # Converge after the end-of-run flush (a reader mid-swap at
        # stop() catches up here; chain failures are retried).
        for _ in range(8):
            fleet.poll()
            if len({r.server._snap.step if r.server._snap else None
                    for r in fleet.readers}) == 1:
                break
        fleet_stats = fleet.stats()
        heartbeats = scan_heartbeats(d)
        # Silent-zero guard (BENCH_r14): a reader that served nothing,
        # or whose liveness beacon went stale relative to its peers, is
        # DEAD — fail the workload instead of averaging a zero into the
        # fleet rate. ("Stale" = older than the freshest beacon by more
        # than the liveness timeout; wall-clock ages don't apply here
        # because training has already stopped by the time we check.)
        from fps_tpu.serve.fleet import DEFAULT_LIVENESS_TIMEOUT_S
        newest_beat = max(
            (hb["t"] for hb in heartbeats.values()), default=None)
        dead = []
        for i, r in enumerate(fleet.readers):
            hb = heartbeats.get(r.reader_id)
            stale = (hb is None or (
                newest_beat is not None
                and newest_beat - hb["t"] > DEFAULT_LIVENESS_TIMEOUT_S))
            if qcounts[i] == 0 or stale:
                dead.append({"reader": r.reader_id,
                             "queries": qcounts[i],
                             "heartbeat": hb})
        if dead:
            raise RuntimeError(
                f"delta fleet reader_dead: {dead} — zero q/s or stale "
                "heartbeat means a wedged reader, not a slow one")

    ratio = (full_arm["publish_bytes_total"]
             / max(delta_arm["publish_bytes_total"], 1))
    readers = []
    for i, st in enumerate(fleet_stats):
        readers.append({
            "reader": st["reader"],
            "queries_per_sec": round(qcounts[i] / wall, 1),
            "latency_p50_s": st.get("latency_p50_s"),
            "latency_p99_s": st.get("latency_p99_s"),
            "final_step": st.get("step"),
            "fence": st.get("fence"),
            "chain_len": st.get("chain_len"),
        })
    fence_steps = {st.get("step") for st in fleet_stats}
    out = {
        "mesh": dict(mesh.shape), "hot_tier_rows": H,
        "hot_sync_every": E_SYNC, "zipf_alpha": 1.05,
        "table_rows": NI, "rank": RANK,
        "full": full_arm, "delta": delta_arm,
        "publish_bytes_reduction_x": round(ratio, 2),
        "states_bit_identical": bool(
            np.array_equal(full_state, delta_state)),
        "fleet": {
            "n_readers": N_READERS, "quorum": 2,
            "readers": readers,
            "converged_single_step": len(fence_steps) == 1,
            "queries_per_sec_total": round(sum(qcounts) / wall, 1),
            "heartbeat_beacons": len(heartbeats),
            "reader_dead": [],  # non-empty would have raised above
        },
    }
    print(
        f"delta A/B: publish bytes {full_arm['publish_bytes_total']} -> "
        f"{delta_arm['publish_bytes_total']} ({out['publish_bytes_reduction_x']}x"
        f" fewer; {delta_arm['delta_publishes']} deltas + "
        f"{delta_arm['full_publishes']} fulls), hit rate "
        f"{delta_arm['hot_hit_rate']}, write->servable mean "
        f"{full_arm['write_to_servable_s_mean']}s -> "
        f"{delta_arm['write_to_servable_s_mean']}s, fleet "
        f"{out['fleet']['queries_per_sec_total']:.0f} q/s over "
        f"{N_READERS} readers (p99 "
        f"{[r['latency_p99_s'] for r in readers]}), bit-identical "
        f"{out['states_bit_identical']}", file=sys.stderr)
    return {
        "metric": "delta_publish_bytes_reduction",
        "value": out["publish_bytes_reduction_x"],
        "unit": "x_fewer_bytes",
        # The A/B's own ratio mirrors the headline: full-arm publish
        # bytes over delta-arm publish bytes on the same stream.
        "vs_baseline": out["publish_bytes_reduction_x"],
        **out,
    }


def run_storage(args):
    """Hostile-filesystem brownout A/B (docs/resilience.md "Hostile
    filesystem"): the same logreg stream trained twice with per-chunk
    async publishes —

    * **clean**    — healthy storage;
    * **brownout** — ``fps_tpu.testing.faultfs`` injects a deterministic
      schedule against the snapshot plane: an EIO blackout window wide
      enough to exhaust the publish retry budget (the writer DEGRADES:
      skips the publish, raises checkpoint.publish_backlog) plus
      recurring slow-fsync latency, then recovery.

    Reported: training throughput retention (faulted/clean examples/s —
    the degradation must stay on the writer thread, not the training
    loop), the publish-backlog drain curve (rise through the blackout,
    cliff to 0 at the first landed publish), retry/degraded counts, and
    the headline invariant: final weights AND the final recovered
    snapshot's state are BIT-identical to the clean run's.

    ISSUE 20 (the raw-speed pass): both arms run the overlapped
    pipeline (``prefetch=2`` → boundary copies → ``save_deferred``) with
    ``when_full="degrade"`` — the device→host capture, the serialize,
    the fsync delays, AND the retry backoff all live on the writer
    thread, and a save arriving while the writer is wedged is skipped
    (recency spent, dispatch never stalled). The dump/capture second
    totals land in each arm: dump (what the TRAINING thread paid) must
    stay flat under brownout while capture absorbs the damage."""
    import dataclasses
    import tempfile
    import threading

    import jax

    from fps_tpu import obs
    from fps_tpu.core.checkpoint import AsyncCheckpointer
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import multi_epoch_chunks
    from fps_tpu.models.logistic_regression import (
        LogRegConfig,
        logistic_regression,
    )
    from fps_tpu.parallel.mesh import make_ps_mesh
    from fps_tpu.testing import faultfs
    from fps_tpu.testing.faultfs import FaultRule

    from fps_tpu.utils.datasets import synthetic_sparse_classification

    mesh = make_ps_mesh()
    W = num_workers_of(mesh)
    NF, NNZ, EPOCHS = 2048, 16, 2
    data = synthetic_sparse_classification(120_000, NF, NNZ, seed=7,
                                           noise=0.05)
    data = dict(data, label=(data["label"] > 0).astype(np.float32))

    def make_chunks():
        return multi_epoch_chunks(data, EPOCHS, num_workers=W,
                                  local_batch=256, steps_per_chunk=8,
                                  seed=3)

    n_chunks = sum(1 for _ in make_chunks())
    # The blackout window: wide enough that one publish exhausts its
    # whole retry budget (4 attempts) and degrades, while the NEXT
    # publish fails twice and lands on its third attempt — both the
    # degrade and the retried-then-success paths are exercised.
    brownout_rules = [
        FaultRule("snapshot", "write", "errno", errno_name="EIO",
                  start=2, count=6),
        FaultRule("snapshot", "fsync", "delay", delay_s=0.01,
                  start=0, count=None, every=3),
    ]

    def run_arm(faulted: bool):
        cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
        trainer, store = logistic_regression(mesh, cfg)
        trainer.config = dataclasses.replace(trainer.config, prefetch=2)
        rec = obs.Recorder(sinks=[])
        trainer.recorder = rec
        # Checkpoint-layer telemetry (storage.retries, the backlog
        # gauge, checkpoint_degraded events) fires through the process
        # default, not the trainer's recorder.
        obs.events.set_default_recorder(rec)
        tables, ls = trainer.init_state(jax.random.key(0))
        fs = (faultfs.install(brownout_rules, seed=0)
              if faulted else None)
        curve = []  # (t_rel, backlog) drain-curve samples
        stop = threading.Event()
        with tempfile.TemporaryDirectory() as d:
            ck = AsyncCheckpointer(d, keep=n_chunks + 2,
                                   when_full="degrade")
            t0 = time.perf_counter()

            def sample():
                while not stop.is_set():
                    curve.append((round(time.perf_counter() - t0, 3),
                                  ck._publish_backlog))
                    stop.wait(0.02)

            sampler = threading.Thread(target=sample, daemon=True,
                                       name="bench-storage-sampler")
            sampler.start()
            try:
                tables, ls, m = trainer.fit_stream(
                    tables, ls, make_chunks(), jax.random.key(1),
                    checkpointer=ck, checkpoint_every=1)
                wall = time.perf_counter() - t0
                ck.flush()
            finally:
                stop.set()
                sampler.join(timeout=5.0)
                if fs is not None:
                    faultfs.uninstall()
                obs.events.set_default_recorder(None)
            curve.append((round(time.perf_counter() - t0, 3),
                          ck._publish_backlog))
            final_step = ck.latest_valid_step()
            _, snap_tables, _, _ = ck.read_snapshot(final_step)
            ck.close()
        n_ex = float(sum(np.asarray(mm["n"]).sum() for mm in m))
        # Downsample the curve: keep every change point (the drain
        # cliff) plus bounded padding.
        keep, last = [], None
        for t, b in curve:
            if b != last or len(keep) < 2:
                keep.append([t, int(b)])
                last = b
        hists = rec.snapshot()["histograms"]
        dump_h = hists.get("checkpoint.dump_seconds", {})
        cap_h = hists.get("checkpoint.capture_seconds", {})
        arm = {
            "examples_per_sec": round(n_ex / wall, 1),
            "wall_s": round(wall, 4),
            # The raw-speed split: dump = what each save cost the
            # TRAINING thread (an enqueue, with deferred capture);
            # capture = the device→host materialization the WRITER paid.
            "dump_seconds_total": round(dump_h.get("sum", 0.0), 6),
            "dump_count": int(dump_h.get("count", 0)),
            "capture_seconds_total": round(cap_h.get("sum", 0.0), 6),
            "capture_count": int(cap_h.get("count", 0)),
            "publishes_landed": ck.full_publishes + ck.delta_publishes,
            "degraded_publishes": ck.degraded_publishes,
            "retries": int(rec.counter_value("storage.retries",
                                             plane="checkpoint")),
            "backlog_final": ck._publish_backlog,
            "backlog_max": max((b for _, b in curve), default=0),
            "backlog_curve": keep[:40],
            "final_snapshot_step": final_step,
            "injected": (dict((f"{k[0]}/{k[1]}/{k[2]}", v) for k, v in
                              fs.injected_counts().items())
                         if fs is not None else None),
        }
        weights = store.lookup_host("weights", np.arange(NF))
        return arm, weights, snap_tables["weights"]

    # Warm-up (compile) outside the timed arms.
    from itertools import islice

    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    tw, sw = logistic_regression(mesh, cfg)
    t0s, l0s = tw.init_state(jax.random.key(9))
    tw.fit_stream(t0s, l0s, islice(make_chunks(), 2), jax.random.key(9))

    clean_arm, clean_w, clean_snap = run_arm(False)
    faulted_arm, faulted_w, faulted_snap = run_arm(True)
    retention = (faulted_arm["examples_per_sec"]
                 / clean_arm["examples_per_sec"]
                 if clean_arm["examples_per_sec"] else None)
    out = {
        "mesh": dict(mesh.shape), "chunks": n_chunks,
        "clean": clean_arm, "brownout": faulted_arm,
        "throughput_retention": (round(retention, 4)
                                 if retention else None),
        "weights_bit_identical": bool(
            np.array_equal(clean_w, faulted_w)),
        "recovered_snapshot_bit_identical": bool(
            np.array_equal(clean_snap, faulted_snap)),
        "backlog_drained": faulted_arm["backlog_final"] == 0,
    }
    print(
        f"storage brownout A/B: examples/s "
        f"{clean_arm['examples_per_sec']:.0f} -> "
        f"{faulted_arm['examples_per_sec']:.0f} (retention "
        f"{out['throughput_retention']}), degraded "
        f"{faulted_arm['degraded_publishes']} / retries "
        f"{faulted_arm['retries']}, backlog max "
        f"{faulted_arm['backlog_max']} drained "
        f"{out['backlog_drained']}, bit-identical "
        f"{out['weights_bit_identical']} (snapshot "
        f"{out['recovered_snapshot_bit_identical']}), dump_s "
        f"{clean_arm['dump_seconds_total']:.3f} -> "
        f"{faulted_arm['dump_seconds_total']:.3f} / capture_s "
        f"{clean_arm['capture_seconds_total']:.3f} -> "
        f"{faulted_arm['capture_seconds_total']:.3f}", file=sys.stderr)
    return {
        "metric": "storage_brownout_throughput_retention",
        "value": out["throughput_retention"],
        "unit": "x_retention",
        "vs_baseline": out["throughput_retention"],
        **out,
    }


def run_wire(args):
    """Hostile-network wire A/B (docs/resilience.md "Hostile network"):
    one fixed snapshot served over TCP three ways —

    * **legacy**   — raw line-JSON over a plain socket (the pre-wire
      protocol, still accepted by the dual-stack server for one
      release);
    * **framed**   — ``WireClient`` (versioned frames, CRC32, deadlines,
      bounded retry) at the SAME request sequence and load;
    * **brownout** — framed again, but under a deterministic
      ``fps_tpu.testing.faultnet`` schedule (refused reconnects,
      recurring mid-frame cuts, injected send latency) against an
      admission-limited server with hammer threads forcing BUSY sheds.

    Reported: framed-vs-legacy throughput ratio at equal load (framing
    must not cost throughput), shed-rate / retry / reconnect /
    torn-frame counts through the brownout, and RECOVERY BIT-IDENTITY:
    every brownout response byte-identical to the clean framed run's
    (retries and replays never corrupt or duplicate an answer)."""
    import threading

    from fps_tpu.serve import (
        ReadServer,
        ServableSnapshot,
        TcpServe,
        WireClient,
    )
    from fps_tpu.testing import faultnet
    from fps_tpu.testing.faultnet import NetFaultRule

    NROWS, RANK, N_REQ, N_WARM = 4096, 16, 300, 10
    rng = np.random.default_rng(0)
    tables = {"weights": rng.normal(
        size=(NROWS, RANK)).astype(np.float32)}

    def make_server():
        server = ReadServer()
        server.swap_to(ServableSnapshot(7, "bench-wire", tables, [],
                                        "none"))
        return server

    reqs = [{"op": "pull", "table": "weights",
             "ids": rng.integers(0, NROWS, 64).tolist()}
            for _ in range(N_REQ)]

    def drive(client):
        """Warm up, then time the fixed sequence; returns
        (queries_per_sec, [response dicts])."""
        for r in reqs[:N_WARM]:
            client.request(r)
        resps = []
        t0 = time.perf_counter()
        for r in reqs:
            resps.append(client.request(r))
        wall = time.perf_counter() - t0
        if not resps or any(not r.get("ok") for r in resps):
            raise RuntimeError("wire bench arm produced a failed or "
                               "empty response — that is an error, "
                               "not a rate")
        return round(N_REQ / wall, 1), resps

    class _LineClient:
        """The ACTUAL old protocol (JsonlClient is a framed shim now):
        one JSON object per line, raw socket."""

        def __init__(self, host, port):
            import socket

            self._sock = socket.create_connection((host, port),
                                                  timeout=10.0)
            self._sock.setsockopt(socket.IPPROTO_TCP,
                                  socket.TCP_NODELAY, 1)
            self._rfile = self._sock.makefile("rb")

        def request(self, req):
            self._sock.sendall(json.dumps(req).encode("utf-8") + b"\n")
            line = self._rfile.readline()
            if not line:
                raise ConnectionError("server closed the connection")
            return json.loads(line)

        def close(self):
            self._rfile.close()
            self._sock.close()

    # -- clean arms: legacy vs framed against one healthy server.
    # Interleaved rounds, median of the PAIRED per-round ratios:
    # absolute localhost throughput drifts far more run-to-run than
    # the few-percent protocol delta under measurement, but both arms
    # of one round share the same box conditions, so the paired ratio
    # is the stable quantity.
    N_ROUNDS = 5
    rounds = []
    legacy_resps = framed_resps = None
    with TcpServe(make_server()) as tcp:
        for _ in range(N_ROUNDS):
            legacy = _LineClient(tcp.host, tcp.port)
            lq, legacy_resps = drive(legacy)
            legacy.close()
            with WireClient(tcp.host, tcp.port) as wc:
                fq, framed_resps = drive(wc)
            rounds.append((fq / lq, lq, fq))
        clean_stats = tcp.wire_stats()
    rounds.sort()
    _, legacy_qps, framed_qps = rounds[len(rounds) // 2]

    # -- brownout arm: deterministic net faults on the measured client
    # ("client" stream; the hammer threads get their own peer class so
    # the schedule stays replayable) + admission-limited server.
    brownout_rules = [
        # The measured client's first two RECONNECT attempts are
        # refused (connect #0 is the constructor): a reconnect storm
        # that must back off and then resume under the same req_id.
        NetFaultRule("client", "connect", "refuse", start=1, count=2),
        # Recurring mid-frame cuts: torn frames the server must count
        # and never decode; the client reconnects and resends.
        NetFaultRule("client", "send", "cut", cut_bytes=6, start=10,
                     count=None, every=25),
        # Background send latency (congested path).
        NetFaultRule("client", "send", "delay", delay_s=0.001,
                     start=0, count=None, every=7),
    ]
    net = faultnet.install(brownout_rules, seed=0)
    try:
        with TcpServe(make_server()) as tcp:
            wc = WireClient(tcp.host, tcp.port, peer_class="client")
            brown_qps, brown_resps = drive(wc)
            wc.close()
            brown_stats = tcp.wire_stats()
    finally:
        faultnet.uninstall()

    # -- load-shed phase: an admission-limited server (max_inflight=1)
    # whose ONLY execution slot is wedged for a window — every request
    # arriving during the wedge is shed with a retryable BUSY that the
    # hammers' WireClients absorb through their retry budgets; after
    # the slot frees, the same clients recover and get served. Lost
    # WORK, never corruption (docs/STALENESS.md).
    server = make_server()
    with TcpServe(server, max_inflight=1) as tcp:
        stop = threading.Event()
        busy_counts = [0] * 3

        def hammer(idx):
            hc = WireClient(tcp.host, tcp.port, peer_class="hammer")
            while not stop.is_set():
                try:
                    hc.request(reqs[0])
                except Exception:  # noqa: BLE001 — shed work is lost work
                    continue
            busy_counts[idx] = hc.busy_rejections
            hc.close()

        hammers = [threading.Thread(target=hammer, args=(i,),
                                    daemon=True,
                                    name=f"bench-wire-hammer-{i}")
                   for i in range(3)]
        # Wedge the whole cost budget: full house, every request sheds.
        assert tcp.admission.try_admit(tcp.admission.max_cost)
        for t in hammers:
            t.start()
        time.sleep(0.5)
        tcp.admission.release(tcp.admission.max_cost)  # brownout lifts
        time.sleep(0.5)
        stop.set()
        for t in hammers:
            t.join(timeout=10.0)
        shed_stats = tcp.wire_stats()
        served = server.requests

    shed_rate = (shed_stats["shed_requests"]
                 / max(shed_stats["shed_requests"] + served, 1))
    out = {
        "rows": NROWS, "requests": N_REQ,
        "legacy": {"queries_per_sec": legacy_qps},
        "framed": {"queries_per_sec": framed_qps,
                   "wire_stats": clean_stats},
        "brownout": {
            "queries_per_sec": brown_qps,
            "client_retries": wc.retries,
            "client_reconnects": wc.reconnects,
            "wire_stats": brown_stats,
            "injected": dict((f"{k[0]}/{k[1]}/{k[2]}", v) for k, v in
                             net.injected_counts().items()),
        },
        "loadshed": {
            "shed_rate": round(shed_rate, 4),
            "shed_requests": shed_stats["shed_requests"],
            "served_requests": int(served),
            "client_busy_rejections": sum(busy_counts),
        },
        "framed_vs_legacy": round(framed_qps / legacy_qps, 4),
        "responses_bit_identical": bool(
            legacy_resps == framed_resps == brown_resps),
    }
    print(
        f"wire A/B: legacy {legacy_qps:.0f} q/s -> framed "
        f"{framed_qps:.0f} q/s ({out['framed_vs_legacy']}x); brownout "
        f"{brown_qps:.0f} q/s with {wc.retries} retries / "
        f"{wc.reconnects} reconnects / "
        f"{brown_stats['torn_frames']} torn frames; shed rate "
        f"{out['loadshed']['shed_rate']} "
        f"({shed_stats['shed_requests']} shed / {served} served), "
        f"responses bit-identical "
        f"{out['responses_bit_identical']}", file=sys.stderr)
    return {
        "metric": "wire_framed_vs_legacy_qps",
        "value": out["framed_vs_legacy"],
        "unit": "x_legacy_throughput",
        "vs_baseline": out["framed_vs_legacy"],
        **out,
    }


def run_serve_scale(args):
    """Closed-loop user-scale read-plane load (ISSUE 19's tentpole
    witness): a Zipf population of users pulls its feature bundles
    against a live autoscaled ServingFleet over the batched zero-copy
    wire, while a publisher keeps hot-swapping fresh snapshots under
    the load. Four measurements:

    * **unbatched** — the PR-16 shape (one frame per request, JSON
      responses): the p50/p99/p999 reference every batched number is
      judged against.
    * **batch curve** — per-frame latency + aggregate requests/s at
      batch sizes 1..512 over the binary multi path: the amortization
      curve ``docs/performance.md`` reprints.
    * **scaled run** — diurnal shape (ramp → flash crowd → cool) of
      closed-loop users against the whole fleet, the autoscaler
      evaluating live (its decisions reported), snapshots publishing
      throughout; the flash-crowd aggregate q/s is the headline, with
      the fence-lag freshness sampled continuously — a flash crowd
      must cost latency, never staleness.
    * **operating point** — the largest curve batch whose per-frame
      p99 stays within 2x the unbatched p99 (the acceptance bound).

    ``vs_baseline`` is the flash-crowd aggregate against BENCH_r14's
    3-reader fleet total (1477.5 q/s, the unbatched read plane)."""
    import os
    import tempfile
    import threading

    from fps_tpu.core import snapshot_format as fmt
    from fps_tpu.serve import (
        NoSnapshotError,
        ReadAutoscaler,
        ServingFleet,
        TcpServe,
        WireClient,
    )
    from fps_tpu.serve.wire import CAP_BIN, CAP_MULTI

    R14_FLEET_QPS = 1477.5
    NROWS, RANK, IDS_PER_REQ = 65536, 16, 16
    N_USERS = 100_000
    rng = np.random.default_rng(19)

    # Zipf user population: each request is one user's pull of its
    # (fixed) feature bundle, users drawn zipf so the head repeats —
    # the access pattern the warm caches and gathers actually see.
    user_rows = rng.integers(0, NROWS, size=(N_USERS, IDS_PER_REQ))
    zipf_users = (rng.zipf(1.2, size=1 << 14) - 1) % N_USERS
    req_pool = [{"op": "pull", "table": "emb",
                 "ids": user_rows[u].tolist()} for u in zipf_users]

    table = rng.normal(size=(NROWS, RANK)).astype(np.float32)
    ckpt_dir = tempfile.mkdtemp(prefix="fps-serve-scale-")
    published = [0]
    publish_lock = threading.Lock()

    def publish_next():
        with publish_lock:
            published[0] += 1
            step = published[0]
            # A few hot rows move per publish: real swaps, tiny deltas.
            table[rng.integers(0, NROWS, 64)] += 0.001
            arrays = {"table::emb": table,
                      "meta::ls_format": np.array("exported")}
            for k in list(arrays):
                arrays["meta::crc::" + k] = np.uint32(
                    fmt.array_crc32(arrays[k]))
            np.savez(fmt.snapshot_path(ckpt_dir, step), **arrays)
            return step

    publish_next()
    fleet = ServingFleet(ckpt_dir, 2)
    scaler = ReadAutoscaler(fleet, min_readers=2, max_readers=6,
                            latency_slo_s=0.002,
                            fence_lag_slo_steps=8.0, cooldown_s=0.5,
                            liveness_timeout_s=10.0)

    # One TcpServe per live reader, kept in sync with the autoscaler's
    # membership changes; workers round-robin the current set.
    serves: dict = {}
    serve_lock = threading.Lock()

    def sync_serves():
        with serve_lock:
            live = {r.reader_id: r for r in fleet.readers}
            for rid in [r for r in serves if r not in live]:
                serves.pop(rid).close()
            for rid, r in live.items():
                if rid not in serves:
                    serves[rid] = TcpServe(r.server).start()
            return list(serves.items())

    stop = threading.Event()
    active_n = [0]    # workers with idx < active_n[0] run (load shape)
    batch_n = [1]
    recording: list = [None]  # per-phase (latency_s, batch) sink
    N_WORKERS = 8

    def worker(idx):
        clients: dict = {}
        pos = idx * 1013
        while not stop.is_set():
            if idx >= active_n[0]:
                time.sleep(0.005)
                continue
            with serve_lock:
                targets = list(serves.items())
            if not targets:
                time.sleep(0.01)
                continue
            rid, tcp = targets[(pos // 7) % len(targets)]
            wc = clients.get(rid)
            if wc is None or wc.port != tcp.port:
                try:
                    clients[rid] = wc = WireClient(
                        tcp.host, tcp.port, caps=(CAP_MULTI, CAP_BIN))
                except OSError:
                    time.sleep(0.01)
                    continue
            B = batch_n[0]
            batch = [req_pool[(pos + j) % len(req_pool)]
                     for j in range(B)]
            pos += B
            t0 = time.perf_counter()
            try:
                if B == 1:
                    ok = wc.request(batch[0]).get("ok")
                else:
                    ok = all(r.get("ok") for r in wc.multi(batch))
            except Exception:  # noqa: BLE001 — churned reader: move on
                clients.pop(rid, None)
                continue
            dt = time.perf_counter() - t0
            sink = recording[0]
            if ok and sink is not None:
                sink.append((dt, B))
        for wc in clients.values():
            wc.close()

    def measure(n_active, B, seconds):
        """One closed-loop phase; returns (aggregate requests/s,
        per-frame latency percentiles, frames)."""
        sink: list = []
        batch_n[0] = B
        active_n[0] = n_active
        time.sleep(0.15)   # let the shape settle before recording
        recording[0] = sink
        time.sleep(seconds)
        recording[0] = None
        lat = np.array([d for d, _ in sink]) if sink else np.array([])
        reqs_done = sum(b for _, b in sink)
        pct = {p: (round(float(np.percentile(lat, q)), 6)
                   if lat.size else None)
               for p, q in (("p50", 50), ("p99", 99), ("p999", 99.9))}
        return round(reqs_done / seconds, 1), pct, len(sink)

    fence_trail: list = []

    def sample_fence():
        fence = fleet.readers[0].fence
        while not stop.is_set():
            f = fence.read()
            if f is not None:
                fence_trail.append(published[0] - f[1])
            time.sleep(0.02)

    out = {"rows": NROWS, "rank": RANK, "ids_per_request": IDS_PER_REQ,
           "users": N_USERS, "workers": N_WORKERS}
    workers = [threading.Thread(target=worker, args=(i,), daemon=True,
                                name=f"bench-scale-user{i}")
               for i in range(N_WORKERS)]
    fleet.start(interval_s=0.02)
    sync_serves()
    try:
        dl = time.monotonic() + 30.0
        while time.monotonic() < dl:
            try:
                if all(r.server.snapshot.step >= 1
                       for r in fleet.readers):
                    break
            except NoSnapshotError:
                pass
            time.sleep(0.02)
        for t in workers:
            t.start()

        # -- unbatched reference (the PR-16 shape, 4 users).
        measure(4, 1, 0.5)  # warm connections + caches off the record
        unb_qps, unb_pct, _ = measure(4, 1, 2.0)
        out["unbatched"] = {"queries_per_sec": unb_qps, **unb_pct}

        # -- batch-size/latency curve (1 user, binary multi).
        curve = []
        for B in (1, 8, 32, 128, 512):
            qps, pct, frames = measure(1, B, 1.0)
            curve.append({"batch": B, "queries_per_sec": qps,
                          "frames": frames, **pct})
        out["batch_curve"] = curve
        # Operating point: largest batch whose per-frame p99 holds
        # within 2x the unbatched p99.
        bound = 2.0 * (unb_pct["p99"] or float("inf"))
        oper = [c for c in curve
                if c["p99"] is not None and c["p99"] <= bound]
        oper_b = max((c["batch"] for c in oper), default=32)
        out["operating_batch"] = oper_b
        out["p99_bound_s"] = round(bound, 6)

        # -- scaled run: publisher + autoscaler live, diurnal shape.
        pub_stop = threading.Event()

        def publisher():
            while not pub_stop.is_set():
                publish_next()
                pub_stop.wait(0.3)

        def autoscale_loop():
            while not pub_stop.is_set():
                scaler.evaluate(newest_step=published[0])
                sync_serves()
                pub_stop.wait(0.2)

        sampler = threading.Thread(target=sample_fence, daemon=True)
        pub_t = threading.Thread(target=publisher, daemon=True)
        auto_t = threading.Thread(target=autoscale_loop, daemon=True)
        sampler.start()
        pub_t.start()
        auto_t.start()
        phases = {}
        flash_lag_start = None
        for name, n_active, seconds in (("ramp", 2, 1.5),
                                        ("flash", N_WORKERS, 2.5),
                                        ("cool", 2, 1.5)):
            if name == "flash":
                flash_lag_start = len(fence_trail)
            qps, pct, frames = measure(n_active, oper_b, seconds)
            phases[name] = {"queries_per_sec": qps, "frames": frames,
                            "active_users": n_active, **pct}
        flash_lags = fence_trail[flash_lag_start:len(fence_trail)]
        pub_stop.set()
        pub_t.join(timeout=10)
        auto_t.join(timeout=10)
        out["phases"] = phases
        out["published_steps"] = published[0]
        out["fence_lag_steps_max"] = (max(fence_trail)
                                      if fence_trail else None)
        out["flash_fence_lag_max"] = (max(flash_lags)
                                      if flash_lags else None)
        out["autoscale"] = {
            "final_fleet_size": len(fleet.readers),
            "actions": sorted({d["action"] for d in scaler.decisions
                               if d["action"] != "hold"}),
            "evaluations": len(scaler.decisions),
        }
    finally:
        stop.set()
        for t in workers:
            t.join(timeout=10)
        with serve_lock:
            for tcp in serves.values():
                tcp.close()
            serves.clear()
        fleet.stop()

    flash_qps = phases["flash"]["queries_per_sec"]
    oper_curve = next(c for c in curve if c["batch"] == oper_b)
    out["aggregate_queries_per_sec"] = flash_qps
    out["speedup_vs_r14_fleet"] = round(flash_qps / R14_FLEET_QPS, 2)
    out["p99_within_2x_unbatched"] = bool(
        oper_curve["p99"] is not None and oper_curve["p99"] <= bound)
    out["fence_slo_held_in_flash"] = bool(
        out["flash_fence_lag_max"] is not None
        and out["flash_fence_lag_max"] <= scaler.fence_lag_slo_steps)
    print(
        f"serve_scale: unbatched {unb_qps:.0f} q/s "
        f"(p99 {unb_pct['p99']}s) -> batch {oper_b} flash crowd "
        f"{flash_qps:.0f} q/s ({out['speedup_vs_r14_fleet']}x r14 "
        f"fleet), frame p99 {oper_curve['p99']}s "
        f"(bound {out['p99_bound_s']}s), flash fence lag max "
        f"{out['flash_fence_lag_max']} steps, fleet "
        f"{out['autoscale']['final_fleet_size']} readers "
        f"({out['autoscale']['actions']})", file=sys.stderr)
    return {
        "metric": "serve_scale_aggregate_qps",
        "value": flash_qps,
        "unit": "queries/s",
        "vs_baseline": out["speedup_vs_r14_fleet"],
        **out,
    }


def run_restart(args):
    """The cost of the restart ITSELF (ISSUE 20): wedge a real training
    child under ``tools/supervise.py`` twice — once with
    ``--compilation-cache-dir`` (a persistent XLA cache every attempt
    shares) and once without — and report the supervisor's
    ``restart_to_first_signal_s`` for both: seconds from the supervisor
    killing the wedged attempt to its replacement observably making
    progress. One supervised run per arm is the honest A/B: the FIRST
    attempt populates the cache, so the restarted attempt is the warm
    reader. On CPU the recompile is cheap and the arms sit close; on a
    real TPU recompilation dominates the restart, which is what the
    cache-dir flag exists to kill."""
    import os
    import subprocess
    import tempfile

    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=root,
               # Cache even sub-second CPU compiles so the with-cache
               # arm exercises the real read path (no-op without a
               # cache dir, so the cold arm is untouched).
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    demo = [sys.executable, "-m", "fps_tpu.testing.supervised_demo",
            "--examples", "8000", "--epochs", "2"]

    def one_arm(workdir, cache_dir):
        sup_dir = os.path.join(workdir, "sup")
        cmd = [sys.executable,
               os.path.join(root, "tools", "supervise.py"),
               "--state-dir", sup_dir, "--stall-timeout-s", "10",
               "--startup-grace-s", "300", "--term-grace-s", "2",
               "--backoff-base-s", "0.2", "--max-restarts", "2",
               "--poll-s", "0.2"]
        if cache_dir is not None:
            cmd += ["--compilation-cache-dir", cache_dir]
        cmd += ["--", *demo, "--ckpt-dir", sup_dir,
                "--out", os.path.join(workdir, "out.npz"),
                "--wedge-at", "3", "--wedge-mode", "sigstop"]
        r = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                           text=True, timeout=600)
        try:
            digest = json.loads(r.stdout.strip().splitlines()[-1])
        except (json.JSONDecodeError, IndexError):
            return {"success": False,
                    "error": (r.stdout + r.stderr)[-500:]}
        rts = [round(float(t), 3) for t in
               digest.get("restart_to_first_signal_s") or []]
        return {"success": bool(digest.get("success")),
                "restarts": digest.get("restarts"),
                "restart_to_first_signal_s": rts,
                "worst_s": max(rts) if rts else None}

    with tempfile.TemporaryDirectory() as d:
        cache = os.path.join(d, "xla-cache")
        cold = one_arm(os.path.join(d, "cold"), None)
        warm = one_arm(os.path.join(d, "warm"), cache)
        cache_entries = sum(len(fs) for _, _, fs in os.walk(cache))

    cold_s, warm_s = cold.get("worst_s"), warm.get("worst_s")
    speedup = (round(cold_s / warm_s, 3)
               if cold_s and warm_s else None)
    print(f"restart: restart_to_first_signal_s "
          f"{cold_s} (no cache) -> {warm_s} "
          f"(--compilation-cache-dir, {cache_entries} cache entries), "
          f"ratio {speedup}", file=sys.stderr)
    return {
        "metric": "restart_to_first_signal_s",
        "value": warm_s,
        "unit": "s",
        "vs_baseline": speedup,
        "without_cache": cold,
        "with_cache": warm,
        "compilation_cache_entries": cache_entries,
    }


RUNNERS = {"mf": run_mf, "w2v": run_w2v, "logreg": run_logreg,
           "pa": run_pa, "ials": run_ials, "tiered": run_tiered,
           "tiered_drift": run_tiered_drift, "serve": run_serve,
           "megastep": run_megastep_ab, "delta": run_delta,
           "storage": run_storage, "wire": run_wire,
           "serve_scale": run_serve_scale, "restart": run_restart}


def compact_summary(results):
    """Digest for the driver-parsed FINAL stdout line.

    Per workload only {value, vs_baseline}, floats rounded to 4
    significant-ish decimals — no nested baseline dicts, no prose, no
    per-workload unit or metric string (the workload KEY names the row;
    the headline's metric/unit ride at top level. The serve workload
    already cost the units, and tiered_drift's eighth entry cost the
    metric copies — each shrink is what keeps the line inside the
    driver's bounded tail window) — so the whole line stays <=1000
    bytes (asserted in the contract test against worst-case verbose
    stubs). The headline (mf when present, else the last completed
    workload) is mirrored at top level for the driver's single-metric
    parse. Emitted CUMULATIVELY after every workload in all-mode: if
    the run is killed partway (a cold full bench is mostly compilation),
    the final stdout line is still a parseable digest of everything that
    finished.
    """
    def rnd(v):
        return round(v, 4) if isinstance(v, float) else v

    digest = {
        name: {k: rnd(res.get(k)) for k in ("value", "vs_baseline")}
        for name, res in results.items()
    }
    head_name = "mf" if "mf" in digest else (
        list(digest)[-1] if digest else None)
    head = results.get(head_name, {}) if head_name else {}
    return {"metric": head.get("metric"), "value": rnd(head.get("value")),
            "unit": head.get("unit"),
            "vs_baseline": rnd(head.get("vs_baseline")),
            "workloads": digest}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="all",
                    choices=["all", "mf", "w2v", "logreg", "pa", "ials",
                             "tiered", "tiered_drift", "serve",
                             "megastep", "delta", "storage", "wire",
                             "serve_scale", "restart"])
    ap.add_argument("--scale", default="20m", choices=["100k", "1m", "20m"])
    ap.add_argument("--rank", type=int, default=10)
    ap.add_argument("--local-batch", type=int, default=32768)
    ap.add_argument("--movielens-path", default=None)
    ap.add_argument("--text8-path", default=None)
    ap.add_argument("--input", default=None,
                    help="real dataset file for --workload logreg "
                         "(Criteo TSV or svmlight; default: synthetic)")
    ap.add_argument("--num-tokens", type=int, default=17_000_000)
    ap.add_argument("--dim", type=int, default=100)
    ap.add_argument("--block-len", type=int, default=8192)
    ap.add_argument("--rmse-target", type=float, default=0.12,
                    help="mf workload: train to this train-RMSE "
                         "(planted-structure noise floor is ~0.1)")
    ap.add_argument("--recall-target", type=float, default=0.35,
                    help="ials workload: train to this recall@20 on the "
                         "held-out planted-implicit split (plateau ~0.39, "
                         "chance 20/16384 = 0.0012)")
    ap.add_argument("--max-epochs", type=int, default=8)
    args = ap.parse_args()
    from fps_tpu.utils.hostenv import enable_compilation_cache

    enable_compilation_cache()

    if args.workload == "all":
        # Headline (mf) LAST among the per-workload lines.
        order = ["w2v", "logreg", "pa", "ials", "tiered", "tiered_drift",
                 "serve", "megastep", "delta", "storage", "wire",
                 "serve_scale", "restart", "mf"]
    else:
        order = [args.workload]
    results = {}
    for name in order:
        print(f"--- workload: {name} ---", file=sys.stderr)
        results[name] = RUNNERS[name](args)
        print(json.dumps(results[name]), flush=True)
        if args.workload == "all" and name != order[-1]:
            # Cumulative digest after every non-final workload (see
            # compact_summary): a killed run's final line still certifies
            # what completed. The last workload's digest IS the final
            # line printed after the rich combined line below.
            print(json.dumps(compact_summary(results)), flush=True)

    if args.workload == "all":
        # Self-certifying artifact: the driver parses the FINAL line and
        # keeps only a bounded TAIL, so the last line must carry every
        # workload's result by itself AND fit the tail window. Round 3's
        # tail truncated mid-stream; round 4's single rich combined line
        # (nested baseline dicts, prose "kind" strings) was itself longer
        # than the window and BENCH_r04.json.parsed came back null. So:
        # the rich combined line goes out first, and the FINAL line is a
        # compact digest — per workload only {metric, value, unit,
        # vs_baseline}, floats rounded — size-asserted at <=1000 bytes by
        # tests/test_examples.py::test_bench_combined_summary_line_contract.
        # Top-level keys stay the mf headline for the driver's
        # metric/value/vs_baseline parse.
        mf = results["mf"]
        combined = {
            "metric": mf["metric"],
            "value": mf["value"],
            "unit": mf["unit"],
            "vs_baseline": mf["vs_baseline"],
            "workloads": results,
        }
        print(json.dumps(combined), flush=True)
        print(json.dumps(compact_summary(results)), flush=True)


if __name__ == "__main__":
    sys.exit(main())
