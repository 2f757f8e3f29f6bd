"""Certify the example workloads' compiled step programs and write the
machine-readable certificate JSON.

The batch CLI over ``fps_tpu.analysis`` (``docs/analysis.md``): builds
each of the six example workloads (mf, streaming_mf, logreg, w2v, pa,
ials) plus the tiered/untiered MF pair on the 8-device CPU mesh at a
small fixed audit scale, lowers the exact program the driver would
dispatch (``Trainer._get_compiled(mode).lower(...)``; the iALS
accumulate kernel for the solver workload), and runs the full pass
suite against a PINNED :class:`~fps_tpu.analysis.ProgramContract` per
``(workload, route, tiering)`` row — collective count/byte budgets,
host-transfer freedom, table donation, dtype drift, and the hot-tier
reconcile psum for the tiered row.

The budgets in :data:`BUDGETS` are the certified collective structure
of each program (the table in ``docs/analysis.md`` is generated from a
run of this tool). They are exact counts, not ceilings-with-slack: a
future PR that adds or removes a data-plane collective fails this audit
until it re-pins the budget — which is the point (the diff becomes the
review artifact).

Usage:
  python tools/audit_programs.py [--out CERTS.json] [--only mf,logreg]
                                 [--measure]
  python tools/audit_programs.py --hlo DUMP.txt [--hlo ...]
                                 [--min-bytes N]

``--measure`` prints each program's measured profile instead of
enforcing budgets — the workflow for re-pinning after a deliberate
program change. Exit status is 0 iff every selected program certifies
clean.

``--hlo`` profiles saved ``lower(...).as_text()`` dumps instead of
building workloads: no jax, no mesh, no re-exec (the analysis package
is loaded through a stub root so ``fps_tpu/__init__`` never imports
jax) — the login-node workflow for programs lowered elsewhere.

Like bench/conftest, re-execs itself into a cleaned 8-CPU-device
environment when the current process cannot see 8 devices.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

# Audit scale: tiny but structurally faithful — every route (gathered
# pull, push scatter, SSP snapshot, hot tier, iALS normal equations)
# lowers the same op structure it has at bench scale; only the payload
# bytes shrink. Fixed so the pinned budgets are deterministic.
NU, NI, RANK = 96, 64, 8
NF, NNZ = 400, 8
VOCAB, W2V_DIM = 50, 8
LOCAL_BATCH, STEPS = 32, 4


def _reexec_if_needed() -> None:
    """Re-exec into an 8-virtual-CPU-device process: the pinned budgets
    are specified over the 8-device mesh, and the device count is fixed
    once jax initialises, so it is set in the environment first
    (fps_tpu.utils.hostenv)."""
    spec = importlib.util.spec_from_file_location(
        "_fps_hostenv", os.path.join(_ROOT, "fps_tpu", "utils",
                                     "hostenv.py"))
    hostenv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hostenv)
    if hostenv.in_reexec():
        return
    env = hostenv.cpu_mesh_env(8)
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def _load_analysis_offline():
    """Import ``fps_tpu.analysis`` without executing ``fps_tpu/__init__``
    (which imports jax): register a stub root package whose ``__path__``
    points at the real package directory, then import the subpackage
    normally — the analysis modules themselves are stdlib-only."""
    import importlib
    import types

    if "fps_tpu" not in sys.modules:
        stub = types.ModuleType("fps_tpu")
        stub.__path__ = [os.path.join(_ROOT, "fps_tpu")]
        sys.modules["fps_tpu"] = stub
    return importlib.import_module("fps_tpu.analysis")


def _offline_main(argv) -> int:
    """``--hlo`` mode: profile saved ``.as_text()`` dumps — no jax, no
    device mesh, no re-exec, so it runs on a login node against programs
    lowered elsewhere."""
    ap = argparse.ArgumentParser(
        description="profile saved StableHLO dumps (fps_tpu.analysis, "
                    "jax-free)")
    ap.add_argument("--hlo", action="append", required=True, metavar="PATH",
                    help="saved lower(...).as_text() dump (repeatable)")
    ap.add_argument("--min-bytes", type=int, default=1024,
                    help="collective payload threshold (default 1024)")
    args = ap.parse_args(argv)
    analysis = _load_analysis_offline()
    out = {}
    for path in args.hlo:
        with open(path, encoding="utf-8") as f:
            prof = analysis.collective_profile(f.read(), args.min_bytes)
        out[path] = {
            "collectives": len(prof),
            "bytes": sum(c.payload_bytes for c in prof),
            "profile": [{"kind": c.kind, "bytes": c.payload_bytes,
                         "replica_groups": c.replica_groups}
                        for c in prof],
        }
    print(json.dumps(out))
    return 0


if __name__ == "__main__" and any(
        a == "--hlo" or a.startswith("--hlo=") for a in sys.argv[1:]):
    sys.exit(_offline_main(sys.argv[1:]))

if __name__ == "__main__":
    # Only the CLI re-execs (os.execve REPLACES the process — an
    # importer reusing BUDGETS/builders must not be swallowed);
    # importers are responsible for their own device mesh.
    _reexec_if_needed()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from fps_tpu.analysis import (  # noqa: E402
    ProgramContract,
    certify,
    collective_profile,
)
from fps_tpu.core.driver import num_workers_of  # noqa: E402
from fps_tpu.core.ingest import multi_epoch_chunks  # noqa: E402
from fps_tpu.parallel.mesh import make_ps_mesh  # noqa: E402

# ---------------------------------------------------------------------------
# Pinned per-program budgets: (max_collectives, max_collective_bytes,
# per_kind_max). Measured at the audit scale above on the 8-device mesh
# (``--measure`` re-derives them); docs/analysis.md carries the same
# table with the rationale per row.
# ---------------------------------------------------------------------------

BUDGETS: dict[str, dict] = {
    # Untiered sync MF: gathered pull (all_gather) + routed push
    # (all_to_all) — the 2-collective data plane of BENCH r05.
    "mf": dict(max_collectives=2, max_collective_bytes=4096,
               per_kind_max={"all_gather": 1, "all_to_all": 1}),
    # SSP MF (streaming example's mode): the data plane is the same two
    # collectives — the sync-round snapshot all_gather lowers OUTSIDE
    # the per-step window at this audit scale (sub-threshold per step).
    "streaming_mf": dict(max_collectives=2, max_collective_bytes=4096,
                         per_kind_max={"all_gather": 1, "all_to_all": 1}),
    # Tiered MF (hot head replicated, E=2), SHARDED reconcile (PR 10,
    # arXiv:2004.13336): cold routes keep their two collectives; the
    # window reconcile is now a reduce-scatter (H*rank*4 = 1024B, each
    # replica receives its disjoint 1/S slice) + the re-broadcast
    # all_gather (1024B) in place of the old full-head psum —
    # ReplicaConsistency certifies the reduce_scatter.
    "mf_tiered": dict(max_collectives=4, max_collective_bytes=6144,
                      per_kind_max={"all_gather": 2, "all_to_all": 1,
                                    "reduce_scatter": 1}),
    # Partial head (H=32 of 64) over the GATHERED cold routes with the
    # STATIC full-batch payload — the ROADMAP scaling cliff this PR's
    # compacted row is measured against: pull = ids all_gather (1024B) +
    # vals reduce_scatter (8192B), push = ids+deltas all_gathers
    # (1024B + 8192B), plus the sharded reconcile RS+AG (1024B each).
    "mf_tiered_gathered": dict(max_collectives=6,
                               max_collective_bytes=20480,
                               per_kind_max={"all_gather": 4,
                                             "reduce_scatter": 2}),
    # The same partial head with cold_budget=8 (payload-proportional
    # routing): cold ids compact into the certified 8-wide lane, so the
    # gathered collectives shrink to O(lane) — vals RS 2048B + deltas AG
    # 2048B (the 256B id lanes fall below the 1024B payload threshold).
    # Cold-route bytes 18432 -> 4096: the statically-pinned 4.5x form of
    # the bench A/B's >= 3x acceptance claim.
    "mf_tiered_compact": dict(max_collectives=4,
                              max_collective_bytes=6144,
                              per_kind_max={"all_gather": 2,
                                            "reduce_scatter": 2}),
    # ADAPTIVE tier over the mf_tiered config (fps_tpu.tiering: mapped
    # hot set + online tracking): the cold routes and the sharded
    # reconcile RS+AG of mf_tiered (the mapped reconcile scatters by gid
    # DATA — same collectives), plus ONE all_reduce: the tracker's
    # end-of-call sketch merge (4x2048 f32 = 32768B). The slot-map/gid
    # lookups are local gathers — re-ranks swap those arrays without
    # touching this profile (rerank_byte_identity pins that claim
    # exactly).
    "mf_retier": dict(max_collectives=5, max_collective_bytes=38912,
                      per_kind_max={"all_gather": 2, "all_to_all": 1,
                                    "all_reduce": 1,
                                    "reduce_scatter": 1}),
    # Device-resident megastep over the compacted tiered config (H=32
    # of 64, cold_budget=8, K chunk segments fused into one program —
    # fps_tpu.core.megastep). The census covers BOTH cold-route
    # branches of the per-window overflow vote's lax.cond (compacted
    # and bit-identical static — the compact branch's 8-wide lanes sit
    # below the 1024B payload threshold, so the counted collectives are
    # the static branch's cold routes plus each branch's sharded
    # reconcile RS+AG). Pinned IDENTICAL for any K — the
    # megastep_k_independence check asserts the census does not move
    # between K=2 and K=4 (collective cost is O(traffic), never O(K)).
    "mf_megastep": dict(max_collectives=10, max_collective_bytes=26624,
                        per_kind_max={"all_gather": 6,
                                      "reduce_scatter": 4}),
    # Sparse logreg, gathered route + adagrad server fold.
    "logreg": dict(max_collectives=2, max_collective_bytes=3200,
                   per_kind_max={"all_gather": 1, "all_to_all": 1}),
    # Word2vec, both tables under the per-id mean and dense at this
    # scale: a dense pull (the table's all_gather) and, since PR 36, a
    # dense push (the (rows, dim + 1) accumulator's all_to_all,
    # ``push.dense_acc``) a table. Until then the mean pushes kept the
    # gathered exchange: an all_gather of ids and one of deltas a table
    # more (6 all_gathers, 40,448 B).
    "w2v": dict(max_collectives=4, max_collective_bytes=7616,
                per_kind_max={"all_gather": 2, "all_to_all": 2}),
    # Passive-aggressive shares logreg's route structure.
    "pa": dict(max_collectives=2, max_collective_bytes=3200,
               per_kind_max={"all_gather": 1, "all_to_all": 1}),
    # iALS accumulate (``als.grouped`` since PR 41): the scan's pulls of
    # the fixed side's rows and of the solved side's (the sweep's own
    # loss), each an all_gather of ids and a reduce_scatter of rows; the
    # block loop's pull of the fixed side's rows in sorted order (one
    # more of each) and its two gathered pushes' all_gathers of the
    # finished sums' ids and rows. The bytes are a block's at the
    # audit's scale (``ials.RUN_PUSH`` = 128 rows a push whatever the
    # chunk), not a step's: 8 / 94,208 B while a row a rating was pushed.
    "ials": dict(max_collectives=10, max_collective_bytes=690176,
                 per_kind_max={"all_gather": 7, "reduce_scatter": 3}),
}


def _mf_pieces(mesh, *, sync_every=None, hot_tier=0, hot_sync_every=1,
               cold_budget=0, gathered=False, skew=False):
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.utils.datasets import synthetic_ratings

    cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK)
    trainer, store = online_mf(mesh, cfg, sync_every=sync_every)
    if hot_tier:
        for name, spec in store.specs.items():
            over = {}
            if gathered:
                # Force the gathered cold route: the compacted-lane rows
                # audit the payload-proportional claim, which is about
                # embedding-scale tables whose cold route cannot afford
                # table-sized dense collectives (the audit-scale table
                # would otherwise auto-resolve dense).
                over["dense_collectives"] = False
            store.specs[name] = dataclasses.replace(
                spec, hot_tier=min(hot_tier, spec.num_ids),
                cold_budget=cold_budget, **over)
        trainer.config = dataclasses.replace(
            trainer.config, hot_sync_every=hot_sync_every)
    data = synthetic_ratings(NU, NI, 2000, rank=3, seed=3)
    if skew:
        # Hot-heavy item stream (~95% head hits) so the compacted row's
        # host certifier accepts the audit chunk — the program SHAPES
        # (the pinned payloads) are data-independent; the data only
        # decides whether the compacted or the static program lowers.
        rng = np.random.default_rng(7)
        item = np.where(
            rng.random(len(data["item"])) < 0.95,
            rng.integers(0, min(hot_tier, NI) or NI,
                         len(data["item"])),
            rng.integers(min(hot_tier, NI), NI, len(data["item"])),
        ).astype(np.int32)
        data = dict(data, item=item)
    chunks = multi_epoch_chunks(
        data, 1, num_workers=num_workers_of(mesh), local_batch=LOCAL_BATCH,
        steps_per_chunk=STEPS, route_key="user", sync_every=sync_every,
        seed=11)
    return trainer, chunks


def _lower_chunk_program(trainer, chunks, mode="sync") -> str:
    """The exact per-chunk program ``fit_stream`` dispatches."""
    return trainer.lowered_chunk_text(next(iter(chunks)), mode)


def build_mf(mesh) -> str:
    return _lower_chunk_program(*_mf_pieces(mesh))


def build_streaming_mf(mesh) -> str:
    # The streaming example's distinct program is the SSP mode (chunked
    # sync_every windows over an unbounded source).
    trainer, chunks = _mf_pieces(mesh, sync_every=2)
    return _lower_chunk_program(trainer, chunks, mode="ssp")


def build_mf_tiered(mesh) -> str:
    trainer, chunks = _mf_pieces(mesh, hot_tier=32, hot_sync_every=2)
    return _lower_chunk_program(trainer, chunks)


def build_mf_tiered_gathered(mesh) -> str:
    """Partial head over the GATHERED (non-dense) cold routes, STATIC
    full-batch payload — the baseline the compacted row's >= 3x
    cold-byte claim is measured against."""
    trainer, chunks = _mf_pieces(mesh, hot_tier=32, hot_sync_every=2,
                                 gathered=True, skew=True)
    return _lower_chunk_program(trainer, chunks)


def build_mf_tiered_compact(mesh) -> str:
    """The same partial head with ``cold_budget=8``: cold ids compact
    into the certified lane, so the gathered collectives carry O(lane)
    payload — the payload-proportional routing row."""
    trainer, chunks = _mf_pieces(mesh, hot_tier=32, hot_sync_every=2,
                                 gathered=True, cold_budget=8, skew=True)
    return _lower_chunk_program(trainer, chunks)


def _mf_retier_pieces(mesh):
    """Adaptive (mapped + tracked) tier over the tiered-MF audit config:
    partial head H=32 of NI=64 under a Retierer, so the program carries
    the slot-map routes, the mapped reconcile, and the tracker's sketch
    ops."""
    from fps_tpu.tiering import Retierer

    trainer, chunks = _mf_pieces(mesh, hot_tier=32, hot_sync_every=2)
    trainer.retierer = Retierer(check_every=4)
    return trainer, chunks


def build_mf_retier(mesh) -> str:
    return _lower_chunk_program(*_mf_retier_pieces(mesh))


def rerank_byte_identity(mesh) -> bool:
    """THE recompile-freedom claim as a pinned contract: two different
    re-ranks of the same (H, table) must lower BYTE-IDENTICAL programs —
    the hot id membership rides as replicated slot-map/gid DATA, never
    as trace constants. A future change that bakes the ranking into the
    program (a fresh compile per re-rank) fails this audit."""
    trainer, chunks = _mf_retier_pieces(mesh)
    chunk = next(iter(chunks))
    t1 = trainer.lowered_chunk_text(chunk, "sync")
    # Re-rank to a disjoint hot id set of the same size (num_ids=64,
    # H=32: the complementary half) and lower again.
    trainer.retierer.hot_ids["item_factors"] = np.arange(
        32, 64, dtype=np.int64)
    t2 = trainer.lowered_chunk_text(chunk, "sync")
    return t1 == t2


def _mf_megastep_pieces(mesh, K: int):
    """Tiered partial-head MF (H=32 of 64, cold_budget=8, gathered cold
    routes) over the device-ingest path, fused into a K-chunk megastep —
    the program contains BOTH cold-route branches (the device-side
    overflow VOTE ``lax.cond``-selects per window), so the pinned census
    covers the compacted AND the static branch bodies plus the vote's
    verdict psum and the window reconcile."""
    from fps_tpu.core.device_ingest import DeviceDataset, DeviceEpochPlan
    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.utils.datasets import synthetic_ratings

    cfg = MFConfig(num_users=NU, num_items=NI, rank=RANK)
    trainer, store = online_mf(mesh, cfg, max_steps_per_call=STEPS)
    for name, spec in store.specs.items():
        store.specs[name] = dataclasses.replace(
            spec, hot_tier=32, cold_budget=8, dense_collectives=False)
    trainer.config = dataclasses.replace(trainer.config, hot_sync_every=2)
    data = synthetic_ratings(NU, NI, 2000, rank=3, seed=3)
    plan = DeviceEpochPlan(
        DeviceDataset(mesh, data), num_workers=num_workers_of(mesh),
        local_batch=LOCAL_BATCH, route_key="user", seed=11)
    return trainer, plan


def build_mf_megastep(mesh) -> str:
    trainer, plan = _mf_megastep_pieces(mesh, 2)
    return trainer.lowered_megastep_text(plan, chunks_per_dispatch=2)


def megastep_k_independence(mesh) -> bool:
    """THE megastep scaling claim as a pinned contract: collective count
    AND payload bytes must be IDENTICAL when K doubles — the per-step
    collectives live inside the scan body (one static occurrence
    whatever K is) and the boundary ticks move O(window) payload per
    window, so megastep collective cost scales with traffic, never with
    how many chunks are fused into the dispatch. A change that unrolls
    the segment loop (or adds a per-segment collective outside the scan
    body) fails this audit."""
    t2, p2 = _mf_megastep_pieces(mesh, 2)
    t4, p4 = _mf_megastep_pieces(mesh, 4)
    prof2 = collective_profile(
        t2.lowered_megastep_text(p2, chunks_per_dispatch=2))
    prof4 = collective_profile(
        t4.lowered_megastep_text(p4, chunks_per_dispatch=4))

    def census(prof):
        kinds: dict[str, list] = {}
        for c in prof:
            kinds.setdefault(c.kind, []).append(c.payload_bytes)
        return {k: sorted(v) for k, v in sorted(kinds.items())}

    return census(prof2) == census(prof4)


def build_logreg(mesh) -> str:
    from fps_tpu.models.logistic_regression import (
        LogRegConfig,
        logistic_regression,
    )
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    cfg = LogRegConfig(num_features=NF, learning_rate=0.5)
    trainer, _ = logistic_regression(mesh, cfg)
    data = synthetic_sparse_classification(2000, NF, NNZ, seed=7)
    data = dict(data, label=(data["label"] > 0).astype(np.float32))
    chunks = multi_epoch_chunks(
        data, 1, num_workers=num_workers_of(mesh), local_batch=LOCAL_BATCH,
        steps_per_chunk=STEPS, seed=3)
    return _lower_chunk_program(trainer, chunks)


def build_w2v(mesh) -> str:
    from fps_tpu.models.word2vec import (
        W2VConfig,
        skipgram_chunks,
        word2vec,
    )

    rng = np.random.default_rng(5)
    tokens = rng.integers(0, VOCAB, 20_000, dtype=np.int32)
    uni = np.bincount(tokens, minlength=VOCAB).astype(np.float64)
    cfg = W2VConfig(vocab_size=VOCAB, dim=W2V_DIM, window=2, negatives=2,
                    subsample_t=None)
    trainer, _ = word2vec(mesh, cfg, uni)
    chunks = skipgram_chunks(
        tokens, uni, cfg, num_workers=num_workers_of(mesh),
        local_batch=LOCAL_BATCH, steps_per_chunk=STEPS, seed=9)
    return _lower_chunk_program(trainer, chunks)


def build_pa(mesh) -> str:
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.passive_aggressive import (
        PAConfig,
        passive_aggressive,
    )
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    cfg = PAConfig(num_features=NF, variant="PA-I", C=1.0)
    trainer, _ = passive_aggressive(mesh, cfg)
    data = synthetic_sparse_classification(2000, NF, NNZ, seed=7)
    chunks = epoch_chunks(
        data, num_workers=num_workers_of(mesh), local_batch=LOCAL_BATCH,
        steps_per_chunk=STEPS, seed=3)
    return _lower_chunk_program(trainer, chunks)


def build_ials(mesh) -> str:
    """The iALS accumulate kernel — the solver's streaming hot path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.core.store import rows_per_shard
    from fps_tpu.models.ials import (
        IALSConfig,
        IALSSolver,
        interaction_chunks,
    )
    from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS
    from fps_tpu.utils.datasets import synthetic_implicit

    cfg = IALSConfig(num_users=NU, num_items=NI, rank=RANK)
    solver = IALSSolver(mesh, cfg)
    solver.init(jax.random.key(0))
    data = synthetic_implicit(NU, NI, 2000, seed=3)
    chunk = next(iter(interaction_chunks(
        data, num_workers=num_workers_of(mesh), local_batch=LOCAL_BATCH,
        steps_per_chunk=STEPS, seed=11)))
    sharding = NamedSharding(mesh, P(None, (DATA_AXIS, SHARD_AXIS)))
    dev = {
        "solve_ids": jax.device_put(np.asarray(chunk["user"]), sharding),
        "fixed_ids": jax.device_put(np.asarray(chunk["item"]), sharding),
        "rating": jax.device_put(np.asarray(chunk["rating"]), sharding),
        "weight": jax.device_put(np.asarray(chunk["weight"]), sharding),
    }
    rps = rows_per_shard(cfg.num_users, solver.num_shards)
    A = solver._zeros_acc(rps * solver.num_shards, RANK * RANK)
    b = solver._zeros_acc(rps * solver.num_shards, RANK)
    acc = solver._accumulate_fn("user")
    from fps_tpu.models.ials import ITEM_TABLE, USER_TABLE

    tables = solver.store.tables
    return acc.lower(tables[ITEM_TABLE], tables[USER_TABLE], A, b,
                     dev).as_text()


BUILDERS = {
    "mf": build_mf,
    "streaming_mf": build_streaming_mf,
    "mf_tiered": build_mf_tiered,
    "mf_tiered_gathered": build_mf_tiered_gathered,
    "mf_tiered_compact": build_mf_tiered_compact,
    "mf_retier": build_mf_retier,
    "mf_megastep": build_mf_megastep,
    "logreg": build_logreg,
    "w2v": build_w2v,
    "pa": build_pa,
    "ials": build_ials,
}

_TIERED_ROWS = ("mf_tiered", "mf_tiered_gathered", "mf_tiered_compact",
                "mf_retier", "mf_megastep")


def diff_budgets(old_doc: dict, measured: dict) -> list[str]:
    """UNPINNED budget regressions of ``measured`` (``{program:
    {"collective_count": n, "collective_bytes": b}}``) against a prior
    audit JSON (``--out`` format). A program regresses when its measured
    collective count or payload bytes GREW versus the old certificate
    AND the growth is not covered by the current pinned ``BUDGETS`` row
    — i.e. someone changed the data plane without re-pinning, which is
    exactly the silent drift this gate exists to catch. Deliberate,
    re-pinned growth is reported by the caller but passes. Programs
    absent from either side are skipped (new rows cannot regress)."""
    problems = []
    old = old_doc.get("audit_programs", {})
    for name in sorted(measured):
        o = old.get(name)
        if not o:
            continue
        # Certificate JSON (--out format) nests the census under
        # "collectives": {"count": n, "bytes": b}.
        oc = o.get("collectives", o)
        old_n = oc.get("count", oc.get("collective_count", 0))
        old_b = oc.get("bytes", oc.get("collective_bytes", 0))
        cur_n = measured[name]["collective_count"]
        cur_b = measured[name]["collective_bytes"]
        if cur_n <= old_n and cur_b <= old_b:
            continue
        pinned = BUDGETS.get(name)
        if (pinned is None
                or cur_n > pinned["max_collectives"]
                or cur_b > pinned["max_collective_bytes"]):
            problems.append(
                f"{name}: measured {cur_n} collectives / {cur_b}B vs "
                f"{old_n} / {old_b}B in the reference audit, and the "
                "growth is NOT covered by the pinned budget — re-pin "
                "BUDGETS (and the docs table) if the change is "
                "deliberate")
    return problems


def contract_for(name: str) -> ProgramContract:
    budget = BUDGETS[name]
    tiered = name in _TIERED_ROWS
    # H=32 head rows x RANK f32 (+1 mean-count column headroom is not
    # needed: MF folds are sum) — the smallest tiered head's byte size.
    hot_bytes = 32 * RANK * 4 if tiered else 0
    return ProgramContract(
        name=f"audit/{name}",
        max_collectives=budget["max_collectives"],
        max_collective_bytes=budget["max_collective_bytes"],
        per_kind_max=budget["per_kind_max"],
        # Counts are pinned EXACT (the docstring's "not
        # ceilings-with-slack"): a removed collective or a new kind
        # fails the audit until the budget is re-pinned.
        exact_collectives=True,
        donated_tables=True,
        max_float_bits=32,
        require_shard_psum=tiered,
        hot_reconcile_bytes=hot_bytes,
        shard_group_size=8 if tiered else None,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="certify the example workloads' compiled programs "
                    "(fps_tpu.analysis)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the certificate JSON here (default: "
                         "stdout only)")
    ap.add_argument("--only", default=None,
                    help="comma-separated workload subset "
                         f"(default: all of {', '.join(BUILDERS)})")
    ap.add_argument("--measure", action="store_true",
                    help="print measured profiles without enforcing "
                         "budgets (for re-pinning after a deliberate "
                         "program change)")
    ap.add_argument("--diff", default=None, metavar="OLD.json",
                    help="also diff the measured profiles against a "
                         "prior audit JSON (--out format) and FAIL on "
                         "any unpinned budget regression: a program "
                         "whose collective count/bytes grew vs OLD "
                         "without the BUDGETS row being re-pinned. "
                         "Deliberate re-pinned growth is reported but "
                         "passes — the diff is the review artifact")
    args = ap.parse_args(argv)

    names = (args.only.split(",") if args.only else list(BUILDERS))
    unknown = [n for n in names if n not in BUILDERS]
    if unknown:
        ap.error(f"unknown workload(s): {', '.join(unknown)}")

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    certs = {}
    for name in names:
        text = BUILDERS[name](mesh)
        if args.measure:
            contract = ProgramContract(name=f"measure/{name}")
        else:
            contract = contract_for(name)
        cert = certify(text, contract, program=name)
        certs[name] = cert
        mark = "OK " if cert.ok else "FAIL"
        print(f"[{mark}] {name}: {cert.collective_count} collectives, "
              f"{cert.collective_bytes} bytes "
              f"{json.dumps(cert.per_kind())}", file=sys.stderr)
        for v in cert.violations:
            print(f"       [{v.pass_name}] {v.summary}", file=sys.stderr)

    rerank_identical = None
    if "mf_retier" in names:
        # The adaptive tier's recompile-freedom contract: two different
        # re-ranks of the same (H, table) lower byte-identical programs.
        rerank_identical = rerank_byte_identity(mesh)
        mark = "OK " if rerank_identical else "FAIL"
        print(f"[{mark}] mf_retier: re-rank byte-identity "
              f"({'identical' if rerank_identical else 'programs DIFFER'}"
              " across disjoint hot id sets)", file=sys.stderr)

    megastep_k_ind = None
    if "mf_megastep" in names:
        # The megastep scaling contract: collective census identical as
        # K doubles — megastep collective cost is O(traffic), not O(K).
        megastep_k_ind = megastep_k_independence(mesh)
        mark = "OK " if megastep_k_ind else "FAIL"
        verdict = ("census identical" if megastep_k_ind
                   else "census DIFFERS")
        print(f"[{mark}] mf_megastep: K-independence ({verdict} across "
              "K=2 vs K=4)", file=sys.stderr)

    diff_problems = []
    if args.diff:
        with open(args.diff, encoding="utf-8") as f:
            old_doc = json.load(f)
        measured = {
            n: {"collective_count": c.collective_count,
                "collective_bytes": c.collective_bytes}
            for n, c in certs.items()
        }
        diff_problems = diff_budgets(old_doc, measured)
        for n in sorted(measured):
            o = old_doc.get("audit_programs", {}).get(n)
            if not o:
                continue
            oc = o.get("collectives", o)
            old_pair = (oc.get("count", 0), oc.get("bytes", 0))
            cur_pair = (measured[n]["collective_count"],
                        measured[n]["collective_bytes"])
            if old_pair != cur_pair:
                print(f"[DIFF] {n}: {old_pair[0]}/{old_pair[1]}B -> "
                      f"{cur_pair[0]}/{cur_pair[1]}B", file=sys.stderr)
        for p in diff_problems:
            print(f"[FAIL] diff: {p}", file=sys.stderr)

    ok = (all(c.ok for c in certs.values())
          and rerank_identical is not False
          and megastep_k_ind is not False
          and not diff_problems)
    doc = {
        "audit_programs": {n: c.to_json() for n, c in certs.items()},
        "rerank_byte_identical": rerank_identical,
        "megastep_k_independent": megastep_k_ind,
        "ok": ok,
        "mesh": {"shard": 8, "data": 1},
        "scale": {"nu": NU, "ni": NI, "rank": RANK, "nf": NF,
                  "vocab": VOCAB, "local_batch": LOCAL_BATCH,
                  "steps_per_chunk": STEPS},
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}", file=sys.stderr)
    print(json.dumps({
        "audit": {n: {"ok": c.ok, "collectives": c.collective_count,
                      "bytes": c.collective_bytes}
                  for n, c in certs.items()},
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
