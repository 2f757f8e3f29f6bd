"""The names a device trace reads the program by (docs/observability.md).

A compiled step's ops carry the ``jax.named_scope`` path they were traced
under, and the benchmark's trace reducer selects by it — so the names are
an interface, pinned here on the lowered text WITH locations
(``as_text(debug_info=True)``; the default text carries none, which is
why scopes cannot change a program). Two rules besides the names: a
route's scope covers only its own call (the head-prefix composite's tail
opens its scope beside the head's), and ``fps.*`` scopes live in step
bodies only — device programs that run once a call or once a chunk are
named without that prefix, because a reader counts steps by the ops
under ``fps.*``.
"""

import re

import jax
import numpy as np
import pytest

import fps_tpu.ops as ops
from fps_tpu import DeviceDataset, DeviceEpochPlan
from fps_tpu.models.matrix_factorization import MFConfig, online_mf
from fps_tpu.models.passive_aggressive import PAConfig, passive_aggressive
from fps_tpu.parallel.mesh import key_to_replicated, make_ps_mesh


def _scope_paths(lowered) -> set:
    """Every op's scope path in a lowered program, the primitive cut off
    (a named location reads ``loc("jit(run)/fps.pull/gather"(#loc7))``; a
    file location has a colon after its closing quote, not a bracket)."""
    text = lowered.as_text(debug_info=True)
    return {name.rsplit("/", 1)[0]
            for name in re.findall(r'loc\("([^"]+/[^"]*)"\(', text)}


def _holds(paths, scope) -> bool:
    """Some op's path is ``scope`` or lies under it."""
    return any(p == scope or p.startswith(scope + "/")
               or f"/{scope}/" in p + "/" for p in paths)


@pytest.fixture(scope="module")
def pa_step_scopes(devices8):
    """PA's epoch program on one device at the benchmark cell's shape
    (47,236 features, 16,384 x 64 slots a step, 16 head columns), traced
    and lowered under the forced ``pallas`` backend; nothing runs."""
    F, S, B = 47_236, 64, 16_384
    rng = np.random.default_rng(0)
    ids = rng.integers(0, F, (B, S)).astype(np.int32)
    ids[:, :16] = rng.integers(0, 2048, (B, 16))
    data = {"feat_ids": ids,
            "feat_vals": rng.random((B, S)).astype(np.float32),
            "label": rng.choice([-1.0, 1.0], B).astype(np.float32)}
    mesh = make_ps_mesh(devices=devices8[:1])
    prev = ops.get_backend()
    ops.set_backend("pallas")
    try:
        trainer, _ = passive_aggressive(
            mesh, PAConfig(num_features=F, variant="PA-I", C=1.0,
                           hot_features=2048, head_prefix_cols=16))
        tables, ls = trainer.init_state(jax.random.key(0))
        plan = DeviceEpochPlan(DeviceDataset(mesh, data), num_workers=1,
                               local_batch=B, seed=1)
        lowered = trainer._get_indexed_fn(plan, "sync").lower(
            tables, ls, plan.epoch_args(0), np.int32(0),
            key_to_replicated(jax.random.key(1), mesh))
    finally:
        ops.set_backend(prev)
    return _scope_paths(lowered)


@pytest.mark.parametrize("scope", [
    "fps.ingest", "fps.pull", "fps.compute", "fps.push", "fps.metrics",
    "fps.pull/fps.ops/gather.dim1_head", "fps.pull/fps.ops/gather.dim1",
    "fps.push/fps.ops/scatter_add.dim1_head",
    "fps.push/fps.ops/scatter_add.dim1",
])
def test_pa_step_holds_scope(pa_step_scopes, scope):
    assert _holds(pa_step_scopes, scope), sorted(pa_step_scopes)


def test_head_prefix_tail_scope_is_beside_the_head_not_under_it(
        pa_step_scopes):
    under_head = [p for p in pa_step_scopes
                  if re.search(r"dim1_head/.*(gather|scatter_add)\.dim1(/|$)",
                               p)]
    assert not under_head
    # ... and fps.ops is entered once per route, not once per composite.
    assert not [p for p in pa_step_scopes if p.count("fps.ops") > 1]


@pytest.fixture(scope="module")
def mf_plans(devices8):
    """A packable (all 1-D, 4-byte) MF data set on two workers: the
    transposed-buffer plan and a ``shuffle="sort"`` one over it."""
    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    rng = np.random.default_rng(1)
    n = 512
    ds = DeviceDataset(mesh, {
        "user": rng.integers(0, 64, n).astype(np.int32),
        "item": rng.integers(0, 32, n).astype(np.int32),
        "rating": rng.random(n).astype(np.float32)})
    mk = lambda **kw: DeviceEpochPlan(  # noqa: E731
        ds, num_workers=2, local_batch=32, route_key="user", seed=3, **kw)
    return mesh, mk(), mk(shuffle="sort")


@pytest.mark.parametrize("program,name", [
    ("tbuf", "ingest.tbuf"), ("tbuf.columns", "ingest.tbuf"),
    ("perm", "ingest.perm"), ("chunk", "ingest.chunk"),
])
def test_once_a_call_programs_carry_no_fps_scope(mf_plans, program, name):
    mesh, plan, sort_plan = mf_plans
    if program == "tbuf":
        packed = plan.dataset.packed(plan.route_key, plan.num_workers)[0]
        lowered = plan._tbuf_jit.lower(
            packed, np.zeros(plan.num_workers, np.int32))
    elif program == "tbuf.columns":
        # An unkeyed plan over a 2-D column: a buffer a column (PR 50).
        sliced = DeviceEpochPlan(
            DeviceDataset(mesh, {"x": np.zeros((512, 3), np.float32),
                                 "y": np.zeros(512, np.float32)}),
            num_workers=2, local_batch=32, seed=3)
        assert sliced.sliced
        lowered = sliced._tbuf_jit.lower(
            sliced.dataset.columns, np.zeros(2, np.int32))
    elif program == "perm":
        lowered = sort_plan._perm_jit.lower(
            np.zeros(sort_plan._key_data_shape, np.uint32))
    else:
        lowered = plan._chunk_builder(4).lower(plan.epoch_args(0),
                                               np.int32(0))
    paths = _scope_paths(lowered)
    assert any(name in p.split("/") for p in paths), sorted(paths)
    assert not [p for p in paths if "fps." in p]


def test_mf_step_ingest_is_scoped_and_tbuf_is_not_in_the_step(mf_plans):
    mesh, plan, _ = mf_plans
    trainer, _ = online_mf(mesh, MFConfig(num_users=64, num_items=32,
                                          rank=4))
    tables, ls = trainer.init_state(jax.random.key(0))
    paths = _scope_paths(trainer._get_indexed_fn(plan, "sync").lower(
        tables, ls, plan.epoch_args(0), np.int32(0),
        key_to_replicated(jax.random.key(1), mesh)))
    assert any("fps.ingest" in p.split("/") for p in paths)
    assert any("fps.ops" in p.split("/") for p in paths)
    assert not [p for p in paths if "ingest.tbuf" in p]


# -- word2vec SGNS: fps.prepare, fps.combine, ingest.compact ---------------

@pytest.fixture(scope="module")
def w2v_programs(devices8):
    """The block worker's step in the step builders that take its plan
    (the indexed epoch and the megastep; all three builders go through
    ``Trainer._compute_step``, where the scope is) on one device, traced
    and lowered; nothing runs. Scope paths by builder, the
    compaction's, and the route log of the indexed program."""
    from fps_tpu.models.word2vec import (
        W2VConfig,
        Word2VecDevicePlan,
        word2vec_block,
    )

    V, D, L = 300, 300, 64      # the cell's row width, a toy vocabulary
    mesh = make_ps_mesh(devices=devices8[:1])
    rng = np.random.default_rng(2)
    counts = 1.0 / (np.arange(V) + 1.5)
    tokens = rng.integers(0, V, 4096).astype(np.int32)
    cfg = W2VConfig(vocab_size=V, dim=D)
    trainer, _ = word2vec_block(mesh, cfg, counts, L)
    plan = Word2VecDevicePlan(DeviceDataset(mesh, {"token": tokens}),
                              counts, cfg, mesh, num_workers=1, block_len=L,
                              seed=1, mode="block")
    tables, ls = trainer.init_state(jax.random.key(0))
    key = key_to_replicated(jax.random.key(1), mesh)
    iargs = plan.epoch_args(0)
    ops.clear_routes()
    indexed = trainer._get_indexed_fn(plan, "sync").lower(
        tables, ls, iargs, np.int32(0), key)
    routes = ops.routes_traced()
    mega = trainer._get_megastep_fn(plan, "sync", 2).lower(
        tables, ls, iargs, np.int32(0), key, {})
    compact = plan._compact_jit.lower(
        key, plan.dataset.columns["token"], plan._keep_p)
    return {"indexed": _scope_paths(indexed), "megastep": _scope_paths(mega),
            "compact": _scope_paths(compact), "routes": routes}


@pytest.mark.parametrize("builder", ["indexed", "megastep"])
@pytest.mark.parametrize("scope", [
    "fps.ingest", "fps.prepare", "fps.pull", "fps.compute", "fps.push",
    "fps.push/fps.combine", "fps.pull/fps.ops/gather.xla",
    "fps.push/fps.ops/scatter_add.xla",
])
def test_w2v_step_holds_scope_in_every_step_builder(w2v_programs, builder,
                                                    scope):
    assert _holds(w2v_programs[builder], scope), sorted(
        w2v_programs[builder])


def test_scope_lists_name_every_scope_in_the_tree(w2v_programs):
    """``obs.timing.STEP_SCOPES`` / ``ONCE_SCOPES`` against every literal
    ``jax.named_scope("...")`` and ``COMBINE_SCOPE`` under ``fps_tpu/``,
    and against what the lowered w2v step really carries."""
    import ast
    import os

    from fps_tpu.core.store import COMBINE_SCOPE
    from fps_tpu.obs import timing

    declared = (set(timing.STEP_SCOPES) | set(timing.ONCE_SCOPES)
                | set(timing.ROUND_SCOPES) | set(timing.INNER_SCOPES))
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fps_tpu")
    named = {COMBINE_SCOPE}
    for d, _, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            for node in ast.walk(ast.parse(open(os.path.join(d, f)).read())):
                if (isinstance(node, ast.Call)
                        and getattr(node.func, "attr", "") == "named_scope"
                        and node.args
                        and isinstance(node.args[0], ast.Constant)):
                    named.add(node.args[0].value)
    assert named == declared, named ^ declared
    assert not [n for n in (timing.ONCE_SCOPES + timing.ROUND_SCOPES
                            + timing.INNER_SCOPES) if n.startswith("fps.")]
    used = {part for p in w2v_programs["indexed"] for part in p.split("/")
            if part.startswith("fps.")}
    assert {"fps.prepare", "fps.combine"} <= used <= set(timing.STEP_SCOPES)


def test_combine_scope_lies_beside_the_routed_scatter_not_round_it(
        w2v_programs):
    """An op counts under ``fps.combine`` or under ``fps.ops/<route>``,
    never both; and prepare's gathers on the alias tables are not the
    pull's."""
    for paths in (w2v_programs["indexed"], w2v_programs["megastep"]):
        both = [p for p in paths if "fps.combine" in p and "fps.ops" in p]
        assert not both, both
        assert not [p for p in paths
                    if "fps.prepare" in p and ("fps.pull" in p
                                               or "fps.compute" in p)]


def test_compaction_is_named_without_the_fps_prefix(w2v_programs):
    paths = w2v_programs["compact"]
    assert any("ingest.compact" in p.split("/") for p in paths), sorted(paths)
    assert not [p for p in paths if "fps." in p]
    assert not [p for p in w2v_programs["indexed"] if "ingest.compact" in p]


def test_route_log_of_a_300_wide_table(w2v_programs):
    """Both tables take the plain XLA routes, a gather and a scatter-add
    each; each mean push logs its branch BEFORE its scatter-add: the
    accumulator (one column wider) or, where the toy table is still large
    enough against the push (``ops.MEAN_ROWS_TABLE_RATIO``; the cell's
    own tables are, 23 and 136 times: tests/test_v5e_compile.py), the
    rows themselves. On the CPU the scatter's reason is the backend's (on
    the TPU: ``shape``, tests/test_ops.py)."""
    L, W, K, V = 64, 5, 5, 300
    got = [(r.route, r.rows, r.dim, r.ids) for r in w2v_programs["routes"]]
    want = [("gather.xla", V, 300, L + W),
            ("gather.xla", V, 300, (L + W) * (1 + K))]
    for ids in (L + W, (L + W) * (1 + K)):
        rows = V >= ops.MEAN_ROWS_TABLE_RATIO * ids  # 301 tiles as 300 does
        want += [("push.mean_rows" if rows else "push.mean_dense",
                  V, 300, ids),
                 ("scatter_add.xla", V, 300 if rows else 301, ids)]
    assert got == want, got
    assert want[-2][0] == "push.mean_dense"  # 414 ids into 300 rows
    assert not any(r.route in ops.PALLAS_ROUTES
                   for r in w2v_programs["routes"])
