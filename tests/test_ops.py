"""Row-op parity and routing tests (Pallas kernels interpreted on the CPU).

Oracle: numpy gather / np.add.at. Covers duplicates (Zipfian ids), drop
sentinels, ragged (non-tile-multiple) shapes, the dispatcher's backend
switching and its route log — including a full training chunk run
end-to-end with the Pallas backend to prove the kernels compose inside
shard_map + scan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fps_tpu.ops as ops


@pytest.fixture
def pallas_backend():
    prev = ops.get_backend()
    ops.set_backend("pallas")
    yield
    ops.set_backend(prev)


def _numpy_scatter_add(table, ids, deltas):
    want = table.astype(np.float64).copy()
    keep = (ids >= 0) & (ids < table.shape[0])
    np.add.at(want, ids[keep], deltas[keep].astype(np.float64))
    return want


@pytest.mark.parametrize("R,D,B,hot", [(64, 8, 100, 16), (130, 3, 513, 7),
                                       (57, 200, 64, 8)])
def test_scatter_add_hot_cold_split_parity(pallas_backend, R, D, B, hot):
    """``hot_rows`` is the H of the ``head_prefix`` guarantee and nothing
    else: without ``head_prefix`` the call is the plain scatter bit for
    bit — drops, duplicates, and ids on the head's boundary."""
    rng = np.random.default_rng(7)
    table = rng.normal(0, 1, (R, D)).astype(np.float32)
    ids = (rng.zipf(1.5, B) % R).astype(np.int32)  # heavy head duplication
    ids[::9] = -1
    ids[4::13] = R
    ids[1::17] = hot - 1  # boundary: last head row
    ids[2::17] = hot      # boundary: first tail row
    deltas = rng.normal(0, 1, (B, D)).astype(np.float32)
    args = (jnp.asarray(table), jnp.asarray(ids), jnp.asarray(deltas))

    ops.clear_routes()
    got = np.asarray(ops.scatter_add(*args, hot_rows=hot))
    plain = np.asarray(ops.scatter_add(*args))
    assert [r.route for r in ops.routes_traced()] == ["scatter_add.xla"] * 2
    np.testing.assert_array_equal(got, plain)
    np.testing.assert_allclose(got, _numpy_scatter_add(table, ids, deltas),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(ops.gather_rows(args[0], args[1], hot_rows=hot)),
        np.asarray(ops.gather_rows(args[0], args[1])))


# Odd shapes through the dispatcher: sizes that are no multiple of a tile,
# a table narrower and wider than a lane row, more ids than rows.
_GATHER_SHAPES = [(64, 8, 32), (57, 5, 40), (8, 128, 256)]
_SCATTER_SHAPES = [(64, 8, 100), (57, 5, 40), (130, 3, 513)]


@pytest.mark.parametrize("op,R,D,B", [
    ("both", 30, 4, 50),
    *[("gather", *s) for s in _GATHER_SHAPES],
    *[("scatter_add", *s) for s in _SCATTER_SHAPES],
])
def test_dispatcher_backends(op, R, D, B):
    """Under each backend value: out-of-range ids drop (a gather reads
    zeros), duplicates accumulate, against numpy."""
    with pytest.raises(ValueError):
        ops.set_backend("cuda")
    assert ops.get_backend() in ("xla", "pallas", "auto")

    rng = np.random.default_rng(2)
    table = rng.normal(0, 1, (R, D)).astype(np.float32)
    # Zipfian ids -> heavy duplication, plus drop sentinels -1 and R.
    ids = (rng.zipf(1.5, B) % R).astype(np.int32)
    ids[::7] = -1
    ids[3::11] = R
    deltas = rng.normal(0, 1, (B, D)).astype(np.float32)
    keep = (ids >= 0) & (ids < R)

    prev = ops.get_backend()
    try:
        for backend in ("xla", "pallas", "auto"):
            ops.set_backend(backend)
            if op != "gather":
                got = np.asarray(ops.scatter_add(
                    jnp.asarray(table), jnp.asarray(ids), jnp.asarray(deltas)))
                np.testing.assert_allclose(
                    got, _numpy_scatter_add(table, ids, deltas), rtol=1e-5,
                    atol=1e-5, err_msg=f"backend={backend}")
            if op != "scatter_add":
                g = np.asarray(ops.gather_rows(jnp.asarray(table),
                                               jnp.asarray(ids)))
                np.testing.assert_array_equal(
                    g, np.where(keep[:, None], table[np.where(keep, ids, 0)],
                                0), err_msg=f"backend={backend}")
    finally:
        ops.set_backend(prev)


def test_gather_oob_zero_rows_on_every_backend():
    """Padding ids (-1) must read as zero rows identically on all backends."""
    rng = np.random.default_rng(4)
    table = rng.normal(0, 1, (20, 70)).astype(np.float32)  # D>=64: pallas path
    ids = np.array([-1, 3, 20, 0, -1], np.int32)
    prev = ops.get_backend()
    try:
        outs = {}
        for backend in ("xla", "pallas"):
            ops.set_backend(backend)
            outs[backend] = np.asarray(
                ops.gather_rows(jnp.asarray(table), jnp.asarray(ids))
            )
        want = np.stack([
            np.zeros(70), table[3], np.zeros(70), table[0], np.zeros(70)
        ]).astype(np.float32)
        for backend, got in outs.items():
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=0,
                                       err_msg=f"backend={backend}")
    finally:
        ops.set_backend(prev)


def _pa_chunk_on(mesh, steps):
    """A PA trainer on a 2,048-feature SCALAR table and one chunk whose
    worker step moves 128 x 64 = DIM1_MIN_BATCH ids: the shape where the
    backend decides between the dim-1 kernels and XLA."""
    from fps_tpu.core.driver import num_workers_of
    from fps_tpu.core.ingest import epoch_chunks
    from fps_tpu.models.passive_aggressive import PAConfig, passive_aggressive
    from fps_tpu.utils.datasets import synthetic_sparse_classification

    W = num_workers_of(mesh)
    trainer, store = passive_aggressive(
        mesh, PAConfig(num_features=2048, variant="PA-I", C=1.0),
        donate=False)
    data = synthetic_sparse_classification(128 * W * steps, 2048, 64, seed=5)
    chunk = next(epoch_chunks(data, num_workers=W, local_batch=128,
                              steps_per_chunk=steps))
    return trainer, store, chunk


def test_set_backend_takes_effect_on_compiled_trainer(devices8):
    """set_backend() after a chunk has compiled must retrace, not silently
    reuse the old backend's executable (Trainer keys its cache on it)."""
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=2, num_data=1, devices=devices8[:2])
    trainer, _, chunk = _pa_chunk_on(mesh, steps=2)
    tables, ls = trainer.init_state(jax.random.key(0))
    prev = ops.get_backend()
    try:
        ops.set_backend("xla")
        ops.clear_routes()
        trainer.run_chunk(tables, ls, chunk, jax.random.key(1))
        assert any(k[:2] == ("sync", "xla") for k in trainer._compiled)
        assert not {r.route for r in ops.routes_traced()} & ops.PALLAS_ROUTES
        ops.set_backend("pallas")
        ops.clear_routes()
        trainer.run_chunk(tables, ls, chunk, jax.random.key(1))
        assert any(k[:2] == ("sync", "pallas") for k in trainer._compiled)
        # The retrace is another program: the dim-1 kernels are in it.
        assert {r.route for r in ops.routes_traced()} >= {
            "gather.dim1", "scatter_add.dim1"}
    finally:
        ops.set_backend(prev)


def test_mf_chunk_runs_with_pallas_backend(devices8, pallas_backend):
    """Full compiled training chunk (shard_map + scan + collectives) with the
    Pallas kernels in the pull/push hot path, vs the XLA backend result.
    On PA's scalar table: it is the dim-1 kernels the backend still
    switches (an MF table takes XLA under every backend)."""
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
    steps = 4

    def run_one():
        trainer, store, chunk = _pa_chunk_on(mesh, steps)
        tables, ls = trainer.init_state(jax.random.key(0))
        ops.clear_routes()
        tables, ls, m = trainer.run_chunk(tables, ls, chunk, jax.random.key(1))
        return (np.asarray(store.dump_model("weights")[1]),
                {r.route for r in ops.routes_traced()})

    got, routes = run_one()
    assert routes >= {"gather.dim1", "scatter_add.dim1"}
    ops.set_backend("xla")
    want, routes = run_one()
    assert not routes & ops.PALLAS_ROUTES
    assert np.abs(want).max() > 0
    # One kernel read and one kernel push of every touched weight a step,
    # each off by at most 2**-16 relative (the hi+lo bf16 contract), and
    # PA-I's step is continuous in the weights (chip_smoke.py's bound).
    np.testing.assert_allclose(
        got, want, rtol=0, atol=2 * steps * 2.0 ** -16 * np.abs(want).max())


def test_hot_ids_auto_resolution(devices8):
    """``TableSpec.hot_ids`` is an int, the certified head H, and resolves
    to the head's local rows ``ceil(H / S)``; ``"auto"`` went with the
    route it selected and raises like any other string."""
    from fps_tpu.core.api import ServerLogic, StepOutput, WorkerLogic
    from fps_tpu.core.driver import Trainer
    from fps_tpu.core.store import ParamStore, TableSpec
    from fps_tpu.parallel.mesh import make_ps_mesh

    class Noop(WorkerLogic):
        def pull_ids(self, batch):
            return {}

        def step(self, batch, pulled, local_state, key):
            return StepOutput(pushes={}, local_state=local_state, out={})

    mesh = make_ps_mesh(num_shards=8, num_data=1)
    specs = [TableSpec("none", 8 * 1024, 10).zeros_init(),
             TableSpec("head", 8 * 65536, 10, hot_ids=4096).zeros_init(),
             TableSpec("ragged", 8 * 65536, 10, hot_ids=4097).zeros_init(),
             TableSpec("auto", 8 * 1024, 10, hot_ids="auto").zeros_init(),
             TableSpec("Auto", 100, 4, hot_ids="Auto").zeros_init()]
    store = ParamStore(mesh, specs)
    tr = Trainer(mesh, store, Noop(), server_logic=ServerLogic())

    assert tr._resolve_hot_rows(store.specs["none"]) == 0
    assert tr._resolve_hot_rows(store.specs["head"]) == 512   # 4096 / 8
    assert tr._resolve_hot_rows(store.specs["ragged"]) == 513  # ceil
    # A string must fail loudly at the right altitude, not as a cryptic
    # TypeError inside the jitted push.
    for name in ("auto", "Auto"):
        with pytest.raises(ValueError, match="hot_ids"):
            tr._resolve_hot_rows(store.specs[name])


# ---------------------------------------------------------------------------
# Dim-1 (scalar table) lane-packed kernels — the PA/logreg weight-vector
# shape, where XLA pays ~8 ns per scalar moved (measured dedup-safe on
# chip: dim1 kernels 2.8 ms vs XLA 7.7/8.2 ms at R=47k, B=2^20).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,B", [(1000, 5000), (128, 300), (47_236, 4096),
                                 (130, 513)])
def test_dim1_scatter_parity(R, B):
    from fps_tpu.ops.pallas_kernels import scatter_add_dim1_pallas

    rng = np.random.default_rng(1)
    table = rng.normal(0, 1, (R, 1)).astype(np.float32)
    # include drop sentinels and out-of-range ids
    ids = rng.integers(-3, R + 200, B).astype(np.int32)
    deltas = rng.normal(0, 1, (B, 1)).astype(np.float32)
    ref = table.copy()
    keep = (ids >= 0) & (ids < R)
    np.add.at(ref[:, 0], ids[keep], deltas[keep, 0])
    got = np.asarray(scatter_add_dim1_pallas(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(deltas),
        interpret=True,
    ))
    # hi+lo bf16 contract: ~16 mantissa bits per delta.
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("R,B", [(1000, 5000), (128, 300), (47_236, 4096)])
def test_dim1_gather_parity(R, B):
    from fps_tpu.ops.pallas_kernels import gather_rows_dim1_pallas

    rng = np.random.default_rng(2)
    table = rng.normal(0, 1, (R, 1)).astype(np.float32)
    ids = rng.integers(-3, R + 200, B).astype(np.int32)
    ref = np.where(((ids >= 0) & (ids < R))[:, None],
                   table[np.clip(ids, 0, R - 1)], 0.0)
    got = np.asarray(gather_rows_dim1_pallas(
        jnp.asarray(table), jnp.asarray(ids), interpret=True))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# (R, D, B, taken) under the forced backend.
_DIM1_CASES = [
    (47_236, 1, 1 << 20, True),
    (47_236, 2, 1 << 20, False),      # not scalar
    (1_000_000, 1, 1 << 20, False),   # row cap
    (47_236, 1, 1024, False),         # batch floor
]


def test_dim1_routing_conditions(pallas_backend):
    """_route_dim1: only scalar tables below the measured row cap at large
    batch route to the dim-1 kernels; everything else keeps its path."""
    for R, D, B, taken in _DIM1_CASES:
        assert ops._route_dim1(R, D, B) == taken, (R, D, B)
    prev = ops.get_backend()
    ops.set_backend("xla")
    try:
        assert not ops._route_dim1(47_236, 1, 1 << 20)  # forced xla
    finally:
        ops.set_backend(prev)


def test_dim1_routed_scatter_and_gather_through_dispatcher(pallas_backend):
    """The dispatcher-level ops with a routed dim-1 shape must match the
    XLA backend to the hi+lo precision contract."""
    rng = np.random.default_rng(3)
    R, B = 9_000, 16_384
    table = rng.normal(0, 1, (R, 1)).astype(np.float32)
    ids = rng.integers(-1, R, B).astype(np.int32)
    deltas = rng.normal(0, 1e-2, (B, 1)).astype(np.float32)
    assert ops._route_dim1(R, 1, B)

    got_s = np.asarray(ops.scatter_add(jnp.asarray(table), jnp.asarray(ids),
                                       jnp.asarray(deltas)))
    ref_s = table.copy()
    keep = ids >= 0
    np.add.at(ref_s[:, 0], ids[keep], deltas[keep, 0])
    np.testing.assert_allclose(got_s, ref_s, rtol=2e-4, atol=2e-4)

    got_g = np.asarray(ops.gather_rows(jnp.asarray(table), jnp.asarray(ids)))
    ref_g = np.where((ids >= 0)[:, None], table[np.clip(ids, 0, None)], 0.0)
    np.testing.assert_allclose(got_g, ref_g, rtol=2e-4, atol=2e-4)


def test_gather_exact_overrides_lossy_routes(pallas_backend):
    """``exact=True`` must take the bit-exact XLA gather even on shapes the
    dim-1 hi+lo-bf16 route would claim — the read-only escape hatch that
    keeps eval/export pulls out of training's precision concession."""
    rng = np.random.default_rng(7)
    R, B = 9_000, 16_384
    # Values with >16 significant mantissa bits so the hi+lo bf16 pair
    # visibly diverges from the exact read.
    table = (rng.normal(0, 1, (R, 1)) * (1 + 1e-7)).astype(np.float32)
    ids = rng.integers(-1, R, B).astype(np.int32)
    assert ops._route_dim1(R, 1, B)

    ref = np.where((ids >= 0)[:, None], table[np.clip(ids, 0, None)], 0.0)
    got_exact = np.asarray(
        ops.gather_rows(jnp.asarray(table), jnp.asarray(ids), exact=True))
    # Bit-exact, not just close.
    np.testing.assert_array_equal(got_exact, ref)

    # Sanity: the routed (non-exact) read on this shape is NOT bit-exact
    # under the forced-pallas backend, which is the whole reason the
    # override exists.
    got_routed = np.asarray(
        ops.gather_rows(jnp.asarray(table), jnp.asarray(ids)))
    assert not np.array_equal(got_routed, ref)
    np.testing.assert_allclose(got_routed, ref, rtol=2e-4, atol=2e-4)


def test_pull_exact_plumbs_through_both_routes(devices8):
    """store.pull(exact=True) must produce bit-exact reads on both the
    gathered and dense collective routes."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from fps_tpu.core.store import SHARD_AXIS, pull
    from fps_tpu.parallel.mesh import make_ps_mesh

    prev = ops.get_backend()
    ops.set_backend("pallas")
    try:
        mesh = make_ps_mesh(num_shards=4, num_data=1, devices=devices8[:4])
        S, R = 4, 36_000
        rps = R // S
        rng = np.random.default_rng(11)
        full = (rng.normal(0, 1, (R, 1)) * (1 + 1e-7)).astype(np.float32)
        # owner-major physical layout: shard s holds ids with id % S == s
        shards = np.stack([full[s::S, 0] for s in range(S)])  # (S, rps)
        ids = rng.integers(0, R, 16_384).astype(np.int32)

        for dense in (False, True):
            def f(local, i):
                return pull(local.reshape(-1)[:, None], i, num_shards=S,
                            dense=dense, exact=True)

            got = jax.jit(shard_map(
                f, mesh=mesh,
                in_specs=(P(SHARD_AXIS), P()), out_specs=P(SHARD_AXIS),
            ))(jnp.asarray(shards), jnp.asarray(ids))
            # One (B, 1) answer block per shard-position worker; every
            # worker asked for the same ids, so each must be bit-exact.
            for blk in np.split(np.asarray(got), S):
                np.testing.assert_array_equal(
                    blk, full[ids], err_msg=f"dense={dense}")
    finally:
        ops.set_backend(prev)


@pytest.mark.parametrize("R,H,B,q", [(47_236, 2048, 12_288, 8192),
                                     (9_000, 1024, 6_000, 2048)])
def test_head_prefix_scatter_and_gather_parity(pallas_backend, R, H, B, q):
    """head_prefix routing: ids[:q] in [0, H) ∪ {-1} ride the head-only
    kernel; results match plain numpy to the hi+lo contract."""
    rng = np.random.default_rng(7)
    table = rng.normal(0, 1, (R, 1)).astype(np.float32)
    head_ids = rng.integers(0, H, q).astype(np.int32)
    head_ids[::11] = -1  # dropped slots inside the guaranteed prefix
    tail_ids = rng.integers(-1, R, B - q).astype(np.int32)
    ids = np.concatenate([head_ids, tail_ids])
    deltas = rng.normal(0, 1, (B, 1)).astype(np.float32)
    assert ops._route_head_prefix(R, 1, q, H, np.float32)

    got = np.asarray(ops.scatter_add(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(deltas),
        hot_rows=H, head_prefix=q,
    ))
    ref = table.copy()
    keep = ids >= 0
    np.add.at(ref[:, 0], ids[keep], deltas[keep, 0])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    got_g = np.asarray(ops.gather_rows(
        jnp.asarray(table), jnp.asarray(ids), hot_rows=H, head_prefix=q))
    ref_g = np.where(keep[:, None], table[np.clip(ids, 0, None)], 0.0)
    np.testing.assert_allclose(got_g, ref_g, rtol=2e-4, atol=2e-4)


# (R, D, head_prefix, hot_rows, taken) under the forced backend.
_HEAD_PREFIX_CASES = [
    (47_236, 1, 8192, 2048, True),
    (47_236, 1, 1024, 2048, False),   # short
    (47_236, 2, 8192, 2048, False),   # D != 1
    (47_236, 1, 8192, 0, False),      # no head
    (4_096, 1, 8192, 2048, False),    # H ~ R
]


def test_head_prefix_routing_conditions(pallas_backend):
    for R, D, prefix, H, taken in _HEAD_PREFIX_CASES:
        assert ops._route_head_prefix(R, D, prefix, H, np.float32) == taken, (
            R, D, prefix, H)


# ---------------------------------------------------------------------------
# The route log: what a program holds, said by the layer that chose it.
# ---------------------------------------------------------------------------

# PA's step on one chip (perfbench/configs/pa-rcv1.json): 47,236 features,
# 16,384 documents x 64 slots a step, the first 16 columns of every document
# guaranteed inside the 2,048-row head.
_PA = dict(R=47_236, H=2_048, ids=16_384 * 64, prefix=16_384 * 16)


def _pa_routes():
    """Route log of PA's pull and push at the cell's shape, traced only
    (``eval_shape``: nothing of this size runs on the CPU)."""
    table = jax.ShapeDtypeStruct((_PA["R"], 1), jnp.float32)
    ids = jax.ShapeDtypeStruct((_PA["ids"],), jnp.int32)
    deltas = jax.ShapeDtypeStruct((_PA["ids"], 1), jnp.float32)

    def step(table, ids, deltas):
        vals = ops.gather_rows(table, ids, hot_rows=_PA["H"],
                               head_prefix=_PA["prefix"])
        return ops.scatter_add(table, ids, deltas + vals,
                               hot_rows=_PA["H"], head_prefix=_PA["prefix"])

    ops.clear_routes()
    jax.eval_shape(step, table, ids, deltas)
    return ops.routes_traced()


@pytest.mark.parametrize("route,rows,ids", [
    ("gather.dim1_head", _PA["H"], _PA["prefix"]),
    ("gather.dim1", _PA["R"], _PA["ids"] - _PA["prefix"]),
    ("scatter_add.dim1_head", _PA["H"], _PA["prefix"]),
    ("scatter_add.dim1", _PA["R"], _PA["ids"] - _PA["prefix"]),
])
def test_route_log_holds_pa_routes_once_each(pallas_backend, route, rows,
                                             ids):
    log = _pa_routes()
    assert len(log) == 4  # head and tail of each composite, nothing else
    (entry,) = [r for r in log if r.route == route]
    assert entry == ops.Route(route.split(".")[0], route, rows, 1, ids,
                              interpret=True, reason="")


@pytest.mark.parametrize("case,route,reason", [
    ("f64", "scatter_add.xla", "f64"),
    ("auto_on_cpu", "scatter_add.xla", "backend"),
    ("exact_read", "gather.xla", ""),
    # The three calls that took the Pallas scatters PR 29 removed (a whole
    # shard marked hot, a hot head, the forced backend on a wide table):
    # one plain XLA scatter each, for want of a route that serves the shape.
    ("packed", "scatter_add.xla", "shape"),
    ("packed_head", "scatter_add.xla", "shape"),
    ("onehot", "scatter_add.xla", "shape"),
])
def test_route_log_says_why_a_pallas_route_was_passed_over(case, route,
                                                           reason):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    backend = "auto" if case == "auto_on_cpu" else "pallas"
    # Fresh lambdas: ``eval_shape`` caches a trace by function and shapes,
    # and the backend, which decides the route, is not in that key.
    scatter = lambda t, i, d, **kw: ops.scatter_add(t, i, d, **kw)  # noqa: E731
    gather = lambda t, i, **kw: ops.gather_rows(t, i, **kw)  # noqa: E731
    small = (f32(64, 4), i32(32), f32(32, 4))
    calls = {
        "f64": lambda: jax.eval_shape(
            scatter, jax.ShapeDtypeStruct((64, 4), jnp.float64), i32(32),
            jax.ShapeDtypeStruct((32, 4), jnp.float64)),
        "auto_on_cpu": lambda: jax.eval_shape(scatter, *small),
        "exact_read": lambda: jax.eval_shape(
            lambda t, i: gather(t, i, exact=True),
            f32(_PA["R"], 1), i32(_PA["ids"])),
        "packed": lambda: jax.eval_shape(
            lambda t, i, d: scatter(t, i, d, hot_rows=64), *small),
        "packed_head": lambda: jax.eval_shape(
            lambda t, i, d: scatter(t, i, d, hot_rows=16), *small),
        "onehot": lambda: jax.eval_shape(scatter, *small),
    }
    prev, x64 = ops.get_backend(), jax.config.jax_enable_x64
    ops.set_backend(backend)
    jax.config.update("jax_enable_x64", case == "f64")
    try:
        ops.clear_routes()
        calls[case]()
        log = ops.routes_traced()
    finally:
        ops.set_backend(prev)
        jax.config.update("jax_enable_x64", x64)
    # Exactly one entry; an XLA route never runs interpreted.
    assert [(r.route, r.reason, r.interpret) for r in log] == [
        (route, reason, False)], log


# --- The lane-packed XLA route (gather.xla_packed / scatter_add.xla_packed).

def _packed_case(R, D, dtype, B=2048, seed=0):
    """Table, ids, deltas: duplicates of one id, two ids sharing a packed
    row (``i`` and ``i + Rp``), ``-1`` and ids at and past ``R``."""
    rng = np.random.default_rng(seed + R + D)
    ids = rng.integers(0, R, B)
    ids[:64] = ids[64:128]            # one id more than once
    Rp = ops._xla_packed_rows(R, D)
    assert Rp < R                     # so packed rows are shared
    ids[200:264] = (ids[264:328] + Rp) % R   # mostly: same packed row
    ids[300], ids[301], ids[302], ids[303] = -1, R, R + Rp, -R
    table = jnp.asarray(rng.normal(0, 1, (R, D)), dtype)
    deltas = jnp.asarray(rng.normal(0, 1, (B, D)), dtype)
    return table, jnp.asarray(ids, jnp.int32), deltas


_PACKED_SHAPES = [(D, R) for D in (8, 10, 11, 16, 20, 32)
                  for R in (128 // D * 128 * 3,      # whole packed rows
                            128 // D * 128 * 2 + 77)]  # a ragged last group


def _bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("D,R", _PACKED_SHAPES)
def test_xla_packed_scatter_add_equals_plain_bit_for_bit(D, R, dtype):
    table, ids, deltas = _packed_case(R, D, dtype)
    keep = (ids >= 0) & (ids < R)
    want = table.at[jnp.where(keep, ids, R)].add(deltas, mode="drop")
    got = jax.jit(ops._xla_packed_scatter_add)(table, ids, deltas)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("D,R", _PACKED_SHAPES)
def test_xla_packed_gather_equals_take_bit_for_bit(D, R, dtype):
    table, ids, _ = _packed_case(R, D, dtype)
    keep = np.asarray((ids >= 0) & (ids < R))
    want = np.where(keep[:, None],
                    _bits(table)[np.where(keep, np.asarray(ids), 0)], 0)
    got = jax.jit(ops._xla_packed_gather)(table, ids)
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(_bits(got), want)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Routing as a TPU process decides it (kernels compiled, never
    interpreted; ``"xla"`` keeps every other route out): what the
    predicates read, with no chip."""
    monkeypatch.setattr(ops, "_use_pallas",
                        lambda: (ops.get_backend() != "xla", False))


_NETFLIX = (480_189, 10, 32_768)


_XLA_PACKED_CASES = [
    (*_NETFLIX, "float32", "auto", True, ""),
    (*_NETFLIX, "bfloat16", "auto", True, ""),       # 123 MB tiled: out too
    (200_000, 10, 32_768, "float32", "auto", True, ""),   # 102.4 MB tiled
    (320_126, 32, 32_768, "float32", "auto", False, "shape"),  # packed 41 MB
    (1_000_000, 20, 32_768, "float32", "auto", False, "shape"),  # 89.5 MB
    (120_048, 10, 32_768, "float32", "auto", False, "vmem_fit"),   # x4
    (17_770, 11, 32_768, "float32", "auto", False, "vmem_fit"),
    (47_236, 1, 4_096, "float32", "auto", False, "shape"),
    (1_000_000, 1, 32_768, "float32", "auto", False, "shape"),
    (200_000, 100, 32_768, "float32", "auto", False, "shape"),  # pack 1
    (480_189, 48, 32_768, "float32", "auto", False, "shape"),   # not swept
    (8_000_000, 10, 32_768, "float32", "auto", False, "shape"),  # packed out
    (*_NETFLIX[:2], 1_024, "float32", "auto", False, "shape"),  # too few ids
    (*_NETFLIX, "float64", "auto", False, "f64"),
    (*_NETFLIX, "float32", "xla", False, "backend"),
]


@pytest.mark.parametrize("R,D,B,dtype,backend,taken,reason",
                         _XLA_PACKED_CASES)
def test_xla_packed_predicate_over_shapes(as_on_tpu, R, D, B, dtype, backend,
                                          taken, reason):
    prev = ops.get_backend()
    ops.set_backend(backend)
    try:
        assert ops._route_xla_packed(R, D, B, dtype) == taken
        if not taken:
            assert ops._xla_reason(R, D, dtype) == reason
    finally:
        ops.set_backend(prev)


def test_xla_packed_is_never_taken_off_the_tpu():
    assert not ops._route_xla_packed(*_NETFLIX, jnp.float32)
    assert ops._xla_reason(*_NETFLIX[:2], jnp.float32) == "backend"


@pytest.mark.parametrize("op", ["gather", "scatter_add"])
def test_route_log_holds_the_xla_packed_route(as_on_tpu, op):
    R, D, B = _NETFLIX
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    ids = jax.ShapeDtypeStruct((B,), jnp.int32)
    ops.clear_routes()
    if op == "gather":
        out = jax.eval_shape(lambda t, i: ops.gather_rows(t, i),
                             f32(R, D), ids)
        assert out.shape == (B, D)
    else:
        out = jax.eval_shape(lambda t, i, d: ops.scatter_add(t, i, d),
                             f32(R, D), ids, f32(B, D))
        assert out.shape == (R, D)
    assert ops.routes_traced() == [
        ops.Route(op, f"{op}.xla_packed", R, D, B, interpret=False,
                  reason="")]
    # XLA routes both: no kernel, and a name of their own beside ``xla``.
    assert f"{op}.xla_packed" in {f"{op}.{r}" for r in ops.ROUTES[op]}
    assert f"{op}.xla_packed" not in ops.PALLAS_ROUTES
    assert f"{op}.xla" not in ops.PALLAS_ROUTES


def test_route_log_says_vmem_fit_where_the_plain_op_already_fits(as_on_tpu):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    ops.clear_routes()
    jax.eval_shape(lambda t, i, d: ops.scatter_add(t, ops.gather_rows(
        t, i)[:, 0].astype(jnp.int32), d), f32(120_048, 10),
        jax.ShapeDtypeStruct((32_768,), jnp.int32), f32(32_768, 10))
    assert [(r.route, r.reason) for r in ops.routes_traced()] == [
        ("gather.xla", "vmem_fit"), ("scatter_add.xla", "vmem_fit")]


# --- The sorted XLA route (scatter_add.xla_sorted).

_W2V = (1_115_011, 300, 49_182)

# (rows, dim, ids, dtype, backend, ids_sorted) -> the route taken, its reason
_XLA_SORTED_CASES = [
    (*_W2V, "float32", "auto", True, "xla_sorted", ""),
    (1_115_011, 300, 8_197, "float32", "auto", True, "xla_sorted", ""),
    (*_W2V, "float32", "pallas", True, "xla_sorted", ""),
    (262_144, 128, 32_768, "float32", "auto", True, "xla_sorted", ""),  # 134 MB
    (262_144, 300, 8_192, "float32", "auto", True, "xla_sorted", ""),   # 403 MB
    (262_144, 128, 49_182, "float32", "auto", True, "xla", "shape"),  # 5 rows/id
    (131_072, 300, 32_768, "float32", "auto", True, "xla", "shape"),  # 4 rows/id
    (*_W2V, "bfloat16", "auto", True, "xla_sorted", ""),                # 856 MB
    (*_W2V, "float32", "auto", False, "xla", "shape"),   # never unasked
    (*_W2V[:2], 1_024, "float32", "auto", True, "xla", "shape"),  # one block
    (*_W2V, "float32", "xla", True, "xla", "backend"),   # the exact baseline
    (*_W2V, "float64", "auto", True, "xla", "f64"),
    (65_536, 300, 32_768, "float32", "auto", True, "xla", "shape"),  # 100.7 MB
    (131_072, 128, 32_768, "float32", "auto", True, "xla", "shape"),  # 67 MB
    (*_NETFLIX, "float32", "auto", True, "xla_packed", ""),  # lane-packable
    (1_000_000, 32, 32_768, "float32", "auto", True, "xla", "shape"),
    (8_000_000, 1, 32_768, "float32", "auto", True, "xla", "shape"),
    # Narrow rows (PR 34): a table XLA keeps transposed, whatever the ids.
    (1_000_000, 3, 425_997, "float32", "auto", True, "xla_sorted", ""),
    (300_000, 3, 131_072, "float32", "auto", True, "xla_sorted", ""),  # 154 MB
    (1_000_000, 4, 1_703_988, "float32", "auto", True, "xla_sorted", ""),
    (262_144, 3, 131_072, "float32", "auto", True, "xla", "shape"),  # 134 MB:
    (250_000, 3, 1_703_988, "float32", "auto", True, "xla", "shape"),  # row-major
    (1_000_000, 2, 425_997, "float32", "auto", True, "xla", "shape"),  # unswept
    (1_000_000, 5, 425_997, "float32", "auto", True, "xla", "shape"),  # widths
    (1_000_000, 3, 425_997, "float32", "auto", False, "xla", "shape"),
    (1_000_000, 3, 425_997, "float64", "auto", True, "xla", "f64"),
]


@pytest.mark.parametrize("R,D,B,dtype,backend,ids_sorted,route,reason",
                         _XLA_SORTED_CASES)
def test_xla_sorted_predicate_over_shapes(as_on_tpu, R, D, B, dtype, backend,
                                          ids_sorted, route, reason):
    """The chain takes ``scatter_add.xla_sorted`` exactly when the
    guarantee, the tiled bytes and the width say so: the route log of the
    one call, entry by entry."""
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", dtype == "float64")
    prev = ops.get_backend()
    ops.set_backend(backend)
    try:
        assert ops._route_xla_sorted(R, D, B, dtype, ids_sorted) == (
            route == "xla_sorted")
        ops.clear_routes()
        out = jax.eval_shape(
            lambda t, i, d: ops.scatter_add(t, i, d, ids_sorted=ids_sorted),
            jax.ShapeDtypeStruct((R, D), dtype),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, D), dtype))
        log = ops.routes_traced()
    finally:
        ops.set_backend(prev)
        jax.config.update("jax_enable_x64", x64)
    assert (out.shape, out.dtype) == ((R, D), jnp.dtype(dtype))
    assert log == [ops.Route("scatter_add", f"scatter_add.{route}", R, D, B,
                             interpret=False, reason=reason)]


@pytest.mark.parametrize("backend", ["auto", "pallas", "xla"])
def test_xla_sorted_is_never_taken_off_the_tpu(backend):
    """Off the TPU the guarantee is accepted and the plain op runs."""
    prev = ops.get_backend()
    ops.set_backend(backend)
    try:
        assert not ops._route_xla_sorted(*_W2V, jnp.float32, True)
        ops.clear_routes()
        jax.eval_shape(
            lambda t, i, d: ops.scatter_add(t, i, d, ids_sorted=True),
            jax.ShapeDtypeStruct(_W2V[:2], jnp.float32),
            jax.ShapeDtypeStruct(_W2V[2:], jnp.int32),
            jax.ShapeDtypeStruct((_W2V[2], _W2V[1]), jnp.float32))
        assert [r.route for r in ops.routes_traced()] == ["scatter_add.xla"]
    finally:
        ops.set_backend(prev)


def test_xla_sorted_is_an_xla_route_with_a_name_of_its_own():
    assert ops.ROUTES["scatter_add"][-2:] == ("xla_sorted", "xla")
    assert "xla_sorted" not in ops.ROUTES["gather"]
    assert "scatter_add.xla_sorted" not in ops.PALLAS_ROUTES


def _sorted_case(case, R=700, D=40, B=512):
    """Sorted ids as ``push.mean_rows`` makes them, and their rows."""
    rng = np.random.default_rng(len(case))
    deltas = rng.normal(0, 1, (B, D)).astype(np.float32)
    if case == "runs":            # runs of duplicates, hot ids
        ids = np.sort(rng.integers(0, R, 40)[rng.integers(0, 40, B)])
    elif case == "zero_rows":     # a run's later entries carry exact zeros
        ids = np.sort(rng.integers(0, R, 40)[rng.integers(0, 40, B)])
        deltas[np.concatenate([[False], ids[1:] == ids[:-1]])] = 0.0
    elif case == "sentinel_last":  # the dropped, ``R`` and past it, last
        ids = np.sort(np.concatenate([rng.integers(0, R, B - 100),
                                      np.full(60, R), np.full(40, R + 9)]))
    elif case == "all_dropped":
        ids = np.full(B, R)
    else:                         # distinct ids, first and last row
        ids = np.sort(rng.choice(R, B, replace=False))
        ids[0], ids[-1] = 0, R - 1
    table = rng.normal(0, 1, (R, D)).astype(np.float32)
    return jnp.asarray(table), jnp.asarray(ids, jnp.int32), jnp.asarray(deltas)


@pytest.mark.parametrize("block", [64, 100, 511])
@pytest.mark.parametrize("case", ["runs", "zero_rows", "sentinel_last",
                                  "all_dropped", "distinct"])
def test_xla_sorted_scatter_add_equals_plain_bit_for_bit(monkeypatch, case,
                                                         block):
    """The sorted route's answer (its predicate answering yes at a test's
    size, blocks that divide the 512 ids, that leave a ragged last block
    and that leave one id for it) is the plain route's on ids that keep
    the promise, f32 bit for bit, and the oracle's."""
    table, ids, deltas = _sorted_case(case)
    ops.clear_routes()
    want = jax.jit(lambda t, i, d: ops.scatter_add(t, i, d))(
        table, ids, deltas)
    monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", block)
    monkeypatch.setattr(ops, "_route_xla_sorted",
                        lambda R, D, B, dtype, ids_sorted: ids_sorted)
    got = jax.jit(lambda t, i, d: ops.scatter_add(t, i, d, ids_sorted=True))(
        table, ids, deltas)
    assert [r.route for r in ops.routes_traced()] == [
        "scatter_add.xla", "scatter_add.xla_sorted"]
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_allclose(
        got, _numpy_scatter_add(np.asarray(table), np.asarray(ids),
                                np.asarray(deltas)), rtol=1e-5, atol=1e-5)
    if case == "all_dropped":
        np.testing.assert_array_equal(_bits(got), _bits(table))


def test_xla_sorted_scatter_add_never_reads_past_the_live_ids(monkeypatch):
    """What the route is for: one scatter, inside a loop of
    ``ceil(live / block)`` trips; the blocks past the last live id are
    not run."""
    table, ids, deltas = _sorted_case("sentinel_last")   # 412 live of 512
    monkeypatch.setattr(ops, "XLA_SORTED_BLOCK_IDS", 64)
    text = jax.jit(ops._xla_sorted_scatter_add).lower(
        table, ids, deltas).as_text()
    assert "stablehlo.while" in text
    assert text.count('"stablehlo.scatter"(') == 1  # one scatter, in the loop
    trips = []
    plain = jax.lax.fori_loop

    def counting(lo, hi, body, init):
        trips.append(hi)
        return plain(lo, hi, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", counting)
    ops._xla_sorted_scatter_add(table, ids, deltas)
    assert [int(t) for t in trips] == [-(-412 // 64)]


@pytest.mark.parametrize("R,D,want", [
    (17_770, 10, 17_770 * 512),          # one 128-lane tile: as before
    (480_189, 10, 480_189 * 512),
    (40_064, 120, 40_064 * 512),         # Netflix's packed form
    (47_236, 128, 47_236 * 512),
    (47_236, 129, 47_236 * 1024),        # a second tile for one float more
    (1_115_011, 300, 1_115_011 * 1536),  # w2v-1bw: three tiles a row
    (1_115_011, 301, 1_115_011 * 1536),
])
def test_tiled_table_bytes_counts_a_rows_real_lane_tiles(R, D, want):
    assert ops._tiled_table_bytes(R, D, jnp.float32) == want
    assert ops._tiled_table_bytes(R, D, jnp.bfloat16) == want // 2


# Every row op of every cell of the benchmark: (rows, dim, ids) -> the
# route "auto" takes on the TPU and why it passed the others over. The
# first nine are PERF.md's route logs as PR 25 and PR 26 read them on the
# chip: counting a row's real lane tiles changes none of them.
_CELL_ROW_OPS = [
    ("gather", 17_770, 10, 32_768, "gather.xla", "vmem_fit"),
    ("gather", 480_189, 10, 32_768, "gather.xla_packed", ""),
    ("scatter_add", 480_189, 10, 32_768, "scatter_add.xla_packed", ""),
    ("scatter_add", 17_770, 11, 32_768, "scatter_add.xla", "vmem_fit"),
    ("gather", 17_772, 10, 32_768, "gather.xla", "vmem_fit"),
    ("gather", 120_048, 10, 32_768, "gather.xla", "vmem_fit"),
    ("scatter_add", 120_048, 10, 32_768, "scatter_add.xla", "vmem_fit"),
    ("scatter_add", 4_443, 11, 131_072, "scatter_add.xla", "vmem_fit"),
    ("gather", 47_236, 1, 786_432, "gather.dim1", ""),
    ("gather", 1_115_011, 300, 8_197, "gather.xla", "shape"),
    ("gather", 1_115_011, 300, 49_182, "gather.xla", "shape"),
    ("scatter_add", 1_115_011, 301, 8_197, "scatter_add.xla", "shape"),
    ("scatter_add", 1_115_011, 301, 49_182, "scatter_add.xla", "shape"),
    # w2v-1bw's two pushes since PR 30: ``push.mean_rows`` hands its ids
    # over sorted (the op is "scatter_add" under ``ids_sorted=True``).
    ("scatter_add_sorted", 1_115_011, 300, 8_197,
     "scatter_add.xla_sorted", ""),
    ("scatter_add_sorted", 1_115_011, 300, 49_182,
     "scatter_add.xla_sorted", ""),
    # kge-wikidata5m.epochs (PR 51): the pull of 4 KB rows, and the table's
    # own fold on its sparse body: the state's gather, the table's
    # scatter-add and the state's scatter, ids sorted; the 822-row relation
    # table keeps the accumulator.
    ("gather", 393_216, 1000, 49_152, "gather.xla", "shape"),
    ("gather", 822, 1000, 4_096, "gather.xla", "shape"),
    ("scatter_add_sorted", 393_216, 1000, 49_152,
     "scatter_add.xla_sorted", ""),
    ("scatter_set_sorted", 393_216, 1000, 49_152,
     "scatter_set.xla_sorted", ""),
    ("scatter_add", 822, 1001, 4_096, "scatter_add.xla", "shape"),
]

# ``scatter_set`` shares the sorted route's predicate; everywhere else the
# plain XLA scatter: (rows, width, ids, ids_sorted) -> route.
_SCATTER_SET_CASES = [
    (393_216, 1000, 49_152, True, "xla_sorted"),
    (393_216, 1000, 49_152, False, "xla"),       # no guarantee given
    (393_216, 1000, 65_536, True, "xla"),        # under 8 rows an id
    (16_384, 1000, 2_048, True, "xla"),          # inside XLA's VMEM regime
]


@pytest.mark.parametrize("R,D,B,ids_sorted,route", _SCATTER_SET_CASES)
def test_scatter_set_takes_the_sorted_route_by_its_predicate(
        as_on_tpu, R, D, B, ids_sorted, route):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    ops.clear_routes()
    jax.eval_shape(lambda t, i, d: ops.scatter_set(
        t, i, d, ids_sorted=ids_sorted), f32(R, D),
        jax.ShapeDtypeStruct((B,), jnp.int32), f32(B, D))
    assert [r.route for r in ops.routes_traced()] == ["scatter_set." + route]


@pytest.mark.parametrize("op,R,D,B,route,reason", _CELL_ROW_OPS)
def test_route_of_every_row_op_of_the_benchmarks_cells(as_on_tpu, op, R, D,
                                                       B, route, reason):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    ids = jax.ShapeDtypeStruct((B,), jnp.int32)
    ops.clear_routes()
    if op == "gather":
        jax.eval_shape(lambda t, i: ops.gather_rows(t, i), f32(R, D), ids)
    else:
        scatter = (ops.scatter_set if op.startswith("scatter_set")
                   else ops.scatter_add)
        jax.eval_shape(lambda t, i, d: scatter(
            t, i, d, ids_sorted=op.endswith("_sorted")), f32(R, D), ids,
            f32(B, D))
    assert ops.routes_traced() == [
        ops.Route(op.removesuffix("_sorted"), route, R, D, B,
                  interpret=False, reason=reason)]


def test_every_declared_route_is_a_cells_or_a_swept_predicates():
    """``ROUTES`` holds nothing but what a cell of the benchmark runs or a
    predicate table above sweeps on both sides: a route that only a
    user-set option reaches has neither."""
    swept = {"dim1": {taken for *_, taken in _DIM1_CASES},
             "dim1_head": {taken for *_, taken in _HEAD_PREFIX_CASES},
             "xla_packed": {taken for *_, taken, _ in _XLA_PACKED_CASES},
             "xla_sorted": {route == "xla_sorted"
                            for *_, route, _ in _XLA_SORTED_CASES},
             "xla": {route == "xla" for *_, route in _SCATTER_SET_CASES}}
    assert all(sides == {True, False} for sides in swept.values()), swept
    for op, declared in ops.ROUTES.items():
        of_cells = {route.split(".", 1)[1] for o, *_, route, _
                    in _CELL_ROW_OPS if o.removesuffix("_sorted") == op}
        assert of_cells, op
        assert set(declared) <= of_cells | set(swept), (
            op, sorted(set(declared) - of_cells - set(swept)))
