"""Compile-only guards of the layouts the ops routing stands on (v5e).

The TPU's compiler is installed wherever the suite runs and compiles for a
chip that is DESCRIBED, not attached: nothing runs, no time is taken. What
is read is the compiled program's text — which memory space XLA puts a
table in (``S(1)`` is VMEM) and whether it sorts a scatter's indices —
the facts ``fps_tpu.ops.XLA_VMEM_TABLE_BYTES`` and the lane-packed XLA
route were set from. A later compiler that moves the edge fails here, at
no chip time. The topology is described inside a fixture of THIS file
(one process may load the TPU's library; see the on-chip-measurement
guide), and every test of it lives here.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import fps_tpu.ops as ops

NETFLIX = (480_189, 10, 32_768)  # user block rows, rank, ratings a step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu out
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _row_op_outputs(text, prim):
    """Result types of the compiled row ops traced from ``prim``
    (``gather`` / ``scatter-add``): XLA's custom fusions."""
    return [m.group(1) for line in text.splitlines()
            if f'/{prim}"' in line and "kind=kCustom" in line
            and (m := re.search(r"= (\S+) fusion\(", line))]


def _plain_scatter(R, D, B, one_chip):
    c = _compiled(lambda t, i, d: t.at[i].add(d, mode="drop"), one_chip,
                  ((R, D), jnp.float32), ((B,), jnp.int32),
                  ((B, D), jnp.float32))
    return c.as_text()


def _top_level(text):
    """The instructions of a compiled module outside its entry computation
    and its fused computations' bodies."""
    for comp in text.split("\n}\n"):
        head, _, body = comp.strip().partition("\n")
        if not head.startswith(("ENTRY", "%fused_computation", "HloModule")):
            yield from body.splitlines()


def test_sorted_route_scatters_by_blocks_in_place(one_chip, monkeypatch):
    """``w2v-1bw``'s out-table push, ``[1115011, 300]`` x 49,182 ids in a
    loop whose carry is the table, by the sorted route: an inner loop of
    dynamic trip count round ONE plain scatter fusion of a block's ids
    (417,792 B of scoped VMEM, as the plain route's over all the ids), in
    place on the carry. XLA's own emitter for a scatter it is TOLD is
    sorted is not what the route uses: at this shape it takes 14.8 MB of
    scoped VMEM and walks the whole table (7.2 ms whatever the ids; chip
    run, PR 30)."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    R, D, B = 1_115_011, 300, 49_182

    def steps(t, ids, deltas):
        return lax.scan(lambda t, x: (ops.scatter_add(
            t, *x, ids_sorted=True), None), t, (ids, deltas))[0]

    ops.clear_routes()
    c = _compiled(steps, one_chip, ((R, D), jnp.float32),
                  ((2, B), jnp.int32), ((2, B, D), jnp.float32))
    assert [r.route for r in ops.routes_traced()] == [
        "scatter_add.xla_sorted"]
    text = c.as_text()
    assert "indices_are_sorted=true" not in text
    sized = [ln for ln in _top_level(text)
             if (m := re.search(rf"= f32\[{R},{D}\]\S* ([\w\-]+)\(", ln))
             and m.group(1) not in ("get-tuple-element", "parameter")
             and "/while/body/" in ln]
    (fusion,) = sized
    assert "/fps.ops/scatter_add.xla_sorted/while/body/" in fusion
    assert "kind=kCustom" in fusion and '"size":"417792"' in fusion
    assert len(re.findall(r" while\(", text)) == 2  # the scan, the blocks
    assert c.memory_analysis().temp_size_in_bytes < 2 << 30  # one table


@pytest.mark.parametrize("R", [120_048, 200_000])
def test_plain_scatter_runs_in_vmem_up_to_the_edge(one_chip, R):
    """x4's user block (61.5 MB row-major tiled: passed over, ``vmem_fit``)
    and 200,000 rows x 512 B (102.4 MB: the largest measured table XLA
    still copies into VMEM, where ``XLA_VMEM_TABLE_BYTES`` already hands
    over to the packed form) ride VMEM with sorted indices."""
    D, B = NETFLIX[1:]
    fits = (ops._tiled_table_bytes(R, D, jnp.float32)
            <= ops.XLA_VMEM_TABLE_BYTES)
    assert fits == (R == 120_048)
    text = _plain_scatter(R, D, B, one_chip)
    assert f"f32[{R},{D}]{{1,0:T(8,128)S(1)}}" in _row_op_outputs(
        text, "scatter-add")
    assert "indices_are_sorted=true" in text


@pytest.mark.parametrize("R", [240_095, NETFLIX[0]])
def test_plain_scatter_is_out_of_vmem_past_the_edge(one_chip, R):
    """The upper side: 240,095 rows (122.9 MB) and Netflix's user block
    are scattered into in HBM — the regime the packed route leaves."""
    D, B = NETFLIX[1:]
    assert (ops._tiled_table_bytes(R, D, jnp.float32)
            > ops.XLA_VMEM_TABLE_BYTES)
    outs = _row_op_outputs(_plain_scatter(R, D, B, one_chip), "scatter-add")
    assert outs and not any("S(1)" in o for o in outs), outs


def test_packed_route_scatters_netflix_block_in_vmem_sorted(one_chip):
    """The route's own scatter at Netflix's shape: operand
    ``f32[40064,120]`` row-major in memory space 1, indices sorted; so is
    its gather's table."""
    R, D, B = NETFLIX
    Rp, lanes = ops._xla_packed_rows(R, D), 128 // D * D
    assert (Rp, lanes) == (40_064, 120)
    assert (ops._tiled_table_bytes(Rp, lanes, jnp.float32)
            <= ops.XLA_PACKED_TABLE_BYTES)
    text = _compiled(ops._xla_packed_scatter_add, one_chip,
                     ((R, D), jnp.float32), ((B,), jnp.int32),
                     ((B, D), jnp.float32)).as_text()
    assert f"f32[{Rp},{lanes}]{{1,0:T(8,128)S(1)}}" in _row_op_outputs(
        text, "scatter-add")
    assert "indices_are_sorted=true" in text
    text = _compiled(ops._xla_packed_gather, one_chip,
                     ((R, D), jnp.float32), ((B,), jnp.int32)).as_text()
    assert re.search(
        rf"f32\[{Rp},{lanes}\]\{{1,0:T\(8,128\)S\(1\)\}}", text)


def test_packed_route_keeps_the_loop_carry_compact(one_chip):
    """A worker step's gather then scatter-add in a loop whose carry is
    the PLAIN ``[R, D]`` table: XLA keeps the carry transposed (30.7 MB,
    not the 246 MB row-major form), relayouts in VMEM, and needs no
    table-sized temporary. Packing consecutive rows fails exactly this."""
    R, D, B = NETFLIX

    def steps(t, ids, deltas):
        def body(t, x):
            i, d = x
            return ops._xla_packed_scatter_add(
                t, i, d + ops._xla_packed_gather(t, i)), None
        return lax.scan(body, t, (ids, deltas))[0]

    c = _compiled(steps, one_chip, ((R, D), jnp.float32),
                  ((4, B), jnp.int32), ((4, B, D), jnp.float32))
    (loop,) = [ln for ln in c.as_text().splitlines() if " while(" in ln]
    assert re.search(rf"f32\[{R},{D}\]\{{0,1:T\(8,128\)", loop), loop
    assert c.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.fixture(scope="module")
def x4_step(topo):
    """``mf-netflix.x4``'s step program (the trainer's own chunk builder
    on the described 1x4 mesh) compiled, and its route log."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh

    W, B, T, rank = 4, NETFLIX[2], 2, NETFLIX[1]
    mesh = make_ps_mesh(num_shards=W, devices=list(topo.devices)[:W])
    trainer, _ = online_mf(
        mesh, MFConfig(num_users=NETFLIX[0], num_items=17_770, rank=rank),
        combine="mean")

    def shape(s, dtype, spec):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    workers = P(None, ("data", "shard"))
    tables = {"item_factors": shape((-(-17_770 // W) * W, rank),
                                    jnp.float32, P("shard", None))}
    local = shape((-(-NETFLIX[0] // W) * W, rank), jnp.float32,
                  P(("data", "shard")))
    batches = {k: shape((T, W * B), d, workers) for k, d in (
        ("user", jnp.int32), ("item", jnp.int32), ("rating", jnp.float32),
        ("weight", jnp.float32))}
    key = shape((), jax.random.key(0).dtype, P())
    ops.clear_routes()
    text = trainer._build_chunk_fn("sync").lower(
        tables, local, batches, key).compile().as_text()
    return text, ops.routes_traced()


def test_x4_push_exchanges_its_accumulator_compact(x4_step):
    """The layouts ``mf-netflix.x4``'s rate stands on, which no CPU test
    can see. Since PR 36 the push's ``(rows, 11)`` accumulator is filled by
    the dense exchange: the ``all_to_all``'s operand, four windows of
    ``[4443, 11]``, is the compact transposed form in VMEM (1.15 MB;
    row-major tiles would pad 11 lanes to 128: 9.1 MB on the wire), and no
    collective carries a step's 131,072 pushes any more. Its siblings keep
    theirs: the worker's scatter into its ``[120048, 10]`` block and the
    push's into the ``[17772, 11]`` buffer row-major in VMEM, the former
    handed sorted ids; the pull's all-gather of the table compact. (Until
    PR 36 this pinned the gathered push's all-gather of ``[131072, 10]``
    deltas: a select on the worker's LOCAL deltas, its scatter-add's
    neighbour in one fusion, once turned that row-major in HBM and cost
    the cell a third of its rate, PR 25.)"""
    text = x4_step[0]
    W, B, rank = 4, NETFLIX[2], NETFLIX[1]
    rps = -(-17_770 // W)
    vmem = "T(8,128)S(1)}"
    exchanged = re.findall(r"= (f32\[\S+) all-to-all\(", text)
    assert exchanged == [f"f32[{W},{rps},{rank + 1}]{{1,2,0:{vmem}"], exchanged
    gathered = re.findall(r"= (f32\[\S+) all-gather\(", text)
    assert gathered == [f"f32[{W},{rps},{rank}]{{1,2,0:{vmem}"], gathered
    assert f"[{W * B}," not in text  # nobody holds every worker's pushes
    scatters = {m.group(1): m.group(2) for m in re.finditer(
        r"= (f32\[\S+) scatter\((.*)", text)}
    block = f"f32[{-(-NETFLIX[0] // W)},{rank}]{{1,0:{vmem}"
    buffer = f"f32[{W * rps},{rank + 1}]{{1,0:{vmem}"
    assert sorted(scatters) == sorted([block, buffer]), list(scatters)
    assert "indices_are_sorted=true" in scatters[block]


def test_x4_step_fills_the_accumulator_by_the_dense_exchange(x4_step):
    """``mf-netflix.x4``'s route log, the four-shard twin of the pin below:
    the movie table (782 KB: dense by ``ops.DENSE_TABLE_BYTES``) keeps the
    accumulator (``push.mean_dense`` / ``small_table``, asked about the
    32,768 ids a worker scatters into all 17,772 rows), fills it by the
    dense exchange (``push.dense_acc`` / ``mean_dense``), and the
    scatter-add under them is handed the worker's own 32,768 ids, not the
    131,072 of the step."""
    W, B, rank = 4, NETFLIX[2], NETFLIX[1]
    rows = -(-17_770 // W) * W
    routes = [r for r in x4_step[1] if r.op in ("push", "scatter_add")]
    assert routes[1:] == [
        ops.Route("push", "push.mean_dense", rows, rank, B, False,
                  "small_table"),
        ops.Route("push", "push.dense_acc", rows // W, rank, B, False,
                  "mean_dense"),
        ops.Route("scatter_add", "scatter_add.xla", rows, rank + 1, B,
                  False, routes[-1].reason),
    ], routes
    assert routes[0].route == "scatter_add.xla"  # the worker's own block


def test_w2v_epoch_program_fits_and_names_its_table_sized_work(
        topo, monkeypatch):
    """``w2v-1bw.epochs``'s epoch program at the cell's own size (two
    ``[1115011, 300]`` tables, blocks of 8,192 tokens) for one described
    chip. Both mean pushes take the row branch (``push.mean_rows``): the
    only table-sized work left in the loop body is the two scatter-adds
    into the tables themselves, in place on the carry, a block of ids at
    a time in the sorted route's own loops (PR 30); no ``[rows, 301]``
    accumulator anywhere, no copy, add or fill of a table a step. The
    counts (three sorts of the pushed ids) and the multiply keep
    ``fps.combine`` in the COMPILED text and make nothing of the table's
    length; temporaries under 4 GB of 16 (8 with the accumulators,
    PR 27). No CPU test can see a copy XLA puts round a scatter into a
    live table."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.models.word2vec import (
        W2VConfig,
        Word2VecDevicePlan,
        word2vec_block,
    )
    from fps_tpu.parallel.mesh import make_ps_mesh

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    V, D, L, T = 1_115_011, 300, 8_192, 172
    mesh = make_ps_mesh(num_shards=1, devices=list(topo.devices)[:1])
    cfg = W2VConfig(vocab_size=V, dim=D)
    trainer, _ = word2vec_block(mesh, cfg, 1.0 / (jnp.arange(V) + 1.5), L)
    # The plan's geometry without its uploads (no device holds an array).
    plan = object.__new__(Word2VecDevicePlan)
    plan.cfg, plan.mode, plan.num_workers, plan.block_len = cfg, "block", 1, L
    plan.steps_per_epoch, plan.sync_every = T, None

    def shape(s, dtype, spec=P()):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    key = shape((), jax.random.key(0).dtype)
    tables = {n: shape((V, D), jnp.float32, P("shard", None))
              for n in ("in_embeddings", "out_embeddings")}
    iargs = {"compacted": shape((T * L + cfg.window,), jnp.int32),
             "kept": shape((), jnp.int32), "wkey": key}
    ops.clear_routes()
    compiled = trainer._build_indexed_fn(plan, "sync").lower(
        tables, (), iargs, jnp.int32(0), key).compile()
    assert [(r.route, r.dim, r.reason) for r in ops.routes_traced()] == [
        ("gather.xla", 300, "shape"), ("gather.xla", 300, "shape"),
        ("push.mean_rows", 300, ""), ("scatter_add.xla_sorted", 300, ""),
        ("push.mean_rows", 300, ""), ("scatter_add.xla_sorted", 300, "")]
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
    text = compiled.as_text()
    assert f"f32[{V},{D + 1}]" not in text
    # Every computation but the entry (the epoch's loop body, and the
    # bodies of the sorted route's own loops inside it).
    sized = [ln for ln in _top_level(text)
             if (m := re.search(rf"= f32\[{V},{D}\]\S* ([\w\-]+)\(", ln))
             and m.group(1) not in ("get-tuple-element", "parameter")]
    assert len(sized) == 2 and all(
        "/fps.ops/scatter_add.xla_sorted/while/body/" in ln
        and "/scatter-add" in ln and " fusion(" in ln for ln in sized), sized
    combine = [ln for ln in text.splitlines()
               if "fps.push/fps.combine/" in ln]
    assert any("/sort" in ln for ln in combine), combine
    assert any("/mul" in ln for ln in combine), combine
    assert not [ln for ln in combine if f"[{V}" in ln.split(" fusion(")[0]]


def _loop_bodies(text):
    """``{computation name: its instructions}`` for the compiled module's
    computations that are neither fused bodies nor reducers: the entry and
    the bodies and conditions of its loops."""
    out = {}
    for comp in text.split("\n\n"):
        head, _, body = comp.strip().partition("\n")
        if head.startswith(("%fused_computation", "HloModule")):
            continue
        out[head.split(" ")[0]] = [ln for ln in body.splitlines()
                                   if " = " in ln]
    return out


def test_w2v_hot_epoch_program_reconciles_once_a_window_on_four_chips(
        topo, monkeypatch):
    """``w2v-1bw-hot.x4``'s epoch program at the cell's own size for a
    described four-chip v5e (two ``[1115012, 300]`` tables over four
    shards, a head of 32,768 rows of each replicated, windows of 8 steps,
    168 steps a call). Read from the COMPILED text. The call is a loop of
    21 windows round a loop of 8 steps; the step's body holds the replica
    reads (``pull.hot``, a plain gather on ``[32768, 300]``), the cold
    exchange at payload size (the ids of four workers, 32,788 and 196,728
    rows of 300: no collective of a table's length) and the pending
    scatter (``push.hot``, ``[32768, 301]``) INSIDE ``fps.push``; the
    window's body, under ``hot.reconcile`` and outside every ``fps.``
    scope but the routed gather's, reduces the two pending buffers
    ``[32768, 301]`` once and all-gathers the combined step ``[32768,
    300]`` once a table. What ``store._reconcile_combine`` asks for is a
    reduce-scatter and an all-gather (the CPU lowering holds that:
    ``tests/test_hot_tier.py``); the TPU's compiler makes the
    reduce-scatter an all-reduce of the whole buffer (as it does with the
    pull's ``psum_scatter``: ``[199424, 300]`` a step), and hoists the
    step's metric sums out of the step loop into the same all-reduce: the
    test takes either reduction. Since PR 45 each of the step's four
    exchanges is a conditional on its lanes' certificate: the ROUTED
    branch holds ``all-to-all`` s alone (ids ``[4, 1, L]``, rows ``[4, L,
    300]``, ``L`` 2,568 and 15,376) and nothing of the gathered
    exchange's ``S x B`` length, the gathered branch the all-gathers and
    the pull's all-reduce as before; the shard itself enters no
    conditional of a push (the accumulator ``[278753, 301]`` or the row
    branch's sorted ids leave it). The ops named under ``fps.*`` outside
    those branches are in the step's body but for the window's handful,
    so a reader that counts steps by a median over those names reads
    steps (a ragged tail, 172 steps, compiles a SECOND step body of as
    many: PERF.md, section 7). Fits in 3 GB a chip before the runner's
    copies."""
    import argparse

    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.core.store import _lane_width
    from fps_tpu.examples.common import apply_hot_tier
    from fps_tpu.models.word2vec import (
        W2VConfig,
        Word2VecDevicePlan,
        word2vec_block,
    )
    from fps_tpu.parallel.mesh import make_ps_mesh

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    V, D, L, T, W, H, E = 1_115_011, 300, 8_192, 168, 4, 32_768, 8
    mesh = make_ps_mesh(num_shards=W, devices=list(topo.devices)[:W])
    cfg = W2VConfig(vocab_size=V, dim=D)
    trainer, store = word2vec_block(mesh, cfg, 1.0 / (jnp.arange(V) + 1.5),
                                    L)
    apply_hot_tier(argparse.Namespace(hot_tier=H, hot_sync_every=E),
                   trainer, store)
    plan = object.__new__(Word2VecDevicePlan)
    plan.cfg, plan.mode, plan.num_workers, plan.block_len = cfg, "block", W, L
    plan.steps_per_epoch, plan.sync_every = T, None

    def shape(s, dtype, spec=P()):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    key = shape((), jax.random.key(0).dtype)
    rows = -(-V // W) * W
    tables = {}
    for n in ("in_embeddings", "out_embeddings"):
        tables[n] = shape((rows, D), jnp.float32, P("shard", None))
        tables[n + "::hot"] = shape((H, D), jnp.float32)
    iargs = {"compacted": shape((T * L * W + cfg.window,), jnp.int32),
             "kept": shape((), jnp.int32), "wkey": key}
    ops.clear_routes()
    compiled = trainer._build_indexed_fn(plan, "sync").lower(
        tables, (), iargs, jnp.int32(0), key).compile()
    rps, ids_in, ids_out = rows // W, W * (L + 5), W * 6 * (L + 5)
    # Since PR 45 the cold rows go to their owners in lanes (15,376 and
    # 2,568 wide, four a worker), and the gathered exchange is the other
    # branch of each exchange's certificate: a mean push is logged once on
    # the lanes' 10,272 / 61,504 handed rows and once on the gathered
    # 32,788 / 196,728, by the branch the lanes' rows choose.
    lanes_in, lanes_out = (W * _lane_width(n, W)
                           for n in (L + 5, 6 * (L + 5)))
    assert (lanes_in, lanes_out) == (10_272, 61_504)
    assert [(r.route, r.rows, r.dim, r.ids) for r in ops.routes_traced()
            if not r.route.startswith(("gather.", "scatter_add."))] == [
        ("pull.hot", H, D, L + 5), ("pull.routed", rps, D, L + 5),
        ("pull.hot", H, D, 6 * (L + 5)),
        ("pull.routed", rps, D, 6 * (L + 5)),
        ("push.hot", H, D + 1, L + 5), ("push.hot", H, D + 1, 6 * (L + 5)),
        ("push.routed", rps, D, L + 5),
        ("push.mean_rows", rps, D, lanes_in),
        ("push.mean_rows", rps, D, ids_in),
        ("push.routed", rps, D, 6 * (L + 5)),
        ("push.mean_dense", rps, D, lanes_out),
        ("push.mean_dense", rps, D, ids_out),
        ("reconcile.hot", H, D + 1, 0), ("reconcile.hot", H, D + 1, 0)]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 3 << 30
    text = compiled.as_text()
    def collectives(lines, kinds=("all-gather", "all-reduce",
                                  "reduce-scatter", "all-to-all")):
        """``(kind, result type)`` of each collective among ``lines`` (a
        combined one's type is a tuple)."""
        pat = re.compile(r" = (.*?) (%s)(?:-start)?\(" % "|".join(kinds))
        return [(m.group(2), m.group(1)) for ln in lines
                if (m := pat.search(ln))]

    trivial = re.compile(
        r"= \S+ (get-tuple-element|parameter|tuple|constant|bitcast)\(")
    comps = _loop_bodies(text)
    # The four exchanges' certificates: a conditional each, (gathered,
    # routed). Their branches run inside the step, not beside it.
    branches = [m.group(1).split(", ") for m in re.finditer(
        r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]
    assert len(branches) == 4 and all(len(b) == 2 for b in branches)
    for gathered, routed in branches:
        kinds = {k for k, _ in collectives(comps[routed])}
        assert kinds == {"all-to-all"}, (routed, kinds)
        assert "all-to-all" not in {
            k for k, _ in collectives(comps[gathered])}
        # A shard is handed its own rows only: lanes, never S x B of them
        # (the row branch's sorted ids and sums are LENGTHENED to that by
        # a pad of dropped ids as the branch ends: no op works on it).
        assert not [ln for ln in comps[routed]
                    if (f"[{ids_in}," in ln or f"[{ids_out}," in ln
                        or "[199424," in ln)
                    and not re.search(r" (pad|tuple)\(", ln)]
        assert [ln for ln in comps[routed]
                if f"[{lanes_in}," in ln or f"[{lanes_out}," in ln]
    bodies = sorted(
        ((sum("/fps." in ln and not trivial.search(ln) for ln in lines),
          lines) for name, lines in comps.items()
         if name not in sum(branches, [])),
        key=lambda b: -b[0])
    in_step_named, inner = bodies[0]
    # One step body and no tail's: nothing else holds a thirtieth as many
    # ops named under fps.* (the window's body, the loops of a route).
    assert all(30 * n < in_step_named for n, _ in bodies[1:]), [
        n for n, _ in bodies]
    (outer,) = [lines for _, lines in bodies
                if any("/hot.reconcile/all_gather" in ln for ln in lines)]
    assert outer is not inner
    # No collective anywhere moves a table's length.
    for _, shape in collectives(text.splitlines()):
        assert f"[{rps}," not in shape and f"[{rows}," not in shape, shape
    # The step: no reduction or gather of the head's shapes; the pending
    # scatter inside the push.
    in_step = collectives(inner)
    assert in_step and not [t for _, t in in_step if f"[{H},{D}" in t]
    assert not [ln for ln in text.splitlines() if "fps.hot_accumulate" in ln
                and "fps.push/fps.hot_accumulate/" not in ln]
    assert any("fps.push/fps.hot_accumulate/fps.ops/scatter_add.xla/" in ln
               and f"f32[{H},{D + 1}]" in ln for ln in inner)
    # The window: each pending buffer reduced once, each combined step
    # gathered once, under hot.reconcile (a combined all-reduce is named
    # after one of its parts).
    reduced = collectives(outer, ("all-reduce", "reduce-scatter"))
    assert sum(t.count(f"f32[{H},{D + 1}]") + t.count(f"f32[{H // W},{D + 1}]")
               for _, t in reduced) == 2, reduced
    gathered = [ln for ln in outer if collectives([ln], ("all-gather",))]
    assert len(gathered) == 2 and all(
        f" = f32[{H},{D}]" in ln and "/hot.reconcile/all_gather" in ln
        for ln in gathered), [ln[:200] for ln in gathered]
    assert not [ln for ln in text.splitlines() if "fps.reconcile" in ln]
    scoped = [ln for ln in outer if "/hot.reconcile/" in ln and "/fps." in ln]
    assert scoped and all("/hot.reconcile/fps.ops/gather.xla/" in ln
                          for ln in scoped), scoped


def _reachable(comps, name):
    """The instructions of computation ``name`` and of every computation
    it calls (loop bodies, fused bodies and reducers left out of
    ``comps`` are not followed)."""
    seen, todo, lines = set(), [name], []
    while todo:
        cur = todo.pop()
        if cur in seen or cur not in comps:
            continue
        seen.add(cur)
        lines += comps[cur]
        for ln in comps[cur]:
            todo += re.findall(r"(?:body|condition|to_apply|calls)=(%[\w.]+)",
                               ln)
    return lines


@pytest.mark.parametrize("side", ["pull", "push"])
def test_routed_exchange_moves_no_row_that_is_nobodys(topo, monkeypatch,
                                                      side):
    """``store.pull`` / ``store.push`` (the per-id mean, on the
    accumulator branch as the cell's out table takes it) at
    ``w2v-1bw-hot.x4``'s larger shape, a ``[278753, 300]`` shard on each
    of four described chips and 49,182 ids a worker, read from the
    COMPILED text: one conditional on the lanes' certificate whose routed
    branch trades lanes by ``all-to-all`` (the ids ``[4, 1, 15376]`` out,
    the rows ``[4, 15376, 300]`` out for a push, back for a pull) and holds no
    all-gather, no all-reduce and no reduce-scatter (no ``psum``: each row
    comes from one shard), nor anything of the gathered exchange's 196,728
    handed rows; the gathered branch keeps the all-gather (and the pull's
    reduce, which the TPU's compiler makes an all-reduce of ``[199424,
    300]``); a push's conditional returns the accumulator, so the shard
    itself is not copied through it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.core import store
    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    W, rps, D, B = 4, 278_753, 300, 49_182
    L = store._lane_width(B, W)
    assert L == 15_376
    mesh = make_ps_mesh(num_shards=W, devices=list(topo.devices)[:W])

    def shape(s, dtype, spec):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def fn(t, i, d):
        if side == "pull":
            return store.pull(t, i, num_shards=W)
        return store.push(t, i, d, num_shards=W, data_axis=None,
                          combine="mean")

    rows = P(SHARD_AXIS, None)
    ops.clear_routes()
    text = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(rows, P(SHARD_AXIS), rows),
        out_specs=rows, check_vma=False)).lower(
            shape((rps * W, D), jnp.float32, rows),
            shape((W * B,), jnp.int32, P(SHARD_AXIS)),
            shape((W * B, D), jnp.float32, rows)).compile().as_text()
    assert [(r.route, r.rows, r.dim, r.ids, r.reason)
            for r in ops.routes_traced() if r.route.endswith(".routed")] == [
        (f"{side}.routed", rps, D, B, f"lanes={W}x{L}")]
    comps = _loop_bodies(text)
    ((gathered, routed),) = [m.group(1).split(", ") for m in re.finditer(
        r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]
    pat = re.compile(r" = (.*?) (all-gather|all-reduce|reduce-scatter|"
                     r"all-to-all|collective-permute)(?:-start)?\(")
    moved = {side_: [(m.group(2), m.group(1)) for ln in _reachable(comps, c)
                     if (m := pat.search(ln))]
             for side_, c in (("routed", routed), ("gathered", gathered))}
    assert {k for k, _ in moved["routed"]} == {"all-to-all"}, moved
    assert sorted(t.split("{")[0] for _, t in moved["routed"]) == sorted(
        [f"s32[{W},1,{L}]", f"f32[{W},{L},{D}]"]), moved["routed"]
    assert "all-to-all" not in {k for k, _ in moved["gathered"]}
    assert {k for k, _ in moved["gathered"]} >= (
        {"all-reduce"} if side == "pull" else {"all-gather"})
    handed = [ln for ln in _reachable(comps, routed)
              if f"[{W * B}," in ln or f"[{W},{B}," in ln
              or "[199424," in ln]
    assert not handed, handed[:3]
    (cond,) = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert re.search(r"= \(?f32\[%d,%d\]" % (
        (B, D) if side == "pull" else (rps, D + 1)), cond), cond[:200]
    if side == "push":
        assert not [ln for c in (gathered, routed) for ln in comps[c]
                    if " parameter(" in ln and f"f32[{rps},{D}]" in ln]


def test_mf_epoch_step_keeps_the_accumulator_for_its_small_table(topo):
    """The MF twin: ``mf-netflix.epochs``'s step pushes 32,768 ratings'
    deltas into ``[17770, 10]`` (9.1 MB of accumulator under 16.8 MB of
    payload): ``push.mean_dense`` / ``small_table``, the count riding the
    one scatter as an eleventh column, as before PR 28."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.models.matrix_factorization import MFConfig, online_mf
    from fps_tpu.parallel.mesh import make_ps_mesh

    B, rank = NETFLIX[2], NETFLIX[1]
    mesh = make_ps_mesh(num_shards=1, devices=list(topo.devices)[:1])
    trainer, _ = online_mf(
        mesh, MFConfig(num_users=NETFLIX[0], num_items=17_770, rank=rank),
        combine="mean")

    def shape(s, dtype, spec=P()):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    workers = P(None, ("data", "shard"))
    tables = {"item_factors": shape((17_770, rank), jnp.float32,
                                    P("shard", None))}
    local = shape((NETFLIX[0], rank), jnp.float32, P(("data", "shard")))
    batches = {k: shape((2, B), d, workers) for k, d in (
        ("user", jnp.int32), ("item", jnp.int32), ("rating", jnp.float32),
        ("weight", jnp.float32))}
    ops.clear_routes()
    trainer._build_chunk_fn("sync").lower(
        tables, local, batches, shape((), jax.random.key(0).dtype))
    pushes = [r for r in ops.routes_traced() if r.op == "push"]
    assert pushes == [ops.Route("push", "push.mean_dense", 17_770, rank, B,
                                False, "small_table")], pushes  # no acc_runs


@pytest.mark.parametrize("shards", [1, 4])
def test_lr_criteo_epoch_program_names_its_round_and_fits(topo, monkeypatch,
                                                          shards):
    """``lr-criteo.epochs``'s epoch program at the cell's own size (a
    ``[1000000, 2]`` table under rounds of 8 steps, 16,384 rows x 39 slots
    a worker a step, 2^23 resident rows) for one described chip, and the
    same job over four. Read from the COMPILED text, since a scope round
    an op XLA drops names nothing. On FOUR shards the round's snapshot is
    an all-gather of the ``[250000, 2]`` shards under ``ssp.snapshot``
    (and the relayout of what it gathered). On ONE the gather is gone and
    the round's copy of the live table is XLA's own, without a name: no
    device op is under ``ssp.snapshot``. On one chip also: the stateful
    fold's ``(rows, dim + 1)`` accumulator is kept transposed and in VMEM
    (16 MB, not the 512 MB of its row-major tiles), and so is the select
    that writes the table back; the step holds one gather of 425,997 rows
    and NO scatter of as many: the pushed rows are summed by id run under
    ``fps.combine`` (``push.acc_runs``: two sorts and the passes between
    them) and the accumulator's scatter runs a block of the distinct ids
    at a time (``scatter_add.xla_sorted``), its carry in VMEM across
    trips; every table-sized op of the body is the fold's, under
    ``fps.combine``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu import DeviceEpochPlan
    from fps_tpu.core.store import _lane_width
    from fps_tpu.models.logistic_regression import (
        LogRegConfig, logistic_regression,
    )
    from fps_tpu.parallel.mesh import make_ps_mesh

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    F, B, N, s, slots = 1_000_000, 16_384, 1 << 23, 8, 39
    q = N // shards
    mesh = make_ps_mesh(num_shards=shards,
                        devices=list(topo.devices)[:shards])
    trainer, _ = logistic_regression(
        mesh, LogRegConfig(num_features=F, learning_rate=0.001,
                           optimizer="adagrad", dense_features=13),
        sync_every=s)
    # The plan's geometry without its uploads (no device holds an array).
    plan = object.__new__(DeviceEpochPlan)
    plan.local_batch, plan.shuffle, plan.num_workers = B, "interleave", shards
    plan.sync_every, plan.maxq, plan.grid_r, plan.route_key = s, q, 4096, None
    plan.counts = np.full(shards, q, np.int32)
    plan.grid_c = np.full(shards, q // 4096, np.int32)
    plan.grid_m = np.full(shards, q, np.int32)
    plan.steps_per_epoch = 520 // shards // s * s
    plan.sliced = True      # these columns take the slices (PR 50)

    def shape(sh, dtype, spec=P()):
        return jax.ShapeDtypeStruct(sh, dtype,
                                    sharding=NamedSharding(mesh, spec))

    key = shape((), jax.random.key(0).dtype)
    tables = {"weights": shape((F, 2), jnp.float32, P("shard", None))}
    buf = shards * plan.steps_per_epoch * B
    iargs = {"tbuf": {"feat_ids": shape((buf, slots), jnp.int32),
                      "feat_vals": shape((buf, slots), jnp.float32),
                      "label": shape((buf,), jnp.float32)},
             "off_w": shape((shards,), jnp.int32),
             "perm": shape((1, 1), jnp.int32)}
    rows = B * (slots - 13) + 13
    lane = _lane_width(rows, shards)
    ops.clear_routes()
    compiled = trainer._build_indexed_fn(plan, "ssp").lower(
        tables, (), iargs, jnp.int32(0), key).compile()
    assert [(r.route, r.rows, r.dim, r.ids, r.reason)
            for r in ops.routes_traced()] == [
        # The plan is unkeyed and its columns 39 slots wide: a step is
        # three slices of the columns' transposed buffers (PR 50), and
        # the program has no parameter of the queue's shape.
        ("ingest.rows_sliced", N, 3, B, ""),
        ("pull.snapshot", F, 2, rows, ""),
        ("gather.xla", F, 2, rows, "shape"),
        *([("push.fold", F, 2, rows, "apply_fn"),
           ("push.acc_runs", F, 2, rows, "fold"),
           ("scatter_add.xla_sorted", F, 3, rows, "")] if shards == 1 else
          # 250,000 rows a shard: 128 MB of row-major tiles, which XLA
          # keeps row-major in HBM; the plain accumulator stays. Since
          # PR 45 the pushes go to their owners in lanes (a shard is
          # handed 532,512 where the gathered branch hands 1,703,988),
          # and the fold is logged once a branch of the certificate.
          [("push.routed", F // shards, 2, rows,
            f"table=weights lanes={shards}x{lane}")]
          + [entry for handed in (shards * lane, shards * rows)
             for entry in (
                 ("push.fold", F // shards, 2, handed, "apply_fn"),
                 ("scatter_add.xla", F // shards, 3, handed, "shape"))])]
    text = compiled.as_text()
    assert f"s32[{shards},{q}]" not in text
    snap = [ln for ln in text.splitlines() if "/ssp.snapshot/" in ln]
    assert not [ln for ln in snap if "/fps." in ln]
    if shards > 1:
        gathers = [ln for ln in snap if " all-gather(" in ln]
        assert len(gathers) == 1, snap
        assert f"f32[{shards},{F // shards},2]" in gathers[0]
        return
    assert snap == []
    # The round's copy of the live table: XLA's own, and nameless.
    copies = [ln for ln in text.splitlines()
              if re.search(rf"= f32\[{F},2\]\S* copy\(", ln)]
    assert copies and not [ln for ln in copies if "op_name" in ln], copies
    mem = compiled.memory_analysis()
    assert 2.6e9 < mem.argument_size_in_bytes < 2.9e9     # the buffers
    # The ingest: the buffers stay as they are handed over, column-major
    # like the columns (no copy of one, as a ``[N, 64]`` column gets), a
    # step's rows are dynamic slices of them, and no gather makes a batch.
    for dtype in ("s32", "f32"):
        assert f"{dtype}[{buf},{slots}]{{0,1:T(8,128)}} parameter(" in text
        assert not re.search(
            rf"= {dtype}\[{buf},{slots}\]\S* (copy|fusion)\(", text)
        assert re.search(rf"{dtype}\[{B},\d+\]\S* dynamic-slice\(", text)
    assert not re.search(rf"\[{B}(,{slots})?\]\S* gather\(", text)
    assert mem.temp_size_in_bytes < 256 << 20
    # The fold's accumulator: ONE scatter fusion, of a block of the summed
    # runs' ids inside the sorted route's own loop, transposed and in VMEM
    # (and so is the loop's carry); no scatter is handed all the pushed
    # rows any more.
    acc = [ln for ln in text.splitlines()
           if re.search(rf"= f32\[{F},3\]\S* fusion\(", ln)]
    assert len(acc) == 1 and "{0,1:T(4,128)S(1)}" in acc[0], acc
    assert ("/fps.push/fps.ops/scatter_add.xla_sorted/while/body/"
            "scatter-add" in acc[0])
    carry = [ln for ln in text.splitlines()
             if re.search(rf"= f32\[{F},3\]\S* get-tuple-element\(", ln)
             and "/scatter_add.xla_sorted/while" in ln]
    assert carry and all("{0,1:T(4,128)S(1)}" in ln for ln in carry), carry
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    assert scatters and not [ln for ln in scatters
                             if f"f32[{rows},3]" in ln], scatters
    # The sums by run: two sorts of the pushed ids carrying the rows'
    # columns and the doubling passes between them, all under fps.combine.
    sorts = [ln for ln in text.splitlines()
             if re.search(r"= \(s32\[%d\]\S*, f32\[%d\]" % (rows, rows), ln)
             and " sort(" in ln]
    assert len(sorts) == 2, sorts
    assert all("/fps.push/fps.combine/" in ln for ln in sorts), sorts
    passes = [ln for ln in _top_level(text)
              if re.search(rf"= \(?f32\[{rows}\]\S* fusion\(", ln)
              and "/fps.push/" in ln]
    assert passes and all("/fps.push/fps.combine/" in ln
                          for ln in passes), passes
    sized = [ln for ln in _top_level(text)
             if re.search(rf"= \w+\[{F},\d\]\S* fusion\(", ln)]
    assert sized and all(
        "/fps.push/fps.combine/" in ln
        or "/fps.push/fps.ops/scatter_add.xla_sorted/" in ln
        for ln in sized), sized


@pytest.fixture(scope="module")
def ials_user_sweep(topo):
    """``ials-ml20m.sweeps``'s user sweep at the cell's own size (138,493
    users x 26,744 movies, rank 64, 16,384 ratings a step, 64 steps a
    chunk) for one described chip: its Gramian, accumulate and solve
    programs compiled, and the accumulate program's route log."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.models.ials import IALSConfig, IALSSolver
    from fps_tpu.parallel.mesh import make_ps_mesh

    NU, NI, K, B, T = 138_493, 26_744, 64, 16_384, 64
    mesh = make_ps_mesh(num_shards=1, devices=list(topo.devices)[:1])
    solver = IALSSolver(mesh, IALSConfig(num_users=NU, num_items=NI, rank=K))

    def shape(s, dtype, spec=P()):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def table(rows, dim):
        return shape((rows, dim), jnp.float32, P("shard", None))

    chunk = {k: shape((T, B), jnp.int32 if k.endswith("ids")
                      else jnp.float32, P(None, ("data", "shard")))
             for k in ("solve_ids", "fixed_ids", "rating", "weight")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_use_pallas", lambda: (True, False))
        gram = solver._gram_fn(NI, NI).lower(table(NI, K)).compile()
        ops.clear_routes()
        acc = solver._accumulate_fn("user").lower(
            table(NI, K), table(NU, K), table(NU, K * K), table(NU, K),
            chunk).compile()
        routes = [(r.route, r.rows, r.dim, r.ids, r.reason)
                  for r in ops.routes_traced()]
        solve = solver._solve_fn(NU, NU).lower(
            shape((K, K), jnp.float32), table(NU, K * K),
            table(NU, K)).compile()
    return gram, acc, solve, routes


def test_ials_gramian_and_solve_are_float32_and_the_solve_fits(
        ials_user_sweep):
    """The Gramian's contraction carries ``HIGHEST`` in the COMPILED text
    and nothing in either program is bfloat16; the solve is XLA's Cholesky
    kernel a block of 8,192 ids at a time under ``als.solve``: under
    1.5 GB of temporaries, where the whole batch at once needs 13.6 GB
    beside the 2.27 GB accumulator (my compile-only reading, PR 35)."""
    gram, _, solve, _ = ials_user_sweep
    text = gram.as_text()
    dots = [ln for ln in text.splitlines() if " convolution(" in ln]
    assert dots and all("operand_precision={highest,highest}" in ln
                        and "/als.gram/dot_general" in ln
                        for ln in dots), dots
    assert "bf16" not in text
    text = solve.as_text()
    assert "bf16" not in text
    assert 'custom_call_target="Cholesky"' in text
    chol = [ln for ln in text.splitlines() if '"Cholesky"' in ln]
    assert all("/als.solve/while/body/" in ln and "f32[8192,64,64]" in ln
               for ln in chol), chol
    assert "f32[138493,64,64]" not in text
    assert solve.memory_analysis().temp_size_in_bytes < 1.5 * (1 << 30)


def test_ials_accumulate_scatters_in_place_under_the_step_scopes(
        ials_user_sweep):
    """The accumulate program since PR 41 (``als.grouped``): no row a
    RATING is pushed (no ``f32[16384,4096]``, no ``f32[16384,64,64]``);
    the chunk is sorted STABLY under ``fps.push`` (an id's ratings stay
    in the plan's order); a block of 2,048 ratings' addends is one fusion
    of 40 lane rows a rating under ``fps.push/while/body`` and the Mosaic
    kernel ``als_run_sums`` chains them in float32 (no contraction: the
    program holds no ``convolution`` and nothing bfloat16); the one op
    that makes an ``f32[138493,4096]`` is the finished sums' scatter-add,
    32 rows a push, in place on the loops' carry (the accumulator is
    aliased from argument to result and nothing copies it); the pulls
    and the pushes take the plain XLA routes; every scope a reader
    selects by is in the compiled text (``fps.metrics`` holds the
    cross-worker sums alone, which one chip has none of)."""
    _, acc, _, routes = ials_user_sweep
    # 64 steps of 16,384 ratings: no more runs of one id than users.
    assert routes == [
        ("als.grouped", 138_493, 4096, 138_493, "user"),
        ("gather.xla", 26_744, 64, 16_384, "shape"),
        ("gather.xla", 138_493, 64, 16_384, "shape"),
        ("gather.xla", 26_744, 64, 2_048, "shape"),
        ("scatter_add.xla", 138_493, 4096, 32, "shape"),
        ("scatter_add.xla", 138_493, 64, 32, "shape")]
    mem = acc.memory_analysis()
    assert mem.alias_size_in_bytes >= 138_493 * 4096 * 4
    assert mem.temp_size_in_bytes < 1 << 30
    text = acc.as_text()
    assert "bf16" not in text and " convolution(" not in text
    assert "f32[16384,4096]" not in text and "f32[16384,64,64]" not in text
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 1 and "is_stable=true" in sorts[0] and (
        "/fps.push/sort" in sorts[0]), sorts
    kernels = [ln for ln in _top_level(text)
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1 and "f32[2048,40,128]" in kernels[0] and (
        "/fps.push/while/body/" in kernels[0]
        and "als_run_sums" in kernels[0]), kernels
    sized = [ln for ln in _top_level(text)
             if (m := re.search(r"= f32\[138493,4096\]\S* ([\w\-]+)\(", ln))
             and m.group(1) not in ("get-tuple-element", "parameter")]
    assert len(sized) == 1 and " fusion(" in sized[0] and re.search(
        r"/fps\.push/while/body/(closed_call/)?while/body/fps\.ops/"
        r"scatter_add\.xla/scatter-add", sized[0]), sized
    assert not [ln for ln in text.splitlines()
                if re.search(r"= f32\[138493,4096\]\S* copy\(", ln)]
    for scope in (r"/fps\.pull/fps\.ops/gather\.xla/", r"/fps\.compute/",
                  r"/fps\.push/sort",
                  r"/fps\.push/while/body/(closed_call/)?fps\.ops/gather\.xla/"):
        assert re.search(scope, text), scope


@pytest.mark.parametrize("shards,chunking,tiles_a_step", [
    (1, (16, 1152), None), (4, (8, 640), None), (1, (16, 1152), 3)])
def test_topk_selection_prunes_with_its_kernel(topo, monkeypatch, shards,
                                               chunking, tiles_a_step):
    """``mf-netflix-topk``'s selection (17,770 rows, K 100, 256 queries a
    worker) compiled for one shard and for four: ``lax.top_k`` is handed
    the chunk maxima and the fetched candidates, never a shard's whole
    row of scores; the candidates are fetched by the Mosaic kernel
    ``topk_fetch_chunks`` (the lane gather compiles for the v5e), once a
    program, under ``topk.select``; so it does with its VMEM budget cut to
    three lane tiles a step (the runs of tiles a table of millions of rows
    is fetched in)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.models import recommendation as rec
    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    if tiles_a_step:
        monkeypatch.setattr(rec, "_FETCH_BLOCK_BYTES",
                            4 * 8 * chunking[0] * 128 * tiles_a_step)
    rows, k, q, rank = 17_770, 100, 256, NETFLIX[1]
    rps = -(-rows // shards)
    c, C = chunking
    assert rec._prune_plan(rps, k) == (c, C)
    mesh = make_ps_mesh(num_shards=shards, devices=list(topo.devices)[:shards])

    def shape(s, spec):
        return jax.ShapeDtypeStruct(s, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    text = jax.jit(jax.shard_map(
        lambda t, qs: rec._topk_local_queries(
            t, qs, num_shards=shards, num_ids=rows, k=k),
        mesh=mesh, in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
        check_vma=False)).lower(
            shape((rps * shards, rank), P(SHARD_AXIS, None)),
            shape((q * shards, rank), P(SHARD_AXIS, None))
    ).compile().as_text()
    # XLA lowers a narrow lax.top_k to a sort and a wide one to its TopK
    # custom call: either way, the widths it is handed.
    handed = sorted(
        int(m.group(1)) for ln in text.splitlines()
        if '/top_k"' in ln and (" sort(" in ln or '"TopK"' in ln)
        and (m := re.search(r"= \(?f32\[\d+,(\d+)\]", ln)))
    merged = [shards * k] if shards > 1 else []
    assert handed == sorted([C, c * 128] + merged), handed
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1 and "topk_fetch_chunks" in kernels[0] and (
        "/topk.select/" in kernels[0]
        and f"f32[{q * shards},{c},128]" in kernels[0]), kernels


def test_dlrm_table_is_read_and_written_in_place_transposed(one_chip,
                                                             monkeypatch):
    """``dlrm-criteo``'s table, f32[33762577,16] under a step's 425,984
    ids as a donated loop carry: XLA keeps it TRANSPOSED
    (``{0,1:T(8,128)}``: 2.16 GB; row-major tiles would be 17.3 GB and
    could not be held) as parameter, carry and scatter operand, the plain
    routes take both ops (reason ``shape``), the scatter writes the carry
    in place, and no temporary is of the table's size: what ``ops``'
    comment table says of the shape (PR 48; on the chip 9.6 and 43.3 ms a
    call, builder's run)."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    R, D, B = 33_762_577, 16, 425_984

    def steps(t, ids):
        def body(t, i):
            rows = ops.gather_rows(t, i)
            return ops.scatter_add(t, i, 0.1 * rows), jnp.sum(rows)
        return lax.scan(body, t, ids)

    ops.clear_routes()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((R, D), jnp.float32), ((2, B), jnp.int32))]
    c = jax.jit(steps, donate_argnums=0).lower(*args).compile()
    assert [(r.route, r.reason) for r in ops.routes_traced()] == [
        ("gather.xla", "shape"), ("scatter_add.xla", "shape")]
    text = c.as_text()
    table = f"f32[{R},{D}]"
    layouts = set(re.findall(re.escape(table) + r"(\{[^}]*\})", text))
    assert layouts == {"{0,1:T(8,128)}"}, layouts
    (scatter,) = [ln for ln in _top_level(text)
                  if re.search(r"= " + re.escape(table) + r"\S* fusion\(", ln)]
    assert "/fps.ops/scatter_add.xla/" in scatter and "kind=kCustom" in scatter
    assert not re.search(r"= " + re.escape(table) + r"\S* (copy|transpose)\(",
                         text)
    memory = c.memory_analysis()
    assert memory.temp_size_in_bytes < 256 << 20        # the table: 2.16 GB
    assert memory.alias_size_in_bytes >= R * D * 4      # donated, in place


def test_dlrm_push_sums_its_runs_and_scatters_by_blocks_in_place(
        topo, monkeypatch):
    """The same table through ``store.push`` (the additive sum; PR 49):
    the routes are ``push.sum_runs`` and ``scatter_add.xla_sorted``; the
    table is ``{0,1:T(8,128)}`` everywhere, written in place by ONE
    scatter fusion inside the block loop, an operand of no conditional
    (the look at the batch chooses between payload-sized arrays), never
    copied or transposed; the sums' temporaries are the payload's (three
    sorts of ids and positions alone, none that carries the rows' 16
    columns: 73 s of compile where ``_sum_id_runs``' form takes 426; the
    long runs chained in a ``[12909,16]`` buffer in VMEM), and the alias
    is the table's size."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fps_tpu.core import store
    from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    R, D, B = 33_762_577, 16, 425_984
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                (DATA_AXIS, SHARD_AXIS))

    def steps(t, ids, deltas):
        def body(t, x):
            return jax.shard_map(
                lambda t, i, d: store.push(t, i, d, num_shards=1,
                                           data_axis=None, table="emb"),
                mesh=mesh, in_specs=(P(SHARD_AXIS, None), P(), P()),
                out_specs=P(SHARD_AXIS, None), check_vma=False)(t, *x), None
        return lax.scan(body, t, (ids, deltas))[0]

    ops.clear_routes()
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, p))
            for s, d, p in (((R, D), jnp.float32, P(SHARD_AXIS, None)),
                            ((2, B), jnp.int32, P()),
                            ((2, B, D), jnp.float32, P()))]
    c = jax.jit(steps, donate_argnums=0).lower(*args).compile()
    assert [(r.route, r.rows, r.dim, r.ids, r.reason)
            for r in ops.routes_traced()] == [
        ("push.sum_runs", R, D, B, "xla_transposed_hbm"),
        ("scatter_add.xla_sorted", R, D, B, "")]
    text = c.as_text()
    table = f"f32[{R},{D}]"
    layouts = set(re.findall(re.escape(table) + r"(\{[^}]*\})", text))
    assert layouts == {"{0,1:T(8,128)}"}, layouts
    (scatter,) = [ln for ln in _top_level(text)
                  if re.search(r"= " + re.escape(table) + r"\S* fusion\(", ln)]
    assert ("/fps.ops/scatter_add.xla_sorted/while/body/" in scatter
            and "kind=kCustom" in scatter)
    assert not re.search(
        r"= " + re.escape(table) + r"\S* (copy|transpose)\(", text)
    conditionals = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert conditionals and not [ln for ln in conditionals if table in ln]
    # The three sorts carry ids and positions, nothing of the rows.
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 3 and all(
        re.search(r"= \(?s32\[\d+\]\S*(, s32\[\d+\]\S*\))? sort\(", ln)
        for ln in sorts), sorts
    # The long runs are chained by XLA's own scatter in its VMEM regime.
    assert re.search(r"f32\[12909,16\]\{1,0:T\(8,128\)S\(1\)\} scatter\(",
                     text)
    memory = c.memory_analysis()
    payload = B * D * 4
    assert memory.temp_size_in_bytes < (256 << 20) + 4 * payload
    assert memory.alias_size_in_bytes >= R * D * 4      # donated, in place


KGE = (393_216, 822, 500, 4_096, 10)  # entities, relations, rank, B, N


def test_dlrm_pull_reads_each_distinct_row_once_and_copies_no_table(
        topo, monkeypatch):
    """The same table through ``store.pull`` (PR 54): the routes are
    ``pull.distinct_rows`` and the two ``gather.xla`` reads it chooses
    between (a block of 1,024 ids a trip of its loop; all the ids at
    once); the table is ``{0,1:T(8,128)}`` everywhere, an operand of the
    look's conditional that is never copied or transposed; the buffer of
    the distinct rows has the batch's shape, and XLA keeps it and the rows
    the expand hands back in ONE layout, transposed and in VMEM, the
    expand reading the buffer as the block loop left it (a shorter buffer
    it copies row-major into HBM first, and the expand then costs twice as
    much: chip runs, PR 54); and the three sorts carry ids and positions
    alone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fps_tpu.core import store
    from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS

    monkeypatch.setattr(ops, "_use_pallas", lambda: (True, False))
    R, D, B = 33_762_577, 16, 425_984
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                (DATA_AXIS, SHARD_AXIS))

    def steps(t, ids):
        def pulled(t, i):
            rows = store.pull(t, i, num_shards=1, data_axis=None,
                              table="emb")
            # The next step's table depends on this one's rows.
            return lax.dynamic_update_slice(t, rows[:1, :1], (0, 0)), \
                jnp.sum(rows)

        def body(t, i):
            return jax.shard_map(
                pulled, mesh=mesh, in_specs=(P(SHARD_AXIS, None), P()),
                out_specs=(P(SHARD_AXIS, None), P()), check_vma=False)(t, i)
        return lax.scan(body, t, ids)

    ops.clear_routes()
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, p))
            for s, d, p in (((R, D), jnp.float32, P(SHARD_AXIS, None)),
                            ((2, B), jnp.int32, P()))]
    c = jax.jit(steps, donate_argnums=0).lower(*args).compile()
    assert [(r.route, r.rows, r.dim, r.ids, r.reason)
            for r in ops.routes_traced()] == [
        ("pull.distinct_rows", R, D, B, "xla_transposed_hbm"),
        ("gather.xla", R, D, ops.XLA_SORTED_BLOCK_IDS, "shape"),
        ("gather.xla", R, D, B, "shape")]
    text = c.as_text()
    table = f"f32[{R},{D}]"
    layouts = set(re.findall(re.escape(table) + r"(\{[^}]*\})", text))
    assert layouts == {"{0,1:T(8,128)}"}, layouts
    assert not re.search(
        r"= " + re.escape(table) + r"\S* (copy|transpose)\(", text)
    # The look: one conditional, the table an operand of both branches.
    (look,) = [ln for ln in text.splitlines() if " conditional(" in ln]
    branches = re.search(r"branch_computations=\{%([\w.]+), %([\w.]+)\}",
                         look).groups()
    for name in branches:
        (head,) = [ln for ln in text.splitlines()
                   if ln.startswith(f"%{name} (")]
        assert table in head, head
    # The distinct rows are gathered inside the block loop, under the
    # route's own scope.
    assert re.search(r"branch_1_fun/while/body/fps\.ops/gather\.xla/", text)
    # The expand reads the buffer as the loop left it, in the same layout.
    buffer = f"f32[{B},{D}]" + "{0,1:T(8,128)S(1)}"
    (expand,) = [ln for ln in text.splitlines()
                 if "branch_1_fun/jit(_take)/gather" in ln
                 and "kind=kCustom" in ln]
    assert f" = {buffer} fusion(" in expand, expand
    source = re.search(r" fusion\((%[\w.]+),", expand).group(1)
    (left,) = [ln for ln in text.splitlines()
               if ln.lstrip().startswith(f"{source} = ")]
    assert f" = {buffer} get-tuple-element(" in left and (
        "branch_1_fun/while" in left), left
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 3 and all(
        re.search(r"= \(?s32\[\d+\]\S*(, s32\[\d+\]\S*\))? sort\(", ln)
        for ln in sorts), sorts
    memory = c.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20         # the table: 2.16 GB
    assert memory.alias_size_in_bytes >= R * D * 4      # donated, in place


@pytest.fixture(scope="module")
def kge_step(topo):
    """``kge-wikidata5m.epochs``'s step at the cell's own size through the
    trainer's chunk program, compiled ONCE for one described chip: the
    compiled program and the routes its trace logged."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fps_tpu.core.store import fold_key
    from fps_tpu.models.kge import KGEConfig, kge
    from fps_tpu.parallel.mesh import make_ps_mesh

    E, R, K, B, N = KGE
    D = 2 * K
    mesh = make_ps_mesh(num_shards=1, devices=list(topo.devices)[:1])
    trainer, _ = kge(mesh, KGEConfig(num_entities=E, num_relations=R,
                                     rank=K, negatives=N))

    def shape(s, dtype, spec=P()):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    rows = P("shard", None)
    tables = {"entity": shape((E, D), jnp.float32, rows),
              fold_key("entity"): shape((E, D), jnp.float32, rows),
              "relation": shape((R, D), jnp.float32, rows),
              fold_key("relation"): shape((R, D), jnp.float32, rows)}
    workers = P(None, ("data", "shard"))
    batches = {k: shape((2, B), d, workers) for k, d in (
        ("s", jnp.int32), ("r", jnp.int32), ("o", jnp.int32),
        ("weight", jnp.float32))}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_use_pallas", lambda: (True, False))
        ops.clear_routes()
        compiled = trainer._build_chunk_fn("sync").lower(
            tables, (), batches, shape((), jax.random.key(0).dtype)).compile()
        return compiled, ops.routes_traced()


def test_kge_step_folds_the_pushed_rows_alone_in_place(kge_step):
    """``kge-wikidata5m.epochs``'s step at the cell's own size (``entity``
    ``[393216, 1000]`` and its AdaGrad state of the same shape, 4,096
    positives and 10 corruptions each a step: 49,152 entity ids) for one
    described chip, through the trainer's chunk program. The entity table
    takes the table's own fold on its SPARSE body (``push.fold_rows``):
    the step's one gather of the state and its two writes (the table's
    scatter-add and the state's scatter, each a block of ids at a time in
    the sorted route's loop, in place on the donated carry) are the only
    ops whose result has the table's or the state's shape; no ``[rows,
    1001]`` accumulator, no copy, fill or select of either, and no
    conditional takes either in. The 822-row relation table keeps the
    accumulator body (``push.fold``, ``small_table``). ONCE A CALL, in the
    entry computation, XLA relays both out: it takes the parameters
    column-major (``{0,1}``: 1,000 is no multiple of 128 lanes, 393,216
    is) and carries them row-major through the loop, a copy each way of
    each, which is the 3.15 GB of the temporaries that is no step's."""
    E, R, K, B, N = KGE
    D, ids = 2 * K, B * (2 + N)
    compiled, routes = kge_step
    assert [(r.route, r.rows, r.dim, r.ids, r.reason) for r in routes] == [
        ("gather.xla", E, D, ids, "shape"),
        ("gather.xla", R, D, B, "shape"),
        ("push.fold_rows", E, D, ids, ""),
        ("gather.xla", E, D, ids, "shape"),
        ("scatter_add.xla_sorted", E, D, ids, ""),
        ("scatter_set.xla_sorted", E, D, ids, ""),
        ("push.fold", R, D, B, "small_table"),
        ("scatter_add.xla", R, D + 1, B, "shape")]
    text = compiled.as_text()
    table = f"f32[{E},{D}]"
    assert f"f32[{E},{D + 1}]" not in text
    # Every computation but the entry: the step's loop body and the
    # bodies of the sorted route's own loops inside it.
    sized = [ln for ln in _top_level(text)
             if (m := re.search(r"= " + re.escape(table) + r"\S* ([\w\-]+)\(",
                                ln))
             and m.group(1) not in ("get-tuple-element", "parameter")]
    assert len(sized) == 2 and all(
        "/fps.push/fps.fold_rows/fps.ops/" in ln and "/while/body/" in ln
        and " fusion(" in ln for ln in sized), sized
    assert [("scatter_add.xla_sorted" in ln, "scatter_set.xla_sorted" in ln)
            for ln in sorted(sized, key=lambda ln: "scatter_set" in ln)] == [
        (True, False), (False, True)]
    assert not [ln for ln in _top_level(text) if re.search(
        r"= " + re.escape(table) + r"\S* (copy|transpose|select|broadcast)\(",
        ln)]
    relayouts = re.findall(
        r"= " + re.escape(table) + r"(\{[01],[01])\S* copy\(", text)
    assert sorted(relayouts) == ["{0,1", "{0,1", "{1,0", "{1,0"], relayouts
    assert not [ln for ln in text.splitlines()
                if " conditional(" in ln and table in ln]
    memory = compiled.memory_analysis()
    payload, both = ids * D * 4, 2 * E * D * 4  # a step's rows: 197 MB
    assert memory.temp_size_in_bytes < both + 12 * payload
    assert memory.alias_size_in_bytes >= both   # donated, in place


def test_kge_scoring_writes_the_pushes_and_no_other_replacement_sized_array(
        kge_step):
    """The same compiled step, under ``fps.compute``: the ComplEx worker
    scores through the score's linear form on the pulled rows' own 2-D
    layout (PR 52), the ten corruptions as ten ``[4096, 1000]`` slabs
    against ``[4096, 1000]`` partners. What is held:

    * under ``kge.score`` no instruction's result keeps N as an axis of
      its own beside whole or half rows (``[.., 10, 1000]``,
      ``[.., 10, 500]``, either order: a ``[4096, 10, 1000]`` float32 array
      pads 10 sublanes to 16, 268 MB for 164) and none is a half-row slice
      of the replacements (``[40960, 500]``);
    * in all of ``fps.compute`` the results of ``B N 2K`` elements or more
      (tuple elements counted one by one, bitcasts not) number ONE, the
      pushes' own ``[49152, 1000]`` under ``kge.score``. The parent's step
      (``jax.value_and_grad`` through two ``where``s on ``[4096, 10,
      1000]``) had TEN: two broadcasts, four reshapes that are physical
      copies, four fusion results; held to a third of that. XLA writes
      that one buffer a ``[4096, 1000]`` slab at a time, in place: the
      concatenation becomes twelve dynamic-update-slice fusions, each
      computing its slab's pushes where they land, and only the LAST
      keeps the concatenation's name. The other eleven carry no
      ``op_name`` at all, so a trace reads their time under no scope
      (``PERF.md`` section 7): pinned here so that a compiler that names
      them, or stops writing in place, is noticed;
    * ``memory_analysis()``'s temporaries: both entity arrays' once-a-call
      relayout and under four payloads of a step's rows (3.73 GB read;
      the parent's step read 5,341,910,528, eleven payloads)."""
    E, _, K, B, N = KGE
    D, ids = 2 * K, B * (2 + N)
    compiled, _ = kge_step
    top = list(_top_level(compiled.as_text()))
    results = []        # (scope path, opcode, [dims of each array result])
    for ln in top:
        m = re.search(r"= (.*?) ([\w\-]+)\(", ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        if (m and name and "/fps.compute/" in name.group(1)
                and m.group(2) not in ("get-tuple-element", "parameter",
                                       "bitcast")):
            results.append((name.group(1), m.group(2), [
                [int(d) for d in dims.split(",") if d]
                for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]))
    scoring = [r for r in results if "/kge.score/" in r[0]]
    assert len(scoring) > 5 and len(results) > len(scoring)
    padded = [r for r in scoring for dims in r[2]
              if (N in dims[:-1] and dims[-1] in (D, K) and len(dims) > 2)
              or dims == [B * N, K]]
    assert not padded, padded
    big = [(r[0], r[1], dims) for r in results for dims in r[2]
           if int(np.prod(dims)) >= B * N * D]
    assert len(big) <= 10 // 3, big
    assert [(b[1], b[2]) for b in big] == [("fusion", [ids, D])] and big[0][
        0].endswith("/kge.score/concatenate"), big
    nameless = [ln for ln in top if "op_name=" not in ln
                and re.search(rf"= f32\[{ids},{D}\]\S* fusion\(", ln)]
    assert len(nameless) == 1 + N and all(
        "dynamic-update-slice" in ln for ln in nameless), nameless
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 * E * D * 4 + 4 * ids * D * 4
