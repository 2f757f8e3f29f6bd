"""On-chip scatter/gather microbench with dedup-safe timing.

Each timed call runs a scan of T iterations whose table carry chains, so no
dispatch dedup; timing is fenced by a host read. Reports us per scatter.

``dim1`` arm: XLA's gather and scatter against the dim-1 Pallas kernels at
the PA step's shape (``fps_tpu/ops/pallas_kernels.py``'s dim-1 header).

``rows`` arm: the plain XLA gather / scatter-add against the lane-packed XLA
route (``fps_tpu.ops``: ``gather.xla_packed`` / ``scatter_add.xla_packed``)
over table rows x row width at uniform ids — the sweep that set
``ops.XLA_VMEM_TABLE_BYTES`` / ``XLA_PACKED_DIMS`` / ``XLA_PACKED_MIN_IDS``.

``rows dlrm``: the plain ops at ``dlrm-criteo.epochs``' own table and ids
(``[33762577, 16]``, a step's 425,984 ids of the 26 fields, Zipf(1.05)
within a field), the table a loop carry in the one form XLA keeps it in
(transposed, 2.16 GB), beside a RESIDENT lane-packed form of the same
rows (``[4220323, 128]``, eight rows a packed row, never relaid out: what
a store that owned the layout would carry). ``rows dlrm sums`` (PR 49): the
additive push that sums a step's repeated ids first (``push.sum_runs``) at
that shape: the ways to form the sums at width 16, the block loop alone
on sorted ids, and the whole push plain against summed under the cell's
ids, uniform ids and a half-and-half batch; ``rows dlrm edge``: the push
plain against summed over the table's rows, the sweep
``ops.XLA_TRANSPOSED_HBM_ROWS`` stands on. ``rows dlrm pull`` (PR 54): the
pull that reads each distinct row of a step once (``pull.distinct_rows``)
at that shape: the plain gather against ``store.pull`` as shipped under
the cell's ids and under uniform ids (what a batch without repeats pays),
and the form's parts apart (first sort, compaction, the block loop at the
live ids, the way back to the batch's order by a sort and by a scalar
scatter, the expand).

``mean`` arm: the store's per-id mean push (``fps_tpu.core.store.push``,
``combine="mean"``) by its accumulator branch against its row branch over
rows x width x ids at Zipf(1.0) ids — the sweep that set
``ops.MEAN_ROWS_TABLE_RATIO`` — and the ways to count an id's pushes.

``wide`` arm: the plain XLA scatter-add against the sorted route
(``scatter_add.xla_sorted``: the same op by blocks of ids, stopping where
the dropped ids begin) on Zipf(1.0) ids handed over as ``push.mean_rows``
does, over rows x width x ids, wide rows on both sides of XLA's VMEM edge
— the sweep ``ops._route_xla_sorted`` and its two constants stand on.

``fold`` arm: the store's accumulator body (a stateful fold's push) by the
plain accumulator against the pushed rows summed by id run first
(``push.acc_runs``), over rows x ids at width 2 under uniform ids,
Zipf(1.05) ids and ``lr-criteo.epochs``' own columns, with the scatter-add
alone plain against sorted — the sweep ``ops.XLA_TRANSPOSED_TABLE_BYTES``
and ``ops.ACC_RUNS_MIN_IDS_PER_ROW`` stand on; ``fold probes``: the ways to
scatter the summed runs and to sum them, at the cell's shape.
"""

import contextlib
import os
import sys
import time

# `python tools/bench_scatter.py` puts tools/ (not the repo root) on sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np
from jax import lax

T = 256


def timeit(fn, *args):
    print("  compiling...", flush=True)
    r = fn(*args)
    print("  compiled", flush=True)
    np.asarray(jax.tree.leaves(r)[0]).ravel()[0]
    best = 1e9
    for _ in range(2):
        t0 = time.perf_counter()
        r = fn(*args)
        np.asarray(jax.tree.leaves(r)[0]).ravel()[0]
        best = min(best, time.perf_counter() - t0)
    return best / T * 1e6


def xla_scatter(tab, ids, deltas):
    safe = jnp.where((ids >= 0) & (ids < tab.shape[0]), ids, tab.shape[0])
    return tab.at[safe].add(deltas, mode="drop")


def dim1_shapes():
    """Scalar-table (D=1) kernels at the PA workload shape: XLA gather and
    scatter vs the in-kernel-lane-packed dim-1 kernels (numbers quoted in
    fps_tpu/ops/pallas_kernels.py's dim-1 header). B = 2^20 ids, Zipf(0.9), ~95% duplication."""
    from fps_tpu.ops.pallas_kernels import (
        gather_rows_dim1_pallas, scatter_add_dim1_pallas,
    )

    R, B = 47_236, 16_384 * 64
    rng = np.random.default_rng(0)
    tab = jnp.asarray(rng.normal(0, 0.1, (R, 1)), jnp.float32)
    pop = 1.0 / np.arange(1, R + 1) ** 0.9
    pop /= pop.sum()
    cdf = np.cumsum(pop)
    ids = jnp.asarray(np.searchsorted(cdf, rng.random((T, B))), jnp.int32)
    dup = 1 - len(np.unique(np.asarray(ids[0]))) / B
    deltas = jnp.asarray(rng.normal(0, 1e-4, (T, B, 1)), jnp.float32)
    print(f"PA shape R={R} D=1 B={B}: dup frac {dup:.2f}", flush=True)

    def scan_of(op):
        @jax.jit
        def f(tab, ids, deltas):
            def body(t, x):
                i, d = x
                return op(t, i, d), None
            return lax.scan(body, tab, (ids, deltas))[0]
        return f

    def gathers(take_fn):
        def op(t, i, d):
            v = take_fn(t, i)
            return t + 1e-12 * jnp.sum(v)  # chain so nothing is elided
        return op

    for name, fn in (
        ("xla scatter", scan_of(xla_scatter)),
        ("dim1 scatter", scan_of(
            lambda t, i, d: scatter_add_dim1_pallas(
                t, i, d, row_tile=512, batch_tile=8192))),
        ("xla gather", scan_of(gathers(lambda t, i: jnp.take(t, i, axis=0)))),
        ("dim1 gather", scan_of(gathers(gather_rows_dim1_pallas))),
    ):
        us = timeit(fn, tab, ids, deltas)
        print(f"{name:16s} {us / 1e3:8.2f} ms/call", flush=True)

    a = np.asarray(xla_scatter(tab, ids[0], deltas[0]))
    b = np.asarray(scatter_add_dim1_pallas(tab, ids[0], deltas[0]))
    print(f"dim1 scatter vs xla max abs err {np.max(np.abs(a - b)):.2e}")


ROWS_R = (17_770, 120_048, 200_000, 240_095, 320_126, 480_189, 1_048_576)
ROWS_D = (8, 10, 11, 16, 20, 32)
ROWS_T = 64


def rows_point(R, D, B, ops_wanted=("scatter", "gather")):
    """us a call of the plain XLA ops and of the lane-packed XLA route on
    an f32 ``[R, D]`` table, ``B`` uniform ids, the table a loop carry of
    its plain shape (so the packed route's relayout is counted, both
    ways, every iteration). ``scatter`` chains on the table; ``gather``
    chains on an accumulator with one table element rewritten each
    iteration (nothing hoisted out of the loop); ``pair`` is a worker
    step's gather then scatter-add of the same ids (the route packs the
    table once for both)."""
    import fps_tpu.ops as ops

    rng = np.random.default_rng(R * 131 + D)
    tab = jnp.asarray(rng.normal(0, 0.1, (R, D)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, R, (ROWS_T, B)), jnp.int32)
    deltas = jnp.asarray(rng.normal(0, 1e-4, (ROWS_T, B, D)), jnp.float32)

    def plain_gather(t, i):
        return jnp.take(t, i, axis=0)

    routes = {"plain": (plain_gather, xla_scatter),
              "packed": (ops._xla_packed_gather, ops._xla_packed_scatter_add)}

    def program(op, gather, scatter):
        def body(carry, x):
            t, acc = carry
            i, d = x
            if op == "scatter":
                return (scatter(t, i, d), acc), None
            if op == "gather":
                t = lax.dynamic_update_slice(t, acc[:1, :1], (0, 0))
                return (t, acc + gather(t, i)), None
            return (scatter(t, i, d + 1e-6 * gather(t, i)), acc), None

        @jax.jit
        def f(t, ids, deltas):
            return lax.scan(body, (t, jnp.zeros((B, D), jnp.float32)),
                            (ids, deltas))[0]
        return f

    out = {"rows": R, "dim": D, "ids": B}
    for op in ops_wanted:
        for name, (g, sc) in routes.items():
            f = program(op, g, sc)
            r = f(tab, ids, deltas)
            np.asarray(r[1]).ravel()[0]
            best = 1e9
            for _ in range(2):
                t0 = time.perf_counter()
                r = f(tab, ids, deltas)
                np.asarray(r[1]).ravel()[0], np.asarray(r[0][0, 0])
                best = min(best, time.perf_counter() - t0)
            out[f"{op}_{name}_us"] = round(best / ROWS_T * 1e6, 1)
    return out


DLRM_R, DLRM_D, DLRM_B, DLRM_T = 33_762_577, 16, 425_984, 16


def dlrm_rows():
    """us a call of the plain XLA gather, scatter-add and their pair on
    ``dlrm-criteo.epochs``' table under its own ids, and of the same three
    on a resident lane-packed form (``pack`` = 8 consecutive rows a
    128-lane row; a gather fetches whole packed rows and picks the id's
    lanes by a one-hot, a scatter-add widens each update to its packed
    row). The deltas are made from the ids inside the loop: ``[T, B, 16]``
    of them would be 3.5 GB in 128-lane tiles."""
    import json

    R, D, B, steps = DLRM_R, DLRM_D, DLRM_B, DLRM_T
    ids = jnp.asarray(_dlrm_ids("cell"))
    distinct = int(np.mean([len(np.unique(r)) for r in np.asarray(ids)]))
    pack, Rp = 128 // D, -(-R // (128 // D))

    def deltas(i):
        return ((i % 7).astype(jnp.float32) * 1e-6)[:, None] * jnp.ones(
            (1, D), jnp.float32)

    def plain_gather(t, i):
        return jnp.take(t, i, axis=0)

    def packed_slots(i):
        return i // pack, jnp.arange(pack)[None, :] == (i % pack)[:, None]

    def packed_gather(t, i):
        row, sel = packed_slots(i)
        rows = jnp.take(t, row, axis=0).reshape(B, pack, D)
        return jnp.sum(jnp.where(sel[:, :, None], rows, 0), axis=1)

    def packed_scatter(t, i, dl):
        row, sel = packed_slots(i)
        upd = jnp.where(sel[:, :, None], dl[:, None, :], 0).reshape(B, 128)
        return t.at[row].add(upd, mode="drop")

    forms = {"plain": ((R, D), plain_gather, xla_scatter),
             "packed_resident": ((Rp, 128), packed_gather, packed_scatter)}
    out = {"rows": R, "dim": D, "ids": B, "distinct_ids": distinct}
    for name, (shape, gather, scatter) in forms.items():
        def fresh_table():
            return jax.jit(lambda k: 0.01 * jax.random.normal(k, shape))(
                jax.random.key(1))

        tab = fresh_table()
        for op in ("gather", "scatter", "pair"):
            def body(carry, i):
                t, acc = carry
                if op == "gather":
                    t = lax.dynamic_update_slice(t, acc[:1, :1], (0, 0))
                    return (t, acc + gather(t, i)), None
                if op == "scatter":
                    return (scatter(t, i, deltas(i)), acc), None
                return (scatter(t, i, deltas(i) + 1e-6 * gather(t, i)),
                        acc), None

            f = jax.jit(lambda t, ids: lax.scan(
                body, (t, jnp.zeros((B, D), jnp.float32)), ids)[0],
                donate_argnums=0)
            try:
                best = 1e9
                for _ in range(3):  # the first call compiles
                    t0 = time.perf_counter()
                    tab, acc = f(tab, ids)
                    np.asarray(acc[0, 0]), np.asarray(tab[0, 0])
                    best = min(best, time.perf_counter() - t0)
                out[f"{op}_{name}_us"] = round(best / steps * 1e6, 1)
            except Exception as e:  # noqa: BLE001 (does not fit / compile)
                out[f"{op}_{name}_us"] = f"{type(e).__name__}: {str(e)[:200]}"
                tab = fresh_table()  # the failed call donated the old one
            print(json.dumps(out), flush=True)
        del tab
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_scatter_rows.jsonl", "a") as fh:
        fh.write(json.dumps(dict(
            out, device=jax.devices()[0].device_kind)) + "\n")


def _dlrm_ids(kind, steps=None, R=DLRM_R, B=DLRM_B):
    """``(steps, B)`` ids a step: ``cell`` (``dlrm-criteo.epochs``' own:
    26 fields, Zipf(1.05) within a field), ``uniform`` over the ``R`` rows,
    ``half`` (the cell's in thirteen fields, uniform in the others) or
    ``zipf`` (Zipf(1.05) over ``R`` rows, for a table of other rows)."""
    import json

    from perfbench.datasets.criteo_rows import zipf_tokens

    steps = steps or DLRM_T
    rng = np.random.default_rng(R * 131 + B)
    if kind == "zipf":
        return _zipf_ids(rng, R, (steps, B), alpha=1.05)
    uniform = rng.integers(0, R, (steps, B)).astype(np.int32)
    if kind == "uniform":
        return uniform
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench/configs/dlrm-criteo.json")) as fh:
        d = json.load(fh)["data"]
    cards = d["categorical_cardinalities"]
    assert sum(cards) == R and B == 16_384 * len(cards)
    offsets = np.concatenate([[0], np.cumsum(cards)[:-1]]).astype(np.int32)
    u = jax.random.uniform(jax.random.key(48), (steps, B // len(cards),
                                                len(cards)))
    cell = np.asarray(zipf_tokens(u, cards, d["token_zipf"])) + offsets
    if kind == "half":
        cell[:, :, 1::2] = uniform.reshape(cell.shape)[:, :, 1::2]
    return cell.reshape(steps, B).astype(np.int32)


def _dlrm_deltas(i, D=DLRM_D):
    """A step's pushed rows made from its ids on the device (no ``[T, B,
    16]`` operand), the columns different multiples of a small number."""
    g = ((i % 97).astype(jnp.float32) - 48.0) * 1e-6
    return g[:, None] * (1.0 + jnp.arange(D, dtype=jnp.float32))[None, :]


def _dlrm_timer(R, ids, D=DLRM_D):
    """``us_a_step(op)`` of ``op(table [R, D], ids [B]) -> table`` scanned
    over the steps of ``ids``, the table a DONATED loop carry (one
    resident copy: 2.16 GB at the cell's rows), the best of two timed
    calls after the one that compiles; and that compile's seconds."""
    steps = ids.shape[0]
    ids = jnp.asarray(ids)

    def us_a_step(op):
        tab = jax.jit(lambda k: 0.01 * jax.random.normal(k, (R, D)))(
            jax.random.key(1))
        f = jax.jit(lambda t, ids: lax.scan(
            lambda t, i: (op(t, i), None), t, ids)[0], donate_argnums=0)
        took = []
        for _ in range(3):  # the first call compiles
            t0 = time.perf_counter()
            tab = f(tab, ids)
            np.asarray(tab[0, 0])
            took.append(time.perf_counter() - t0)
        del tab
        best = min(took[1:])
        return round(best / steps * 1e6, 1), round(took[0] - best, 1)
    return us_a_step


def _sum_forms():
    """Ways to form ``push.sum_runs``' sums at width 16, ``(table, idx,
    rows, drop) -> (ids, sums)``: ``a`` :func:`store._sum_id_runs` as it
    is (two sorts carrying all 16 columns and a count: 426 s of compile
    for a described v5e, so it runs only when named); ``b_full`` one sort
    of ``(id, position)``, a gather of the rows into that order, the run
    sums over the transposed rows, a second sort of ``(id or sentinel,
    position)`` and ALL ``B`` totals gathered by it; ``b_blocks`` the
    same with the totals gathered a block of 1,024 at a time, live blocks
    only; ``c`` the rows transposed BEFORE the first gather and gathered
    along the lanes both times; ``shipped`` what ``store.push`` runs
    (``b_blocks`` with the long runs chained at their rows' values, the
    batch taken as repeating)."""
    import fps_tpu.ops as ops
    from fps_tpu.core import store

    def two_sorts(idx, drop):
        pos = jnp.arange(idx.shape[0], dtype=jnp.int32)
        s, order = lax.sort((idx, pos), num_keys=1, is_stable=False)
        first, last = store._run_ends(s)
        ids, at = lax.sort((jnp.where(last & (s < drop), s, drop), pos),
                           num_keys=1, is_stable=False)
        return first, order, ids, at

    def a(t, idx, rows, drop):
        ids, sums = store._sum_id_runs(idx, rows, drop)
        return ids, sums[:, :-1]

    def b_full(t, idx, rows, drop):
        first, order, ids, at = two_sorts(idx, drop)
        (tot,) = store._run_sums(first, (jnp.take(rows, order, axis=0).T,))
        return ids, jnp.take(tot.T, at, axis=0)

    def b_blocks(t, idx, rows, drop):
        B, C = idx.shape[0], ops.XLA_SORTED_BLOCK_IDS
        first, order, ids, at = two_sorts(idx, drop)
        (tot,) = store._run_sums(first, (jnp.take(rows, order, axis=0).T,))
        tot = tot.T

        def fetch(c, sums):
            start = jnp.minimum(c * C, B - C)
            block = jnp.take(tot, lax.dynamic_slice(at, (start,), (C,)),
                             axis=0)
            return lax.dynamic_update_slice(sums, block, (start, 0))

        live = jnp.sum((ids < drop).astype(jnp.int32))
        return ids, lax.fori_loop(0, (live + C - 1) // C, fetch,
                                  jnp.zeros_like(rows))

    def c(t, idx, rows, drop):
        first, order, ids, at = two_sorts(idx, drop)
        (tot,) = store._run_sums(first, (jnp.take(rows.T, order, axis=1),))
        return ids, jnp.take(tot, at, axis=1).T

    def shipped(t, idx, rows, drop):
        keep = ops.SUM_RUNS_MAX_DISTINCT_SHARE
        ops.SUM_RUNS_MAX_DISTINCT_SHARE = 2.0  # summed whatever the batch
        try:
            *runs, long_ids, pushed, live = store._sorted_runs(idx, rows,
                                                               drop)
            return store._summed_runs(
                *runs, jnp.take(t, jnp.minimum(long_ids, drop - 1), axis=0),
                pushed, live, drop)
        finally:
            ops.SUM_RUNS_MAX_DISTINCT_SHARE = keep

    return {"a": a, "b_full": b_full, "b_blocks": b_blocks, "c": c,
            "shipped": shipped}


def _reader(out):
    """``read(name, us_a_step, op)``: one reading into ``out`` (``<name>_us``
    and its compile's seconds; what does not fit or compile as its error),
    printed as it is made."""
    import json

    def read(name, us_a_step, op):
        try:
            out[f"{name}_us"], out["compile_s"][name] = us_a_step(op)
        except Exception as e:  # noqa: BLE001 (does not fit / compile)
            out[f"{name}_us"] = f"{type(e).__name__}: {str(e)[:200]}"
        print(json.dumps({name: out[f"{name}_us"],
                          "compile_s": out["compile_s"].get(name)}),
              flush=True)
    return read


@contextlib.contextmanager
def _regime(route, share=None):
    """While a store call is traced: ``ops._xla_transposed_hbm`` answering
    ``route`` whatever the rows (so ``push.sum_runs`` and
    ``pull.distinct_rows`` engage or stay out), and
    ``ops.SUM_RUNS_MAX_DISTINCT_SHARE`` at ``share`` where given (2: the
    batch taken as repeating whatever it holds; -1: never)."""
    import fps_tpu.ops as ops

    keep = ops.XLA_TRANSPOSED_HBM_ROWS, ops.SUM_RUNS_MAX_DISTINCT_SHARE
    ops.XLA_TRANSPOSED_HBM_ROWS = 0 if route else 1 << 62
    if share is not None:
        ops.SUM_RUNS_MAX_DISTINCT_SHARE = share
    try:
        yield
    finally:
        ops.XLA_TRANSPOSED_HBM_ROWS, ops.SUM_RUNS_MAX_DISTINCT_SHARE = keep


def _store_sum_push(route, share=None, R=DLRM_R, D=DLRM_D):
    """``store.push`` of the additive sum on a one-shard mesh with
    ``store._sum_runs_route`` answering ``route`` whatever the shape while
    it is traced (and ``ops._route_xla_sorted``'s third regime with it),
    and ``ops.SUM_RUNS_MAX_DISTINCT_SHARE`` at ``share`` where given (2:
    the sums always formed; -1: never, the sorted batch handed on)."""
    from jax.sharding import PartitionSpec as P

    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh
    from fps_tpu.core import store

    mesh = make_ps_mesh(num_shards=1, devices=jax.devices()[:1])

    def op(t, i):
        with _regime(route, share):
            return jax.shard_map(
                lambda t, i: store.push(t, i, _dlrm_deltas(i, D),
                                        num_shards=1, data_axis=None),
                mesh=mesh, in_specs=(P(SHARD_AXIS, None), P()),
                out_specs=P(SHARD_AXIS, None), check_vma=False)(t, i)
    return op


def dlrm_sums(forms=("b_full", "b_blocks", "c", "shipped")):
    """``push.sum_runs`` at ``dlrm-criteo.epochs``' shape, us a call: the
    plain scatter-add and the whole additive push, plain against shipped,
    under the cell's ids, uniform ids and the half-and-half batch (under
    each the distinct ids a step); the push with the sums always formed
    under uniform ids and never under the cell's (what the look at the
    batch is worth both ways); the block loop of ``scatter_add.xla_sorted``
    ALONE on a step's distinct ids sorted, the sentinel after (the cell's:
    78,500 live; uniform: 423,300), and on all of a step's ids sorted,
    repeats and all; and each way to form the sums, no scatter
    (:func:`_sum_forms`; ``rows dlrm sums a`` adds the 17-operand sorts)."""

    import fps_tpu.ops as ops

    R, D, B = DLRM_R, DLRM_D, DLRM_B
    out = {"rows": R, "dim": D, "ids": B, "compile_s": {}}
    read = _reader(out)

    def plain(t, i):
        return xla_scatter(t, i, _dlrm_deltas(i))

    def blocks(t, i):
        return ops._xla_sorted_scatter_add(t, i, _dlrm_deltas(i))

    def sums_alone(form):
        def op(t, i):
            ids, sums = form(t, i, _dlrm_deltas(i), R)
            return lax.dynamic_update_slice(
                t, (jnp.sum(sums) + jnp.sum(ids) * 1e-9)[None, None] * 1e-9,
                (0, 0))
        return op

    for kind in ("cell", "uniform", "half"):
        ids = _dlrm_ids(kind)
        out[f"distinct_{kind}"] = int(np.mean(
            [len(np.unique(r)) for r in ids]))
        us_a_step = _dlrm_timer(R, ids)
        read(f"scatter_plain_{kind}", us_a_step, plain)
        read(f"push_plain_{kind}", us_a_step, _store_sum_push(False))
        read(f"push_shipped_{kind}", us_a_step, _store_sum_push(True))
        if kind == "uniform":
            read("push_always_summed_uniform", us_a_step,
                 _store_sum_push(True, 2.0))
        if kind == "cell":
            read("push_never_summed_cell", us_a_step,
                 _store_sum_push(True, -1.0))
            for name in forms:
                read(f"sums_{name}_cell", us_a_step,
                     sums_alone(_sum_forms()[name]))
        if kind != "half":
            read(f"blocks_distinct_{kind}",
                 _dlrm_timer(R, _compacted(ids, R)), blocks)
            read(f"blocks_all_sorted_{kind}",
                 _dlrm_timer(R, np.sort(ids, axis=1)), blocks)
    return out


def _pull_timer(R, xs, D=DLRM_D, B=DLRM_B):
    """``us_a_step(op)`` of ``op(table [R, D], acc [B, D], x) -> arrays``
    scanned over the steps of ``xs`` (a step's host-made inputs, stacked),
    the table a DONATED loop carry written at one corner from ``acc`` each
    step (no step's reads can be hoisted or shared), what ``op`` returns
    folded into ``acc``: added where it is a ``[B, D]`` array, summed into
    one corner otherwise. The best of two timed calls after the one that
    compiles; and that compile's seconds."""
    steps = jax.tree.leaves(xs)[0].shape[0]
    xs = jax.tree.map(jnp.asarray, xs)

    def us_a_step(op):
        def body(carry, x):
            t, acc = carry
            t = lax.dynamic_update_slice(t, acc[:1, :1], (0, 0))
            for out in jax.tree.leaves(op(t, acc, x)):
                acc = (acc + out if out.shape == acc.shape else
                       acc.at[0, 0].add(jnp.sum(out.astype(jnp.float32))))
            return (t, acc), None

        tab = jax.jit(lambda k: 0.01 * jax.random.normal(k, (R, D)))(
            jax.random.key(1))
        f = jax.jit(lambda t, xs: lax.scan(
            body, (t, jnp.zeros((B, D), jnp.float32)), xs)[0],
            donate_argnums=0)
        took = []
        for _ in range(3):  # the first call compiles
            t0 = time.perf_counter()
            tab, acc = f(tab, xs)
            np.asarray(acc[0, 0]), np.asarray(tab[0, 0])
            took.append(time.perf_counter() - t0)
        del tab, acc
        best = min(took[1:])
        return round(best / steps * 1e6, 1), round(took[0] - best, 1)
    return us_a_step


def _store_pull(route, share=None, D=DLRM_D):
    """``store.pull`` on a one-shard mesh with
    ``store._distinct_pull_route`` answering ``route`` whatever the shape
    while it is traced, and ``ops.SUM_RUNS_MAX_DISTINCT_SHARE`` at
    ``share`` where given (2: each distinct row read once whatever the
    batch)."""
    from jax.sharding import PartitionSpec as P

    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh
    from fps_tpu.core import store

    mesh = make_ps_mesh(num_shards=1, devices=jax.devices()[:1])

    def op(t, acc, i):
        with _regime(route, share):
            return jax.shard_map(
                lambda t, i: store.pull(t, i, num_shards=1, data_axis=None),
                mesh=mesh, in_specs=(P(SHARD_AXIS, None), P()),
                out_specs=P(), check_vma=False)(t, i)
    return op


def dlrm_pull():
    """``pull.distinct_rows`` at ``dlrm-criteo.epochs``' shape, us a call,
    under the cell's ids and under ids uniform over the rows (under each
    the distinct ids a step): the plain gather; ``store.pull`` plain and
    as shipped (the batch looked at: a batch without repeats pays the
    first sort beside the plain gather) and with each distinct row read
    once WHATEVER the batch (what dropping the look would cost it); and,
    under the cell's ids, the form's parts apart, each from host-made
    inputs: the first sort of ``(id, position)``; the compaction (the
    runs' firsts, their running count and the sort of the ids alone); the
    block loop over the live ids alone, into a buffer of the batch's
    shape; the run numbers brought back to the batch's order by
    a sort of ``(position, run)`` and by a scalar scatter; and the expand,
    a gather of the ``B`` rows out of that buffer."""
    import fps_tpu.ops as ops
    from fps_tpu.core import store

    R, D, B, C = DLRM_R, DLRM_D, DLRM_B, ops.XLA_SORTED_BLOCK_IDS
    out = {"rows": R, "dim": D, "ids": B, "compile_s": {}}
    read = _reader(out)
    pos = jnp.arange(B, dtype=jnp.int32)

    def blocks(M):
        def op(t, acc, x):
            ids, live = x["compact"][:M], x["live"]

            def fetch(c, buf):
                start = jnp.minimum(c * C, M - C)
                block = ops.gather_rows(
                    t, lax.dynamic_slice(ids, (start,), (C,)))
                return lax.dynamic_update_slice(buf, block, (start, 0))
            return lax.fori_loop(0, (live + C - 1) // C, fetch,
                                 jnp.zeros((M, D), t.dtype))
        return op

    def compact(t, acc, x):
        first = store._run_ends(x["s"])[0]
        return (lax.sort((jnp.where(first, x["s"], R),), is_stable=False)[0],
                jnp.cumsum(first.astype(jnp.int32)) - 1)

    parts = {
        "sort_first": lambda t, acc, x: lax.sort(
            (x["ids"], pos), num_keys=2, is_stable=False),
        "compact": compact,
        "blocks_live": blocks(B),
        "back_sort": lambda t, acc, x: lax.sort(
            (x["order"], x["run"]), num_keys=1, is_stable=False)[1],
        "back_scatter": lambda t, acc, x: jnp.zeros((B,), jnp.int32).at[
            x["order"]].set(x["run"], unique_indices=True),
        "expand": lambda t, acc, x: jnp.take(
            acc, x["run_back"], axis=0, mode="clip"),
    }
    for kind in ("cell", "uniform"):
        ids = _dlrm_ids(kind)
        order = np.argsort(ids, axis=1, kind="stable").astype(np.int32)
        s = np.take_along_axis(ids, order, axis=1)
        first = np.concatenate(
            [np.ones((len(ids), 1), bool), s[:, 1:] != s[:, :-1]], axis=1)
        run = (np.cumsum(first, axis=1) - 1).astype(np.int32)
        run_back = np.empty_like(run)
        np.put_along_axis(run_back, order, run, axis=1)
        live = first.sum(axis=1).astype(np.int32)
        out[f"distinct_{kind}"] = int(live.mean())
        us_a_step = _pull_timer(R, {
            "ids": ids, "s": s, "order": order, "run": run,
            "run_back": run_back, "compact": _compacted(ids, R),
            "live": live})
        read(f"gather_plain_{kind}", us_a_step,
             lambda t, acc, x: ops.gather_rows(t, x["ids"]))
        for name, op in (("plain", _store_pull(False)),
                         ("shipped", _store_pull(True)),
                         ("always_distinct", _store_pull(True, 2.0))):
            read(f"pull_{name}_{kind}", us_a_step,
                 lambda t, acc, x, op=op: op(t, acc, x["ids"]))
        if kind == "cell":
            for name, op in parts.items():
                read(f"{name}_cell", us_a_step, op)
        else:
            read("blocks_live_uniform", us_a_step, parts["blocks_live"])
    return out


def dlrm_edge_point(R, kind):
    """us a call of the additive push into ``[R, 16]`` under 425,984 ids,
    plain against ``push.sum_runs`` (both predicates answering yes
    whatever the rows), and the distinct ids a step."""
    ids = _dlrm_ids(kind, R=R)
    us_a_step = _dlrm_timer(R, ids)
    out = {"rows": R, "dim": DLRM_D, "ids": DLRM_B, "dist": kind,
           "distinct": int(np.mean([len(np.unique(r)) for r in ids]))}
    for name, route in (("plain", False), ("sum_runs", True)):
        out[f"push_{name}_us"], _ = us_a_step(_store_sum_push(route, R=R))
    return out


DLRM_EDGE_R = (1_048_576, 4_194_304, 8_440_645)


def rows_sweep(args):
    """``rows``: the whole grid at B = 32768 (pair at D = 10 only), then
    the fewest ids at which the route pays at Netflix's user block.
    ``rows quick``: Netflix's user block alone. ``rows dlrm``: the plain
    ops and the resident packed form at ``dlrm-criteo.epochs``' shape;
    ``rows dlrm sums [a]`` and ``rows dlrm edge``: ``push.sum_runs`` there
    and over the rows; ``rows dlrm pull``: ``pull.distinct_rows`` there.
    One JSON line a point on stdout, all of them in
    ``chiprun_out/bench_scatter_rows.jsonl``."""
    import json

    if args == ["dlrm"]:
        return dlrm_rows()
    if args[:2] == ["dlrm", "sums"]:
        forms = ("b_full", "b_blocks", "c", "shipped") + tuple(args[2:])
        return _write_points("rows", [(dlrm_sums, (forms,))])
    if args == ["dlrm", "pull"]:
        return _write_points("rows", [(dlrm_pull, ())])
    if args == ["dlrm", "edge"]:
        return _write_points("rows", [
            (dlrm_edge_point, (R, kind)) for R in DLRM_EDGE_R
            for kind in ("zipf", "uniform")])
    B = 32768
    if args == ["quick"]:
        points = [(480_189, 10, B, ("scatter", "gather", "pair"))]
    else:
        points = [(R, D, B, ("scatter", "gather", "pair") if D == 10
                   else ("scatter", "gather"))
                  for D in ROWS_D for R in ROWS_R]
        points += [(480_189, 10, b, ("pair",))
                   for b in (1024, 4096, 8192, 16384)]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_scatter_rows.jsonl", "a") as fh:
        fh.write(json.dumps({"device": jax.devices()[0].device_kind,
                             "platform": jax.default_backend()}) + "\n")
        for R, D, b, wanted in points:
            line = json.dumps(rows_point(R, D, b, wanted))
            print(line, flush=True)
            fh.write(line + "\n")
            fh.flush()


MEAN_R = (17_770, 32_768, 65_536, 131_072, 262_144, 1_115_011)
MEAN_D = (10, 64, 300)
MEAN_B = (8_192, 32_768)
W2V_1BW = ((1_115_011, 300, 8_197), (1_115_011, 300, 49_182))


def _zipf_ids(rng, R, shape, alpha=1.0):
    pop = 1.0 / np.arange(1, R + 1) ** alpha
    cdf = np.cumsum(pop / pop.sum())
    return np.minimum(np.searchsorted(cdf, rng.random(shape)),
                      R - 1).astype(np.int32)


def _scan_timer(tab, *xs):
    """``us_a_step(op)``: us a step (the best of two timed calls after one
    that compiles) of ``op(table, *x) -> table`` scanned over the steps of
    ``xs`` (each ``(T, ...)``) with the table a loop carry; and the last
    table."""
    T = xs[0].shape[0]

    def us_a_step(op):
        f = jax.jit(lambda t, *xs: lax.scan(
            lambda t, x: (op(t, *x), None), t, xs)[0])
        r = f(tab, *xs)
        np.asarray(r[0, 0])
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            r = f(tab, *xs)
            np.asarray(r[0, 0])
            best = min(best, time.perf_counter() - t0)
        return round(best / T * 1e6, 1), r
    return us_a_step


def _compacted(ids, R, front=True):
    """Each step's ids as the summed runs leave them: the distinct ids
    sorted, at the front, the drop sentinel ``R`` after the last
    (``front=False``: before the sort that brings them there, the sentinel
    in the place of every repeat)."""
    ids = np.sort(np.where(ids < 0, R, ids), axis=1)
    ids[:, 1:][ids[:, 1:] == ids[:, :-1]] = R
    return np.sort(ids, axis=1) if front else ids


def _mean_case(R, D, B, sorted_ids=False):
    """A table, ``T`` steps of Zipf(1.0) ids and deltas (at most ~1 GB of
    tiled deltas), and the runner of one program over them: us a step of
    ``op(table, ids, deltas) -> table`` with the table a loop carry.
    ``sorted_ids``: each step's ids as ``push.mean_rows`` hands them to
    ``ops.scatter_add``: the distinct ids sorted, at the front, the drop
    sentinel ``R`` in the place of every duplicate, last."""
    rng = np.random.default_rng(R * 131 + D + B)
    T = int(max(4, min(64, (1 << 30) // (B * -(-D // 128) * 512))))
    tab = jnp.asarray(rng.normal(0, 0.1, (R, D)), jnp.float32)
    ids = _zipf_ids(rng, R, (T, B))
    if sorted_ids:
        ids = _compacted(ids, R)
    ids = jnp.asarray(ids)
    deltas = jnp.asarray(rng.normal(0, 1e-2, (T, B, D)), jnp.float32)
    us_a_step = _scan_timer(tab, ids, deltas)
    us_a_step.live = round(float(jnp.mean(jnp.sum(ids < R, axis=1))), 1)
    return us_a_step


def _store_mean_push(ratio):
    """``store.push(combine="mean")`` on a one-shard mesh with
    ``ops.MEAN_ROWS_TABLE_RATIO`` set to ``ratio`` while it is traced:
    0 takes the row branch whatever the shape, inf the accumulator."""
    from jax.sharding import PartitionSpec as P

    import fps_tpu.ops as ops
    from fps_tpu.core import store
    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh

    mesh = make_ps_mesh(num_shards=1, devices=jax.devices()[:1])

    def op(t, i, d):
        ops.MEAN_ROWS_TABLE_RATIO = ratio
        return jax.shard_map(
            lambda t, i, d: store.push(t, i, d, num_shards=1, data_axis=None,
                                       combine="mean"),
            mesh=mesh, in_specs=(P(SHARD_AXIS, None), P(), P()),
            out_specs=P(SHARD_AXIS, None), check_vma=False)(t, i, d)
    return op


def _both_branches(us_a_step):
    """``(accumulator us, its table, rows us, its table)`` of one case."""
    import fps_tpu.ops as ops

    keep = ops.MEAN_ROWS_TABLE_RATIO
    try:
        return (*us_a_step(_store_mean_push(float("inf"))),
                *us_a_step(_store_mean_push(0.0)))
    finally:
        ops.MEAN_ROWS_TABLE_RATIO = keep


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def mean_point(R, D, B):
    """us a step of the mean push's two branches (``push.mean_dense``: the
    ``(R, D + 1)`` accumulator; ``push.mean_rows``: the pushed rows
    normalised, summed by id and scattered into the table once) and the
    largest gap of their tables over the largest value."""
    from fps_tpu.core.store import _mean_push_ratio

    dense_us, want, rows_us, got = _both_branches(_mean_case(R, D, B))
    return {"rows": R, "dim": D, "ids": B,
            "ratio": round(_mean_push_ratio(R, D, B, jnp.float32), 2),
            "mean_dense_us": dense_us, "mean_rows_us": rows_us,
            "gap": _gap(got, want)}


def _counts_vector(idx, R):
    """(a) a count VECTOR of the table's rows, scattered into and read
    back at the ids: O(B) + O(R) scalars."""
    cnt = jnp.zeros((R,), jnp.float32).at[idx].add(1.0, mode="drop")
    return jnp.take(cnt, idx, mode="fill", fill_value=0)


def _counts_runs(idx, R):
    """(b) sort the ids, count by run length, sort back: [B]-sized arrays
    only. ``store.push``'s (``core/store._id_runs``)."""
    from fps_tpu.core.store import _id_runs

    return _id_runs(idx, R)[0].astype(jnp.float32)


def counts_point(R, D, B):
    """us a step at one shape of: the bare scatter-add ("sum": what no
    mean can beat); the accumulator branch; the scaled rows scattered
    STRAIGHT into the table under each way to count an id's pushes
    (``*_alone_us``: the counts alone, chained through one table element);
    and the row branch as ``store.push`` has it (the rows of an id summed
    in a (B, D) buffer first). ``*_gap``: largest gap to the accumulator
    branch's table over its largest value."""
    import fps_tpu.ops as ops

    us_a_step = _mean_case(R, D, B)
    out = {"rows": R, "dim": D, "ids": B}
    out["sum_us"], _ = us_a_step(ops.scatter_add)
    (out["mean_dense_us"], want,
     out["mean_rows_us"], got) = _both_branches(us_a_step)
    out["mean_rows_gap"] = _gap(got, want)
    for name, counts in (("vector", _counts_vector), ("runs", _counts_runs)):
        def push(t, i, d, counts=counts):
            inv = 1.0 / jnp.maximum(counts(i, R), 1.0)
            return ops.scatter_add(t, i, d * inv[:, None])

        def alone(t, i, d, counts=counts):
            i = i.at[0].set(t[0, 0].astype(jnp.int32) % 2)
            return lax.dynamic_update_slice(
                t, jnp.sum(counts(i, R))[None, None] * 1e-9, (0, 0))

        out[f"{name}_straight_us"], r = us_a_step(push)
        out[f"{name}_alone_us"], _ = us_a_step(alone)
        out[f"{name}_straight_gap"] = _gap(r, want)
    return out


def _write_points(name, points):
    """Run ``fn(*args)`` for each of ``points``: one JSON line a point on
    stdout, all of them in ``chiprun_out/bench_scatter_<name>.jsonl``."""
    import json

    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/bench_scatter_{name}.jsonl", "a") as fh:
        fh.write(json.dumps({"device": jax.devices()[0].device_kind,
                             "platform": jax.default_backend()}) + "\n")
        for fn, p in points:
            line = json.dumps(fn(*p))
            print(line, flush=True)
            fh.write(line + "\n")
            fh.flush()


def mean_sweep(args):
    """``mean``: both branches of the mean push over rows x width x ids at
    Zipf(1.0) ids (the sweep that set ``ops.MEAN_ROWS_TABLE_RATIO``), and
    ``w2v-1bw``'s two shapes. ``mean counts``: the ways to count, and
    both branches, at those two shapes alone. One JSON line a point, all
    in ``chiprun_out/bench_scatter_mean.jsonl``."""
    if args == ["counts"]:
        points = [(counts_point, p) for p in W2V_1BW]
    else:
        points = [(mean_point, (R, D, B))
                  for D in MEAN_D for B in MEAN_B for R in MEAN_R]
        points += [(mean_point, p) for p in W2V_1BW]
    _write_points("mean", points)


WIDE_R = (65_536, 131_072, 262_144, 1_115_011)
WIDE_D = (64, 128, 300)
WIDE_B = (8_192, 32_768, 49_182)
WIDE_BLOCKS = (512, 1_024, 2_048, 4_096, 8_192)


def wide_point(R, D, B, block=None):
    """us a step of ``ops.scatter_add`` on sorted ids by the plain route
    and by the sorted one (its predicate answering yes whatever the
    shape while it is traced; ``block`` ids a block where given, else
    ``ops.XLA_SORTED_BLOCK_IDS``), the route each logged, and the largest
    gap of their tables over the largest value."""
    import fps_tpu.ops as ops

    us_a_step = _mean_case(R, D, B, sorted_ids=True)
    out = {"rows": R, "dim": D, "ids": B, "tiled_mb": round(
        ops._tiled_table_bytes(R, D, jnp.float32) / 1e6, 1),
        "live": us_a_step.live,
        "block": block or ops.XLA_SORTED_BLOCK_IDS}
    keep = ops._route_xla_sorted, ops.XLA_SORTED_BLOCK_IDS
    ops._route_xla_sorted = lambda R, D, B, dtype, ids_sorted: ids_sorted
    ops.XLA_SORTED_BLOCK_IDS = out["block"]
    try:
        ops.clear_routes()
        out["plain_us"], want = us_a_step(ops.scatter_add)
        out["sorted_us"], got = us_a_step(
            lambda t, i, d: ops.scatter_add(t, i, d, ids_sorted=True))
        out["routes"] = [r.route for r in ops.routes_traced()]
    finally:
        ops._route_xla_sorted, ops.XLA_SORTED_BLOCK_IDS = keep
    out["gap"] = _gap(got, want)
    return out


def wide_sweep(args):
    """``wide``: the whole grid, then ``w2v-1bw``'s two shapes, then its
    larger one under every block size. ``wide quick``: the last two
    alone. One JSON line a point, all in
    ``chiprun_out/bench_scatter_wide.jsonl``."""
    points = list(W2V_1BW) + [(*W2V_1BW[1], b) for b in WIDE_BLOCKS]
    if args != ["quick"]:
        points = [(R, D, B) for D in WIDE_D for B in WIDE_B
                  for R in WIDE_R] + points
    _write_points("wide", [(wide_point, p) for p in points])


FOLD_R = (262_144, 1_000_000, 4_194_304)
FOLD_B = (32_768, 131_072, 425_997)
FOLD_D = 2          # the pushed row [g, g^2]; the accumulator is D + 1 wide
LR_CRITEO = (1_000_000, 16_384)   # rows; examples a step (x 26 + 13 ids)
MF_X4 = (4_443, 131_072, "zipf", 10)  # mf-netflix.x4's shard push, in VMEM
# Round the grid: the first rows XLA keeps transposed (320,126: PR 25's
# sweep), many ids a row there, and one shard of four of the cell's job.
FOLD_EDGE = ((320_126, 131_072), (320_126, 425_997), (500_000, 425_997),
             (1_000_000, 1_703_988), (250_000, 1_703_988))


def _criteo_ids(T, seed=33):
    """``T`` steps of ``lr-criteo.epochs``'s pushed ids: 16,384 rows x 26
    categorical columns drawn by the configuration's own generator
    (``perfbench/datasets/criteo_rows.py``: Zipf(1.05) within a column,
    hashed into 1,000,000 features) and the dense head's 13."""
    import json

    from perfbench.datasets.criteo_rows import hash_tokens, zipf_tokens

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench/configs/lr-criteo.json")) as fh:
        d = json.load(fh)["data"]
    cards = d["categorical_cardinalities"]
    u = jax.random.uniform(jax.random.key(seed),
                           (T, LR_CRITEO[1], len(cards)))
    ids = hash_tokens(zipf_tokens(u, cards, d["token_zipf"]),
                      d["num_features"], d["numeric_columns"])
    head = jnp.broadcast_to(jnp.arange(d["numeric_columns"], dtype=jnp.int32),
                            (T, d["numeric_columns"]))
    return np.asarray(jnp.concatenate([head, ids.reshape(T, -1)], axis=1))


def _fold_ids(R, B, dist, T):
    """``(T, B)`` ids over ``R`` rows: ``uniform``, ``zipf`` (Zipf(1.05)
    over the rows) or ``criteo`` (the cell's own columns; its own R, B)."""
    rng = np.random.default_rng(R * 131 + B)
    if dist == "criteo":
        return _criteo_ids(T)
    if dist == "criteo_x4":  # shard 0's view of four workers' pushes
        ids = np.concatenate([_criteo_ids(T, seed=33 + w) for w in range(4)],
                             axis=1)
        return np.where(ids % 4 == 0, ids // 4, -1).astype(np.int32)
    if dist == "uniform":
        return rng.integers(0, R, (T, B)).astype(np.int32)
    return _zipf_ids(rng, R, (T, B), alpha=1.05)


def _fold_rows(ids, D=FOLD_D):
    """The pushed rows of a step, made on the device from its ids (no
    ``(T, B, D)`` operand: its row-major tiles would be 218 MB a step):
    ``[g, g^2]`` as the AdaGrad fold is pushed them, and multiples of
    ``g`` for the columns past two."""
    g = ((ids % 97).astype(jnp.float32) - 48.0) * 1e-3
    return jnp.stack([g, g * g] + [g * (k + 2) for k in range(D - 2)],
                     axis=1)


def _fold_rows_counted(ids, R, D=FOLD_D):
    """:func:`_fold_rows` with the accumulator's count column: 1 beside
    an id under ``R``, 0 beside the drop sentinel."""
    return jnp.concatenate(
        [_fold_rows(ids, D), (ids < R).astype(jnp.float32)[:, None]], axis=1)


def _fold_runner(R, ids, width=FOLD_D):
    """us a step of ``op(table [R, width], ids [B]) -> table`` over the
    steps of ``ids (T, B)``, the table a loop carry; and the last table."""
    return _scan_timer(jnp.zeros((R, width), jnp.float32), jnp.asarray(ids))


def _store_fold_push(runs, D=FOLD_D):
    """``store.push`` through its accumulator body on a one-shard mesh
    with ``store._acc_runs_route`` answering ``runs`` whatever the shape
    while it is traced: the id runs summed before the accumulator's
    scatter (``push.acc_runs``), or the plain accumulator. At width 2 a
    stateful fold (``adagrad_fold``), at any other the per-id mean kept on
    its accumulator (MF's push)."""
    from jax.sharding import PartitionSpec as P

    import fps_tpu.ops as ops
    from fps_tpu.core import store
    from fps_tpu.models.logistic_regression import adagrad_fold
    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh

    mesh = make_ps_mesh(num_shards=1, devices=jax.devices()[:1])
    kw = ({"apply_fn": adagrad_fold(0.001, 1e-6)} if D == 2
          else {"combine": "mean"})

    def op(t, i):
        keep = store._acc_runs_route, ops.MEAN_ROWS_TABLE_RATIO
        store._acc_runs_route = lambda *shape: runs
        ops.MEAN_ROWS_TABLE_RATIO = float("inf")
        try:
            return jax.shard_map(
                lambda t, i: store.push(t, i, _fold_rows(i, D), num_shards=1,
                                        data_axis=None, **kw),
                mesh=mesh, in_specs=(P(SHARD_AXIS, None), P()),
                out_specs=P(SHARD_AXIS, None), check_vma=False)(t, i)
        finally:
            store._acc_runs_route, ops.MEAN_ROWS_TABLE_RATIO = keep
    return op


def fold_point(R, B, dist, D=FOLD_D):
    """us a step of a push through ``store.push``'s accumulator body by
    the plain accumulator and with the id runs summed first
    (``push.acc_runs``), the live ids a step (the distinct ones) and the
    largest gap of the two tables over the largest value; and of the
    scatter-add ALONE into an ``[R, D + 1]`` loop carry under the summed
    runs' ids (the distinct ones sorted, the sentinel after), by the
    plain route and by ``scatter_add.xla_sorted`` (its predicate answering
    yes whatever the shape)."""
    import fps_tpu.ops as ops

    T = int(max(4, min(32, (1 << 24) // B)))
    ids = _fold_ids(R, B, dist, T)
    us_a_step = _fold_runner(R, ids, D)
    ops.clear_routes()
    plain_us, want = us_a_step(_store_fold_push(False, D))
    runs_us, got = us_a_step(_store_fold_push(True, D))
    out = {"rows": R, "dim": D, "ids": ids.shape[1], "dist": dist,
           "ids_per_row": round(ids.shape[1] / R, 3),
           "live": round(float(np.mean(
               [len(np.unique(i[i >= 0])) for i in ids])), 1),
           "tiled_mb": round(ops._tiled_table_bytes(
               R, D + 1, jnp.float32) / 1e6, 1),
           "acc_us": plain_us, "acc_runs_us": runs_us,
           "gap": _gap(got, want),
           "routes": [r.route for r in ops.routes_traced()]}

    def scatter(ids_sorted):
        return lambda t, i: ops.scatter_add(
            t, i, _fold_rows_counted(i, R, D), ids_sorted=ids_sorted)

    us_a_step = _fold_runner(R, _compacted(ids, R), D + 1)
    keep = ops._route_xla_sorted
    ops._route_xla_sorted = lambda R, D, B, dtype, ids_sorted: ids_sorted
    try:
        out["scatter_plain_us"], want = us_a_step(scatter(False))
        out["scatter_sorted_us"], got = us_a_step(scatter(True))
    finally:
        ops._route_xla_sorted = keep
    out["scatter_gap"] = _gap(got, want)
    return out


def _ladder_scatter(zeros, ids, rows, rungs):
    """Probe (c): ``lax.switch`` on the live count over static prefixes of
    the sorted ids, each branch one plain scatter-add of its slice."""
    import fps_tpu.ops as ops

    B, R = ids.shape[0], zeros.shape[0]
    live = jnp.sum((ids < R).astype(jnp.int32))
    sizes = [-(-B * k // rungs) for k in range(1, rungs + 1)]
    which = jnp.searchsorted(jnp.asarray(sizes, jnp.int32), live)
    return lax.switch(
        jnp.minimum(which, rungs - 1),
        [lambda z, i, d, n=n: ops.scatter_add(z, i[:n], d[:n])
         for n in sizes], zeros, ids, rows)


def _runs_variants():
    """Ways to sum a batch's rows by id run, ``(idx, rows, drop) -> (ids,
    sums and count)`` as ``store._sum_id_runs`` (``shipped``), and the
    parts of it alone (what they return is not the sums)."""
    from fps_tpu.core import store

    def unstable(*operands):
        return lax.sort(operands, num_keys=1, is_stable=False)

    def counted(s, cols, drop):
        return (*cols, (s != drop).astype(cols[0].dtype))

    def assoc_scan(idx, rows, drop):
        s, *cols = unstable(idx, *rows.T)
        first, last = store._run_ends(s)

        def seg(a, b):
            return a[0] | b[0], jnp.where(b[0][:, None], b[1], a[1] + b[1])

        _, tot = lax.associative_scan(
            seg, (first, jnp.stack(counted(s, cols, drop), axis=1)))
        ids, *cols = unstable(jnp.where(last, s, drop), *tot.T)
        return ids, jnp.stack(cols, axis=1)

    def positions(idx, rows, drop):
        pos = jnp.arange(idx.shape[0], dtype=jnp.int32)
        s, order = unstable(idx, pos)
        first, last = store._run_ends(s)
        cols = store._run_sums(first, counted(
            s, tuple(jnp.take(rows, order, axis=0).T), drop))
        ids, order = unstable(jnp.where(last, s, drop), pos)
        return ids, jnp.take(jnp.stack(cols, axis=1), order, axis=0)

    def ones_through_both_sorts(idx, rows, drop):
        s, *cols = unstable(idx, *counted(idx, tuple(rows.T), drop))
        first, last = store._run_ends(s)
        ids, *cols = unstable(jnp.where(last, s, drop),
                              *store._run_sums(first, tuple(cols)))
        return ids, jnp.stack(cols, axis=1)

    def sort_once(idx, rows, drop):
        s, *cols = unstable(idx, *rows.T)
        return s, jnp.stack(cols, axis=1)

    def sorts_no_scan(idx, rows, drop):
        s, *cols = unstable(idx, *rows.T)
        ids, *cols = unstable(jnp.where(store._run_ends(s)[1], s, drop),
                              *counted(s, cols, drop))
        return ids, jnp.stack(cols, axis=1)

    return {"shipped": store._sum_id_runs, "assoc_scan": assoc_scan,
            "positions": positions,
            "ones_through_both_sorts": ones_through_both_sorts,
            "sort_once": sort_once, "sorts_no_scan": sorts_no_scan}


def _fold_probe_ops(R, B, live):
    """``name -> (op(table, ids) -> table, ids wanted)`` of every probe at
    one shape; ids wanted: ``"front"`` / ``"between"`` as the summed runs
    leave them (:func:`_compacted`), ``"raw"`` as they are pushed."""
    import fps_tpu.ops as ops

    def fold_with(scatter):
        def op(t, i):
            acc = scatter(jnp.broadcast_to(lax.optimization_barrier(
                jnp.zeros((), jnp.float32)), (R, FOLD_D + 1)), i,
                _fold_rows_counted(i, R))
            return jnp.where(acc[:, FOLD_D:] > 0, t + acc[:, :FOLD_D], t)
        return op

    def blocks(C):
        def scatter(z, i, d):
            keep = ops.XLA_SORTED_BLOCK_IDS
            ops.XLA_SORTED_BLOCK_IDS = C
            try:
                return ops._xla_sorted_scatter_add(z, i, d)
            finally:
                ops.XLA_SORTED_BLOCK_IDS = keep
        return scatter

    def flagged(**kw):
        return lambda z, i, d: z.at[i].add(d, mode="drop", **kw)

    def prefix(n):
        return lambda z, i, d: ops.scatter_add(z, i[:n], d[:n])

    def runs_alone(sum_runs):
        def op(t, i):
            idx, cols = sum_runs(i, _fold_rows(i), R)
            return lax.dynamic_update_slice(
                t, (jnp.sum(cols) + jnp.sum(idx) * 1e-9)[None, None] * 1e-9,
                (0, 0))
        return op

    out = {"plain_unsorted": (fold_with(ops.scatter_add), "raw"),
           "a_plain_sorted_sentinel_between": (fold_with(ops.scatter_add),
                                               "between")}
    for name, sc in (
            ("a_plain_sorted_sentinel_tail", ops.scatter_add),
            ("a_told_sorted", flagged(indices_are_sorted=True)),
            ("a_told_sorted_unique", flagged(indices_are_sorted=True,
                                             unique_indices=True)),
            ("b_blocks_1024", blocks(1_024)),
            ("b_blocks_4096", blocks(4_096)),
            ("b_blocks_16384", blocks(16_384)),
            ("c_ladder_4", lambda z, i, d: _ladder_scatter(z, i, d, 4)),
            ("c_ladder_16", lambda z, i, d: _ladder_scatter(z, i, d, 16)),
            ("static_prefix_live", prefix(-(-live // 1024) * 1024)),
            ("static_prefix_quarter", prefix(B // 4))):
        out[name] = (fold_with(sc), "front")
    for name, fn in _runs_variants().items():
        out[f"runs_{name}"] = (runs_alone(fn), "raw")
    return out


def fold_probes():
    """The probes behind ``push.acc_runs``' last step, at the cell's own
    shape (``[1000000, 3]`` zeros, 425,997 ids of ``lr-criteo.epochs``'s
    own columns): us a step of each way to scatter the summed runs, and of
    each way to sum them (``runs_*``: no scatter). ``(a)`` the plain
    scatter-add handed the distinct ids sorted and the sentinel after;
    ``(b)`` the block loop of ``scatter_add.xla_sorted`` on the zeros as
    its carry; ``(c)`` the ladder of static prefixes."""
    R, T = LR_CRITEO[0], 16
    raw = _criteo_ids(T)
    front = _compacted(raw, R)
    live = int(np.mean(np.sum(front < R, axis=1)))
    out = {"rows": R, "ids": raw.shape[1], "live": live}
    runners = {"raw": _fold_runner(R, raw), "front": _fold_runner(R, front),
               "between": _fold_runner(R, _compacted(raw, R, front=False))}
    for name, (op, wanted) in _fold_probe_ops(
            R, raw.shape[1], live).items():
        out[f"{name}_us"], _ = runners[wanted](op)
        print(f"  {name}: {out[f'{name}_us']}", flush=True)
    return out


def fold_sweep(args):
    """``fold``: the stateful fold's push by the plain accumulator against
    ``push.acc_runs`` over rows x ids at width 2, uniform and Zipf(1.05)
    ids (the sweep that set ``ops.ACC_RUNS_MIN_IDS_PER_ROW`` and widened
    ``ops._route_xla_sorted`` to narrow rows), then the cell's own shape
    and columns and ``mf-netflix.x4``'s shard push (inside XLA's VMEM
    regime, the route forced). ``fold edge``: the points round the grid
    alone. ``fold probes``: the ways to scatter and to sum the runs at the
    cell's shape. ``fold quick``: the cell's shape alone. One JSON line a
    point, all in ``chiprun_out/bench_scatter_fold.jsonl``."""
    cell = (fold_point, (LR_CRITEO[0], LR_CRITEO[1] * 26 + 13, "criteo"))
    edge = [(fold_point, (*p, dist)) for p in FOLD_EDGE
            for dist in ("uniform", "zipf")] + [
                (fold_point, (*FOLD_EDGE[-1], "criteo_x4"))]
    if args == ["probes"]:
        points = [(fold_probes, ())]
    elif args == ["quick"]:
        points = [cell]
    elif args == ["edge"]:
        points = edge
    else:
        points = [(fold_point, (R, B, dist)) for dist in ("uniform", "zipf")
                  for B in FOLD_B for R in FOLD_R] + edge + [
                      cell, (fold_point, MF_X4)]
    _write_points("fold", points)


if __name__ == "__main__":
    import sys

    if sys.argv[1:] == ["dim1"]:
        dim1_shapes()
    elif sys.argv[1:2] == ["rows"]:
        rows_sweep(sys.argv[2:])
    elif sys.argv[1:2] == ["mean"]:
        mean_sweep(sys.argv[2:])
    elif sys.argv[1:2] == ["wide"]:
        wide_sweep(sys.argv[2:])
    elif sys.argv[1:2] == ["fold"]:
        fold_sweep(sys.argv[2:])
    else:
        raise SystemExit(
            f"unknown args {sys.argv[1:]!r} — usage: bench_scatter.py "
            "dim1|rows [quick|dlrm [sums [a]|edge|pull]]|mean [counts]|wide [quick]|"
            "fold [quick|edge|probes]  ('dim1' = "
            "scalar-table PA shape; 'rows' = plain XLA against the "
            "lane-packed XLA route over table rows x row width; 'mean' = "
            "the mean push's accumulator against its row branch; 'wide' = "
            "the plain scatter-add against the sorted route on wide rows; "
            "'fold' = the accumulator body's plain scatter against the "
            "pushed rows summed by id run first)"
        )
