"""Are the accepted cells' step programs the same, instruction for
instruction, in two trees? Compile-only: no chip is touched.

    python tools/step_programs.py write <tree> <out_dir> [cell ...]
    python tools/step_programs.py diff <out_dir_a> <out_dir_b> [cell ...]

``write`` imports ``fps_tpu`` FROM ``tree`` (a checkout: this one, or a
``git archive`` of the parent), builds the step program of each cell of
the benchmark at the cell's own shapes (``mf-netflix.epochs``,
``pa-rcv1.epochs``, ``mf-netflix.x4``, ``w2v-1bw.epochs``,
``lr-criteo.epochs`` and, since PR 36, both accumulate programs of
``ials-ml20m.sweeps``, the ones that push; since PR 45
``mf-netflix-topk.epochs`` and ``w2v-1bw-hot.x4``; since PR 48
``dlrm-criteo.epochs`` and since PR 51 ``kge-wikidata5m.epochs``, each of
which a tree from before it skips; all of them, or the cells named), compiles it for a described
``v5e:2x2`` with the ops layer routing as on the chip, and writes the
compiled text with metadata,
stack frames and location tables dropped, and the route log, under
``out_dir``, and prints the compile's temporary bytes beside the count. One process per tree (a process imports one ``fps_tpu``).
``diff`` counts the instructions of each program and the lines that
differ; what is left are Pallas kernels' debug strings, which hold the
checkout's path: it says so when the two differ in nothing else. Exit 1
if a program or a route log differs otherwise. A PR that changes one
cell's program on purpose names the OTHER cells to ``diff``.

The method is PR 29's (PERF.md section 6); a PR that must leave the other
cells' programs alone shows it this way before it spends chip time.
"""

from __future__ import annotations

import json
import os
import re
import sys

# What one v5e chip's ``memory_stats()["bytes_limit"]`` reads.
V5E_HBM_BYTES = 16_909_336_064

CELLS = ("mf-netflix.epochs", "pa-rcv1.epochs", "mf-netflix.x4",
         "w2v-1bw.epochs", "lr-criteo.epochs", "ials-ml20m.sweeps.user",
         "ials-ml20m.sweeps.item", "mf-netflix-topk.epochs",
         "w2v-1bw-hot.x4", "dlrm-criteo.epochs", "kge-wikidata5m.epochs")


def _normalised(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"stack_frame_id=\d+", "", text)
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith(tables)
                     and not re.match(r"^\d+ ", ln))


def write(tree: str, out: str, cells=CELLS) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    tree = os.path.abspath(tree)
    sys.path.insert(0, tree)
    os.makedirs(out, exist_ok=True)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    jax.config.update("jax_enable_compilation_cache", False)
    import fps_tpu
    import fps_tpu.ops as ops
    from fps_tpu.parallel.mesh import make_ps_mesh

    if not fps_tpu.__file__.startswith(tree + os.sep):
        raise SystemExit(f"imported {fps_tpu.__file__}, not {tree}'s")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    ops._use_pallas = lambda: (True, False)

    def mesh_of(n):
        mesh = make_ps_mesh(num_shards=n, devices=list(topo.devices)[:n])
        return mesh, lambda s, d, spec=P(): jax.ShapeDtypeStruct(
            s, d, sharding=NamedSharding(mesh, spec))

    def emit(name, lower):
        ops.clear_routes()
        compiled = lower().compile()
        text = _normalised(compiled.as_text())
        routes = [list(r) for r in ops.routes_traced()]
        with open(os.path.join(out, name + ".txt"), "w") as f:
            f.write(text)
        with open(os.path.join(out, name + ".routes.json"), "w") as f:
            json.dump(routes, f)
        print(name, sum(" = " in ln for ln in text.splitlines()),
              "instructions", len(routes), "routes",
              compiled.memory_analysis().temp_size_in_bytes, "temp bytes",
              flush=True)

    workers = P(None, ("data", "shard"))

    def ingest_operands(plan, columns, shape):
        """The ingest's operands as ``tree``'s own unkeyed plan over
        ``columns`` hands them to the step on one v5e chip: the resident
        columns, or, where the tree has the sliced branch (PR 50) and its
        predicate takes these shapes, the transposed buffers (``plan`` is
        told which: ``plan.sliced``)."""
        from fps_tpu.core import device_ingest

        take = getattr(device_ingest, "columns_take_slices", None)
        if take is None:
            return {"columns": columns}
        rows = plan.steps_per_epoch * plan.local_batch
        plan.sliced = take(columns, 1, rows, V5E_HBM_BYTES)
        if not plan.sliced:
            return {"columns": columns}
        return {"tbuf": {k: shape((rows,) + c.shape[1:], c.dtype)
                         for k, c in columns.items()}}

    def model(config, part="model"):
        """A group of ``tree``'s own configuration file."""
        with open(os.path.join(tree, "perfbench", "configs",
                               config + ".json")) as f:
            return json.load(f)[part]

    def mf(n, topk=False):
        from fps_tpu.models.matrix_factorization import MFConfig, online_mf

        m = model("mf-netflix-topk" if topk else "mf-netflix")
        U, I, rank = m["num_users"], m["num_items"], m["rank"]
        mesh, shape = mesh_of(n)
        trainer, _ = online_mf(
            mesh, MFConfig(num_users=U, num_items=I, rank=rank,
                           learning_rate=m["learning_rate"], reg=m["reg"]),
            combine=m["combine"])
        if topk:
            import dataclasses

            from fps_tpu.models.recommendation import (
                make_online_topk_tap, mf_topk_query_fn,
            )

            trainer.config = dataclasses.replace(
                trainer.config, step_tap=make_online_topk_tap(
                    trainer.store, "item_factors", m["topk"],
                    every=m["topk_every"], query_fn=mf_topk_query_fn(
                        n, num_queries=m["queries_per_step"])))
        users, items = -(-U // n) * n, -(-I // n) * n
        tables = {"item_factors": shape((items, rank), jnp.float32,
                                        P("shard", None))}
        local = shape((users, rank), jnp.float32, P(("data", "shard")))
        batches = {k: shape((2, m["local_batch"] * n), d, workers)
                   for k, d in (("user", jnp.int32), ("item", jnp.int32),
                                ("rating", jnp.float32),
                                ("weight", jnp.float32))}
        key = shape((), jax.random.key(0).dtype)
        emit("mf-netflix-topk.epochs" if topk
             else "mf-netflix." + ("epochs" if n == 1 else "x4"),
             lambda: trainer._build_chunk_fn("sync").lower(
                 tables, local, batches, key))

    def pa():
        from fps_tpu.models.passive_aggressive import (
            PAConfig, passive_aggressive,
        )

        m = model("pa-rcv1")
        mesh, shape = mesh_of(1)
        trainer, _ = passive_aggressive(
            mesh, PAConfig(num_features=m["num_features"],
                           variant=m["variant"], C=m["C"],
                           hot_features=m["head_features"],
                           head_prefix_cols=m["head_prefix_cols"]))
        tables = {"weights": shape((m["num_features"], 1), jnp.float32,
                                   P("shard", None))}
        B, nnz = m["local_batch"], model("pa-rcv1", "data")["nnz"]
        batches = {"feat_ids": shape((2, B, nnz), jnp.int32, workers),
                   "feat_vals": shape((2, B, nnz), jnp.float32, workers),
                   "label": shape((2, B), jnp.float32, workers),
                   "weight": shape((2, B), jnp.float32, workers)}
        key = shape((), jax.random.key(0).dtype)
        emit("pa-rcv1.epochs",
             lambda: trainer._build_chunk_fn("sync").lower(
                 tables, (), batches, key))

    def w2v(hot=False):
        from fps_tpu.models.word2vec import (
            W2VConfig, Word2VecDevicePlan, word2vec_block,
        )

        m = model("w2v-1bw-hot" if hot else "w2v-1bw")
        # T: the steps of an epoch over the cell's 2^21 resident tokens
        # (the tiered cell's 8,212,000 over four workers: 21 windows).
        V, D, L = m["vocab_size"], m["dim"], m["block_len"]
        W, T = (4, 168) if hot else (1, 172)
        mesh, shape = mesh_of(W)
        cfg = W2VConfig(vocab_size=V, dim=D)
        trainer, store = word2vec_block(mesh, cfg,
                                        1.0 / (jnp.arange(V) + 1.5), L)
        # The plan's geometry without its uploads.
        plan = object.__new__(Word2VecDevicePlan)
        plan.cfg, plan.mode, plan.num_workers, plan.block_len = (
            cfg, "block", W, L)
        plan.steps_per_epoch, plan.sync_every = T, None
        key = shape((), jax.random.key(0).dtype)
        tables = {n: shape((-(-V // W) * W, D), jnp.float32,
                           P("shard", None))
                  for n in ("in_embeddings", "out_embeddings")}
        if hot:
            import argparse

            from fps_tpu.examples.common import apply_hot_tier

            apply_hot_tier(argparse.Namespace(
                hot_tier=m["hot_tier"], hot_sync_every=m["hot_sync_every"],
                cold_budget=m["cold_budget"]), trainer, store)
            tables.update({n + "::hot": shape((m["hot_tier"], D),
                                              jnp.float32)
                           for n in list(tables)})
        iargs = {"compacted": shape((T * L * W + cfg.window,), jnp.int32),
                 "kept": shape((), jnp.int32), "wkey": key}
        emit("w2v-1bw-hot.x4" if hot else "w2v-1bw.epochs",
             lambda: trainer._build_indexed_fn(plan, "sync").lower(
                 tables, (), iargs, jnp.int32(0), key))

    def lr():
        import numpy as np

        from fps_tpu import DeviceEpochPlan
        from fps_tpu.models.logistic_regression import (
            LogRegConfig, logistic_regression,
        )

        m = model("lr-criteo")
        F, B, s = m["num_features"], m["local_batch"], m["sync_every"]
        N = model("lr-criteo", "data")["examples_resident"]
        slots, numeric = 39, m["dense_features"]
        mesh, shape = mesh_of(1)
        trainer, _ = logistic_regression(
            mesh, LogRegConfig(num_features=F,
                               learning_rate=m["learning_rate"],
                               optimizer=m["optimizer"],
                               dense_features=numeric), sync_every=s)
        # The plan's geometry without its uploads.
        plan = object.__new__(DeviceEpochPlan)
        plan.local_batch, plan.shuffle, plan.num_workers = B, "interleave", 1
        plan.sync_every, plan.maxq, plan.grid_r = s, N, 4096
        plan.route_key = None
        plan.counts = np.full(1, N, np.int32)
        plan.grid_c = np.full(1, N // 4096, np.int32)
        plan.grid_m = np.full(1, N, np.int32)
        plan.steps_per_epoch = -(-N // B // s) * s + s
        key = shape((), jax.random.key(0).dtype)
        tables = {"weights": shape((F, 2), jnp.float32, P("shard", None))}
        iargs = {**ingest_operands(plan, {
            "feat_ids": shape((N, slots), jnp.int32),
            "feat_vals": shape((N, slots), jnp.float32),
            "label": shape((N,), jnp.float32)}, shape),
                 "off_w": shape((1,), jnp.int32),
                 "perm": shape((1, 1), jnp.int32)}
        if not hasattr(fps_tpu.core.device_ingest, "unkeyed_queue_rows"):
            # A tree from before PR 46 reads an unkeyed plan's rows from
            # the queue matrix.
            iargs["queues"] = shape((1, N), jnp.int32)
        emit("lr-criteo.epochs",
             lambda: trainer._build_indexed_fn(plan, "ssp").lower(
                 tables, (), iargs, jnp.int32(0), key))

    def dlrm_cell():
        import numpy as np

        from fps_tpu import DeviceEpochPlan

        try:
            from fps_tpu.models.dlrm import DLRMConfig, dlrm
        except ImportError:  # a tree from before PR 48
            print("dlrm-criteo.epochs: no fps_tpu.models.dlrm in this tree",
                  flush=True)
            return
        m, d = model("dlrm-criteo"), model("dlrm-criteo", "data")
        B, N = m["local_batch"], d["examples_resident"]
        cfg = DLRMConfig(field_rows=d["categorical_cardinalities"],
                         embed_dim=m["embed_dim"], numeric=m["numeric"],
                         bottom_mlp=m["bottom_mlp"], top_mlp=m["top_mlp"],
                         learning_rate=m["learning_rate"])
        mesh, shape = mesh_of(1)
        trainer, _ = dlrm(mesh, cfg)
        # The plan's geometry without its uploads.
        grid_r = 1 << min(12, N.bit_length() // 2)
        plan = object.__new__(DeviceEpochPlan)
        plan.local_batch, plan.shuffle, plan.num_workers = B, "interleave", 1
        plan.sync_every, plan.maxq, plan.grid_r = None, N, grid_r
        plan.route_key = None
        plan.counts = np.full(1, N, np.int32)
        plan.grid_c = np.full(1, -(-N // grid_r), np.int32)
        plan.grid_m = plan.grid_c * grid_r
        plan.steps_per_epoch = -(-(N + grid_r) // B)
        key = shape((), jax.random.key(0).dtype)
        tables = {"emb": shape((cfg.num_rows, cfg.embed_dim), jnp.float32,
                               P("shard", None))}
        tables.update({k + "::dense": shape(s, jnp.float32)
                       for k, s in cfg.layer_shapes().items()})
        iargs = {**ingest_operands(plan, {
            "tokens": shape((N, len(cfg.field_rows)), jnp.int32),
            "counts": shape((N, cfg.numeric), jnp.float32),
            "label": shape((N,), jnp.float32)}, shape),
            "off_w": shape((1,), jnp.int32),
            "perm": shape((1, 1), jnp.int32)}
        emit("dlrm-criteo.epochs",
             lambda: trainer._build_indexed_fn(plan, "sync").lower(
                 tables, (), iargs, jnp.int32(0), key))

    def ials():
        from fps_tpu.models.ials import IALSConfig, IALSSolver

        m = model("ials-ml20m")
        NU, NI, K, B = (m["num_users"], m["num_items"], m["rank"],
                        m["local_batch"])
        mesh, shape = mesh_of(1)
        solver = IALSSolver(mesh, IALSConfig(
            num_users=NU, num_items=NI, rank=K, alpha=m["alpha"],
            reg=m["reg"]))

        def table(rows, dim):
            return shape((rows, dim), jnp.float32, P("shard", None))

        chunk = {k: shape((m["steps_per_chunk"], B),
                          jnp.int32 if k.endswith("ids") else jnp.float32,
                          workers)
                 for k in ("solve_ids", "fixed_ids", "rating", "weight")}
        for side, n, other in (("user", NU, NI), ("item", NI, NU)):
            emit("ials-ml20m.sweeps." + side,
                 lambda: solver._accumulate_fn(side).lower(
                     table(other, K), table(n, K), table(n, K * K),
                     table(n, K), chunk))

    def kge_cell():
        try:
            from fps_tpu.models.kge import KGEConfig, kge
        except ImportError:  # a tree from before PR 51
            print("kge-wikidata5m.epochs: no fps_tpu.models.kge in this "
                  "tree", flush=True)
            return
        m = model("kge-wikidata5m")
        E, R, D, B = m["entities"], m["relations"], 2 * m["rank"], m[
            "local_batch"]
        mesh, shape = mesh_of(1)
        trainer, _ = kge(mesh, KGEConfig(
            num_entities=E, num_relations=R, rank=m["rank"],
            negatives=m["negatives"], l2=m["l2"],
            learning_rate=m["learning_rate"], eps=m["eps"]))
        # Both tables beside their own fold's state (``<table>::fold``).
        tables = {name + key: shape((rows, D), jnp.float32, P("shard", None))
                  for name, rows in (("entity", E), ("relation", R))
                  for key in ("", "::fold")}
        batches = {k: shape((2, B), d, workers) for k, d in (
            ("s", jnp.int32), ("r", jnp.int32), ("o", jnp.int32),
            ("weight", jnp.float32))}
        emit("kge-wikidata5m.epochs",
             lambda: trainer._build_chunk_fn("sync").lower(
                 tables, (), batches, shape((), jax.random.key(0).dtype)))

    builders = dict(zip(CELLS, (
        lambda: mf(1), pa, lambda: mf(4), w2v, lr, ials, ials,
        lambda: mf(1, topk=True), lambda: w2v(hot=True), dlrm_cell,
        kge_cell)))
    # (ials builds both its programs: once, whichever is asked for)
    for build in dict.fromkeys(builders[name] for name in CELLS
                               if name in cells):
        build()


def diff(a: str, b: str, cells=CELLS) -> int:
    worse = 0
    for name in cells:
        if not all(os.path.exists(os.path.join(d, name + ".txt"))
                   for d in (a, b)):
            print(name, "is not in both trees; skipped")
            continue
        with open(os.path.join(a, name + ".txt")) as f:
            ta = f.read().splitlines()
        with open(os.path.join(b, name + ".txt")) as f:
            tb = f.read().splitlines()
        with open(os.path.join(a, name + ".routes.json")) as f:
            ra = json.load(f)
        with open(os.path.join(b, name + ".routes.json")) as f:
            rb = json.load(f)
        differ = [(x, y) for x, y in zip(ta, tb) if x != y]
        # A Mosaic body carries the checkout's path in its debug strings.
        other = [p for p in differ if "custom_call_target=\"tpu_custom_call\""
                 not in p[0] or "custom_call_target=\"tpu_custom_call\""
                 not in p[1]]
        same = len(ta) == len(tb) and not other and ra == rb
        print(name, sum(" = " in ln for ln in ta), "instructions;",
              f"{len(differ)} lines differ, all of them Mosaic bodies;"
              if differ and not other else f"{len(other)} lines differ;",
              "route logs", "equal" if ra == rb else "DIFFER")
        worse += not same
    return 1 if worse else 0


def main(argv) -> int:
    cells = tuple(argv[3:]) or CELLS
    if len(argv) >= 3 and set(cells) <= set(CELLS):
        if argv[0] == "write":
            write(argv[1], argv[2], cells)
            return 0
        if argv[0] == "diff":
            return diff(argv[1], argv[2], cells)
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
