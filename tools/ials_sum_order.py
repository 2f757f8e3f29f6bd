"""What the ORDER of an id's float32 sums is worth in iALS's normal
equations, on the CPU (correctness facts, never a rate).

``ials-ml20m.sweeps``'s reference adds a rating's outer product at a time
to sums it carries in float32, in the plan's order. The two modes say what
that order is worth, and why ``models/ials.py`` keeps it (PR 41):

``python tools/ials_sum_order.py chain [SEED]`` (seconds): one id of
27,861 addends at the cell's magnitudes, summed in float32 as one chain in
the plan's order, the same chain reversed inside each of 513 steps, the
chain of addends perturbed in their last bit, and segments of 128 summed
exactly and then chained: each against the float64 sum and against the
first chain, as a share of the sum's largest entry.

``python tools/ials_sum_order.py call SEED`` (about 15 min at the cell's
own size: 3 the program, 4 the reference, 7 the reference in float64;
``IALS_SUM_ORDER_TINY=1`` for a rehearsal): one whole call (a user sweep
and an item sweep) by the program, by the reference as the
benchmark runs it, and by the reference with its sums, Gramian and tables
carried in float64: ``table_gap`` as the cell's comparison reads it (the
largest difference over the largest entry), each pair. Needs the
benchmark's harness (``perfbench/lib``); run from the checkout's root.
"""

import json
import os
import sys

import numpy as np

CELL = "ials-ml20m.sweeps"


def chain(seed: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    k, n, steps, seg = 64, 27_861, 513, 128
    y = (rng.normal(size=(n, k)) * 0.3).astype(np.float32)
    a = (40 * rng.choice(np.arange(0.5, 5.01, 0.5), n)).astype(np.float32)
    add = (a[:, None] * y)[:, :, None] * y[:, None, :]
    exact = add.astype(np.float64).sum(0)

    def chained(addends):
        s = np.zeros((k, k), np.float32)
        for x in addends:
            s += x
        return s

    base = chained(add)
    in_step = np.concatenate(
        [x[::-1] for x in np.array_split(np.arange(n), steps)])
    noise = 1 + rng.uniform(-6e-8, 6e-8, size=add.shape)
    sums = {
        "plan_order": base,
        "reversed_inside_steps": chained(add[in_step]),
        "addends_perturbed_6e-8": chained(
            (add.astype(np.float64) * noise).astype(np.float32)),
        "segments_of_128_then_chained": chained(
            add[lo:lo + seg].astype(np.float64).sum(0).astype(np.float32)
            for lo in range(0, n, seg)),
    }
    top = np.abs(exact).max()
    return {name: {"from_exact": float(np.abs(s - exact).max() / top),
                   "from_plan_order": float(
                       np.abs(s.astype(np.float64) - base).max() / top)}
            for name, s in sums.items()}


def call(seed: int) -> dict:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())
    import jax
    import jax.numpy as jnp

    from perfbench.lib import check, resolve, spec, window

    loaded = spec.load_cell(spec.load_benchmark(), CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    if os.environ.get("IALS_SUM_ORDER_TINY"):
        tiny = {"num_users": 301, "num_items": 97}
        cfg["model"].update(tiny, rank=8, local_batch=64, steps_per_chunk=8)
        cfg["data"].update(tiny, num_ratings=9001, ratings_resident=9001,
                           user_shift=3.0, item_shift=2.0)
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:1]  # the cell runs on one chip
    data, _ = resolve.generator(cfg)(seed, cfg["data"])
    system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    del data
    init = resolve.reference(cfg).init_tables(seed, cfg)

    def factors(tables):
        return {k: np.asarray(v, np.float64) for k, v in sorted(tables.items())
                if k.endswith("factors")}

    state, warm = window.queue_call(system, system.place(init))
    warm.wait()
    program = factors(system.export(*state))
    del state
    ref32 = factors(check.run_reference(system, cfg, init)[0])
    chunks = [jax.tree.map(np.asarray, c)
              for c, _ in system.fed_chunks(0, check.STEPS_PER_CHUNK)]
    jax.config.update("jax_enable_x64", True)
    step = resolve.reference(cfg).make_step(cfg, dtype=jnp.float64,
                                            workers=system.W)
    run_chunk = jax.jit(lambda tables, chunk: jax.lax.scan(
        lambda t, b: (step(t, b)[0], 0), tables, chunk)[0])
    tables = {k: jnp.asarray(np.asarray(v), jnp.float64)
              for k, v in sorted(init.items())}
    for c in chunks:
        tables = run_chunk(tables, c)
    ref64 = factors(tables)

    def gap(a, b):
        return {k: float(np.max(np.abs(a[k] - b[k])) / np.max(np.abs(b[k])))
                for k in a}

    return {"seed": seed,
            "program_from_reference": gap(program, ref32),
            "reference_from_its_float64_sums": gap(ref32, ref64),
            "program_from_float64_sums": gap(program, ref64)}


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "chain"
    if mode == "chain":
        out = chain(*map(int, sys.argv[2:3]))
    elif mode == "call":
        out = call(int(sys.argv[2]))
    else:
        sys.exit(__doc__)
    print(json.dumps(out, indent=1))
