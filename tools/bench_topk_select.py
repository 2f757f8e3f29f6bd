"""On-chip probe of the exact top-K selection (``recommendation.
_score_and_local_topk``), in the manner of ``tools/bench_scatter.py``: each
timed call is a scan of T iterations whose queries are nudged by a value
read from the last result (nothing hoisted out of the loop, nothing
deduplicated), fenced by a host read. Reports ms an iteration.

Every form makes its scores itself (the float32 product at HIGHEST of
``[q, dim]`` queries with a ``[rows, dim]`` table), as the program does each
step; the ``product`` lines say what that alone costs at each width.

``widths`` arm: XLA's ``TopK`` custom call (``lax.top_k``) on ``[q, w]`` at
k over widths ``w`` -- what pruning's premise rests on (is its time linear
in ``w``, as it is in ``q``?).

``forms`` arm: the pruned selection stage by stage at ``[q, rows]``, k, over
chunk lengths ``c``: the strided scores ``[q, c, C]`` (chunk ``j`` = rows
``j, j + C, ...``, ``C`` whole tiles of 128 lanes), the chunk maxima, their ``TopK``, the candidate fetch by
each form tried, the candidates' ``TopK`` and the translation to positions.
Each line is CUMULATIVE (the stages before it included); the flat forms
(chunks of ``c`` consecutive columns of ``[q, rows]``) beside them.

``program`` arm: ``_score_and_local_topk`` itself (what shipped) against
the direct ``lax.top_k`` over the whole block, on a one-device mesh.

    chiprun --chips 1 -- python tools/bench_topk_select.py            # all
    chiprun --chips 1 -- python tools/bench_topk_select.py widths
    chiprun --chips 1 -- python tools/bench_topk_select.py program forms:16:onehot,kernel
"""

import os
import sys
import time

# `python tools/bench_topk_select.py` puts tools/ (not the repo root) on
# sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np
from jax import lax

T = 64
Q, ROWS, DIM, K = 256, 17_770, 10, 100
WIDTHS = (128, 512, 640, 1_024, 1_111, 1_152, 1_600, 2_048, 2_222, 3_072,
          4_096, 4_443, 8_885, 17_770)
CHUNKS = (8, 16, 32)
HIGHEST = lax.Precision.HIGHEST
NEG = -3.0e38


def timeit(step, *consts):
    """ms an iteration of ``step(queries, *consts)`` in a scan of ``T``."""
    @jax.jit
    def run(qs, *consts):
        def body(nudge, _):
            out = step(qs + nudge, *consts)
            top = jnp.max(jnp.stack(
                [jnp.max(x).astype(jnp.float32)
                 for x in jax.tree.leaves(out)]))
            return jnp.clip(top, -1.0, 1.0) * 1e-12, None
        return lax.scan(body, jnp.float32(0), None, length=T)[0]

    rng = np.random.default_rng(7)
    qs = jnp.asarray(rng.normal(0, 0.3, (Q, DIM)), jnp.float32)
    float(run(qs, *consts))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        float(run(qs, *consts))
        best = min(best, time.perf_counter() - t0)
    return best / T * 1e3


def table(rows):
    rng = np.random.default_rng(rows)
    return jnp.asarray(rng.normal(0, 0.3, (rows, DIM)), jnp.float32)


def flat_scores(qs, tab):
    live = jnp.arange(tab.shape[0]) < ROWS
    return jnp.where(live[None], jnp.matmul(
        qs, tab.T, precision=HIGHEST), NEG)


def widths():
    print(f"lax.top_k on [{Q}, w] at k {K}; product = the scores alone")
    print(f"{'w':>7s} {'product':>9s} {'+top_k':>9s} {'top_k':>9s} "
          f"{'ns/score':>9s}")
    for w in WIDTHS:
        tab = table(w)
        base = timeit(flat_scores, tab)
        both = timeit(lambda qs, t: lax.top_k(flat_scores(qs, t), min(K, w)),
                      tab)
        print(f"{w:7d} {base:9.4f} {both:9.4f} {both - base:9.4f} "
              f"{(both - base) * 1e6 / (Q * w):9.3f}", flush=True)


# -- the pruned selection's stages -------------------------------------------

def strided_scores(qs, tab_v):
    """``[q, c, C]``: chunk ``j`` = rows ``j, j + C, ...`` of the table,
    viewed ``[c, C, dim]``; the chunks on the lanes."""
    c, C, _ = tab_v.shape
    live = (jnp.arange(c)[:, None] * C + jnp.arange(C)[None, :]) < ROWS
    return jnp.where(live[None], jnp.einsum(
        "qd,icd->qic", qs, tab_v, precision=HIGHEST), NEG)


def fetch_scalars(s, j):
    """XLA's gather an element: ``q * c * n`` scalars."""
    q, c, _ = s.shape
    idx = jnp.broadcast_to(j[:, None, :], (q, c, j.shape[1]))
    return jnp.take_along_axis(s, idx, axis=2)


def fetch_columns(s, j):
    """XLA's gather a column: ``q * n`` slices of ``c`` down the sublanes."""
    return jax.vmap(lambda sq, jq: sq[:, jq])(s, j)


def fetch_onehot(s, j):
    """A one-hot product at HIGHEST (exact: one term a sum)."""
    hot = (j[:, :, None] == jnp.arange(s.shape[2])[None, None, :]
           ).astype(jnp.float32)
    return jnp.einsum("qic,qtc->qit", s, hot, precision=HIGHEST)


def fetch_onehot_padded(s, j):
    """The one-hot product with ``n`` out to whole tiles of 128 lanes (the
    candidates then reshape to ``[q, c * 128]`` in place)."""
    n = j.shape[1]
    j = jnp.pad(j, ((0, 0), (0, -n % 128)), constant_values=-1)
    return jnp.where(jnp.arange(j.shape[1]) < n, fetch_onehot(s, j), -jnp.inf)


def fetch_onehot_split(s, j):
    """The one-hot product as ONE bfloat16 pass over the scores split in
    three bfloat16 addends (``hi + mid + lo`` is the float32 exactly)."""
    hi = s.astype(jnp.bfloat16)
    r1 = s - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    parts = jnp.concatenate([hi, mid, lo], axis=1)  # [q, 3c, C]
    hot = (j[:, :, None] == jnp.arange(s.shape[2])[None, None, :]
           ).astype(jnp.bfloat16)
    got = jnp.einsum("qic,qtc->qit", parts, hot,
                     preferred_element_type=jnp.float32)
    c = s.shape[1]
    return (got[:, :c] + got[:, c:2 * c]) + got[:, 2 * c:]


def fetch_kernel(s, j):
    """What shipped: the Mosaic kernel that shuffles each tile of 128
    chunks by the lane gather (``recommendation._fetch_chunks``)."""
    from fps_tpu.models import recommendation as rec

    return rec._fetch_chunks(s, j)


FETCHES = {
    "scalars": fetch_scalars,
    "columns": fetch_columns,
    "onehot": fetch_onehot,
    "onehot_padded": fetch_onehot_padded,
    "onehot_split": fetch_onehot_split,
    "kernel": fetch_kernel,
}


def _pick(j, t):
    """``j[q, t[q, r]]`` without a gather: a one-hot sum over ``n``."""
    n = j.shape[1]
    return jnp.sum(jnp.where(t[:, :, None] == jnp.arange(n)[None, None, :],
                             j[:, None, :], 0), axis=-1)


def strided_select(fetch, s, n):
    """The whole pruned selection over strided scores: scores and
    positions ``i * C + j`` of the ``n`` best."""
    q, c, C = s.shape
    m = jnp.max(s, axis=1)
    _, j = lax.top_k(m, n)
    cand = fetch(s, j)
    w = cand.shape[2]
    top_s, f = lax.top_k(cand.reshape(q, c * w), n)
    return top_s, (f // w) * C + _pick(j, f % w)


def flat_fetch_slices(s, j, c):
    """XLA's gather a slice: ``q * n`` slices of ``c`` along the lanes."""
    return jax.vmap(lambda row, jq: jax.vmap(
        lambda one: lax.dynamic_slice(row, (one * c,), (c,)))(jq))(s, j)


def flat_select(maxima, s, n, c):
    q, rows = s.shape
    m = maxima(s, c)
    _, j = lax.top_k(m, n)
    cand = flat_fetch_slices(s, j, c)  # [q, n, c]
    top_s, f = lax.top_k(cand.reshape(q, n * c), n)
    return top_s, _pick(j, f // c) * c + f % c


def flat_max_reshape(s, c):
    return jnp.max(s.reshape(s.shape[0], -1, c), axis=2)


def flat_max_window(s, c):
    return lax.reduce_window(s, -jnp.inf, lax.max, (1, c), (1, c), "VALID")


def forms(chunks=CHUNKS, fetches=tuple(FETCHES)):
    tab = table(ROWS)
    direct = timeit(lambda qs, t: lax.top_k(flat_scores(qs, t), K), tab)
    ids = jnp.arange(ROWS, dtype=jnp.int32)

    def parent(qs, t, ids):
        top_s, top_i = lax.top_k(flat_scores(qs, t), K)
        return top_s, jnp.take(ids, top_i)

    probe = jnp.asarray(
        np.random.default_rng(11).normal(0, 0.3, (Q, DIM)), jnp.float32)
    want = np.asarray(lax.top_k(flat_scores(probe, tab), K)[0])
    print(f"[{Q}, {ROWS}] k {K}, ms an iteration, each line CUMULATIVE")
    print(f"direct: product + top_k {direct:.4f}; + the id gather "
          f"{timeit(parent, tab, ids):.4f}", flush=True)
    for c in chunks:
        C = -(-ROWS // (c * 128)) * 128  # whole tiles of 128 lanes
        padded = jnp.pad(tab, ((0, c * C - ROWS), (0, 0))).reshape(c, C, DIM)
        print(f"c {c}: C {C}, C + n*c = {C + K * c}", flush=True)

        def run(name, step, *consts):
            try:
                ms = timeit(step, *consts)
                same = ""
                if name.endswith(("positions", "whole")):
                    got = np.asarray(jax.jit(step)(probe, *consts)[0])
                    same = f"  scores equal the direct: {np.array_equal(got, want)}"
                print(f"  {name:34s} {ms:9.4f}{same}", flush=True)
            except Exception as e:  # a form the compiler refuses is a finding
                print(f"  {name:34s} refused: {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)

        run("strided product", strided_scores, padded)
        run("+ maxima", lambda qs, t: jnp.max(strided_scores(qs, t), axis=1),
            padded)
        run("+ top_k of the maxima",
            lambda qs, t: lax.top_k(jnp.max(strided_scores(qs, t), axis=1), K),
            padded)
        for name in fetches:
            fetch = FETCHES[name]
            def fetched(qs, t, fetch=fetch):
                s = strided_scores(qs, t)
                return fetch(s, lax.top_k(jnp.max(s, axis=1), K)[1])

            run(f"+ fetch {name}", fetched, padded)
            run(f"+ fetch {name} + top_k + positions",
                lambda qs, t, fetch=fetch: strided_select(
                    fetch, strided_scores(qs, t), K), padded)
        flat = jnp.pad(tab, ((0, c * C - ROWS), (0, 0)))
        for name, maxima in (("reshape", flat_max_reshape),
                             ("reduce_window", flat_max_window)):
            run(f"flat product + maxima by {name}",
                lambda qs, t, m=maxima: m(flat_scores(qs, t), c), flat)
            run(f"flat, maxima by {name}, slices, whole",
                lambda qs, t, m=maxima: flat_select(m, flat_scores(qs, t),
                                                    K, c), flat)


def program():
    """What shipped against the direct selection, through shard_map on a
    one-device mesh (the tap's own call)."""
    from jax.sharding import PartitionSpec as P

    from fps_tpu.models import recommendation as rec
    from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh

    mesh = make_ps_mesh(num_shards=1)
    tab = table(ROWS)

    def shipped(qs, t):
        return jax.shard_map(
            lambda t, qs: rec._score_and_local_topk(
                t, qs, num_shards=1, num_ids=ROWS, n=K),
            mesh=mesh, in_specs=(P(SHARD_AXIS, None), P()),
            out_specs=(P(), P()), check_vma=False)(t, qs)

    print(f"_score_and_local_topk [{Q}, {ROWS}] k {K}: "
          f"{timeit(shipped, tab):.4f} ms; direct product + top_k "
          f"{timeit(lambda qs, t: lax.top_k(flat_scores(qs, t), K), tab):.4f}",
          flush=True)


if __name__ == "__main__":
    print(f"device: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind}", flush=True)
    # ``program`` first: under JAX 0.9 a program that traces the kernel
    # inside shard_map AFTER one that traced it outside (``forms``) is
    # handed one buffer too few ("supplied 2 buffers but ... expected 3").
    for arm in sys.argv[1:] or ["program", "widths", "forms"]:
        name, *narrow = arm.split(":")  # forms:16,32:onehot,kernel
        if name == "forms" and narrow:
            forms(tuple(int(c) for c in narrow[0].split(",")),
                  *(tuple(f.split(",")) for f in narrow[1:2]))
        else:
            {"widths": widths, "forms": forms, "program": program}[name]()
