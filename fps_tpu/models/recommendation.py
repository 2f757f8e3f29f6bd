"""Top-K recommendation over a sharded factor table.

Reference behavior being rebuilt (SURVEY.md §2 #8): the reference's online-MF
package ships a top-K variant (upstream ``PSOnlineMatrixFactorizationAndTopK``,
expected under ``src/main/scala/hu/sztaki/ilab/ps/matrix/factorization/``)
that, alongside training, emits the current top-K items for a user by scoring
the user's factor vector against the item factors held on the servers.

TPU-native design — instead of the reference's per-rating pull-everything
scoring on one worker, ranking is a sharded dense score + distributed top-k
merge, all on-device:

* each shard scores the queries against **its own rows only**:
  ``(B, dim) @ (rps, dim)^T`` — one MXU matmul per shard, no table movement;
* each shard takes a **local top-(k+E)** of its partial scores
  (``E`` = exclusion capacity, so exclusions can never eat into the true
  top-k);
* the ``S*(k+E)`` candidates per query are ``all_gather``-ed over ICI
  (tiny: candidates only, never the table) and merged with a final top-k.

Exclusion (mask the user's already-rated items — the reference's top-K
worker keeps exactly such a seen-set) is per-query: pass ``exclude`` ids,
``-1`` for unused slots.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fps_tpu import ops
from fps_tpu.core.store import ParamStore, phys_to_id
from fps_tpu.parallel.mesh import SHARD_AXIS

Array = jax.Array

NEG_INF = jnp.float32(-3.0e38)

# The most score-block bytes one ranking pass holds: queries are ranked
# ``_SCORE_BLOCK_BYTES // (4 * rows)`` at a time (472 over 17,770 rows), so
# the ``(queries, rows)`` block does not grow with the number of queries.
_SCORE_BLOCK_BYTES = 32 << 20


def build_topk_fn(store: ParamStore, table: str, k: int,
                  exclude_capacity: int = 0):
    """Compile ``(tables, queries, exclude) -> (ids, scores)`` top-k ranking.

    Args:
      store: the :class:`ParamStore` holding ``table`` (its mesh is used).
      table: name of the ``(num_ids, dim)`` factor table to rank over.
      k: results per query.
      exclude_capacity: max exclusion ids per query (0 disables the
        ``exclude`` argument's effect; slots of ``-1`` are ignored).

    Returns:
      A jitted function ``fn(tables, queries, exclude)``:
        * ``queries`` — ``(B, dim)`` float query vectors (user factors),
        * ``exclude`` — ``(B, exclude_capacity)`` int32 ids to mask
          (pass an all ``-1`` array when unused),
      returning ``(ids (B, k) int32, scores (B, k))``, best first.
    """
    mesh = store.mesh
    spec = store.specs[table]
    num_shards = store.num_shards
    cand = k + exclude_capacity
    table_specs = {name: P(SHARD_AXIS, None) for name in store.specs}

    def device_fn(tables, queries, exclude):
        local = tables[table]  # (rps, dim) this shard's block
        top_s, top_ids = _score_and_local_topk(
            local, queries, num_shards=num_shards, num_ids=spec.num_ids,
            n=cand,
        )  # (B, n_local)

        # Merge: gather every shard's candidates (concat along axis 1).
        with jax.named_scope("topk.merge"):
            all_s = lax.all_gather(top_s, SHARD_AXIS, axis=1, tiled=True)
            all_i = lax.all_gather(top_ids, SHARD_AXIS, axis=1, tiled=True)

            if exclude_capacity:
                hit = jnp.any(
                    all_i[:, :, None] == exclude[:, None, :], axis=-1
                )  # (B, S*n_local)
                all_s = jnp.where(hit, NEG_INF, all_s)

            return _merge_topk(all_s, all_i, k)

    shmapped = jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(table_specs, P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(shmapped)


def recommend_topk(
    store: ParamStore,
    table: str,
    queries: np.ndarray,
    k: int,
    *,
    exclude: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """One-shot host API: rank ``table`` rows for ``queries``.

    ``exclude`` is an optional ``(B, E)`` int array of ids to mask per query
    (``-1`` = unused slot). Returns ``(ids, scores)`` as numpy arrays.

    The online analog of streaming top-K emission: call this between chunks
    (the tables passed are the live sharded arrays — no copies are made).
    """
    B = len(queries)
    E = 0 if exclude is None else int(np.asarray(exclude).shape[1])
    # Memoize the compiled program on the store (repeated streaming calls
    # between training chunks must not re-trace/re-compile).
    cache = store.__dict__.setdefault("_topk_fns", {})
    cache_key = (table, k, E)
    fn = cache.get(cache_key)
    if fn is None:
        fn = cache[cache_key] = build_topk_fn(store, table, k, exclude_capacity=E)
    replicated = NamedSharding(store.mesh, P())
    q = jax.device_put(jnp.asarray(queries), replicated)
    ex = jax.device_put(
        jnp.asarray(
            exclude if exclude is not None else np.full((B, 1), -1), jnp.int32
        ),
        replicated,
    )
    ids, scores = fn(store.tables, q, ex)
    return np.asarray(ids), np.asarray(scores)


def _score_and_local_topk(local, queries, *, num_shards, num_ids, n):
    """Shared per-shard scoring block: score ``queries`` against this
    shard's rows (one matrix product, float32 at ``precision=HIGHEST``:
    the TPU's default would round both operands to bfloat16 and rank by
    scores good to three digits), mask padding rows, and take the EXACT
    local top-``n`` (``lax.top_k``; never ``approx_max_k``) with logical
    ids, best first. Queries go through in blocks of bounded size
    (:data:`_SCORE_BLOCK_BYTES`), so the score block's memory does not grow
    with their number. Used by both the replicated-query ranking
    (:func:`build_topk_fn`) and the per-worker tap path, so masking /
    id-translation fixes cannot drift between them. Device scopes:
    ``topk.score`` (the product and the mask), ``topk.select``."""
    rps = local.shape[0]
    me = lax.axis_index(SHARD_AXIS)
    phys = me * rps + jnp.arange(rps, dtype=jnp.int32)
    ids = phys_to_id(phys, num_shards, rps)
    rows = local.astype(jnp.float32)
    n_local = min(n, rps)

    def rank(block):
        with jax.named_scope("topk.score"):
            scores = jnp.matmul(block, rows.T,
                                precision=lax.Precision.HIGHEST)
            scores = jnp.where((ids < num_ids)[None, :], scores, NEG_INF)
        with jax.named_scope("topk.select"):
            top_s, top_i = lax.top_k(scores, n_local)
            return top_s, jnp.take(ids, top_i)

    queries = queries.astype(jnp.float32)
    q = queries.shape[0]
    blocks = -(-q // max(8, _SCORE_BLOCK_BYTES // (4 * rps)))
    if blocks <= 1:
        return rank(queries)
    per = -(-q // blocks)
    padded = jnp.pad(queries, ((0, blocks * per - q), (0, 0)))
    top_s, top_ids = lax.map(rank, padded.reshape(blocks, per, -1))
    return (top_s.reshape(blocks * per, n_local)[:q],
            top_ids.reshape(blocks * per, n_local)[:q])


def _pad_to_k(ids, scores, k):
    """Out to the ``(B, k)`` contract with the "no candidate" sentinels
    (-1 ids, NEG_INF scores) where the pool held fewer than ``k``."""
    short = k - scores.shape[1]
    if short > 0:
        pad = ((0, 0), (0, short))
        scores = jnp.pad(scores, pad, constant_values=NEG_INF)
        ids = jnp.pad(ids, pad, constant_values=-1)
    return ids.astype(jnp.int32), scores


def _merge_topk(scores, ids, k):
    """Final cross-shard merge: top-``k`` of the ``(B, S*n_local)`` candidate
    pool. On a small table (rows_per_shard < k/S) the pool can undershoot
    ``k``, and ``lax.top_k(x, k)`` with ``k > x.shape[-1]`` fails at trace
    time with an opaque XLA error — clamp, then pad back out to the (B, k)
    contract (:func:`_pad_to_k`). Shared by :func:`build_topk_fn` and
    :func:`_topk_local_queries` so the clamp cannot drift between them."""
    out_s, out_j = lax.top_k(scores, min(k, scores.shape[1]))
    return _pad_to_k(jnp.take_along_axis(ids, out_j, axis=1), out_s, k)


def _topk_local_queries(local, queries, *, num_shards, num_ids, k):
    """Device-side top-k for PER-WORKER queries (inside shard_map).

    Unlike :func:`build_topk_fn` (replicated queries), every worker here
    ranks its OWN ``(q, dim)`` queries: queries are all-gathered across the
    shard axis so each shard scores its rows against everyone's queries,
    local candidates are exchanged, and each worker merges the slice
    belonging to its queries (scope ``topk.merge``). Candidate traffic
    only — the table never moves. On ONE shard the local selection is the
    answer, best first already: nothing is exchanged or merged.
    """
    if num_shards == 1:
        top_s, top_ids = _score_and_local_topk(
            local, queries, num_shards=1, num_ids=num_ids, n=k)
        return _pad_to_k(top_ids, top_s, k)
    me = lax.axis_index(SHARD_AXIS)
    q = queries.shape[0]
    with jax.named_scope("topk.merge"):
        q_all = lax.all_gather(queries, SHARD_AXIS, tiled=True)  # (S*q, dim)
    top_s, top_ids = _score_and_local_topk(
        local, q_all, num_shards=num_shards, num_ids=num_ids, n=k
    )  # (S*q, n_local)

    with jax.named_scope("topk.merge"):
        all_s = lax.all_gather(top_s, SHARD_AXIS)  # (S, S*q, n_local)
        all_i = lax.all_gather(top_ids, SHARD_AXIS)
        mine_s = lax.dynamic_slice_in_dim(all_s, me * q, q, axis=1)
        mine_i = lax.dynamic_slice_in_dim(all_i, me * q, q, axis=1)
        mine_s = mine_s.transpose(1, 0, 2).reshape(q, -1)  # (q, S*n_local)
        mine_i = mine_i.transpose(1, 0, 2).reshape(q, -1)
        return _merge_topk(mine_s, mine_i, k)


def make_online_topk_tap(store: ParamStore, table: str, k: int, *,
                         every: int, query_fn):
    """Build a ``TrainerConfig.step_tap`` emitting top-K INSIDE the loop.

    The reference's ``...AndTopK`` jobs emit the current top-K items for
    the users being trained, interleaved with training on the output
    stream: for a rating event FIRST the user's list, THEN the SGD step on
    it. This tap reproduces that shape: every ``every`` steps each worker
    ranks ``query_fn``'s queries against the sharded table as the step
    BEFORE left it (the driver hands a tap the step's pre-update view:
    ``TrainerConfig.step_tap``) and the results ride the metrics stream
    (leaves ``(T, W, q, k)`` after the driver's per-worker gather), exact
    over every row of the table, best first; off-cadence steps emit ``-1``
    ids and ``NEG_INF`` scores and skip the ranking work entirely
    (``lax.cond``; ``every=1`` ranks unconditionally and lowers no
    ``cond``). A padding query (``query_fn`` answers id ``-1`` for it)
    emits the same sentinels and is counted, per worker and step, in the
    leaf ``topk_padding``.

    ``query_fn(batch, local_state) -> (query_ids (q,) int32,
    queries (q, dim))`` — e.g. the first q users of the worker's current
    batch with their local factor rows (:func:`mf_topk_query_fn`).

    Once a traced program the tap logs a route, ``tap.topk`` (rows ranked,
    K, queries a worker and step, ``shards=S``), in ``fps_tpu.ops``' route
    log; on the host its counts (``tap.journal``: ``topk_answered``,
    ``topk_padding``) land on the epoch's or chunk's journal event.
    """
    num_shards = store.num_shards
    num_ids = store.specs[table].num_ids
    if every < 1:
        raise ValueError(f"every must be at least 1, got {every}")

    def tap(tables, batch, local_state, t):
        qids, queries = query_fn(batch, local_state)
        qids = qids.astype(jnp.int32)
        q = queries.shape[0]
        ops.log_route("tap", "topk", num_ids, k, q, f"shards={num_shards}")

        def skip(_):
            return (jnp.full((q, k), -1, jnp.int32),
                    jnp.full((q, k), NEG_INF), jnp.int32(0))

        def emit(_):
            ids, scores = _topk_local_queries(
                tables[table], queries,
                num_shards=num_shards, num_ids=num_ids, k=k,
            )
            live = (qids >= 0)[:, None]
            return (jnp.where(live, ids, -1),
                    jnp.where(live, scores, NEG_INF),
                    jnp.sum(qids < 0, dtype=jnp.int32))

        if every == 1:
            ids, scores, padding = emit(None)
        else:
            on = (t % every) == 0
            ids, scores, padding = lax.cond(on, emit, skip, None)
            qids = jnp.where(on, qids, -1)
        return {
            "topk_query": qids,
            "topk_ids": ids,
            "topk_scores": scores,
            "topk_padding": padding,
        }

    tap.journal = topk_journal
    return tap


def topk_journal(tapped) -> dict:
    """A call's counts from the top-K tap's host output: lists answered
    (live queries on the cadence) and padding queries that asked."""
    return {
        "topk_answered": int(np.sum(np.asarray(tapped["topk_query"]) >= 0)),
        "topk_padding": int(np.sum(np.asarray(tapped["topk_padding"]))),
    }


def mf_topk_query_fn(num_workers: int, num_queries: int):
    """Query fn for MF: the first ``num_queries`` rows of the worker's
    batch, each user with its worker-local factor row (no communication;
    the read is the store's ``pull_local``, under ``fps.ops``).

    Padding rows (``weight == 0``) emit query id ``-1``: a padded slot's
    user id belongs to ANOTHER worker's routing domain, so its local
    factor-row lookup would silently rank with a different user's vector
    — the tap answers such a query with sentinels and counts it."""
    from fps_tpu.core.store import pull_local

    def query_fn(batch, local_state):
        if num_queries > batch["user"].shape[0]:
            raise ValueError(
                f"num_queries={num_queries} exceeds the worker's batch of "
                f"{batch['user'].shape[0]} rows")
        users = batch["user"][:num_queries].astype(jnp.int32)
        valid = batch["weight"][:num_queries] > 0
        qids = jnp.where(valid, users, -1)
        qvecs = pull_local(local_state, users, num_shards=num_workers)
        return qids, qvecs

    return query_fn


def mf_user_vectors(
    user_factors_global: np.ndarray, num_workers: int, users: np.ndarray
) -> np.ndarray:
    """Extract user factor rows from MF's worker-sharded local state.

    MF keeps user vectors worker-local in owner-major cyclic layout
    (``fps_tpu.models.matrix_factorization``); this resolves logical user
    ids to their physical rows for use as top-k ``queries``.
    """
    table = np.asarray(user_factors_global)
    rps = table.shape[0] // num_workers
    users = np.asarray(users)
    return table[(users % num_workers) * rps + users // num_workers]
