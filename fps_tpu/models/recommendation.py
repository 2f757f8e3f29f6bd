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
  top-k), exactly, and on a table large enough **prunes before it
  selects**: a query's best ``n`` lie in the ``n`` chunks of largest
  maximum, so the exact selection reads the chunk maxima and those
  chunks' scores, a sixth of the row at 17,770 rows and ``n`` 100
  (:func:`_score_and_local_topk` has the proof, :func:`_prune_plan` the
  rule);
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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

# The lane width: chunk counts and fetched candidates come in whole tiles.
_LANES = 128

# The most bytes of scores, and of candidates, one step of the fetch kernel
# holds in VMEM (each twice: the pipeline's two buffers).
_FETCH_BLOCK_BYTES = 2 << 20


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


def _prune_plan(rows: int, n: int):
    """``(c, C)``, the chunking under which pruning by chunk maxima pays
    for a top-``n`` over ``rows`` scores, or ``None`` where the block goes
    straight to ``lax.top_k``.

    A query's scores are laid out ``[c, C]``: ``C`` chunks on the lanes
    (whole tiles of :data:`_LANES`), a chunk's ``c`` scores down the
    sublanes (a power of two from 8, the sublane tile). The two exact
    selections then read ``C`` maxima and ``c`` scores of each of ``n``
    chunks (``n`` out to whole tiles): ``c`` is the one that makes the sum
    least, near ``sqrt(rows / n)``. Pruning pays where that sum is at most
    HALF the ``rows`` it replaces (``lax.top_k``'s time on the v5e goes
    by the width out to its next power of two, for 256 queries at k 100
    0.05 ms to 1,024, 0.12 to 2,048, 0.27 to 4,096, 1.48 at 17,770:
    ``tools/bench_topk_select.py``) and there are ``n`` chunks to select;
    a small table, or ``n`` near ``rows``, stays direct. 17,770 rows at
    ``n`` 100: ``c`` 16, ``C`` 1,152, 3,200 scores selected over."""
    n_pad = -(-n // _LANES) * _LANES

    def chunks_of(c):
        return -(-rows // (c * _LANES)) * _LANES

    lengths = [8 << i for i in range(rows.bit_length())
               if (8 << i) * _LANES <= rows]
    if not lengths:
        return None
    c = min(lengths, key=lambda c: chunks_of(c) + n_pad * c)
    C = chunks_of(c)
    if (n > C or 2 * (C + n_pad * c) > rows
            or 4 * n_pad * c > _FETCH_BLOCK_BYTES):  # a query's candidates
        return None
    return c, C


def _fetch_chunks_kernel(chunks_ref, scores_ref, out_ref, *, per):
    """One step: ``per`` queries, a run of whole lane tiles of their
    chunks. A candidate's chunk lies in ONE tile of 128 lanes: each tile
    is shuffled by the chunk numbers' low bits (the lane gather,
    ``tpu.dynamic_gather``) and kept where the high bits name it. The
    output block stays across the tile runs of a query (the second grid
    axis), opened at ``-inf``, which a lane past ``n`` keeps."""
    c, lanes, n_pad = scores_ref.shape[1], scores_ref.shape[2], out_ref.shape[2]
    first_tile = pl.program_id(1) * (lanes // _LANES)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)

    def one_query(i, _):
        for u in range(0, n_pad, _LANES):
            chunk = jnp.broadcast_to(chunks_ref[i, :, u:u + _LANES],
                                     (c, _LANES))
            lane, tile = chunk % _LANES, chunk // _LANES - first_tile
            got = out_ref[i, :, u:u + _LANES]
            for v in range(lanes // _LANES):
                here = jnp.take_along_axis(
                    scores_ref[i, :, v * _LANES:(v + 1) * _LANES], lane,
                    axis=1, mode="promise_in_bounds")
                got = jnp.where(tile == v, here, got)
            out_ref[i, :, u:u + _LANES] = got
        return 0

    lax.fori_loop(0, per, one_query, 0)


def _fetch_chunks(scores, chunks):
    """``scores[q, :, chunks[q, t]]`` as ``[q, c, n_pad]``: the ``c``
    scores of each selected chunk, ``n`` out to whole lane tiles (a lane
    past ``n`` carries ``-inf``, under every score and under ``NEG_INF``,
    so that it is never answered: ``n * c`` fetched scores stand above
    it). A Mosaic kernel that only MOVES scores (compiled where
    ``fps_tpu.ops`` compiles its own, interpreted elsewhere): at 256
    queries, 17,770 rows and ``n`` 100 XLA's gather of the 409,600
    scores costs 5 ms, its gather of 25,600 columns 0.46, a one-hot
    product at HIGHEST 0.05 more than the kernel, and chunks of
    consecutive columns fetched as slices 22
    (``tools/bench_topk_select.py``)."""
    q, c, C = scores.shape
    n = chunks.shape[1]
    n_pad = -(-n // _LANES) * _LANES
    per = next(p for p in (8, 4, 2, 1) if q % p == 0
               and (p == 1 or 4 * p * c * n_pad <= _FETCH_BLOCK_BYTES))
    tiles = C // _LANES
    most = max(1, _FETCH_BLOCK_BYTES // (4 * per * c * _LANES))
    runs = next(d for d in range(1, tiles + 1)
                if tiles % d == 0 and tiles // d <= most)
    lanes = C // runs
    return pl.pallas_call(
        partial(_fetch_chunks_kernel, per=per),
        grid=(q // per, runs),
        in_specs=[pl.BlockSpec((per, 1, n_pad), lambda i, r: (i, 0, 0)),
                  pl.BlockSpec((per, c, lanes), lambda i, r: (i, 0, r))],
        out_specs=pl.BlockSpec((per, c, n_pad), lambda i, r: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((q, c, n_pad), scores.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="topk_fetch_chunks",
        interpret=ops._use_pallas() != (True, False),
    )(jnp.pad(chunks, ((0, 0), (0, n_pad - n)),
              constant_values=-1)[:, None, :], scores)


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
    ``topk.score`` (the product and the mask), ``topk.select``.

    **Pruning before selecting** (where :func:`_prune_plan` says it pays;
    logged once a traced program as the route ``tap.topk_pruned``).
    ``lax.top_k`` costs 0.32 ns a score it is handed, so it is handed
    fewer: a query's scores are produced as ``[c, C]`` (chunk ``j`` = rows
    ``j, j + C, ...`` of the shard, the product over a ``[c, C, dim]``
    view of the table), each chunk's maximum taken (``[q, C]``), the exact
    top-``n`` of the maxima selects ``n`` chunks, their ``n * c`` scores
    are fetched (:func:`_fetch_chunks`) and the exact top-``n`` of those
    is the answer. The scores answered are the product's own numbers,
    selected and never recomputed.

    *No true top-``n`` item is lost.* Let ``x`` be among the ``n`` best
    and suppose its chunk is NOT among the ``n`` chunks of largest maximum.
    Then ``n`` other chunks each have a maximum ``>= max(chunk of x) >=
    x``, so ``n`` scores outside ``x``'s chunk are ``>= x``: ``x`` is at
    best TIED for place ``n``, and a list that answers those ``n`` in its
    stead holds the same score at every rank. Ties may fall either way
    (as between two equal scores under ``lax.top_k`` itself); no id is
    answered twice (a position is fetched once) and a masked row
    (``NEG_INF``: a padding row, a column that fills the last chunk) is
    answered only where fewer than ``n`` live rows exist, as on the
    direct path.

    Either way a selected position becomes its logical id by arithmetic
    (:func:`~fps_tpu.core.store.phys_to_id` on the ``n`` positions a
    query), not by a gather from a vector of all the shard's ids."""
    rps = local.shape[0]
    me = lax.axis_index(SHARD_AXIS)
    rows = local.astype(jnp.float32)
    n_local = min(n, rps)
    plan = _prune_plan(rps, n_local)

    def ids_of(pos):
        return phys_to_id(me * rps + pos, num_shards, rps)

    def masked(scores, pos):
        return jnp.where((pos < rps) & (ids_of(pos) < num_ids), scores,
                         NEG_INF)

    def rank_direct(block):
        with jax.named_scope("topk.score"):
            scores = masked(
                jnp.matmul(block, rows.T, precision=lax.Precision.HIGHEST),
                jnp.arange(rps, dtype=jnp.int32))
        with jax.named_scope("topk.select"):
            top_s, top_i = lax.top_k(scores, n_local)
            return top_s, ids_of(top_i)

    def rank_pruned(block):
        c, C = plan
        with jax.named_scope("topk.score"):
            view = jnp.pad(rows, ((0, c * C - rps), (0, 0))).reshape(c, C, -1)
            pos = (jnp.arange(c, dtype=jnp.int32)[:, None] * C
                   + jnp.arange(C, dtype=jnp.int32)[None, :])
            scores = masked(
                jnp.einsum("qd,icd->qic", block, view,
                           precision=lax.Precision.HIGHEST), pos)
        with jax.named_scope("topk.select"):
            _, chunks = lax.top_k(jnp.max(scores, axis=1), n_local)
            cand = _fetch_chunks(scores, chunks)  # [q, c, n_pad]
            n_pad = cand.shape[2]
            top_s, flat = lax.top_k(cand.reshape(-1, c * n_pad), n_local)
            # The chosen candidate's chunk, chunks[q, flat % n_pad], as a
            # one-hot sum over the n selected: no gather.
            chunk = jnp.sum(jnp.where(
                (flat % n_pad)[:, :, None]
                == jnp.arange(n_local, dtype=jnp.int32),
                chunks[:, None, :], 0), axis=-1)
            return top_s, ids_of((flat // n_pad) * C + chunk)

    queries = queries.astype(jnp.float32)
    q = queries.shape[0]
    if plan is None:
        rank = rank_direct
    else:
        rank = rank_pruned
        ops.log_route("tap", "topk_pruned", rps, n_local, q,
                      f"chunks={plan[1]}x{plan[0]}")
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
    log, and where its selection prunes by chunk maxima
    (:func:`_score_and_local_topk`: 17,770 rows at K 100 do, a table of a
    few thousand rows does not) ``tap.topk_pruned`` beside it (a shard's
    rows, K, queries a block, ``chunks=CxC's length``); on the host its
    counts (``tap.journal``: ``topk_answered``, ``topk_padding``) land on
    the epoch's or chunk's journal event.
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
