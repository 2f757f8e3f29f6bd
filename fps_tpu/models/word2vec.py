"""word2vec skip-gram with negative sampling (SGNS) on the PS.

Reference behavior being rebuilt (SURVEY.md §2 #10; confirmed to exist by
BASELINE.json "word2vec SGNS (text8)"; exact upstream package unverified —
the survey flags its location as [conf: L]):

* two embedding matrices (input/center and output/context), sharded by
  word id across the servers;
* worker slides a window over the token stream, pulls the center vector,
  the context vector, and K negative-sample vectors, computes the SGNS
  gradient, pushes deltas to both tables;
* negatives drawn from the unigram distribution raised to 3/4; frequent
  words subsampled away (Mikolov et al. 2013); workload: text8.

TPU design
----------
* Skip-gram **pair generation and subsampling are host-side streaming**
  (ingest), producing static-shape (center, context) batches.
* **Negative sampling is on-device** in ``WorkerLogic.prepare``: Vose
  alias-method tables over unigram^0.75 (built once on host) — O(1) per
  draw, two gathers + a compare, fully inside the compiled step
  (``searchsorted`` over the CDF measured ~100x slower on TPU).
* One pull on the input table (centers) and one on the output table
  (contexts ++ negatives, flattened) per step; one push each. The sigmoid/
  gradient math is dense (B, 1+K, dim) VPU work.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from fps_tpu.core.api import StepOutput, WorkerLogic
from fps_tpu.core.store import ParamStore, TableSpec, ranged_uniform_init
from fps_tpu.obs.timing import host_span, watch_program
from fps_tpu.parallel.mesh import host_to_replicated, key_to_replicated

Array = jax.Array

IN_TABLE = "in_embeddings"
OUT_TABLE = "out_embeddings"


def _keep_probs(cfg: W2VConfig, unigram_counts: np.ndarray) -> np.ndarray:
    """Per-token keep probability min(1, sqrt(t/f)) — word2vec's frequent-word
    subsampling; ones when ``cfg.subsample_t`` is None. Single source of
    truth for the host ingest and device-plan paths."""
    counts = np.asarray(unigram_counts, np.float64)
    freq = counts / max(1.0, counts.sum())
    if cfg.subsample_t is None:
        return np.ones_like(freq)
    return np.minimum(1.0, np.sqrt(cfg.subsample_t / np.maximum(freq, 1e-12)))


def _build_alias(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vose alias tables for a discrete distribution ``p`` (sums to 1).

    Returns (prob, alias): draw ``j ~ U{0..V-1}``, ``u ~ U[0,1)``; the
    sample is ``j`` if ``u < prob[j]`` else ``alias[j]``.

    Rounds instead of a walk over Python lists (1.1 M words took seconds
    of set-up): every still-open small column is paired with its own
    large one, all pairs of a round at once. A large column takes, in
    index order, as many of the open smalls as it can fill and stay large
    (a prefix sum and a ``searchsorted``), then one more that turns it
    small for the next round. Each column closes once with an alias that
    was large when it closed, so the tables encode ``p`` exactly up to
    float rounding, as the walk's do; the pairing differs from the
    walk's, the sampled distribution does not.
    """
    V = len(p)
    scaled = np.asarray(p, np.float64) * V
    prob = np.ones(V)
    alias = np.arange(V, dtype=np.int64)
    small = np.flatnonzero(scaled < 1.0)
    large = np.flatnonzero(scaled >= 1.0)
    while len(small) and len(large):
        # Room a large column has above 1, and the smalls' needs, as
        # running totals: small k goes to the first large whose room,
        # cumulated, still covers the smalls before k (so the last one a
        # large takes may leave it under 1: it joins the next round).
        need = np.cumsum(1.0 - scaled[small])
        room = np.cumsum(scaled[large] - 1.0)
        before = np.concatenate([[0.0], need[:-1]])
        owner = np.searchsorted(room, before, side="right")
        placed = owner < len(large)
        if not placed.any():  # rounding left no large with room: all 1
            break
        s_ids, l_ids = small[placed], large[owner[placed]]
        prob[s_ids] = scaled[s_ids]
        alias[s_ids] = l_ids
        np.subtract.at(scaled, l_ids, 1.0 - scaled[s_ids])
        open_ = np.concatenate([small[~placed], large])
        small = open_[scaled[open_] < 1.0]
        large = open_[scaled[open_] >= 1.0]
    return prob, alias


@dataclasses.dataclass
class W2VConfig:
    vocab_size: int
    dim: int = 100
    window: int = 5  # dynamic window: actual half-width ~ U{1..window}
    negatives: int = 5
    learning_rate: float = 0.025
    subsample_t: float | None = 1e-4  # None disables frequent-word subsampling
    neg_power: float = 0.75
    # Block-mode only (Word2VecBlockWorker): positions share one set of K
    # negatives per group of this many tokens. Default 1 = per-POSITION
    # negatives (shared only across a position's ~2*window instances) —
    # already the full transaction win, and quality tracks the pair worker.
    # G>1 shrinks OUT-row traffic further but measurably stalls SGNS: the
    # store's per-id mean-combine weights rows equally, so collapsing many
    # negative instances into few group rows starves hot ids of negative
    # pressure and a common embedding component grows unchecked.
    neg_group_size: int = 1
    dtype: object = jnp.float32


class _AliasNegativeSampler:
    """Shared negative-drawing mixin: Vose alias tables over
    ``unigram^neg_power`` — O(1) per draw on device, two gathers and a
    compare (searchsorted over the CDF measured ~27ms per 200k draws on
    TPU; the alias sampler is ~100x cheaper)."""

    def _init_alias(self, cfg: W2VConfig, unigram_counts: np.ndarray):
        p = np.asarray(unigram_counts, np.float64) ** cfg.neg_power
        p /= p.sum()
        prob, alias = _build_alias(p)
        self._alias_prob = jnp.asarray(prob, jnp.float32)
        self._alias_idx = jnp.asarray(alias, jnp.int32)

    def _draw_negatives(self, key: Array, shape: tuple[int, ...]) -> Array:
        k1, k2 = jax.random.split(key)
        j = jax.random.randint(k1, shape, 0, self.cfg.vocab_size, jnp.int32)
        u = jax.random.uniform(k2, shape)
        return jnp.where(u < jnp.take(self._alias_prob, j),
                         j, jnp.take(self._alias_idx, j))


class Word2VecWorker(WorkerLogic, _AliasNegativeSampler):
    """SGNS worker. Batch columns: ``center (B,)``, ``context (B,)``,
    ``weight (B,)``. ``prepare`` adds ``negatives (B, K)``."""

    def __init__(self, cfg: W2VConfig, unigram_counts: np.ndarray):
        self.cfg = cfg
        self._init_alias(cfg, unigram_counts)

    def prepare(self, batch, key):
        B = batch["center"].shape[0]
        negs = self._draw_negatives(key, (B, self.cfg.negatives))
        return dict(batch, negatives=negs)

    def pull_ids(self, batch) -> Mapping[str, Array]:
        ctx_and_neg = jnp.concatenate(
            [batch["context"].astype(jnp.int32)[:, None], batch["negatives"]],
            axis=1,
        )  # (B, 1+K)
        return {
            IN_TABLE: batch["center"].astype(jnp.int32),
            OUT_TABLE: ctx_and_neg.reshape(-1),
        }

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        B = batch["center"].shape[0]
        K = cfg.negatives
        w = batch["weight"].astype(cfg.dtype)  # (B,)

        v = pulled[IN_TABLE]  # (B, dim) center vectors
        u = pulled[OUT_TABLE].reshape(B, 1 + K, -1)  # ctx ++ negs

        # labels: slot 0 positive, rest negative.
        logits = jnp.einsum("bd,bkd->bk", v, u)  # (B, 1+K)
        labels = jnp.zeros((B, 1 + K), cfg.dtype).at[:, 0].set(1.0)
        sig = jax.nn.sigmoid(logits)
        # dL/dlogit for L = -log σ(x_pos) - Σ log σ(-x_neg):
        g = (sig - labels) * w[:, None]  # (B, 1+K)

        lr = cfg.learning_rate
        dv = -lr * jnp.einsum("bk,bkd->bd", g, u)  # (B, dim)
        du = -lr * g[:, :, None] * v[:, None, :]  # (B, 1+K, dim)

        # SGNS loss (for monitoring): -logσ(pos) - Σ logσ(-neg).
        loss = -(
            jax.nn.log_sigmoid(logits[:, 0])
            + jnp.sum(jax.nn.log_sigmoid(-logits[:, 1:]), axis=1)
        )

        center_ids = jnp.where(w > 0, batch["center"].astype(jnp.int32), -1)
        ctx_and_neg = jnp.concatenate(
            [batch["context"].astype(jnp.int32)[:, None], batch["negatives"]],
            axis=1,
        )
        out_ids = jnp.where(w[:, None] > 0, ctx_and_neg, -1)

        out = {
            "loss": jnp.sum(loss * w).astype(jnp.float32),
            "n": jnp.sum(w).astype(jnp.float32),
        }
        pushes = {
            IN_TABLE: (center_ids, dv),
            OUT_TABLE: (out_ids.reshape(-1), du.reshape(B * (1 + K), -1)),
        }
        return StepOutput(pushes=pushes, local_state=local_state, out=out)


class Word2VecBlockWorker(WorkerLogic, _AliasNegativeSampler):
    """SGNS at token-BLOCK granularity — the transaction-minimal fast path.

    The pair-level worker pulls/pushes one OUT-table row per (pair, slot):
    ``2·window·L`` pairs × ``(1+K)`` rows ≈ 250k row transactions per
    2048-token block — and on TPU sparse row ops are per-transaction bound
    (~12ns/row), so transactions, not FLOPs, set the word2vec ceiling.

    Because pair generation is fused on device (Word2VecDevicePlan), the
    worker can instead receive the raw token block and exploit that every
    pair's endpoints are block positions:

    * pull each block position's IN and OUT row ONCE (``L+W`` rows each);
      pairs are assembled by static slices of those rows (dense VPU work);
    * per-position gradients accumulate across the ``2W`` orientations by
      static slice-adds, and each table takes ONE push of ``L+W`` rows;
    * negatives are shared per group of ``neg_group_size`` positions (each
      center instance weighted by its exact pair count), adding only
      ``K·ceil((L+W)/G)`` OUT rows. Default G=1: one negative set per
      position, shared across its ~2·window instances — see
      ``W2VConfig.neg_group_size`` for why larger groups stall SGNS under
      the store's mean-combine.

    Per block at G=1: ~(4 + 2K)(L+W) transactions vs ~2·2W·L·(1+K) for the
    pair worker — ~10x fewer at the default geometry. The SGNS gradient is
    exact for the stated sampling scheme; only the negative-sampling
    coupling (instance-shared draws) differs from the per-pair reference.

    Batch columns (from ``Word2VecDevicePlan(mode="block")``):
    ``block (L+W,)`` int32 tokens, ``half (L,)`` int32 per-position dynamic
    half-windows, ``valid_len ()`` int32 count of in-stream positions.
    """

    def __init__(self, cfg: W2VConfig, unigram_counts: np.ndarray,
                 block_len: int):
        if cfg.neg_group_size <= 0:
            raise ValueError("neg_group_size must be positive in block mode")
        self.cfg = cfg
        self.block_len = block_len
        self.num_groups = -(-(block_len + cfg.window) // cfg.neg_group_size)
        self._init_alias(cfg, unigram_counts)

    def prepare(self, batch, key):
        negs = self._draw_negatives(
            key, (self.num_groups, self.cfg.negatives)
        )
        return dict(batch, negatives=negs)

    def pull_ids(self, batch) -> Mapping[str, Array]:
        block = batch["block"].astype(jnp.int32)
        return {
            IN_TABLE: block,
            OUT_TABLE: jnp.concatenate(
                [block, batch["negatives"].reshape(-1)]
            ),
        }

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        L, W, K, G = (self.block_len, cfg.window, cfg.negatives,
                      cfg.neg_group_size)
        LW = L + W
        lr = cfg.learning_rate

        half = batch["half"].astype(jnp.int32)  # (L,)
        vlen = batch["valid_len"].astype(jnp.int32)  # ()
        v = pulled[IN_TABLE]  # (LW, dim) center rows
        uo = pulled[OUT_TABLE][:LW]  # (LW, dim) context rows
        negs_u = pulled[OUT_TABLE][LW:].reshape(self.num_groups, K, -1)

        dv = jnp.zeros_like(v)
        du = jnp.zeros_like(uo)
        inst = jnp.zeros((LW,), cfg.dtype)  # center-instance counts
        pos = jnp.arange(L, dtype=jnp.int32)
        loss = jnp.float32(0.0)
        npairs = jnp.float32(0.0)

        for d in range(1, W + 1):
            c, x = v[:L], v[d : L + d]
            uc, ux = uo[:L], uo[d : L + d]
            wk = ((half >= d) & (pos + d < vlen)).astype(cfg.dtype)  # (L,)
            # Both orientations of each ordered adjacency (i, i+d), exactly
            # like the pair path: centers i and i+d, contexts swapped.
            l1 = jnp.sum(c * ux, axis=-1)  # center=i, context=i+d
            l2 = jnp.sum(x * uc, axis=-1)  # center=i+d, context=i
            g1 = (jax.nn.sigmoid(l1) - 1.0) * wk
            g2 = (jax.nn.sigmoid(l2) - 1.0) * wk
            dv = dv.at[:L].add(-lr * g1[:, None] * ux)
            du = du.at[d : L + d].add(-lr * g1[:, None] * c)
            dv = dv.at[d : L + d].add(-lr * g2[:, None] * uc)
            du = du.at[:L].add(-lr * g2[:, None] * x)
            inst = inst.at[:L].add(wk)
            inst = inst.at[d : L + d].add(wk)
            loss += jnp.sum(
                -(jax.nn.log_sigmoid(l1) + jax.nn.log_sigmoid(l2)) * wk
            )
            npairs += 2.0 * jnp.sum(wk)

        # Group-shared negatives: every pair whose center sits in group g
        # scores the same K rows, so per (position, negative) the gradient
        # is the single-pair gradient times the position's instance count.
        pad = self.num_groups * G - LW
        vp = jnp.pad(v, ((0, pad), (0, 0))).reshape(self.num_groups, G, -1)
        instp = jnp.pad(inst, (0, pad)).reshape(self.num_groups, G)
        # precision=HIGHEST: the TPU's default runs an f32 einsum in bf16
        # passes, and the tables are float32 (the positives above are
        # element-wise products, exact in f32 already).
        hi = jax.lax.Precision.HIGHEST
        ln = jnp.einsum("gid,gkd->gik", vp, negs_u,
                        precision=hi)  # (NG, G, K)
        sn = jax.nn.sigmoid(ln) * instp[:, :, None]
        dv_neg = -lr * jnp.einsum("gik,gkd->gid", sn, negs_u, precision=hi)
        du_neg = -lr * jnp.einsum("gik,gid->gkd", sn, vp, precision=hi)
        dv = dv + dv_neg.reshape(-1, v.shape[-1])[:LW]
        loss += jnp.sum(-jax.nn.log_sigmoid(-ln) * instp[:, :, None])

        # Normalize to per-INSTANCE means so block mode takes the same
        # effective step sizes as the pair worker under the store's per-id
        # mean combine: each position's delta above is a SUM over its
        # ~2·window center/context instances (and each negative's over its
        # whole group's instances) — unnormalized, that multiplies the
        # learning rate by the instance count and SGNS plateaus.
        ginst = instp.sum(axis=1)  # (NG,) total instances per group
        inv = 1.0 / jnp.maximum(inst, 1.0)
        dv = dv * inv[:, None]
        du = du * inv[:, None]
        du_neg = du_neg / jnp.maximum(ginst, 1.0)[:, None, None]

        # One push row per block position; zero-instance rows drop (-1) so
        # the mean-combine denominator counts only real contributors.
        block = batch["block"].astype(jnp.int32)
        row_ids = jnp.where(inst > 0, block, -1)
        neg_ids = jnp.where(
            ginst[:, None] > 0, batch["negatives"], -1
        ).reshape(-1)

        out = {
            "loss": loss.astype(jnp.float32),
            "n": npairs.astype(jnp.float32),
        }
        pushes = {
            IN_TABLE: (row_ids, dv),
            OUT_TABLE: (
                jnp.concatenate([row_ids, neg_ids]),
                jnp.concatenate([du, du_neg.reshape(-1, v.shape[-1])]),
            ),
        }
        return StepOutput(pushes=pushes, local_state=local_state, out=out)


def make_store(mesh, cfg: W2VConfig) -> ParamStore:
    half = 0.5 / cfg.dim
    in_spec = TableSpec(
        name=IN_TABLE,
        num_ids=cfg.vocab_size,
        dim=cfg.dim,
        init_fn=ranged_uniform_init(-half, half, cfg.dim, cfg.dtype),
        dtype=cfg.dtype,
    )
    # word2vec initializes the output matrix to zeros.
    out_spec = TableSpec(
        name=OUT_TABLE, num_ids=cfg.vocab_size, dim=cfg.dim, dtype=cfg.dtype,
    ).zeros_init()
    return ParamStore(mesh, [in_spec, out_spec])


def _make_trainer(mesh, cfg: W2VConfig, worker, *, sync_every, donate,
                  max_steps_per_call, push_delay=0, step_tap=None,
                  guard=None):
    from fps_tpu.core.api import MEAN_COMBINE
    from fps_tpu.core.driver import Trainer, TrainerConfig

    if push_delay >= 16:
        import warnings

        # Measured guardrail (docs/STALENESS.md finding #5): the staleness
        # sweep holds SGNS partner recovery at 0.675-0.700 through s=64
        # STALE READS at full lr, but the delayed-WRITE diagonal with the
        # lr-downscale recipe collapses it — 0.125 at s=d=16, 0.050 at
        # s=d=64 (chance 0.017). The mechanism is under-training, not
        # divergence: the downscale that stabilizes MF's bilinear objective
        # leaves the non-convex SGNS objective barely moving.
        downscaled = cfg.learning_rate < W2VConfig.learning_rate
        warnings.warn(
            f"word2vec with push_delay={push_delay}"
            + (f" and downscaled learning_rate={cfg.learning_rate} "
               f"(< default {W2VConfig.learning_rate})" if downscaled
               else "")
            + ": the measured staleness sweep (docs/STALENESS.md finding "
            "#5) collapsed SGNS quality in this regime (partner recovery "
            "0.70 -> 0.125 at delay 16 with the lr-downscale recipe). "
            "Prefer bounding READS (sync_every) at full lr and keeping "
            "push_delay small or zero.",
            UserWarning, stacklevel=3,
        )

    store = make_store(mesh, cfg)
    # Per-id mean combine: with Zipfian word frequencies a hot id appears
    # many times per batch; summing those deltas diverges, averaging gives
    # each touched row one stable step per batch (NuPS-style skew handling).
    trainer = Trainer(
        mesh, store, worker, server_logic=MEAN_COMBINE,
        config=TrainerConfig(sync_every=sync_every, donate=donate,
                             max_steps_per_call=max_steps_per_call,
                             push_delay=push_delay, step_tap=step_tap,
                             guard=guard),
    )
    return trainer, store


def word2vec(mesh, cfg: W2VConfig, unigram_counts: np.ndarray, *,
             sync_every: int | None = None, donate: bool = True,
             max_steps_per_call: int | None = None, push_delay: int = 0,
             step_tap=None, guard=None):
    """(trainer, store) — the analog of the reference's word2vec transform.
    ``sync_every``/``push_delay`` select SSP staleness brackets exactly as
    in :func:`fps_tpu.models.matrix_factorization.online_mf`."""
    return _make_trainer(
        mesh, cfg, Word2VecWorker(cfg, unigram_counts),
        sync_every=sync_every, donate=donate,
        max_steps_per_call=max_steps_per_call, push_delay=push_delay,
        step_tap=step_tap, guard=guard,
    )


def word2vec_block(mesh, cfg: W2VConfig, unigram_counts: np.ndarray,
                   block_len: int, *, sync_every: int | None = None,
                   donate: bool = True,
                   max_steps_per_call: int | None = None,
                   push_delay: int = 0, step_tap=None, guard=None):
    """(trainer, store) with the block-granularity worker — pair with a
    ``Word2VecDevicePlan(..., block_len=block_len, mode="block")``. Same
    tables, same SGNS objective; ~10x fewer sparse row transactions per
    step at the default geometry (see :class:`Word2VecBlockWorker`).
    ``step_tap`` taps (e.g. :func:`cooccurrence_sketch_tap`) see the raw
    block batch and can reconstruct its exact pair stream id-only via
    :func:`block_pair_stream`."""
    return _make_trainer(
        mesh, cfg, Word2VecBlockWorker(cfg, unigram_counts, block_len),
        sync_every=sync_every, donate=donate,
        max_steps_per_call=max_steps_per_call, push_delay=push_delay,
        step_tap=step_tap, guard=guard,
    )


# ---------------------------------------------------------------------------
# Host-side streaming skip-gram pair generation (the ingest source).
# ---------------------------------------------------------------------------

def skipgram_chunks(
    tokens: np.ndarray,
    unigram_counts: np.ndarray,
    cfg: W2VConfig,
    *,
    num_workers: int,
    local_batch: int,
    steps_per_chunk: int,
    sync_every: int | None = None,
    seed: int = 0,
    segment_tokens: int = 1 << 20,
    use_native: bool | None = None,
) -> Iterator[dict]:
    """Stream ``(center, context, weight)`` chunks over one pass of ``tokens``.

    Works segment-by-segment so the full pair list (≈ 2·window·N) never
    materializes. Applies frequent-word subsampling (prob. 1 - sqrt(t/f))
    and a dynamic window (per-position half-width uniform in 1..window),
    both matching word2vec's reference implementation.

    ``use_native`` selects the C++ pair generator (``fps_tpu.native``):
    ``None`` (default) uses it when available, ``True`` requires it,
    ``False`` forces the numpy path. Both paths implement the same sampling
    scheme; streams differ only in RNG draws.
    """
    from fps_tpu import native

    if use_native is None:
        use_native = native.available()
    elif use_native and not native.available():
        raise RuntimeError("use_native=True but fps_tpu.native is unavailable")
    rng = np.random.default_rng(seed)
    n = len(tokens)
    if n and int(np.max(tokens)) >= len(unigram_counts):
        raise ValueError(
            f"token id {int(np.max(tokens))} >= vocab "
            f"{len(unigram_counts)} (unigram_counts too small)"
        )
    keep_p = _keep_probs(cfg, unigram_counts)

    B = num_workers * local_batch
    stride = steps_per_chunk * B
    if sync_every is not None and steps_per_chunk % sync_every:
        raise ValueError("steps_per_chunk must be a multiple of sync_every")

    buf_c: list[np.ndarray] = []
    buf_x: list[np.ndarray] = []
    buffered = 0
    native_kp = (
        keep_p.astype(np.float32) if cfg.subsample_t is not None else None
    )

    def emit(c, x, wgt):
        chunk = {
            "center": c.reshape(steps_per_chunk, B),
            "context": x.reshape(steps_per_chunk, B),
            "weight": wgt.reshape(steps_per_chunk, B).astype(np.float32),
        }
        if sync_every is not None:
            chunk = {
                k: v.reshape(-1, sync_every, B) for k, v in chunk.items()
            }
        return chunk

    # Segments are disjoint: cross-boundary pairs (at most window per
    # ~million-token segment) are dropped rather than double-counted.
    for si, start in enumerate(range(0, n, segment_tokens)):
        seg = tokens[start : start + segment_tokens]
        if use_native:
            pair = native.skipgram_pairs(
                seg, cfg.window, seed=(seed << 20) ^ si, keep_p=native_kp
            )
            if pair is None:  # native failure mid-stream (e.g. OOM)
                raise RuntimeError(
                    "native skipgram_pairs failed mid-stream; rerun with "
                    "use_native=False"
                )
            c, x = pair
            if len(c):
                buf_c.append(c)
                buf_x.append(x)
                buffered += len(c)
        else:
            # subsample frequent words (drop positions entirely, like word2vec).
            keep = rng.random(len(seg)) < keep_p[seg]
            seg = seg[keep]
            if len(seg) < 2:
                continue
            m = len(seg)
            half = rng.integers(1, cfg.window + 1, m)  # dynamic window
            for d in range(1, cfg.window + 1):
                ok = (half >= d)[: m - d]
                c = seg[: m - d][ok]
                x = seg[d:][ok]
                # both directions: (center, context) and (context, center)
                buf_c.append(np.concatenate([c, x]))
                buf_x.append(np.concatenate([x, c]))
                buffered += 2 * len(c)

        while buffered >= stride:
            cs = np.concatenate(buf_c)
            xs = np.concatenate(buf_x)
            take_c, rest_c = cs[:stride], cs[stride:]
            take_x, rest_x = xs[:stride], xs[stride:]
            buf_c, buf_x = [rest_c], [rest_x]
            buffered = len(rest_c)
            yield emit(take_c, take_x, np.ones(stride))

    if buffered:
        cs = np.concatenate(buf_c)[:stride]
        xs = np.concatenate(buf_x)[:stride]
        pad = stride - len(cs)
        wgt = np.concatenate([np.ones(len(cs)), np.zeros(pad)])
        cs = np.concatenate([cs, np.zeros(pad, cs.dtype)])
        xs = np.concatenate([xs, np.zeros(pad, xs.dtype)])
        yield emit(cs, xs, wgt)


def nearest_neighbors(store: ParamStore, word_ids: np.ndarray, k: int = 5,
                      center: bool = True):
    """Host-side cosine nearest neighbors in the input embedding table.

    ``center=True`` removes the common mean vector first — SGNS embeddings
    are strongly anisotropic (a large shared component; cf. "All-but-the-Top",
    Mu et al. 2018), and raw cosine is dominated by it.
    """
    ids = np.arange(store.specs[IN_TABLE].num_ids)
    emb = store.lookup_host(IN_TABLE, ids)
    if center:
        emb = emb - emb.mean(axis=0)
    emb = emb / np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-9)
    q = emb[word_ids]
    sims = q @ emb.T
    order = np.argsort(-sims, axis=1)
    return order[:, 1 : k + 1], np.take_along_axis(sims, order, 1)[:, 1 : k + 1]


# ---------------------------------------------------------------------------
# Streaming co-occurrence similarity via tug-of-war sketches (step_tap).
#
# The reference family's sketch module estimated word co-occurrence
# similarity from the pair stream without storing the |V|x|V| matrix
# (SURVEY.md §2 #10, [conf: L]). Here the estimator RIDES THE TRAINING
# LOOP: a ``step_tap`` sketches each probe word's context distribution
# from the very batches the SGNS worker trains on — no second pass over
# the corpus, no extra host<->device traffic beyond the (P, depth, width)
# delta that joins the metrics stream.
# ---------------------------------------------------------------------------

def _sketch_pair_stream(spec, probe, center, ctx, w):
    """Route each (center, context, weight) pair to its probe row and add
    its tug-of-war contribution — one O(B*P) compare plus ONE scatter into
    the flattened ``(P, depth, width)`` stack (not a full-width scatter
    per probe)."""
    from fps_tpu.sketch import tow_update_rows

    P = int(probe.shape[0])
    eq = center[:, None] == probe[None, :]  # (B, P)
    row = jnp.where(eq.any(axis=1), jnp.argmax(eq, axis=1), -1)
    stack = jnp.zeros((P, spec.depth, spec.width), jnp.float32)
    return tow_update_rows(spec, stack, row, ctx, w)


def block_pair_stream(batch):
    """Reconstruct the exact (center, context, weight) pair stream of one
    BLOCK-worker batch from its raw columns — the same pairs
    :class:`Word2VecBlockWorker.step` trains on without materializing.

    The block batch carries ``block (L+W,)``, ``half (L,)`` and
    ``valid_len ()``; the worker's pair semantics are, for every offset
    ``d in [1, W]`` and position ``i < L``: weight
    ``(half[i] >= d) & (i + d < valid_len)`` on BOTH orientations of the
    adjacency ``(i, i+d)``. ``W`` is inferred from the static shapes
    (``len(block) - len(half)``). Returns ``(center, ctx, w)`` arrays of
    length ``2*W*L`` — ids only, so a probe tap costs O(W·L·P) compares
    per step, no embedding traffic.
    """
    block = batch["block"].astype(jnp.int32)  # (L+W,)
    half = batch["half"].astype(jnp.int32)  # (L,)
    vlen = batch["valid_len"].astype(jnp.int32)  # ()
    L = half.shape[0]
    W = block.shape[0] - L
    pos = jnp.arange(L, dtype=jnp.int32)
    centers, ctxs, ws = [], [], []
    for d in range(1, W + 1):
        wk = ((half >= d) & (pos + d < vlen)).astype(jnp.float32)
        lo, hi = block[:L], block[d : L + d]
        centers += [lo, hi]
        ctxs += [hi, lo]
        ws += [wk, wk]
    return (jnp.concatenate(centers), jnp.concatenate(ctxs),
            jnp.concatenate(ws))


def cooccurrence_sketch_tap(spec, probe_ids):
    """``step_tap`` emitting per-step tug-of-war sketch DELTAS of each probe
    word's context-frequency vector.

    For every training batch the tap sketches ``{context: weight}`` of the
    pairs whose center is ``probe_ids[p]`` into row ``p`` of a
    ``(P, depth, width)`` stack. Sketches are additive, so the stream
    sketch is just the sum of the emitted deltas over steps AND workers —
    exactly :func:`accumulate_sketch_taps`. Pad pairs carry weight 0 and
    vanish from the estimate.

    Works with BOTH worker schemas: the PAIR batch (``center``/``context``/
    ``weight`` columns — :func:`skipgram_chunks` and the pair-mode
    :class:`Word2VecDevicePlan`) is sketched directly, and a BLOCK batch
    (``block``/``half``/``valid_len``) has its exact pair stream
    reconstructed id-only on the fly (:func:`block_pair_stream`) — so the
    estimator also rides the fused fast path that delivers the w2v
    headline, at ~2·window·L extra int32 compares per step.
    """
    probe = jnp.asarray(probe_ids, jnp.int32)  # (P,)

    def tap(tables, batch, local_state, t):
        del tables, local_state, t
        if "block" in batch:
            center, ctx, w = block_pair_stream(batch)
        else:
            center = batch["center"].astype(jnp.int32)
            ctx = batch["context"].astype(jnp.int32)
            w = batch["weight"].astype(jnp.float32)
        return _sketch_pair_stream(spec, probe, center, ctx, w)

    return tap


def accumulate_sketch_taps(metrics) -> np.ndarray:
    """Sum the ``tap`` channel of ``fit_stream``/``run_indexed`` metrics
    into the stream's (P, depth, width) co-occurrence sketch stack."""
    total = None
    for m in metrics:
        # (steps, W, P, depth, width) -> (P, depth, width)
        part = np.asarray(m["tap"]).sum(axis=(0, 1))
        total = part if total is None else total + part
    if total is None:
        raise ValueError("no metrics chunks — nothing was trained")
    return total


def sketch_similarity(sketches: np.ndarray) -> np.ndarray:
    """(P, P) unbiased co-occurrence inner-product estimates among the
    probe words (median-of-rows tug-of-war estimator, all on host — one
    einsum, not P^2 device dispatches)."""
    s = np.asarray(sketches)
    return np.median(np.einsum("pdw,qdw->pqd", s, s), axis=-1)


# ---------------------------------------------------------------------------
# Device-resident SGNS epochs: pair generation fused into the compiled loop.
# ---------------------------------------------------------------------------

class Word2VecDevicePlan:
    """Epoch plan generating skip-gram pairs ON DEVICE for ``run_indexed``.

    The host streaming path (:func:`skipgram_chunks`) materializes and
    uploads every (center, context) chunk — dominated by the host→device
    link on a TPU VM. Here the raw token stream is uploaded once (a
    one-column :class:`~fps_tpu.core.device_ingest.DeviceDataset`, kept as
    ``.dataset`` as :class:`DeviceEpochPlan` keeps its own); each epoch
    then runs as ONE compiled program that:

    1. **subsamples + compacts** the stream on device (uniform-vs-keep_p
       mask → cumsum → scatter), exactly word2vec's semantics where
       dropped tokens vanish from the stream *before* windows apply;
    2. **generates pairs inside the training scan**: worker ``w``'s step
       ``t`` takes a block of ``block_len`` compacted tokens, draws a
       dynamic half-window ``U{1..window}`` per center, and emits the
       ``2 * window * block_len`` candidate pairs (both orientations per
       ordered adjacency, like the host path) with validity weights;
    3. trains the usual SGNS step (negatives drawn in ``prepare``).

    The per-epoch kept-token count is random on device, so the epoch is
    sized from its host-computable expectation ``sum(keep_p[tokens])``
    plus a generous slack; the overflow probability is negligible and any
    overflow tokens are dropped (one-pass streaming semantics).
    """

    TOKEN = "token"  # the data set's one column

    @host_span("plan.build", memory=True)
    def __init__(self, dataset, unigram_counts: np.ndarray,
                 cfg: W2VConfig, mesh, *, num_workers: int,
                 block_len: int = 8192, seed: int = 0,
                 sync_every: int | None = None, mode: str = "pairs"):
        """``dataset``: a :class:`~fps_tpu.core.device_ingest.DeviceDataset`
        of one int32 column ``token`` (the stream, uploaded once under
        ``dataset.place`` like every other plan's data), kept as
        ``.dataset``; a host token array is wrapped into one."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from fps_tpu.core.device_ingest import DeviceDataset

        if mode not in ("pairs", "block"):
            raise ValueError(f"unknown mode {mode!r}")
        if not isinstance(dataset, DeviceDataset):
            dataset = DeviceDataset(
                mesh, {self.TOKEN: np.asarray(dataset, np.int32)})
        if dataset.column_names() != [self.TOKEN]:
            raise ValueError(
                f"the token stream is a DeviceDataset of one column "
                f"{self.TOKEN!r}; got {dataset.column_names()}")
        self.dataset = dataset
        self.cfg = cfg
        self.mode = mode
        self.num_workers = num_workers
        self.block_len = block_len
        self.local_batch = 2 * cfg.window * block_len  # pairs per step
        self.seed = seed
        self.sync_every = sync_every
        self.num_tokens = int(dataset.n)

        replicated = NamedSharding(mesh, P())
        keep_p = _keep_probs(cfg, unigram_counts)
        self._keep_p = host_to_replicated(keep_p.astype(np.float32), mesh)

        expected_kept = float(
            keep_p[dataset.host_column(self.TOKEN)].sum())
        bound = int(expected_kept + 8.0 * np.sqrt(expected_kept + 1.0) + 1024)
        bound = min(bound, self.num_tokens)
        per_worker = -(-bound // (block_len * num_workers))
        steps = max(1, per_worker)
        if sync_every:
            steps = -(-steps // sync_every) * sync_every
        self.steps_per_epoch = steps
        # Compacted buffer: every block slice (+ window lookahead) in range.
        self._buf_len = steps * block_len * num_workers + cfg.window
        buf_len = self._buf_len

        # Once a call, so named WITHOUT the fps. prefix (a reader counts
        # steps by the ops under fps.*; docs/observability.md).
        @jax.named_scope("ingest.compact")
        def compact(key, toks, keep_p):
            keep = (jax.random.uniform(key, toks.shape)
                    < jnp.take(keep_p, toks))
            dest = jnp.cumsum(keep.astype(jnp.int32)) - 1
            kept = dest[-1] + 1
            dest = jnp.where(keep, jnp.minimum(dest, buf_len - 1), buf_len)
            compacted = jnp.zeros((buf_len + 1,), jnp.int32)
            compacted = compacted.at[dest].set(toks, mode="drop")
            return compacted[:buf_len], jnp.minimum(kept, buf_len)

        # Takes a replicated key (key_to_replicated) and pins replicated
        # outputs, so the path works under multi-controller JAX.
        self._compact_jit = watch_program(jax.jit(
            compact, out_shardings=(replicated, replicated)
        ), "ingest.compact")
        self._mesh = mesh

    @host_span("epoch_args", memory=True)
    def epoch_args(self, epoch: int):
        ekey = jax.random.fold_in(jax.random.key(self.seed), epoch)
        ck, wk = jax.random.split(ekey)
        # _compact_jit pins replicated outputs, so the (tokens,)-sized
        # buffer is placed once and never re-broadcast by the dispatches.
        # The key goes in as a device array: reading its data back to the
        # host would wait for the epoch the device is still running
        # (5.4 s a call at 1.1 M words x 300, chip run, PR 27), and the
        # call after it could not be queued ahead.
        with host_span("compact"):
            compacted, kept = self._compact_jit(
                key_to_replicated(ck, self._mesh),
                self.dataset.columns[self.TOKEN], self._keep_p,
            )
        return {
            "compacted": compacted,
            "kept": kept,
            "wkey": key_to_replicated(wk, self._mesh),
        }

    def local_batch_at(self, args, w, t):
        """Worker ``w``'s step-``t`` batch: skip-gram ``(center, context,
        weight)`` pairs in ``"pairs"`` mode, or the raw ``(block, half,
        valid_len)`` columns for :class:`Word2VecBlockWorker` in ``"block"``
        mode (same block slice, same half-window draws — only the
        granularity handed to the worker differs)."""
        L, W = self.block_len, self.cfg.window
        base = (t * self.num_workers + w) * L
        block = jax.lax.dynamic_slice(args["compacted"], (base,), (L + W,))
        key = jax.random.fold_in(args["wkey"], t * self.num_workers + w)
        half = jax.random.randint(key, (L,), 1, W + 1, dtype=jnp.int32)
        if self.mode == "block":
            return {
                "block": block,
                "half": half,
                "valid_len": jnp.clip(args["kept"] - base, 0, L + W),
            }
        pos = jnp.arange(L, dtype=jnp.int32)

        centers, contexts, valids = [], [], []
        for d in range(1, W + 1):
            c = block[:L]
            x = jax.lax.dynamic_slice(block, (d,), (L,))
            ok = (half >= d) & (base + pos + d < args["kept"])
            # both orientations of each ordered adjacency, like word2vec
            centers += [c, x]
            contexts += [x, c]
            valids += [ok, ok]
        return {
            "center": jnp.concatenate(centers),
            "context": jnp.concatenate(contexts),
            "weight": jnp.concatenate(valids).astype(jnp.float32),
        }
