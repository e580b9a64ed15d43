"""Batched device-side bulk construction of the U-HNSW graph pair (DESIGN.md §7).

The paper's structural cost over plain HNSW is that U-HNSW builds *two* base
graphs (G1 under L1, G2 under L2). The faithful incremental builder
(repro.core.build) inserts one point at a time on the host — the only layer
of the stack that is still sequential. This module replaces it at scale with
the kNN-graph-seeded + prune recipe (NN-Descent family, cf. the graph-ANNS
survey in PAPERS.md), restructured as batched device passes:

  1. **Seed** — chunked pairwise-Lp scoring builds a kNN pool per metric
     (`kernels.ops` dispatch: Pallas kernels on TPU, jnp reference
     off-TPU). At or below EXACT_SEED_THRESHOLD the pass scores *every*
     column — exact kNN pools, with L1 and L2 reduced from one shared diff
     block; above it each node scores a random candidate block instead.
  2. **NN-Descent rounds** — a fixed number of refinement rounds for
     random-seeded (large) corpora. Each round samples forward+reverse
     neighbors-of-neighbors from the *union* of the L1 and L2 pools,
     scores the block under both metrics, and sort-merges it into each
     pool (exact distances + keep-best-K, so pool recall is non-decreasing
     per round).
  3. **Emit** — geometric level assignment, then per level: vectorized HNSW
     heuristic (Alg. 4) pruning, reverse-edge symmetrization, a second
     backfilled prune, kNN top-up to full degree, and host-side connectivity
     repair — emitting `GraphArrays` directly (no `HNSWGraph` intermediate).

The shared-pass trick (DESIGN.md §7): steps 1–2 gather the *same* candidate
id blocks for both metrics and evaluate two distances per block (one L1, one
L2 — the gathered rows and all id bookkeeping are shared), so G1 and G2 cost
one candidate-generation pass instead of two. This attacks the paper's 2x
build-cost overhead head-on; `benchmarks/build.py` tracks the resulting
speedup over the incremental builder.

When to prefer the incremental builder: tiny segments (below
`index.segment.BULK_THRESHOLD` the jit warm-up dominates), or when paper-
exact construction semantics are the point of the experiment.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.build import _repair_connectivity
from repro.core.hnsw import GraphArrays

# Row-chunk byte budget for gathered (B, C, d) candidate blocks: the scoring
# and pruning passes materialize one such block per call.
_SCORE_BUDGET = 96 * 1024 * 1024
_POS_INF = np.int32(2**30)  # position/id sentinel for the sort tricks


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _row_chunk_for(c: int, d: int, n_rows: int) -> int:
    """Rows per scoring/pruning call so (chunk, C, d) f32 fits the budget."""
    chunk = max(32, _SCORE_BUDGET // max(4 * c * d, 1))
    return min(_round_up(min(chunk, n_rows), 8), _round_up(n_rows, 8))


# ---------------------------------------------------------------------------
# jitted primitives: top-k pool merge, order-preserving dedup, heuristic prune
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_topk(pool_ids, pool_d, cand_ids, cand_d, k: int):
    """Sort-merge candidate blocks into per-row best-k pools with dedup.

    ids are -1-padded; padded / duplicate slots score +inf and sort last.
    Returns (ids (B, k) int32 ascending by distance, d (B, k) f32).
    """
    ids = jnp.concatenate([pool_ids, cand_ids], axis=1)
    d = jnp.concatenate([pool_d, cand_d], axis=1)
    valid = ids >= 0
    d = jnp.where(valid, d, jnp.inf)
    key = jnp.where(valid, ids, _POS_INF)
    # dedup: group equal ids together, keep each group's best distance
    sk, sd = jax.lax.sort((key, d), num_keys=2)
    first = jnp.concatenate(
        [jnp.ones((ids.shape[0], 1), bool), sk[:, 1:] != sk[:, :-1]], axis=1
    )
    sd = jnp.where(first, sd, jnp.inf)
    sd2, sk2 = jax.lax.sort((sd, sk), num_keys=1)
    out_ids = jnp.where(jnp.isfinite(sd2), sk2, -1).astype(jnp.int32)
    return out_ids[:, :k], sd2[:, :k]


@functools.partial(jax.jit, static_argnames=("k",))
def _dedup_keep_first(ids, k: int):
    """Per-row order-preserving dedup of -1-padded id lists, cut to k."""
    b, c = ids.shape
    pos = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None, :], (b, c))
    key = jnp.where(ids >= 0, ids, _POS_INF)
    sk, sp = jax.lax.sort((key, pos), num_keys=2)
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), sk[:, 1:] != sk[:, :-1]], axis=1
    )
    sp = jnp.where(first & (sk < _POS_INF), sp, _POS_INF)
    sp2, sk2 = jax.lax.sort((sp, sk), num_keys=1)
    out = jnp.where(sp2 < _POS_INF, sk2, -1).astype(jnp.int32)[:, :k]
    if out.shape[1] < k:  # narrow candidate lists (tiny level subsets)
        out = jnp.pad(out, ((0, 0), (0, k - out.shape[1])),
                      constant_values=-1)
    return out


@functools.partial(
    jax.jit, static_argnames=("m_max", "alpha", "backfill")
)
def _prune_chunk(x_sub, node_idx, cand_ids, m_max: int, alpha: float,
                 backfill: bool):
    """Vectorized HNSW heuristic selection (Alg. 4) over a row chunk.

    cand_ids rows must be sorted ascending by *base-metric* distance to the
    node (-1 padded, self excluded). The diversity-rule distances are
    evaluated in L2^2 (one batched matmul) regardless of the base metric —
    the *ordering*, which dominates edge quality, is exact base metric via
    the caller's sort (same convention as the host bulk builder and
    documented there). backfill=True tops short selections up with the
    nearest skipped candidates. Returns (B, m_max) ids, -1 padded, selected
    diversity edges first, both groups ascending by base distance.
    """
    b, c = cand_ids.shape
    valid = cand_ids >= 0
    safe = jnp.clip(cand_ids, 0, x_sub.shape[0] - 1)
    node_vec = x_sub[node_idx]                      # (B, d)
    cand_vec = x_sub[safe]                          # (B, C, d)
    # full f32 products: the chip's default f32 matmul is one bf16 pass,
    # too coarse for the identity's cancellation between close rows
    dot = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    sq = dot("bcd,bcd->bc", cand_vec, cand_vec)
    nsq = dot("bd,bd->b", node_vec, node_vec)
    d_u = jnp.maximum(
        nsq[:, None] + sq - 2.0 * dot("bd,bcd->bc", node_vec, cand_vec), 0.0
    )
    d_u = jnp.where(valid, d_u, jnp.inf)
    pair = jnp.maximum(
        sq[:, :, None] + sq[:, None, :]
        - 2.0 * dot("bid,bjd->bij", cand_vec, cand_vec), 0.0
    )

    def body(j, st):
        run_min, count, selected = st
        sel = valid[:, j] & (d_u[:, j] <= alpha * run_min[:, j]) \
            & (count < m_max)
        selected = selected.at[:, j].set(sel)
        run_min = jnp.where(sel[:, None],
                            jnp.minimum(run_min, pair[:, j, :]), run_min)
        return run_min, count + sel, selected

    st = (jnp.full((b, c), jnp.inf), jnp.zeros((b,), jnp.int32),
          jnp.zeros((b, c), bool))
    _, _, selected = jax.lax.fori_loop(0, c, body, st)

    pos = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None, :], (b, c))
    if backfill:
        key = jnp.where(selected, pos, jnp.where(valid, pos + c, _POS_INF))
    else:
        key = jnp.where(selected & valid, pos, _POS_INF)
    sk, sids = jax.lax.sort((key, cand_ids), num_keys=1)
    out = jnp.where(sk < _POS_INF, sids, -1).astype(jnp.int32)[:, :m_max]
    if out.shape[1] < m_max:  # narrow candidate lists (tiny level subsets)
        out = jnp.pad(out, ((0, 0), (0, m_max - out.shape[1])),
                      constant_values=-1)
    return out


# ---------------------------------------------------------------------------
# chunked scoring (the shared distance pass)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("p",))
def _score_chunk(x, rows, ids, p: float):
    """Root-free Lp from x[rows[i]] to x[ids[i, :]]; ids outside [0, n)
    score +inf."""
    # imported at trace time: repro.core.__init__ pulls in this module
    from repro.kernels.ops import gather_rowwise_lp

    return gather_rowwise_lp(x[rows], ids, x, p)


def _score_ids(x_dev, node_rows: np.ndarray, ids: np.ndarray,
               p: float) -> np.ndarray:
    """Exact base-metric distances node_rows[i] -> ids[i, :] (chunked).

    Each chunk is one XLA gather of its (chunk, C, d) rows, then
    `rowwise_lp` (kernels.ops.gather_rowwise_lp) on every backend. The
    build scores ~3,000 candidate rows per corpus row; the fused Pallas
    gather kernel (the query path's) copies one row at a time, and
    PERF.md records the per-row cost of both paths on the chip.
    Rows are padded to one chunk shape (one compiled program per pass)
    and every chunk is dispatched before the first result is read.
    """
    n_rows, c = ids.shape
    chunk = _row_chunk_for(c, x_dev.shape[1], n_rows)
    n_pad = _round_up(n_rows, chunk)
    rows = np.zeros(n_pad, np.int32)
    rows[:n_rows] = node_rows
    blk = np.full((n_pad, c), -1, np.int32)
    blk[:n_rows] = ids
    parts = [_score_chunk(x_dev, rows[s:s + chunk], blk[s:s + chunk], p=p)
             for s in range(0, n_pad, chunk)]
    return np.concatenate([np.asarray(d) for d in parts])[:n_rows]


def _prune_all(x_dev, n_rows: int, cand_ids: np.ndarray, m_max: int,
               alpha: float, backfill: bool) -> np.ndarray:
    """Chunked driver for `_prune_chunk` over every row of a level."""
    c = cand_ids.shape[1]
    d = x_dev.shape[1]
    # the (B, C, C) pair matrix joins the working set
    chunk = max(8, min(_row_chunk_for(c, d + c, n_rows),
                       _row_chunk_for(c, d, n_rows)))
    out = np.empty((n_rows, m_max), np.int32)
    ids_j = jnp.asarray(cand_ids, dtype=jnp.int32)
    for s in range(0, n_rows, chunk):
        e = min(s + chunk, n_rows)
        pad = chunk - (e - s)
        rows = jnp.arange(s, e, dtype=jnp.int32)
        blk = ids_j[s:e]
        if pad:
            rows = jnp.concatenate([rows, jnp.zeros((pad,), jnp.int32)])
            blk = jnp.concatenate(
                [blk, jnp.full((pad, c), -1, jnp.int32)])
        sel = _prune_chunk(x_dev, rows, blk, m_max, float(alpha), backfill)
        out[s:e] = np.asarray(sel[: e - s])
    return out


# ---------------------------------------------------------------------------
# NN-Descent pools (shared candidate blocks, one distance eval per metric)
# ---------------------------------------------------------------------------


# Below this corpus size the seed phase scores ALL columns (exact kNN via
# chunked pairwise-Lp) instead of a random sample: at segment scale the
# full pass costs about the same as the 3 sampled NN-Descent rounds it
# replaces and leaves nothing for them to refine. Above it, random seeding
# + NN-Descent keeps the build subquadratic.
EXACT_SEED_THRESHOLD = 4096


def _exact_seed_pools(data, metric_ps, k: int, interpret,
                      pool_factor: int = 8):
    """Near-exact per-metric kNN pools via one chunked pairwise scan.

    The shared-pass core at segment scale (DESIGN.md §7): ONE full
    pairwise scan under L2 — the only base metric with a matmul-friendly
    (MXU / GEMM) form — ranks a `pool_factor * k`-wide candidate pool per
    node; every other metric then scores only that shared id block exactly
    (a narrow gather pass) and keeps its own top-k. L2 pools are exact;
    Lp pools are exact within the pool (the host bulk builder uses the
    same prefilter, with the same justification: the generous pool makes
    the re-ranked edges coincide with exact kNN edges in practice).
    """
    from repro.kernels.ops import lp_pairwise_distance

    n, d = data.shape
    x_dev = jnp.asarray(data)
    rows = np.arange(n, dtype=np.int32)
    need_pool = any(p != 2.0 for p in metric_ps)
    width = min(max(pool_factor * k, k) if need_pool else k, n - 1)
    # the (chunk, n) L2 block never materializes a diff tensor; budget on
    # the output tile
    chunk = min(_round_up(max(64, _SCORE_BUDGET // (8 * n)), 8),
                _round_up(n, 8))
    ids2 = np.empty((n, width), np.int32)
    d2 = np.empty((n, width), np.float32)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        # pad the tail to the uniform chunk shape (one compiled program
        # for the whole pass — same pattern as _score_ids); padded rows
        # mask their self-distance at column 0 and are sliced off below
        q = x_dev[s:e]
        if e - s < chunk:
            q = jnp.concatenate(
                [q, jnp.zeros((chunk - (e - s), q.shape[1]), q.dtype)])
        dd = lp_pairwise_distance(q, x_dev, 2.0, root=False,
                                  interpret=interpret)
        diag = jnp.where(jnp.arange(chunk) < e - s,
                         jnp.arange(chunk) + s, 0)
        dd = dd.at[jnp.arange(chunk), diag].set(jnp.inf)
        neg, idx = jax.lax.top_k(-dd, width)
        ids2[s:e] = np.asarray(idx, dtype=np.int32)[: e - s]
        d2[s:e] = np.asarray(-neg)[: e - s]
    pools = {}
    for p in metric_ps:
        if p == 2.0:
            pools[p] = (ids2[:, :k].copy(), d2[:, :k].copy())
            continue
        # exact-p scoring of the shared candidate block (chunked gather)
        dp = _score_ids(x_dev, rows, ids2, p)
        m_ids, m_d = _merge_topk(
            jnp.full((n, 1), -1, jnp.int32), jnp.full((n, 1), jnp.inf),
            jnp.asarray(ids2), jnp.asarray(dp), k,
        )
        pools[p] = (np.asarray(m_ids), np.asarray(m_d))
    return pools


def nn_descent_pools(
    data: np.ndarray,
    metric_ps: tuple[float, ...] = (1.0, 2.0),
    k: int = 64,
    rounds: int = 3,
    sample_t: int = 8,
    cand_cap: int | None = None,
    seed: int = 0,
    interpret=None,
    trajectory: bool = False,
    exact_seed_threshold: int = EXACT_SEED_THRESHOLD,
):
    """Build per-metric kNN candidate pools in one shared pass.

    Returns {p: (ids (n, k) int32 ascending, d (n, k) f32)}. For corpora
    at or below `exact_seed_threshold` the seed scoring pass covers every
    column — the pools are exact kNN and the refinement rounds are skipped
    (they cannot improve an exact pool). Above it, every node seeds from a
    random candidate block and `rounds` NN-Descent iterations refine it.
    With trajectory=True additionally returns a list of per-stage pool
    snapshots (seed, then one per round) for the round-monotonicity test —
    merges use exact distances and keep-best-k, so pool recall cannot
    decrease.
    """
    data = np.ascontiguousarray(data, dtype=np.float32)
    n, d = data.shape
    assert n >= 2, "need at least two points to build a graph"
    k = min(k, n - 1)
    cand_cap = cand_cap or max(3 * k, 128)
    rng = np.random.default_rng(seed)
    x_dev = jnp.asarray(data)
    own = np.arange(n, dtype=np.int32)[:, None]

    if n <= exact_seed_threshold:
        pools = _exact_seed_pools(data, metric_ps, k, interpret)
        if trajectory:
            return pools, [{p: pools[p][0].copy() for p in metric_ps}]
        return pools

    def score_and_merge(pools, cand):
        """The shared pass: one id block, one distance eval per metric."""
        cand = np.where(cand == own, -1, cand)  # no self-loops
        for p in metric_ps:
            dd = _score_ids(x_dev, own[:, 0], cand, p)
            ids_p, d_p = pools[p]
            pools[p] = _merge_topk(
                jnp.asarray(ids_p), jnp.asarray(d_p),
                jnp.asarray(cand, dtype=jnp.int32), jnp.asarray(dd), k
            )
            pools[p] = (np.asarray(pools[p][0]), np.asarray(pools[p][1]))
        return pools

    # 1. seed: a random candidate block per node (uniform, self excluded)
    seed_cand = rng.integers(0, n - 1, size=(n, max(k, 8)), dtype=np.int64)
    seed_cand = (seed_cand + (seed_cand >= own)).astype(np.int32)
    empty_ids = np.full((n, k), -1, np.int32)
    empty_d = np.full((n, k), np.inf, np.float32)
    pools = {p: (empty_ids, empty_d) for p in metric_ps}
    pools = score_and_merge(pools, seed_cand)
    snaps = [{p: pools[p][0].copy() for p in metric_ps}] if trajectory else []

    # 2. NN-Descent rounds over the joint pool. The local join samples
    # from forward AND reverse neighbors (the reverse join is what makes
    # NN-Descent converge on clustered data: a node's neighbors must learn
    # about *it*, not only about each other).
    for _ in range(rounds):
        join = np.concatenate([pools[p][0] for p in metric_ps], axis=1)
        width = join.shape[1]
        rev = _reverse_edges(join, n, width)
        base = np.concatenate([join, rev], axis=1)         # (n, 2*width)
        t = min(sample_t, base.shape[1])
        # sample T in/out neighbors per node, take their whole join sets
        sel = rng.integers(0, base.shape[1], size=(n, t))
        mid = np.take_along_axis(base, sel, axis=1)        # (n, T)
        mid = np.where(mid < 0, own[:, 0][:, None], mid)   # pad -> self
        nn2 = base[mid].reshape(n, t * base.shape[1])
        if nn2.shape[1] > cand_cap:
            sub = rng.integers(0, nn2.shape[1], size=(n, cand_cap))
            nn2 = np.take_along_axis(nn2, sub, axis=1)
        # the node's own join set rides along: reverse edges join the
        # pools directly, and each metric's merge sees the other metric's
        # current neighbors (cross-metric exchange), not only through the
        # sampled second hop
        cand = np.concatenate([base, nn2], axis=1)
        pools = score_and_merge(pools, cand)
        if trajectory:
            snaps.append({p: pools[p][0].copy() for p in metric_ps})

    if trajectory:
        return pools, snaps
    return pools


# ---------------------------------------------------------------------------
# level emission
# ---------------------------------------------------------------------------


def _assign_levels(n: int, m: int, seed: int) -> tuple[np.ndarray, int]:
    """Geometric level assignment (same law as the incremental builder)."""
    rng = np.random.default_rng(seed)
    ml = 1.0 / math.log(m)
    levels = np.minimum(
        (-np.log(np.maximum(rng.random(n), 1e-12)) * ml).astype(np.int32), 30
    )
    return levels, int(np.argmax(levels))


def _exact_knn_local(sub: np.ndarray, p: float, kk: int,
                     interpret=None) -> np.ndarray:
    """Exact base-metric kNN ids within a (small) level subset, chunked."""
    from repro.kernels.ops import lp_pairwise_distance

    nl, d = sub.shape
    sub_dev = jnp.asarray(sub)
    chunk = _row_chunk_for(nl, d, nl)
    out = np.empty((nl, kk), np.int32)
    for s in range(0, nl, chunk):
        e = min(s + chunk, nl)
        # tail padded to the uniform chunk shape (see _exact_seed_pools)
        q = sub_dev[s:e]
        if e - s < chunk:
            q = jnp.concatenate(
                [q, jnp.zeros((chunk - (e - s), d), q.dtype)])
        dd = lp_pairwise_distance(q, sub_dev, p, root=False,
                                  interpret=interpret)
        diag = jnp.where(jnp.arange(chunk) < e - s,
                         jnp.arange(chunk) + s, 0)
        dd = dd.at[jnp.arange(chunk), diag].set(jnp.inf)
        _, idx = jax.lax.top_k(-dd, kk)
        out[s:e] = np.asarray(idx, dtype=np.int32)[: e - s]
    return out


def _reverse_edges(sel: np.ndarray, nl: int, r_max: int) -> np.ndarray:
    """Capped reverse-adjacency (nl, r_max) of a -1-padded forward list.

    Fully vectorized (no per-node Python loop): group edges by target via a
    stable argsort, rank within each group with a cumulative-count trick,
    keep the first r_max per target.
    """
    m_max = sel.shape[1]
    src = np.repeat(np.arange(nl, dtype=np.int32), m_max)
    dst = sel.reshape(-1)
    keep = dst >= 0
    src, dst = src[keep], dst[keep]
    order = np.argsort(dst, kind="stable")
    dst_s, src_s = dst[order], src[order]
    counts = np.bincount(dst_s, minlength=nl)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(dst_s)) - np.repeat(starts, counts)
    rev = np.full((nl, r_max), -1, np.int32)
    sel_rows = pos < r_max
    rev[dst_s[sel_rows], pos[sel_rows]] = src_s[sel_rows]
    return rev


def _build_level(
    sub: np.ndarray, x_dev, cand_ids: np.ndarray, p: float, m_max: int,
    alpha: float, entry_local: int,
) -> np.ndarray:
    """One level's adjacency from sorted candidate pools (local ids).

    Phase 1: diversity prune (spread edges, no backfill). Phase 2:
    symmetrize + re-sort by exact base metric + backfilled prune (keeps the
    spread edges reverse edges would otherwise evict), then top up to full
    degree from the kNN pool and repair connectivity (host BFS) — the
    navigability property sequential insertion gets for free.
    """
    nl = len(sub)
    rows = np.arange(nl, dtype=np.int32)
    sel = _prune_all(x_dev, nl, cand_ids, m_max, alpha, backfill=False)
    # reverse cap of 2*m_max approximates the host builder's unbounded
    # symmetrize: hub nodes in clustered data collect well over m_max
    # reverse edges, and the phase-2 prune needs to see them to keep the
    # right ones
    rev = _reverse_edges(sel, nl, 2 * m_max)
    merged = np.concatenate([sel, rev], axis=1)
    merged = np.where(merged == rows[:, None], -1, merged)
    merged = np.asarray(_dedup_keep_first(jnp.asarray(merged),
                                          merged.shape[1]))
    # exact base-metric ordering for the phase-2 prune
    dd = _score_ids(x_dev, rows, merged, p)
    sd, si = jax.lax.sort(
        (jnp.asarray(dd), jnp.asarray(merged, dtype=jnp.int32)), num_keys=1
    )
    merged = np.where(np.isfinite(np.asarray(sd)), np.asarray(si), -1)
    pruned = _prune_all(x_dev, nl, merged.astype(np.int32), m_max, alpha,
                        backfill=True)
    # np.array (copy): the repair pass mutates rows in place, and
    # np.asarray over a device buffer yields a read-only view
    topped = np.array(_dedup_keep_first(
        jnp.asarray(np.concatenate([pruned, cand_ids], axis=1),
                    dtype=jnp.int32), m_max
    ))
    return _repair_connectivity(topped, rows, sub, p, entry_local)


def _emit_arrays(
    data: np.ndarray, pool_ids: np.ndarray, p: float, m: int,
    levels: np.ndarray, entry: int, alpha: float, interpret,
) -> GraphArrays:
    """Assemble the full GraphArrays hierarchy for one metric."""
    n = len(data)
    m0 = 2 * m
    x_dev = jnp.asarray(data)
    max_level = int(levels.max())

    adj0 = None
    upper_adj, upper_g2l = [], []
    for l in range(max_level + 1):
        nodes = np.nonzero(levels >= l)[0].astype(np.int32)
        m_max = m0 if l == 0 else m
        if l == 0:
            mat = _build_level(data, x_dev, pool_ids, p, m_max, alpha,
                               int(entry))
            adj0 = np.where(mat >= 0, mat, n).astype(np.int32)
            continue
        sub = data[nodes]
        sub_dev = jnp.asarray(sub)
        entry_local = int(np.nonzero(nodes == entry)[0][0])
        if len(nodes) <= 1:
            mat = np.full((len(nodes), m_max), -1, np.int32)
        else:
            kk = min(2 * m_max, len(nodes) - 1)
            cand = _exact_knn_local(sub, p, kk, interpret=interpret)
            mat = _build_level(sub, sub_dev, cand, p, m_max, alpha,
                               entry_local)
        gmat = np.where(mat >= 0, nodes[np.clip(mat, 0, None)], n)
        g2l = np.full(n, -1, np.int32)
        g2l[nodes] = np.arange(len(nodes), dtype=np.int32)
        upper_adj.append(jnp.asarray(gmat.astype(np.int32)))
        upper_g2l.append(jnp.asarray(g2l))

    return GraphArrays(
        adj0=jnp.asarray(adj0),
        upper_adj=tuple(upper_adj),
        upper_g2l=tuple(upper_g2l),
        entry=jnp.asarray(entry, dtype=jnp.int32),
        n=n,
        metric_p=p,
    )


# ---------------------------------------------------------------------------
# public surface
# ---------------------------------------------------------------------------


@dataclass
class DeviceGraph:
    """A bulk-built frozen graph: device `GraphArrays` + host metadata.

    Drop-in for `HNSWGraph` at every consumer surface (UHNSW, SegmentedGraphs,
    benchmarks): exposes metric_p/m/m0/data/levels/entry_point and
    `graph_arrays()` (which `GraphArrays.from_graph` prefers over re-packing
    host adjacency). The topology lives only in the GraphArrays — there is
    no host adjacency intermediate; `adjacency_host` derives one on demand
    for tests and tools.
    """

    metric_p: float
    m: int
    m0: int
    entry_point: int
    max_level: int
    levels: np.ndarray
    data: np.ndarray
    arrays: GraphArrays

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def d(self) -> int:
        return self.data.shape[1]

    def graph_arrays(self) -> GraphArrays:
        return self.arrays

    def adjacency_host(self, level: int) -> np.ndarray:
        """-1-padded host adjacency view of one level (tests/tools only)."""
        a = self.arrays.adj0 if level == 0 else self.arrays.upper_adj[level - 1]
        a = np.asarray(a)
        return np.where(a == self.n, -1, a).astype(np.int32)

    def index_size_bytes(self) -> int:
        """Index size excluding the dataset (HNSWGraph-compatible metric)."""
        total = np.asarray(self.arrays.adj0).nbytes
        for a in self.arrays.upper_adj:
            total += np.asarray(a).nbytes
        for a in self.arrays.upper_g2l:
            total += np.asarray(a).nbytes
        return total


def build_bulk_pair(
    data: np.ndarray,
    m: int = 32,
    *,
    k_pool: int | None = None,
    rounds: int = 3,
    sample_t: int = 8,
    cand_cap: int | None = None,
    alpha: float = 1.2,
    seed: int = 0,
    interpret=None,
    progress_every: int = 0,
    exact_seed_threshold: int = EXACT_SEED_THRESHOLD,
) -> tuple[DeviceGraph, DeviceGraph]:
    """Build the U-HNSW pair (G1 under L1, G2 under L2) in one shared pass.

    The NN-Descent candidate blocks are generated once and scored under both
    metrics (two distance evaluations per block — DESIGN.md §7); level
    assignment is shared, so the two graphs differ only in their edge sets.
    Returns (g1, g2) as `DeviceGraph`s ready for `UHNSW(g1, g2)`.
    """
    data = np.ascontiguousarray(data, dtype=np.float32)
    n = len(data)
    m0 = 2 * m
    # pool floor of 64: at small m a 2*m0-wide pool is too narrow for the
    # heuristic prune to find diverse edges on clustered data (measured on
    # the SIFT-like corpus: m=8 with a 32-wide pool loses ~12 recall pts)
    k_pool = k_pool or min(max(2 * m0, 64), max(n - 1, 1))
    pools = nn_descent_pools(
        data, (1.0, 2.0), k=k_pool, rounds=rounds, sample_t=sample_t,
        cand_cap=cand_cap, seed=seed, interpret=interpret,
        exact_seed_threshold=exact_seed_threshold,
    )
    levels, entry = _assign_levels(n, m, seed)
    graphs = []
    for p in (1.0, 2.0):
        if progress_every:
            print(f"  bulk pair: emitting G{int(p)} (p={p})", flush=True)
        arrays = _emit_arrays(data, pools[p][0], p, m, levels, entry, alpha,
                              interpret)
        graphs.append(DeviceGraph(
            metric_p=p, m=m, m0=m0, entry_point=entry,
            max_level=int(levels.max()), levels=levels, data=data,
            arrays=arrays,
        ))
    return graphs[0], graphs[1]


def build_bulk(
    data: np.ndarray,
    metric_p: float = 2.0,
    m: int = 32,
    *,
    k_pool: int | None = None,
    rounds: int = 3,
    sample_t: int = 8,
    cand_cap: int | None = None,
    alpha: float = 1.2,
    seed: int = 0,
    interpret=None,
    exact_seed_threshold: int = EXACT_SEED_THRESHOLD,
) -> DeviceGraph:
    """Single-metric bulk build (same pipeline, one pool).

    For a base metric other than 2.0 the seed pass still prefilters with
    the L2 scan and re-ranks the shared pool under `metric_p` exactly
    (see `_exact_seed_pools`).
    """
    data = np.ascontiguousarray(data, dtype=np.float32)
    n = len(data)
    m0 = 2 * m
    k_pool = k_pool or min(max(2 * m0, 64), max(n - 1, 1))
    pools = nn_descent_pools(
        data, (float(metric_p),), k=k_pool, rounds=rounds,
        sample_t=sample_t, cand_cap=cand_cap, seed=seed,
        interpret=interpret, exact_seed_threshold=exact_seed_threshold,
    )
    levels, entry = _assign_levels(n, m, seed)
    arrays = _emit_arrays(data, pools[float(metric_p)][0], float(metric_p),
                          m, levels, entry, alpha, interpret)
    return DeviceGraph(
        metric_p=float(metric_p), m=m, m0=m0, entry_point=entry,
        max_level=int(levels.max()), levels=levels, data=data, arrays=arrays,
    )
