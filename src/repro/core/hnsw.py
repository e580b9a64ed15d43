"""Batched JAX beam search over a frozen HNSW graph.

HNSW traversal is pointer-chasing, which is hostile to TPU's dense execution
model. We restructure it (DESIGN.md §2) as fixed-width tensor ops inside
`jax.lax.while_loop`:

  * upper layers: greedy descent, one `while_loop` per layer (layer count is
    static per graph), each hop = gather M neighbors -> one batched base-metric
    distance -> argmin;
  * layer 0: ef-beam-search with the beam kept as a sorted (ef,) array and
    W-way multi-expansion (`expand_width`, DESIGN.md §2 hot path). Each hop
    expands the W best unexpanded beam entries at once: gather their W*m0
    neighbors, dedupe across lists (sort + first-occurrence mask), test-and-
    set a per-query visited *bitmask* (uint32 words; dense compares over the
    word axis up to DENSE_VISITED_MAX_WORDS, a carry-free scatter-add of
    distinct bits above), compute base-metric distances for unseen neighbors in
    one block (an XLA gather of every frontier row, or, for rows that are
    whole DMA tiles, BEAM_FETCH_ROW_ELEMS, one Pallas call over every lane
    that reads only the new rows), and merge the W*m0-entry frontier into the
    already-sorted beam (ranks by dense compares up to
    DENSE_MERGE_MAX_FRONTIER entries, one stable `lax.sort` of beam and
    frontier together above). W=1 is the classic single-expansion search.

The whole search vmaps over the query batch and jits; query batches shard
over the ('pod','data') mesh axes at serve time (see repro.retrieval).

Distances here are *base metric* (L1/L2) — the cheap family (paper §2.1); we
use root=False powers, which are ordering-equivalent. N_b (the number of
base-metric Q2D evaluations, Eq. 1) is counted exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import lp_distance
from repro.kernels import beam_fetch


@jax.tree_util.register_pytree_node_class
class GraphArrays:
    """Frozen device-resident HNSW topology. Padding sentinel is `n`.

    Registered as a pytree with (n, metric_p) as *static* aux data so the
    traversal code can specialize on them inside jit.
    """

    def __init__(self, adj0, upper_adj, upper_g2l, entry, n: int, metric_p: float):
        self.adj0 = adj0          # (n, m0) int32 neighbor ids, pad = n
        self.upper_adj = upper_adj  # per level l>=1: (n_l, m) global ids, pad = n
        self.upper_g2l = upper_g2l  # per level l>=1: (n,) global->local, -1 absent
        self.entry = entry        # () int32
        self.n = n
        self.metric_p = metric_p

    def tree_flatten(self):
        children = (self.adj0, self.upper_adj, self.upper_g2l, self.entry)
        return children, (self.n, self.metric_p)

    @classmethod
    def tree_unflatten(cls, aux, children):
        adj0, upper_adj, upper_g2l, entry = children
        return cls(adj0, upper_adj, upper_g2l, entry, aux[0], aux[1])

    @classmethod
    def from_graph(cls, g) -> "GraphArrays":
        """Device topology for a built graph.

        Accepts the host `HNSWGraph` (re-packs adjacency, -1 -> sentinel n)
        or any graph exposing `graph_arrays()` — e.g. the bulk builder's
        `DeviceGraph` (repro.core.bulk_build), whose topology is already
        device-resident and is returned as-is.
        """
        if hasattr(g, "graph_arrays"):
            return g.graph_arrays()
        n = g.n

        def pad(a):
            a = np.asarray(a, dtype=np.int32).copy()
            a[a < 0] = n
            return jnp.asarray(a)

        adj0 = pad(g.adjacency[0])
        upper_adj = tuple(pad(a) for a in g.adjacency[1:])
        upper_g2l = tuple(jnp.asarray(a) for a in g.local_index[1:])
        return cls(
            adj0=adj0,
            upper_adj=upper_adj,
            upper_g2l=upper_g2l,
            entry=jnp.asarray(g.entry_point, dtype=jnp.int32),
            n=n,
            metric_p=g.metric_p,
        )

    def pad_to(self, n_pad: int, n_levels: int,
               level_sizes: tuple[int, ...],
               upper_m: int | None = None) -> "GraphArrays":
        """Re-pad to a uniform shape so segments can stack (repro.index).

        Grows the node capacity to n_pad (sentinel n -> n_pad everywhere),
        the upper-level count to n_levels and each level-l row count to
        level_sizes[l]. Missing levels become a single all-sentinel row with
        every node mapped onto it: one greedy-descent hop sees only invalid
        neighbors, adds 0 to N_b, and falls through to the next level.
        """
        assert n_pad >= self.n and n_levels >= len(self.upper_adj)
        old_n = self.n

        def repad(a, rows):
            a = np.asarray(a)
            a = np.where(a == old_n, n_pad, a).astype(np.int32)
            out = np.full((rows, a.shape[1]), n_pad, dtype=np.int32)
            out[: a.shape[0]] = a
            return jnp.asarray(out)

        m = upper_m or (
            self.upper_adj[0].shape[1] if self.upper_adj else self.adj0.shape[1]
        )
        upper_adj, upper_g2l = [], []
        for l in range(n_levels):
            if l < len(self.upper_adj):
                upper_adj.append(repad(self.upper_adj[l], level_sizes[l]))
                g2l = np.full(n_pad, -1, dtype=np.int32)
                g2l[:old_n] = np.asarray(self.upper_g2l[l])
            else:
                upper_adj.append(
                    jnp.full((level_sizes[l], m), n_pad, dtype=jnp.int32)
                )
                g2l = np.zeros(n_pad, dtype=np.int32)  # -> harmless row 0
            upper_g2l.append(jnp.asarray(g2l))
        return GraphArrays(
            adj0=repad(self.adj0, n_pad),
            upper_adj=tuple(upper_adj),
            upper_g2l=tuple(upper_g2l),
            entry=self.entry,
            n=n_pad,
            metric_p=self.metric_p,
        )

    @staticmethod
    def stack(arrays: "list[GraphArrays]") -> "GraphArrays":
        """Stack same-shaped GraphArrays on a leading segment axis.

        All inputs must already be pad_to'd to identical shapes (and share
        metric_p); the result vmaps over axis 0 in knn_search.
        """
        n = arrays[0].n
        p = arrays[0].metric_p
        assert all(a.n == n and a.metric_p == p for a in arrays)
        leaves = [a.tree_flatten()[0] for a in arrays]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)
        return GraphArrays(*stacked, n=n, metric_p=p)


def _base_dist(q: jax.Array, x: jax.Array, p: float) -> jax.Array:
    """Ordering-equivalent base-metric distance (root-free power sum)."""
    return lp_distance(q, x, p, root=False)


# Row width (f32 elements) whose multiples the level-0 loop fetches and
# scores through `kernels.beam_fetch`, reading only the rows its visited
# test marks new (a quarter to a third on the cells' segments), in place of
# an XLA gather of every frontier row (`_score_frontier`). A DMA out of HBM
# moves whole (8, 128) f32 layout tiles: a row of d % 1024 == 0 is d / 1024
# of them, with no padding, while a narrower row would read a whole 4 KB
# tile. The kernel costs ~220 ns a lane + 22 ns per new row (d = 4096; 210
# + 21 at 1024), the gather ~100 ns per row at d = 4096 and 18 at 1024, for
# every row: on a TPU v5e the kernel wins at d = 4096 whatever the new share,
# and at d = 1024 up to a share of about 0.55 (PERF.md, "Findings").
BEAM_FETCH_ROW_ELEMS = 1024


def beam_fetch_on(d: int) -> bool:
    """Whether a search over d-wide rows scores its frontier with the
    fetch-only-new-rows kernel (BEAM_FETCH_ROW_ELEMS)."""
    return d % BEAM_FETCH_ROW_ELEMS == 0


def _score_frontier(q, X, safe, new, p, fetch):
    """Base-metric power sums of the frontier, +inf where not new.

    `fetch` None: an XLA gather of every frontier row, masked after. Else
    (q_tiles, src, base): only the new rows are read, from the flat row
    source `src` at the lane's segment offset `base` (kernels.beam_fetch).
    """
    if fetch is None:
        dv = _base_dist(q, X[safe], p)
        return jnp.where(new, dv, jnp.inf)
    q_tiles, src, base = fetch
    return beam_fetch.fetch_score(q_tiles, safe, new, base, src, p)


# Largest per-query visited bitmask, in uint32 words, whose test-and-set runs
# as dense compares over the word axis (`_visited_dense`); a larger bitmask
# keeps the indexed gather and scatter (`_visited_scatter`). The dense form
# costs O(W*m0 * words) element work per query per hop, the indexed form
# O(W*m0) serial element updates, so the choice rests on `words` alone. Set
# from benchmarks/beam_width.py's visited micro-bench on a TPU v5e
# (PERF.md, "Findings").
DENSE_VISITED_MAX_WORDS = 4096


def _visited_scatter(visited, word, bit, eligible):
    """Visited test-and-set as an indexed gather and scatter-add.

    visited (words,) uint32; word, bit, eligible (J,): each neighbour's
    bitmask word, its bit within the word, and whether it may be new (a
    valid id at its first occurrence). Returns (new (J,) bool, visited).
    """
    seen = (visited[word] & bit) != 0
    new = eligible & ~seen
    # distinct ids -> distinct (word, bit); duplicates are masked to 0,
    # so the scatter-add is carry-free.
    return new, visited.at[word].add(bit * new.astype(jnp.uint32))


def _visited_dense(visited, word, bit, eligible):
    """`_visited_scatter`'s arithmetic as dense compares over the word axis.

    Each neighbour's word is picked out by a compare against iota(words)
    and a sum over words (exactly one term is non-zero), and each word's
    new bits are the sum over neighbours of the bits landing in it. Integer
    sums in the same uint32 ring as the scatter-add, so the result is the
    same bit for bit; XLA fuses each into one compare-select-reduce.
    """
    zero = jnp.uint32(0)
    hit = word[:, None] == jnp.arange(visited.shape[0], dtype=word.dtype)
    held = jnp.where(hit, visited[None, :], zero).sum(axis=1, dtype=jnp.uint32)
    new = eligible & ((held & bit) == 0)
    add = jnp.where(hit, (bit * new.astype(jnp.uint32))[:, None], zero)
    return new, visited + add.sum(axis=0, dtype=jnp.uint32)


def _visited_test_and_set(visited, word, bit, eligible):
    """Dense form up to DENSE_VISITED_MAX_WORDS words, indexed form above;
    the two give identical results (tests/test_visited_forms.py)."""
    if visited.shape[0] <= DENSE_VISITED_MAX_WORDS:
        return _visited_dense(visited, word, bit, eligible)
    return _visited_scatter(visited, word, bit, eligible)


# Widest frontier (W*m0 entries per hop) that the level-0 loop merges into its
# sorted beam by ranks and dense compares (`_merge_dense`); a wider frontier
# re-sorts beam and frontier together (`_merge_sort`). The dense merge costs
# O(ef * W*m0) element work per query per hop, the sort
# O((ef + W*m0) * log^2(ef + W*m0)), so the choice rests on the static widths
# alone. Set from benchmarks/beam_width.py's merge micro-bench on a TPU v5e
# (PERF.md, "Findings").
DENSE_MERGE_MAX_FRONTIER = 64


def _merge_sort(beam, front):
    """The level-0 merge as one stable sort of beam and frontier together.

    beam: (dist (ef,), ids, exp), sorted by dist; front: (dist (F,), ids,
    exp) in any order. Returns the first ef of the concatenation sorted by
    dist, ties in concatenation order.
    """
    ef = beam[0].shape[0]
    cat = tuple(jnp.concatenate([b, f]) for b, f in zip(beam, front))
    out = jax.lax.sort(cat, num_keys=1, is_stable=True)
    return tuple(x[:ef] for x in out)


# jitted so that its ~180 ops are traced once per shape, not once per
# search program that holds the loop: a serving warm-up traces hundreds of
# those programs, even from a warm compile cache, at ~60 ms a merge.
@jax.jit
def _merge_dense(beam, front):
    """`_merge_sort`'s result without a sort: the beam is already sorted.

    Each entry's place in the stable order is counted with dense compares.
    Frontier entry j lands at (beam entries <= it: the beam wins ties) +
    (frontier entries before it: smaller, or equal and earlier); beam entry
    i at i + (frontier entries strictly below it). The frontier is placed by
    a one-hot over its F entries per output slot (exactly one term is live);
    the beam, whose shifts rise with i, by a shifter of log2(F + 1) stages
    that moves each entry by one bit of its shift, highest bit first, where
    no two entries meet. Both keep values bit for bit, so the result equals
    `_merge_sort`'s on NaN-free distances (tests/test_merge_forms.py).
    """
    bd, fd = beam[0], front[0]
    ef, f = bd.shape[0], fd.shape[0]
    le = bd[:, None] <= fd[None, :]                             # (ef, F)
    j = jnp.arange(f)
    # before[j, j']: frontier j' precedes frontier j in the stable order
    before = (fd[None, :] < fd[:, None]) | (
        (fd[None, :] == fd[:, None]) & (j[None, :] < j[:, None]))
    pos_f = le.sum(0, dtype=jnp.int32) + before.sum(1, dtype=jnp.int32)
    shift = f - le.sum(1, dtype=jnp.int32)                      # (ef,)

    hit = pos_f[:, None] == jnp.arange(ef, dtype=jnp.int32)     # (F, ef)
    is_f = hit.any(0)

    def lowest(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.array(-jnp.inf, x.dtype)
        return jnp.array(jnp.iinfo(x.dtype).min, x.dtype)

    placed = [jnp.where(hit, x[:, None], lowest(x)).max(0) for x in front]

    vals = list(beam)
    for k in reversed(range(f.bit_length())):
        s = 1 << k
        moves = ((shift >> k) & 1) == 1
        if s >= ef:  # every mover leaves the first ef slots
            shift = jnp.where(moves, 0, shift)
            continue

        def down(x):
            return jnp.concatenate([jnp.zeros((s,), x.dtype), x[:-s]])

        arrives = down(moves)
        vals = [jnp.where(arrives, down(x), x) for x in vals]
        # an entry that left keeps a stale copy behind with shift 0: it
        # never moves again and the entry due there overwrites it
        shift = jnp.where(arrives, down(shift) & (s - 1),
                          jnp.where(moves, 0, shift))
    return tuple(jnp.where(is_f, p, v) for p, v in zip(placed, vals))


def _merge_beam(beam, front):
    """Dense merge up to DENSE_MERGE_MAX_FRONTIER frontier entries, one
    sort above; the two give identical results (tests/test_merge_forms.py)."""
    if front[0].shape[0] <= DENSE_MERGE_MAX_FRONTIER:
        return _merge_dense(beam, front)
    return _merge_sort(beam, front)


def _greedy_descend(q, X, adj_l, g2l, ep, ep_dist, nb, p, max_hops):
    """Greedy ef=1 search on one upper layer. Returns (ep, ep_dist, nb)."""
    n = X.shape[0]

    def cond(s):
        return s[0] & (s[5] < max_hops)

    def body(s):
        _, ep, ep_dist, nb, _, hops = s
        nbrs = adj_l[g2l[ep]]  # (m,) global ids, pad = n
        valid = nbrs < n
        dv = _base_dist(q, X[jnp.clip(nbrs, 0, n - 1)], p)
        dv = jnp.where(valid, dv, jnp.inf)
        j = jnp.argmin(dv)
        better = dv[j] < ep_dist
        ep2 = jnp.where(better, nbrs[j], ep)
        d2 = jnp.minimum(dv[j], ep_dist)
        return (better, ep2, d2, nb + valid.sum(), j, hops + 1)

    go = jnp.asarray(True)
    s = (go, ep, ep_dist, nb, jnp.int32(0), jnp.int32(0))
    s = jax.lax.while_loop(cond, body, s)
    return s[1], s[2], s[3]


def _beam_search_l0(q, X, adj0, entry, entry_dist, nb0, p, ef, max_hops,
                    width: int = 1, thresh=None, fetch_rows=None):
    """Level-0 ef-beam search for one query. Returns (ids, dists, nb, hops).

    `width` (W) is the multi-expansion factor (DESIGN.md §2 hot path): each
    `while_loop` hop expands the W closest unexpanded beam entries at once —
    one (W*m0,) gather, one batched visited test-and-set, one fused distance
    block, one merge of the frontier into the sorted beam. Trip count drops
    ~W×; each trip's tensor work is W× wider, which the hardware prefers to
    W serialized skinny hops. W=1
    reproduces the classic single-expansion search exactly.

    `thresh` (traced scalar, or None for the unmodified program) is the
    cross-segment pruning bound (DESIGN.md §3): a neighbor whose base-metric
    distance exceeds it is counted in N_b (the evaluation happened) and
    marked visited, but is *not admitted* to the beam — it can neither be
    expanded nor returned. The loop therefore terminates once the
    sub-threshold region reachable from the entry is exhausted, instead of
    flooding the whole ef-neighborhood. The entry itself is always admitted
    (it seeds navigation even when its own distance exceeds the bound).

    `fetch_rows` (None, or (src, base): the flat row source of
    `kernels.beam_fetch` and this segment's first row in it) scores each
    frontier by fetching only its new rows (`_score_frontier`).
    """
    n, m0 = X.shape[0], adj0.shape[1]
    fetch = None
    if fetch_rows is not None:
        fetch = (beam_fetch.query_tiles(q),) + tuple(fetch_rows)
    words = (n + 31) // 32
    w = width

    ids0 = jnp.full((ef,), n, dtype=jnp.int32).at[0].set(entry)
    dist0 = jnp.full((ef,), jnp.inf, dtype=jnp.float32).at[0].set(entry_dist)
    # sentinel slots start "expanded" so they are never selected
    exp0 = jnp.ones((ef,), dtype=jnp.int32).at[0].set(0)
    visited0 = jnp.zeros((words,), dtype=jnp.uint32)
    visited0 = visited0.at[entry >> 5].set(jnp.uint32(1) << (entry.astype(jnp.uint32) & 31))

    def cond(s):
        ids, dist, exp, visited, nb, hops = s
        active = (exp == 0) & (ids < n)
        return jnp.any(active) & (hops < max_hops)

    def body(s):
        ids, dist, exp, visited, nb, hops = s
        # 1. select the W closest unexpanded beam entries
        sel_key = jnp.where((exp == 0) & (ids < n), dist, jnp.inf)
        if w == 1:
            js = jnp.argmin(sel_key)[None]        # (1,)
            sel_ok = jnp.isfinite(sel_key[js])
        else:
            neg, js = jax.lax.top_k(-sel_key, w)  # (W,) best = smallest dist
            sel_ok = jnp.isfinite(neg)            # fewer than W unexpanded?
        exp = exp.at[js].set(1)
        # 2. gather all W neighbor lists; unselected slots contribute
        #    sentinels only
        srcs = jnp.where(sel_ok, ids[js], n)                  # (W,)
        nbrs = adj0[jnp.clip(srcs, 0, n - 1)]                 # (W, m0)
        nbrs = jnp.where(sel_ok[:, None], nbrs, n).reshape(-1)  # (W*m0,)
        if w > 1:
            # the W lists can share neighbors; sort + first-occurrence mask
            # dedupes so the bitmask set below stays carry-free
            nbrs = jax.lax.sort(nbrs)
            first = jnp.concatenate(
                [jnp.ones((1,), bool), nbrs[1:] != nbrs[:-1]]
            )
        else:
            # a single adjacency row holds distinct ids by construction
            first = jnp.ones((m0,), bool)
        # 3. batched visited-bitmask test-and-set
        valid = nbrs < n
        safe = jnp.clip(nbrs, 0, n - 1)
        word = safe >> 5
        bit = jnp.uint32(1) << (safe.astype(jnp.uint32) & 31)
        new, visited = _visited_test_and_set(visited, word, bit, valid & first)
        # 4. one base-metric distance block for unseen neighbors only
        dv = _score_frontier(q, X, safe, new, p, fetch)
        nb = nb + new.sum()
        if thresh is not None:
            # cross-segment early-cut: evaluated (counted above, visited
            # stays set) but above the inherited global bound -> inf, which
            # the merge below flags expanded and places after every finite
            # entry
            dv = jnp.where(dv <= thresh, dv, jnp.inf)
        # 5. merge the frontier into the sorted beam, keep the first ef.
        # Frontier entries join unexpanded; anything with inf distance
        # (sentinels, masked duplicates) is flagged expanded so it can never
        # be selected -> guarantees loop progress. Beam entries with inf
        # distance already carry exp=1 (sentinel init + this very forcing
        # in every earlier merge), so only the frontier needs the mask.
        front = (dv, nbrs, jnp.isinf(dv).astype(jnp.int32))
        dist, ids, exp = _merge_beam((dist, ids, exp), front)
        return (ids, dist, exp, visited, nb, hops + 1)

    s = (ids0, dist0, exp0, visited0, nb0, jnp.int32(0))
    ids, dist, exp, visited, nb, hops = jax.lax.while_loop(cond, body, s)
    return ids, dist, nb, hops


def _greedy_descend_l0(q, X, adj0, ep, ep_dist, nb, p, max_hops,
                       thresh=None):
    """Greedy ef=1 descent on the *level-0* adjacency (ids are global, no
    g2l remap). Used only on the thresholded cross-segment path: it walks
    downhill before the admission-cut beam starts, so a far-off entry
    whose whole neighborhood sits above the bound cannot strand the
    search before it reaches the query's region. The walk stops as soon
    as the entry drops below `thresh` — the beam takes over from there,
    so descending further only duplicates evaluations the beam will
    redo."""
    n = X.shape[0]

    def cond(s):
        return s[0] & (s[4] < max_hops)

    def body(s):
        _, ep, ep_dist, nb, hops = s
        nbrs = adj0[ep]  # (m0,) pad = n
        valid = nbrs < n
        dv = _base_dist(q, X[jnp.clip(nbrs, 0, n - 1)], p)
        dv = jnp.where(valid, dv, jnp.inf)
        j = jnp.argmin(dv)
        better = dv[j] < ep_dist
        ep2 = jnp.where(better, nbrs[j], ep)
        d2 = jnp.minimum(dv[j], ep_dist)
        go = better
        if thresh is not None:
            go = go & (d2 > thresh)
        return (go, ep2, d2, nb + valid.sum(), hops + 1)

    s = (jnp.asarray(True), ep, ep_dist, nb, jnp.int32(0))
    if thresh is not None:
        s = (ep_dist > thresh, ep, ep_dist, nb, jnp.int32(0))
    s = jax.lax.while_loop(cond, body, s)
    return s[1], s[2], s[3]


def _search_one(q, X, arrays: GraphArrays, ef: int, max_hops: int,
                expand_width: int = 1, thresh=None, fetch_rows=None):
    p = arrays.metric_p
    n = arrays.n
    ep = arrays.entry
    ep_dist = _base_dist(q, X[ep], p)
    nb = jnp.int32(1)
    # descend upper layers, top to bottom (static python loop over levels)
    for adj_l, g2l in zip(reversed(arrays.upper_adj), reversed(arrays.upper_g2l)):
        ep, ep_dist, nb = _greedy_descend(
            q, X, adj_l, g2l, ep, ep_dist, nb, p, max_hops
        )
    if thresh is not None:
        # finish navigation greedily at level 0 before the admission cut
        # engages — see _greedy_descend_l0
        ep, ep_dist, nb = _greedy_descend_l0(
            q, X, arrays.adj0, ep, ep_dist, nb, p, max_hops, thresh=thresh
        )
    out = _beam_search_l0(q, X, arrays.adj0, ep, ep_dist, nb, p, ef,
                          max_hops, width=expand_width, thresh=thresh,
                          fetch_rows=fetch_rows)
    if fetch_rows is None:
        return out
    # the rows the level-0 loop read: its share of N_b
    return out + (out[2] - nb,)


@functools.partial(jax.jit, static_argnames=("ef", "t", "max_hops", "expand_width"))
def knn_search(
    arrays: GraphArrays,
    X: jax.Array,
    Q: jax.Array,
    ef: int,
    t: int,
    max_hops: int = 4096,
    expand_width: int = 1,
    thresh: jax.Array | None = None,
    fetch_rows: tuple | None = None,
):
    """Batched t-NN search under the graph's base metric.

    Args:
      arrays: frozen graph topology (GraphArrays.from_graph).
      X: (n, d) dataset.
      Q: (B, d) query batch.
      ef: beam width (>= t).
      t: number of candidates to return per query (paper's t).
      expand_width: W-way multi-expansion factor for the level-0 beam
        (W best unexpanded entries per hop; W=1 = classic HNSW).
      thresh: optional (B,) per-query base-metric (root-free) pruning
        bounds — the cross-segment inherited k-th-best (DESIGN.md §3).
        Neighbors beyond a query's bound are evaluated (counted in n_b)
        but never admitted to its beam; slots past the admitted set come
        back as id n with dist inf. None (the default) compiles the
        unmodified program — bit-identical to the pre-threshold search.
      fetch_rows: optional (src, base): `X` as the flat row source of
        `kernels.beam_fetch` (rows of d % BEAM_FETCH_ROW_ELEMS == 0) and
        X's first row in it. The level-0 loop then reads only the
        neighbour rows its visited test marks new. None (the default)
        compiles the XLA gather of every frontier row.

    Returns:
      ids   (B, t) int32 candidate ids sorted by base-metric distance;
      dists (B, t) base-metric distances (root-free powers);
      n_b   (B,)   exact count of base-metric Q2D evaluations (Eq. 1 N_b);
      hops  (B,)   level-0 hop counts (while_loop trips — one trip expands
                   up to `expand_width` beam entries);
      rows  (B,)   with `fetch_rows` only: the corpus rows the level-0 loop
                   read, its share of n_b.
    """
    assert ef >= t, (ef, t)
    assert 1 <= expand_width <= ef, (
        f"expand_width must be in [1, ef]: got expand_width={expand_width}, "
        f"ef={ef} (top_k cannot select more entries than the beam holds)"
    )
    if thresh is None:
        out = jax.vmap(
            lambda q: _search_one(q, X, arrays, ef, max_hops, expand_width,
                                  fetch_rows=fetch_rows)
        )(Q)
    else:
        thresh = jnp.asarray(thresh, dtype=jnp.float32)
        out = jax.vmap(
            lambda q, th: _search_one(q, X, arrays, ef, max_hops,
                                      expand_width, thresh=th,
                                      fetch_rows=fetch_rows)
        )(Q, thresh)
    ids, dists = out[:2]
    return (ids[:, :t], dists[:, :t]) + tuple(out[2:])


@functools.partial(jax.jit, static_argnames=("p",))
def _exact_topk_merge_chunk(best_d, best_i, Q, xc, start, p: float):
    """One brute-force chunk: score + sort-merge into the running top-k.

    Jitted with `start` as a *traced* scalar, so the compile cache is keyed
    only on the chunk shape: one compilation covers every full chunk and one
    more covers the ragged tail, instead of re-tracing per chunk.
    """
    from repro.core.metrics import pairwise_lp

    k = best_d.shape[1]
    d = pairwise_lp(Q, xc, p, root=False)
    ids = jnp.arange(xc.shape[0], dtype=jnp.int32) + start
    ids = jnp.broadcast_to(ids[None, :], d.shape)
    all_d = jnp.concatenate([best_d, d], axis=1)
    all_i = jnp.concatenate([best_i, ids], axis=1)
    sd, si = jax.lax.sort((all_d, all_i), num_keys=1)
    return sd[:, :k], si[:, :k]


def exact_topk(X: jax.Array, Q: jax.Array, p: float, k: int, chunk: int = 8192):
    """Brute-force Lp top-k oracle (used for ground truth and recall).

    When n < k the trailing slots hold id -1 with inf distance — padding,
    not real points; `recall()` and downstream consumers must mask ids < 0.
    """
    n = X.shape[0]
    best_d = jnp.full((Q.shape[0], k), jnp.inf)
    best_i = jnp.full((Q.shape[0], k), -1, dtype=jnp.int32)
    for start in range(0, n, chunk):
        xc = X[start : start + chunk]
        best_d, best_i = _exact_topk_merge_chunk(
            best_d, best_i, Q, xc, jnp.int32(start), p
        )
    return best_i, best_d
