"""Segmented sharded U-HNSW: merge correctness, recall parity, delta tier."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hnsw import GraphArrays, exact_topk, knn_search
from repro.core.uhnsw import UHNSWParams, recall
from repro.index import ShardedUHNSW, build_segments
from repro.index.sharded import segmented_knn_search

P_GRID = [0.5, 1.25, 2.0]
K = 10


# the 4-segment and monolithic indexes come from the session fixtures
# sharded_index / monolithic_index (tests/conftest.py): one graph build
# per session, shared read-only across test modules.

# ---------------------------------------------------------------------------
# pad_to / stack: padding must not change search results
# ---------------------------------------------------------------------------


def test_padded_stacked_search_matches_unpadded(graph_incremental, small_ds):
    g = graph_incremental
    arrays = GraphArrays.from_graph(g)
    X = jnp.asarray(g.data)
    Q = jnp.asarray(small_ds.queries[:8])
    ids, dists, nb, hops = knn_search(arrays, X, Q, ef=32, t=8)

    # pad: +37 phantom nodes, +2 phantom levels, wider level rows
    n_levels = len(arrays.upper_adj) + 2
    sizes = tuple(
        (arrays.upper_adj[l].shape[0] + 5 if l < len(arrays.upper_adj) else 1)
        for l in range(n_levels)
    )
    padded = arrays.pad_to(g.n + 37, n_levels, sizes, upper_m=g.m)
    Xp = jnp.concatenate([X, jnp.zeros((37, g.d))], axis=0)
    ids_p, dists_p, nb_p, _ = knn_search(padded, Xp, Q, ef=32, t=8)

    valid = np.asarray(ids) < g.n
    np.testing.assert_array_equal(
        np.where(valid, np.asarray(ids), -1),
        np.where(np.asarray(ids_p) < padded.n, np.asarray(ids_p), -1),
    )
    np.testing.assert_allclose(np.asarray(dists), np.asarray(dists_p))
    # phantom levels/nodes must not add base-metric evaluations
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(nb_p))

    # a single padded segment stacked S=1 gives identical results again
    stacked = GraphArrays.stack([padded])
    node_ids = jnp.concatenate(
        [jnp.arange(g.n, dtype=jnp.int32),
         jnp.full((37,), -1, dtype=jnp.int32)]
    )[None, :]
    gids, gdists, gnb, _, _, _ = segmented_knn_search(
        stacked, Xp[None], node_ids, Q, ef=32, t=8
    )
    np.testing.assert_array_equal(
        np.where(valid, np.asarray(ids), -1), np.asarray(gids)
    )
    np.testing.assert_allclose(np.asarray(dists), np.asarray(gdists))


# ---------------------------------------------------------------------------
# merge correctness: exhaustive per-segment beams -> merge must equal oracle
# ---------------------------------------------------------------------------


def test_segment_merge_equals_exact_topk(small_ds):
    """With beams wide enough to visit every node, the S-way merge must
    reproduce the monolithic exact top-k (this isolates the merge logic
    from graph-quality effects)."""
    data = small_ds.data[:240]
    segs = build_segments(data, num_segments=4, m=8, seed=3)
    Q = jnp.asarray(small_ds.queries[:12])
    n_seg = max(g.n for g in segs.graphs1)
    for base_p, arrays in ((1.0, segs.arrays1), (2.0, segs.arrays2)):
        gids, gdists, _, _, _, _ = segmented_knn_search(
            arrays, segs.X, segs.node_ids, Q, ef=n_seg, t=K
        )
        true_ids, true_d = exact_topk(jnp.asarray(data), Q, base_p, K)
        np.testing.assert_allclose(
            np.asarray(gdists), np.asarray(true_d), rtol=1e-5, atol=1e-5
        )
        assert recall(gids, true_ids) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# recall parity vs the monolithic index (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", P_GRID)
def test_recall_parity_with_monolithic(p, sharded_index, monolithic_index,
                                       small_ds):
    Q = jnp.asarray(small_ds.queries)
    true_ids, _ = exact_topk(jnp.asarray(small_ds.data), Q, p, K)
    ids_s, dists_s, stats_s = sharded_index.search(Q, p, K)
    ids_m, _, _ = monolithic_index.search(Q, p, K)
    r_s, r_m = recall(ids_s, true_ids), recall(ids_m, true_ids)
    assert r_s >= r_m - 0.02, f"p={p}: sharded {r_s:.3f} vs mono {r_m:.3f}"
    # distances come out sorted and rooted
    d = np.asarray(dists_s)
    assert (np.diff(d, axis=1) >= -1e-5).all()
    # early termination must be live: N_p stays well under t for non-base p
    if p not in (1.0, 2.0):
        assert float(jnp.mean(stats_s.n_p)) < 150


def test_base_p_skips_verification(sharded_index, small_ds):
    Q = jnp.asarray(small_ds.queries[:8])
    for p in (1.0, 2.0):
        _, _, stats = sharded_index.search(Q, p, K)
        assert float(jnp.max(stats.n_p)) == 0.0


# ---------------------------------------------------------------------------
# delta tier: streaming inserts
# ---------------------------------------------------------------------------


def test_delta_insert_findable_at_every_p(small_ds):
    idx = ShardedUHNSW.build(
        small_ds.data[:500], num_segments=4, m=8,
        params=UHNSWParams(t=64), seed=1, delta_capacity=64,
    )
    rng = np.random.default_rng(5)
    v = (small_ds.data[:500].mean(axis=0)
         + 6.0 * rng.standard_normal(small_ds.data.shape[1])).astype(np.float32)
    gid = idx.add(v)
    assert len(idx.delta) == 1  # still in the delta tier

    def assert_found():
        for p in P_GRID + [1.0, 1.7]:
            ids, dists, _ = idx.search(v[None, :], p, k=3)
            assert int(ids[0, 0]) == gid, (p, np.asarray(ids[0]))
            assert float(dists[0, 0]) == pytest.approx(0.0, abs=1e-4)

    assert_found()                     # before compaction (delta scan path)
    segs_before = idx.num_segments
    idx.compact()                      # freeze the delta into a new segment
    assert idx.num_segments == segs_before + 1 and len(idx.delta) == 0
    assert_found()                     # after compaction (graph path)


def test_auto_compaction_at_capacity(small_ds):
    idx = ShardedUHNSW.build(
        small_ds.data[:300], num_segments=2, m=8,
        params=UHNSWParams(t=32), seed=2, delta_capacity=8,
    )
    rng = np.random.default_rng(9)
    gids = [idx.add(rng.standard_normal(small_ds.data.shape[1]).astype(np.float32) * 3)
            for _ in range(20)]
    # 20 adds at capacity 8 -> 2 compactions, 4 residents in the delta
    assert idx.num_segments == 4
    assert len(idx.delta) == 4
    assert idx.n == 320
    # every insert remains findable, whichever tier it landed in
    for gid in gids[::3]:
        q = idx.get_vector(gid)[None, :]
        ids, _, _ = idx.search(q, 1.3, k=1)
        assert int(ids[0, 0]) == gid
