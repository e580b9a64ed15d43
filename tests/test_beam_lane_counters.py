"""The engine's level-0 beam-loop occupancy counters.

`beam_lane_trips` counts the lane-trips that did work (each served row's
`hops`, summed over its segment lanes); `beam_lane_slots` the lane-trips
the batched loops ran (per row, each lane's loop trip count). A lane
that finishes early idles in lockstep until the loop's slowest lane is
done, so slots >= trips, with equality when every lane stops together.
`beam_rows_read` counts the corpus rows those loops read: hops x W*m0
where each trip gathers its whole frontier, the level-0 share of N_b
where the fetch kernel reads only the new rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.uhnsw import UHNSW, UHNSWParams
from repro.index.sharded import ShardedParams, ShardedUHNSW
from repro.retrieval.service import QueryRequest, UniversalVectorService


def _serve(index, queries, ps, max_batch=16, min_bucket=8):
    svc = UniversalVectorService(index=index, max_batch=max_batch,
                                 min_bucket=min_bucket)
    svc.serve([QueryRequest(vector=q, p=float(p), k=10, request_id=i)
               for i, (q, p) in enumerate(zip(queries, ps))])
    return svc.stats


def test_one_query_on_one_segment_fills_every_trip(small_ds):
    index = ShardedUHNSW.build(small_ds.data[:400], num_segments=1, m=8,
                               params=UHNSWParams(t=40), seed=0)
    st = _serve(index, small_ds.queries[:1], [0.8])
    assert st["queries"] == 1 and st["padded_rows"] > 0
    assert st["beam_lane_trips"] > 0
    assert st["beam_lane_slots"] == st["beam_lane_trips"]


def test_lanes_stopping_apart_match_the_hand_computed_fill(small_ds,
                                                           make_sharded):
    """One exact-fit wave of 8 G1 rows over 4 segments: slots are the
    loop's trip count (the largest lane's hops) x 4 lanes x 8 rows. A
    beam far narrower than a segment makes the lanes stop apart."""
    sharded_index = make_sharded(params=UHNSWParams(t=10))
    q = small_ds.queries[:8]
    st = _serve(sharded_index, q, [0.8] * 8, max_batch=8)
    assert st["batches"] == 1 and st["padded_rows"] == 0
    # each segment alone: its lanes' hops, row by row
    per_seg = np.stack([
        np.asarray(sharded_index.search_stage_candidates(
            q, 1.0, k=10, alive=[s]).hops)
        for s in range(sharded_index.num_segments)])
    full = sharded_index.search_stage_candidates(q, 1.0, k=10)
    np.testing.assert_array_equal(per_seg.sum(axis=0),
                                  np.asarray(full.hops))
    assert per_seg.min() < per_seg.max()  # lanes stop at different trips
    trips = int(per_seg.sum())
    slots = int(per_seg.max()) * per_seg.size
    assert st["beam_lane_trips"] == trips
    assert st["beam_lane_slots"] == slots
    assert st["beam_lane_trips"] / st["beam_lane_slots"] == \
        pytest.approx(trips / slots) and trips < slots


@pytest.mark.parametrize("policy", ["independent", "two_phase",
                                    "round_robin", "monolithic"])
def test_slots_never_below_trips(small_ds, make_sharded, graphs_bulk,
                                 policy):
    """Every policy, several waves with padding: each beam program's lanes
    count against that program's own trip count."""
    if policy == "monolithic":
        index = UHNSW(*graphs_bulk, UHNSWParams(t=60))
    else:
        index = make_sharded(params=UHNSWParams(t=60),
                             sharded_params=ShardedParams(policy=policy,
                                                          probe=2))
    rng = np.random.default_rng(5)
    ps = rng.choice([0.5, 0.8, 1.25, 1.7, 2.0], size=21)
    st = _serve(index, small_ds.queries[np.arange(21) % 24], ps)
    assert st["queries"] == 21 and st["padded_rows"] > 0
    assert 0 < st["beam_lane_trips"] <= st["beam_lane_slots"]


@pytest.mark.parametrize("policy", ["independent", "two_phase",
                                    "round_robin", "monolithic"])
def test_rows_read_is_every_frontier_row_of_every_trip(small_ds, make_sharded,
                                                       graphs_bulk, policy):
    """Rows narrower than a DMA tile run the gather of the whole frontier:
    each row reads hops x W*m0 corpus rows, and the engine's
    `beam_rows_read` sums the real rows only (padding rows are out)."""
    if policy == "monolithic":
        index = UHNSW(*graphs_bulk, UHNSWParams(t=60))
        m0 = index.arrays1.adj0.shape[-1]
    else:
        index = make_sharded(params=UHNSWParams(t=60),
                             sharded_params=ShardedParams(policy=policy,
                                                          probe=2))
        m0 = index.segments.arrays1.adj0.shape[-1]
    q = small_ds.queries[:5]
    cands = index.search_stage_candidates(q, 1.0, k=10)
    np.testing.assert_array_equal(np.asarray(cands.rows_read),
                                  np.asarray(cands.hops) * m0)
    st = _serve(index, q, [0.8] * 5)
    assert st["queries"] == 5 and st["padded_rows"] > 0
    assert st["beam_rows_read"] == int(np.asarray(cands.rows_read).sum())


def _upper_share(arrays, x, q, max_hops):
    """N_b of one segment's search before its level-0 loop: the entry
    and the greedy descent of the upper layers."""
    from repro.core.hnsw import _base_dist, _greedy_descend

    p = arrays.metric_p
    ep = arrays.entry
    dist, nb = _base_dist(q, x[ep], p), jnp.int32(1)
    for adj_l, g2l in zip(reversed(arrays.upper_adj),
                          reversed(arrays.upper_g2l)):
        ep, dist, nb = _greedy_descend(q, x, adj_l, g2l, ep, dist, nb, p,
                                       max_hops)
    return int(nb)


def test_rows_read_is_the_level0_share_of_n_b_on_the_kernel_path():
    """Rows of whole DMA tiles (d = 1024) run the fetch kernel: each row
    reads exactly the neighbours its level-0 loops evaluated, N_b less
    each segment's entry and upper-layer descent."""
    rng = np.random.default_rng(2)
    data = rng.standard_normal((480, 1024)).astype(np.float32)
    index = ShardedUHNSW.build(data, num_segments=2, m=8, seed=1,
                               params=UHNSWParams(t=16, ef=32))
    q = data[:4] + 0.05 * rng.standard_normal((4, 1024)).astype(np.float32)
    cands = index.search_stage_candidates(q, 2.0, k=10)
    seg = index.segments
    upper = np.array([
        sum(_upper_share(jax.tree.map(lambda a: a[s], seg.arrays2),
                         seg.X[s], jnp.asarray(qi), index.params.max_hops)
            for s in range(seg.num_segments))
        for qi in q])
    np.testing.assert_array_equal(np.asarray(cands.rows_read),
                                  np.asarray(cands.n_b) - upper)
    m0 = seg.arrays2.adj0.shape[-1]
    assert (np.asarray(cands.rows_read) < np.asarray(cands.hops) * m0).all()
