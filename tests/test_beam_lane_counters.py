"""The engine's level-0 beam-loop occupancy counters.

`beam_lane_trips` counts the lane-trips that did work (each served row's
`hops`, summed over its segment lanes); `beam_lane_slots` the lane-trips
the batched loops ran (per row, each lane's loop trip count). A lane
that finishes early idles in lockstep until the loop's slowest lane is
done, so slots >= trips, with equality when every lane stops together.
"""

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
