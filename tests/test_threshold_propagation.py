"""Cross-segment threshold propagation (DESIGN.md §3): parity + invariants.

The two-phase policy probes a prior-ordered subset of segments with the
full beam, then searches the remaining segments with the probe's running
rank-r base distance as an admission bound. The properties pinned here:

  * knob validation + rank derivation — `ShardedParams` rejects bad
    configs; `resolve_thresh_rank` always returns an ADMISSIBLE rank:
    r >= ceil(t * probe / S) (each probed segment holds at least t/S of
    any merged top-t on average, so bounding at that rank can only prune
    candidates outside the merged top-t) and r >= k (never prunes inside
    the caller's top-k), clamped to [1, t];
  * merge monotonicity — `merge_phase_lists` / `merge_tagged_lists` can
    only tighten the running list: every output rank's distance is <= the
    same rank's distance before the merge. This is the inductive step of
    threshold monotonicity across phases: the bound the cascade hands to
    segment i+1 is never looser than the one it handed to segment i;
  * threshold semantics — thresh=+inf is bitwise the unthresholded
    program (the None-vs-inf jit split must not change results), and a
    degenerate two_phase (probe >= S) is bitwise the independent policy;
  * exactness under the conservative bound — with thresh_rank=t (the
    loosest admissible rank: nothing that could enter the merged top-t is
    ever pruned) the two-phase ids match the exhaustive independent
    policy's ids exactly at the base metrics, where the pruning bound and
    the result metric coincide;
  * recall parity vs the monolithic index at p in {0.5, 1.0, 1.25, 2.0},
    with the delta tier live before AND after compaction — delta-resident
    hits are scanned exactly and must never be pruned by the inherited
    bound;
  * phase attribution — n_b == n_b_probe + n_b_spill exactly, per row,
    and the split surfaces through both serving paths' stats.

Property tests use the optional-hypothesis shim (they skip when the dep
is missing); every property also has a seeded-parametrize fallback that
always runs, so the invariants stay enforced in the bare container.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.hnsw import GraphArrays, exact_topk, knn_search
from repro.core.uhnsw import recall
from repro.index import ShardedParams, build_segments
from repro.index.sharded import (
    merge_phase_lists,
    merge_tagged_lists,
    segmented_knn_search,
)
from repro.retrieval.service import QueryRequest, UniversalVectorService
from tests_hypothesis_compat import given, settings, st  # optional dep shim

P_GRID = [0.5, 1.0, 1.25, 2.0]
K = 10


# ---------------------------------------------------------------------------
# ShardedParams: validation + rank derivation
# ---------------------------------------------------------------------------


def test_params_validation():
    with pytest.raises(ValueError, match="unknown policy"):
        ShardedParams(policy="telepathic")
    with pytest.raises(ValueError, match="probe"):
        ShardedParams(policy="two_phase", probe=0)
    with pytest.raises(ValueError, match="ef_shrink"):
        ShardedParams(policy="two_phase", ef_shrink=0.0)
    with pytest.raises(ValueError, match="ef_shrink"):
        ShardedParams(policy="two_phase", ef_shrink=1.5)
    assert ShardedParams().policy == "independent"  # seed-compatible default


def test_resolve_thresh_rank_cases():
    sp = ShardedParams(policy="two_phase")
    # derived: max(k, ceil(t * probe / S)), clamped to [1, t]
    assert sp.resolve_thresh_rank(t=100, num_segments=4, k=10) == 25
    assert sp.resolve_thresh_rank(t=100, num_segments=4, k=None) == 25
    assert sp.resolve_thresh_rank(t=100, num_segments=4, k=60) == 60
    assert sp.resolve_thresh_rank(t=100, num_segments=4, k=300) == 100
    sp2 = ShardedParams(policy="two_phase", probe=2)
    assert sp2.resolve_thresh_rank(t=100, num_segments=4, k=1) == 50
    # probe clamps to S: the degenerate single-phase case derives rank t
    sp8 = ShardedParams(policy="two_phase", probe=8)
    assert sp8.resolve_thresh_rank(t=100, num_segments=4, k=1) == 100
    # explicit rank wins, clamped to [1, t]
    spx = ShardedParams(policy="two_phase", thresh_rank=999)
    assert spx.resolve_thresh_rank(t=50, num_segments=4, k=10) == 50
    assert ShardedParams(policy="two_phase", thresh_rank=-3) \
        .resolve_thresh_rank(t=50, num_segments=4, k=10) == 1


def _assert_rank_admissible(t, s, probe, k):
    sp = ShardedParams(policy="two_phase", probe=probe)
    r = sp.resolve_thresh_rank(t=t, num_segments=s, k=k)
    pe = max(1, min(probe, s))
    assert 1 <= r <= t
    assert r * s >= t * pe, f"inadmissible rank {r} (t={t} S={s} probe={pe})"
    if k is not None and k <= t:
        assert r >= k, "derived rank prunes inside the caller's top-k"


@pytest.mark.parametrize("seed", range(8))
def test_derived_rank_admissible_seeded(seed):
    rng = np.random.default_rng(seed)
    for _ in range(50):
        t = int(rng.integers(1, 500))
        s = int(rng.integers(1, 12))
        probe = int(rng.integers(1, 12))
        k = None if rng.random() < 0.2 else int(rng.integers(1, t + 1))
        _assert_rank_admissible(t, s, probe, k)


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 500), st.integers(1, 12), st.integers(1, 12),
       st.one_of(st.none(), st.integers(1, 500)))
def test_derived_rank_admissible_property(t, s, probe, k):
    _assert_rank_admissible(t, s, probe, k)


# ---------------------------------------------------------------------------
# merge primitives: the running list only ever tightens
# ---------------------------------------------------------------------------


def _rand_lists(rng, b, w1, w2):
    d_a = np.sort(rng.exponential(1.0, (b, w1)), axis=1).astype(np.float32)
    d_b = np.sort(rng.exponential(1.0, (b, w2)), axis=1).astype(np.float32)
    g_a = rng.integers(0, 10_000, (b, w1)).astype(np.int32)
    g_b = rng.integers(0, 10_000, (b, w2)).astype(np.int32)
    return (jnp.asarray(g_a), jnp.asarray(d_a),
            jnp.asarray(g_b), jnp.asarray(d_b))


def _assert_merge_tightens(g_a, d_a, g_b, d_b, t):
    sg, sd, sf = merge_phase_lists(g_a, d_a, g_b, d_b, t)
    sd, sf = np.asarray(sd), np.asarray(sf)
    # sorted ascending, and never looser than the pre-merge list at any rank
    assert (np.diff(sd, axis=1) >= 0).all()
    w = min(t, d_a.shape[1])
    assert (sd[:, :w] <= np.asarray(d_a)[:, :w] + 1e-7).all(), \
        "merge loosened the running bound"
    # flags attribute each survivor to its source list
    assert np.isin(sf, (0, 1)).all()
    # cascade form: one more merge with a fresh list keeps tightening
    sg2, sd2, sf2 = merge_tagged_lists(sg, jnp.asarray(sd),
                                       jnp.asarray(sf, np.int32),
                                       g_b, d_b, t)
    assert (np.asarray(sd2) <= sd[:, :t] + 1e-7).all()
    assert (np.diff(np.asarray(sd2), axis=1) >= 0).all()


@pytest.mark.parametrize("seed", range(6))
def test_merge_monotone_seeded(seed):
    rng = np.random.default_rng(100 + seed)
    b = int(rng.integers(1, 6))
    w1 = int(rng.integers(1, 40))
    w2 = int(rng.integers(1, 40))
    t = int(rng.integers(1, w1 + w2 + 1))
    _assert_merge_tightens(*_rand_lists(rng, b, w1, w2), t)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 5), st.integers(1, 40),
       st.integers(1, 40))
def test_merge_monotone_property(seed, b, w1, w2):
    rng = np.random.default_rng(seed)
    t = int(rng.integers(1, w1 + w2 + 1))
    _assert_merge_tightens(*_rand_lists(rng, b, w1, w2), t)


# ---------------------------------------------------------------------------
# threshold semantics at the search primitives
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_segments(small_ds):
    """240-point 4-segment corpus: small enough for exhaustive beams."""
    return small_ds.data[:240], build_segments(small_ds.data[:240],
                                               num_segments=4, m=8, seed=3)


def test_thresh_inf_bitwise_equals_none(graph_incremental, small_ds):
    g = graph_incremental
    arrays = GraphArrays.from_graph(g)
    X = jnp.asarray(g.data)
    Q = jnp.asarray(small_ds.queries[:8])
    ids, dists, nb, hops = knn_search(arrays, X, Q, ef=32, t=8)
    inf = jnp.full((Q.shape[0],), jnp.inf)
    ids_i, dists_i, nb_i, hops_i = knn_search(arrays, X, Q, ef=32, t=8,
                                              thresh=inf)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_i))
    np.testing.assert_array_equal(np.asarray(dists), np.asarray(dists_i))
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(nb_i))


def test_segmented_thresh_inf_bitwise_equals_none(tiny_segments, small_ds):
    _, segs = tiny_segments
    Q = jnp.asarray(small_ds.queries[:8])
    a = segmented_knn_search(segs.arrays1, segs.X, segs.node_ids, Q,
                             ef=32, t=K)
    b = segmented_knn_search(segs.arrays1, segs.X, segs.node_ids, Q,
                             ef=32, t=K,
                             thresh=jnp.full((Q.shape[0],), jnp.inf))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("base_p", [1.0, 2.0])
def test_oracle_threshold_sound_and_cheaper(tiny_segments, small_ds, base_p):
    """Bound the search at the TRUE k-th-best base distance (the tightest
    admissible oracle). The admission cut never fabricates results: every
    finite returned distance is a true top-k distance, exactly (any point
    at base distance <= the true k-th best IS a top-k member). It also
    must save base-metric work vs the open search. Recall under a bound
    this tight is NOT exactly 1.0 — pruned nodes are not expanded, so a
    below-bound point whose only graph paths run through above-bound
    nodes can strand (measured ~0.95 here). That reachability loss is why
    the two_phase policy derives a looser rank-based bound, and why the
    conservative thresh_rank=t variant (tested below) recovers exact ids
    parity."""
    data, segs = tiny_segments
    Q = jnp.asarray(small_ds.queries[:12])
    arrays = segs.arrays1 if base_p == 1.0 else segs.arrays2
    n_seg = max(g.n for g in segs.graphs1)
    true_ids, true_d = exact_topk(jnp.asarray(data), Q, base_p, K)
    thresh = jnp.asarray(true_d[:, K - 1] * (1 + 1e-6))
    gids, gdists, nb_t, _, _, _ = segmented_knn_search(
        arrays, segs.X, segs.node_ids, Q, ef=n_seg, t=K, thresh=thresh)
    gids, gdists = np.asarray(gids), np.asarray(gdists)
    true_ids, true_d = np.asarray(true_ids), np.asarray(true_d)
    thresh_np = np.asarray(thresh)
    for i in range(gids.shape[0]):
        # below-bound survivors only: entry-point seeds stay in the list
        # with finite above-bound distances (they are never admitted to
        # expansion, but they do occupy result slots)
        fin = gdists[i] <= thresh_np[i]
        assert set(gids[i][fin]) <= set(true_ids[i]), \
            "thresholded search admitted a non-top-k candidate"
        for j in np.flatnonzero(fin):
            pos = int(np.where(true_ids[i] == gids[i, j])[0][0])
            np.testing.assert_allclose(gdists[i, j], true_d[i, pos],
                                       rtol=1e-5, atol=1e-5)
    assert recall(jnp.asarray(gids), jnp.asarray(true_ids)) >= 0.9
    # the bound actually saved base-metric work vs the open search
    _, _, nb_open, _, _, _ = segmented_knn_search(
        arrays, segs.X, segs.node_ids, Q, ef=n_seg, t=K)
    assert float(jnp.mean(nb_t)) < float(jnp.mean(nb_open))


# ---------------------------------------------------------------------------
# policy parity on the session 4-segment index
# ---------------------------------------------------------------------------


def test_degenerate_two_phase_is_independent_bitwise(make_sharded, small_ds):
    """probe >= S leaves nothing to spill: bitwise the independent policy."""
    Q = jnp.asarray(small_ds.queries)
    ref = make_sharded(sharded_params=ShardedParams(policy="independent"))
    deg = make_sharded(sharded_params=ShardedParams(policy="two_phase",
                                                    probe=4))
    for p in (0.8, 2.0):
        ids_r, d_r, st_r = ref.search(Q, p, K)
        ids_d, d_d, st_d = deg.search(Q, p, K)
        np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_d))
        np.testing.assert_array_equal(np.asarray(d_r), np.asarray(d_d))
        np.testing.assert_array_equal(np.asarray(st_r.n_b),
                                      np.asarray(st_d.n_b))
        assert float(jnp.max(jnp.asarray(st_d.n_b_spill))) == 0.0


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_conservative_rank_ids_equal_independent(make_sharded, small_ds, p):
    """thresh_rank=t (the loosest admissible bound: nothing that could
    enter the merged top-t is pruned) at the base metrics, where the
    pruning bound and the result metric coincide: ids must match the
    exhaustive independent policy exactly — sharding with threshold
    propagation is then a pure speedup."""
    t = 150
    Q = jnp.asarray(small_ds.queries)
    ref = make_sharded(sharded_params=ShardedParams(policy="independent"))
    safe = make_sharded(sharded_params=ShardedParams(
        policy="two_phase", thresh_rank=t))
    ids_r, d_r, st_r = ref.search(Q, p, K)
    ids_s, d_s, st_s = safe.search(Q, p, K)
    np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_s))
    np.testing.assert_allclose(np.asarray(d_r), np.asarray(d_s), rtol=1e-6)
    # and it must actually be cheaper than exhaustive search
    assert float(jnp.mean(st_s.n_b)) < float(jnp.mean(st_r.n_b))


@pytest.mark.parametrize("policy", ["two_phase", "round_robin"])
@pytest.mark.parametrize("p", P_GRID)
def test_recall_parity_vs_monolithic(make_sharded, monolithic_index,
                                     small_ds, policy, p):
    """Thresholded policies vs the monolithic index across the p grid:
    bounded recall cost (the bench gates the exact budget; here we pin a
    generous invariant floor) at visibly lower N_b than independent."""
    Q = jnp.asarray(small_ds.queries)
    true_ids, _ = exact_topk(jnp.asarray(small_ds.data), Q, p, K)
    idx = make_sharded(sharded_params=ShardedParams(policy=policy))
    ids, _, stats = idx.search(Q, p, K)
    ids_m, _, _ = monolithic_index.search(Q, p, K)
    r_s, r_m = recall(ids, true_ids), recall(ids_m, true_ids)
    assert r_s >= r_m - 0.05, f"{policy} p={p}: {r_s:.3f} vs mono {r_m:.3f}"
    ref = make_sharded(sharded_params=ShardedParams(policy="independent"))
    _, _, st_ref = ref.search(Q, p, K)
    assert float(jnp.mean(stats.n_b)) < float(jnp.mean(st_ref.n_b))


@pytest.mark.parametrize("p", P_GRID)
def test_delta_hits_survive_threshold_pre_and_post_compaction(
        make_sharded, small_ds, p):
    """Delta-resident rows are scanned exactly — the inherited bound must
    never prune them, before or after compaction."""
    idx = make_sharded(sharded_params=ShardedParams(policy="two_phase"),
                       delta_capacity=64)
    rng = np.random.default_rng(7)
    v = (small_ds.data.mean(axis=0)
         + 6.0 * rng.standard_normal(small_ds.data.shape[1])
         ).astype(np.float32)
    gid = idx.add(v)
    assert len(idx.delta) == 1
    ids, dists, _ = idx.search(v[None, :], p, k=3)
    assert int(ids[0, 0]) == gid
    # self-distance ~0 up to the exact-lane's expanded-form |x-q|^2
    # cancellation at this vector scale (identical under independent)
    assert float(dists[0, 0]) == pytest.approx(0.0, abs=0.05)
    idx.compact()
    assert len(idx.delta) == 0
    ids, dists, _ = idx.search(v[None, :], p, k=3)
    assert int(ids[0, 0]) == gid, "compacted insert lost under thresholding"


# ---------------------------------------------------------------------------
# phase attribution: stats stay conserved through every layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["independent", "two_phase",
                                    "round_robin"])
def test_phase_split_conserves_totals(make_sharded, small_ds, policy):
    Q = jnp.asarray(small_ds.queries)
    idx = make_sharded(sharded_params=ShardedParams(policy=policy))
    for p in (0.8, 1.25):
        _, _, stats = idx.search(Q, p, K)
        nb_pr, nb_sp = stats.phase_n_b()
        np_pr, np_sp = stats.phase_n_p()
        np.testing.assert_allclose(
            np.asarray(nb_pr) + np.asarray(nb_sp), np.asarray(stats.n_b),
            err_msg=f"{policy} p={p}: n_b != probe + spill")
        assert (np.asarray(np_pr) + np.asarray(np_sp)
                <= np.asarray(stats.n_p) + 1e-5).all()
        if policy == "independent":
            assert float(np.max(np.asarray(nb_sp))) == 0.0
        else:
            assert float(np.mean(np.asarray(nb_sp))) > 0.0


def test_serving_paths_surface_phase_stats(make_sharded, small_ds):
    """Both serving paths (v1 submit/drain and the continuous-batching
    engine) aggregate the probe/spill split into their stats dicts."""
    idx = make_sharded(sharded_params=ShardedParams(policy="two_phase"))
    reqs = [QueryRequest(vector=small_ds.queries[i % 8],
                         p=[0.8, 1.25, 2.0][i % 3], k=K, request_id=i)
            for i in range(12)]
    # v1 path
    svc = UniversalVectorService(index=idx, max_batch=16)
    svc.submit(reqs)
    out = svc.drain()
    assert len(out) == 12
    st = svc.stats
    assert st["n_b_spill"] > 0.0
    np.testing.assert_allclose(st["n_b_probe"] + st["n_b_spill"], st["n_b"],
                               rtol=1e-6)
    # engine path (serve)
    svc2 = UniversalVectorService(index=idx, max_batch=16)
    out2 = svc2.serve(reqs)
    assert len(out2) == 12
    st2 = svc2.stats
    assert st2["n_b_spill"] > 0.0
    np.testing.assert_allclose(st2["n_b_probe"] + st2["n_b_spill"],
                               st2["n_b"], rtol=1e-6)
