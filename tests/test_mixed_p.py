"""Mixed-p (vector-p) serving: bit-parity vs per-p grouped serving.

The tentpole guarantee (DESIGN.md §6): a mixed-p batch served in ONE
device call returns bitwise-identical (ids, dists) to per-p grouped
serving, on both the jnp-reference and the interpret=True Pallas paths.

Two parity layers are pinned here:

  * STRUCTURAL (bitwise): the traced-p program computes each row from that
    row's data alone, so its per-row results are invariant to batch
    composition and batch size. `serve` and `serve_grouped` run the same
    traced-p programs, so mixed == grouped bit-for-bit.
  * CROSS-PROGRAM (tight rtol): a traced-p row vs the *static-p
    specialized* program at that row's p. The op sequences are selected
    bit-identically (core/lp_ops), but XLA may reassociate the d-axis
    reduction by ~1 ulp on some tile shapes, so this layer asserts
    rtol=1e-6 + identical inf masks rather than bit equality.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.metrics import base_metric_for, pairwise_lp, rowwise_lp
from repro.core.uhnsw import UHNSW, UHNSWParams, verify_candidates
from repro.kernels.ops import lp_gather_distance, pallas_rowwise_lp
from repro.retrieval.service import (
    QueryRequest,
    QueueFull,
    UniversalVectorService,
)

# the acceptance grid: two verification ps (one per base graph), one
# G1-base special p, one G2-base special p
P_ACCEPT = [0.5, 0.8, 1.25, 2.0]
P_ALL = P_ACCEPT + [1.0, 1.5, 0.9]


def _close_with_inf(got, want, err=""):
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want), err_msg=err)
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-6, err_msg=err)


def _mixed_case(seed, b, c, n, d):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32) * 3)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) * 3)
    ids = rng.integers(-1, n + 2, size=(b, c)).astype(np.int32)
    ps = rng.choice(P_ALL, size=b).astype(np.float32)
    return q, x, jnp.asarray(ids), ps


# ---------------------------------------------------------------------------
# kernel layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("interpret", [None, True])
@pytest.mark.parametrize("root", [False, True])
def test_gather_vector_p_rows_match_scalar(interpret, root):
    """Cross-program: vector-p gather rows vs scalar-p specialization."""
    q, x, ids, ps = _mixed_case(3, b=9, c=37, n=120, d=24)
    got = np.asarray(lp_gather_distance(q, ids, x, jnp.asarray(ps),
                                        root=root, interpret=interpret))
    for i, p in enumerate(ps):
        want = np.asarray(lp_gather_distance(q[i:i + 1], ids[i:i + 1], x,
                                             float(p), root=root,
                                             interpret=interpret))[0]
        _close_with_inf(got[i], want, err=f"p={p}")


@pytest.mark.parametrize("interpret", [None, True])
def test_gather_vector_p_batch_invariance_bitwise(interpret):
    """STRUCTURAL: traced-p rows are bit-invariant to batch composition —
    the property mixed-vs-grouped serving parity rests on."""
    q, x, ids, ps = _mixed_case(7, b=16, c=41, n=150, d=24)
    full = np.asarray(lp_gather_distance(q, ids, x, jnp.asarray(ps),
                                         root=True, interpret=interpret))
    for bs in (1, 3, 7, 11):
        sub = np.asarray(lp_gather_distance(q[:bs], ids[:bs], x,
                                            jnp.asarray(ps[:bs]),
                                            root=True, interpret=interpret))
        np.testing.assert_array_equal(full[:bs], sub, err_msg=f"bs={bs}")


def test_gather_vector_p_1d_ids_match_scalar():
    """The delta-scan (shared 1-D ids) shape under vector p."""
    rng = np.random.default_rng(5)
    b, n, d = 8, 90, 16
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ps = rng.choice(P_ALL, size=b).astype(np.float32)
    ids1 = jnp.asarray(rng.integers(-1, n + 1, size=(33,)).astype(np.int32))
    for interpret in (None, True):
        got = np.asarray(lp_gather_distance(q, ids1, x, jnp.asarray(ps),
                                            root=True, interpret=interpret))
        for i, p in enumerate(ps):
            want = np.asarray(lp_gather_distance(q[i:i + 1], ids1, x,
                                                 float(p), root=True,
                                                 interpret=interpret))[0]
            _close_with_inf(got[i], want, err=f"p={p} int={interpret}")


def test_rowwise_kernel_vector_p_matches_scalar():
    rng = np.random.default_rng(11)
    b, c, d = 6, 40, 32
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    cands = jnp.asarray(rng.normal(size=(b, c, d)).astype(np.float32))
    ps = rng.choice(P_ALL, size=b).astype(np.float32)
    got = np.asarray(pallas_rowwise_lp(q, cands, jnp.asarray(ps),
                                       root=True, interpret=True))
    for i, p in enumerate(ps):
        want = np.asarray(pallas_rowwise_lp(q[i:i + 1], cands[i:i + 1],
                                            float(p), root=True,
                                            interpret=True))[0]
        _close_with_inf(got[i], want, err=f"p={p}")


def test_reference_metrics_vector_p_match_scalar():
    rng = np.random.default_rng(17)
    b, n, c, d = 6, 50, 21, 12
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    cands = jnp.asarray(rng.normal(size=(b, c, d)).astype(np.float32))
    ps = np.asarray(P_ALL[:b], dtype=np.float32)
    pw = np.asarray(pairwise_lp(q, x, jnp.asarray(ps)))
    rw = np.asarray(rowwise_lp(q, cands, jnp.asarray(ps)))
    for i, p in enumerate(ps):
        _close_with_inf(pw[i],
                        np.asarray(pairwise_lp(q[i:i + 1], x, float(p)))[0],
                        err=f"pairwise p={p}")
        _close_with_inf(rw[i],
                        np.asarray(rowwise_lp(q[i:i + 1], cands[i:i + 1],
                                              float(p)))[0],
                        err=f"rowwise p={p}")


def test_base_metric_for_vectorized():
    base = base_metric_for(np.asarray([0.5, 1.4, 1.41, 2.0], np.float32))
    np.testing.assert_array_equal(base, [1.0, 1.0, 2.0, 2.0])
    with pytest.raises(ValueError):
        base_metric_for(np.asarray([0.4, 1.0], np.float32))
    with pytest.raises(ValueError):
        base_metric_for(2.5)


# ---------------------------------------------------------------------------
# verification layer
# ---------------------------------------------------------------------------


def _verify_case(seed=23, b=8, t=60, n=300, d=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    # plausible candidate lists: random ids with a little padding
    ids = rng.permuted(np.tile(np.arange(n), (b, 1)), axis=1)[:, :t]
    ids[:, -2:] = -1
    ids = jnp.asarray(ids.astype(np.int32))
    ps = rng.choice(P_ACCEPT, size=b).astype(np.float32)
    return q, x, ids, ps


@pytest.mark.parametrize("interpret", [None, True])
def test_verify_candidates_vector_p_batch_invariance(interpret):
    """STRUCTURAL: mixed-batch verification freezes each row at its own
    convergence point — per-row (ids, dists, n_p) are bit-invariant to
    batch mixing. (The convergence while_loop runs until the *slowest*
    row finishes, but finished rows' states are frozen.)"""
    q, x, ids, ps = _verify_case()
    k, kappa = 10, 5
    mv = verify_candidates(q, ids, x, jnp.asarray(ps), k, kappa, 0.92,
                           interpret=interpret)
    for bs in (1, 3, 5):
        sv = verify_candidates(q[:bs], ids[:bs], x, jnp.asarray(ps[:bs]),
                               k, kappa, 0.92, interpret=interpret)
        m_rows = (mv[0], mv[1], mv[2].n_p)
        s_rows = (sv[0], sv[1], sv[2].n_p)
        for j in range(3):  # ids, dists, n_p
            np.testing.assert_array_equal(np.asarray(m_rows[j])[:bs],
                                          np.asarray(s_rows[j]), err_msg=f"{j}")


def test_verify_candidates_vector_p_matches_scalar():
    """Cross-program: each vector-p row vs the static-p specialization."""
    q, x, ids, ps = _verify_case()
    k, kappa = 10, 5
    mv = verify_candidates(q, ids, x, jnp.asarray(ps), k, kappa, 0.92)
    for i, p in enumerate(ps):
        sv = verify_candidates(q[i:i + 1], ids[i:i + 1], x, float(p),
                               k, kappa, 0.92)
        np.testing.assert_array_equal(np.asarray(mv[0])[i],
                                      np.asarray(sv[0])[0], err_msg=f"p={p}")
        np.testing.assert_allclose(np.asarray(mv[1])[i],
                                   np.asarray(sv[1])[0], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(mv[2].n_p)[i],
                                      np.asarray(sv[2].n_p)[0])


# ---------------------------------------------------------------------------
# index + scheduler layer (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=[None, True],
                ids=["jnp-ref", "pallas-interpret"])
def service_pair(request, small_ds, graphs_bulk):
    """A service on the monolithic index, per exact-Lp dispatch path."""
    params = UHNSWParams(t=100, interpret=request.param)
    return UniversalVectorService(
        index=UHNSW(*graphs_bulk, params), max_batch=32, min_bucket=8,
    ), small_ds


def _accept_stream(small_ds, n=40, seed=0):
    rng = np.random.default_rng(seed)
    return [
        QueryRequest(vector=small_ds.queries[i % len(small_ds.queries)],
                     p=float(rng.choice(P_ACCEPT)), k=10, request_id=i)
        for i in range(n)
    ]


def test_mixed_batch_bitwise_equals_grouped(service_pair):
    """ACCEPTANCE: one mixed-p batched call == per-p grouped serving,
    bitwise on (ids, dists), at p in {0.5, 0.8, 1.25, 2.0}, on both the
    jnp reference and the interpret=True Pallas path."""
    service, small_ds = service_pair
    reqs = _accept_stream(small_ds)
    mixed = service.serve(reqs)
    grouped = service.serve_grouped(reqs)
    for r in reqs:
        np.testing.assert_array_equal(mixed[r.request_id][0],
                                      grouped[r.request_id][0],
                                      err_msg=f"ids p={r.p}")
        np.testing.assert_array_equal(mixed[r.request_id][1],
                                      grouped[r.request_id][1],
                                      err_msg=f"dists p={r.p}")


def test_index_mixed_search_matches_grouped(small_ds, graphs_bulk):
    """Direct index-level vector-p search (no scheduler) is bitwise equal
    to per-p constant-vector calls (structural), and matches the static
    scalar specialization on ids + near-bitwise dists."""
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=100))
    rng = np.random.default_rng(1)
    Q = jnp.asarray(small_ds.queries[:16])
    ps = rng.choice(P_ACCEPT, size=16).astype(np.float32)
    mids, mdists, mstats = idx.search(Q, ps, 10)
    assert np.asarray(mstats.n_b).shape == (16,)
    for pval in np.unique(ps):
        sel = np.flatnonzero(ps == pval)
        # structural: the same traced-p program, grouped batch
        gids, gdists, gstats = idx.search(Q[sel], np.full(sel.size, pval),
                                          10)
        np.testing.assert_array_equal(np.asarray(mids)[sel], np.asarray(gids))
        np.testing.assert_array_equal(np.asarray(mdists)[sel],
                                      np.asarray(gdists))
        np.testing.assert_array_equal(np.asarray(mstats.n_p)[sel],
                                      np.asarray(gstats.n_p))
        np.testing.assert_array_equal(np.asarray(mstats.n_b)[sel],
                                      np.asarray(gstats.n_b))
        # cross-program: the classic static-p path
        sids, sdists, _ = idx.search(Q[sel], float(pval), 10)
        np.testing.assert_array_equal(np.asarray(mids)[sel],
                                      np.asarray(sids))
        np.testing.assert_allclose(np.asarray(mdists)[sel],
                                   np.asarray(sdists), rtol=1e-6)


def test_sharded_mixed_search_with_delta_matches_grouped(small_ds,
                                                         make_sharded):
    # fresh wrapper over the session's frozen 4-segment build: this test
    # mutates the index (delta adds), so it cannot share sharded_index
    sh = make_sharded(params=UHNSWParams(t=80), delta_capacity=64)
    for i in range(8):  # delta-resident rows must merge identically
        sh.add(small_ds.data[i] + 0.01)
    rng = np.random.default_rng(2)
    Q = jnp.asarray(small_ds.queries[:12])
    ps = rng.choice(P_ACCEPT, size=12).astype(np.float32)
    mids, mdists, _ = sh.search(Q, ps, 10)
    for pval in np.unique(ps):
        sel = np.flatnonzero(ps == pval)
        gids, gdists, _ = sh.search(Q[sel], np.full(sel.size, pval), 10)
        np.testing.assert_array_equal(np.asarray(mids)[sel], np.asarray(gids))
        np.testing.assert_array_equal(np.asarray(mdists)[sel],
                                      np.asarray(gdists))


# ---------------------------------------------------------------------------
# scheduler behavior
# ---------------------------------------------------------------------------


def test_scheduler_buckets_two_entry_points(service_pair):
    """A stream with many distinct p values runs in (bases x chunks)
    device batches — not one batch per distinct p."""
    service, small_ds = service_pair
    before = service.stats["batches"]
    many_p = [0.5 + 0.015 * i for i in range(32)]  # 32 distinct ps, all G1
    reqs = [QueryRequest(vector=small_ds.queries[i % 8],
                         p=many_p[i], k=10, request_id=i)
            for i in range(32)]
    out = service.serve(reqs)
    assert len(out) == 32
    n_batches = service.stats["batches"] - before
    bases = {base_metric_for(p) for p in many_p}
    assert n_batches == len(bases), (
        f"{n_batches} device batches for 32 distinct ps; expected one per "
        f"base graph ({len(bases)})"
    )


def test_scheduler_bucket_padding_shapes(service_pair):
    """Chunk sizes pad to the power-of-two ladder; stats exclude padding."""
    service, small_ds = service_pair
    before_q = service.stats["queries"]
    before_pad = service.stats["padded_rows"]
    reqs = _accept_stream(small_ds, n=11, seed=4)
    service.serve(reqs)
    assert service.stats["queries"] - before_q == 11  # padding not counted
    assert service.stats["padded_rows"] > before_pad  # 11 never fits ladder


def test_scheduler_queue_bound_and_stats(small_ds, graphs_bulk):
    service = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)),
        max_batch=16, queue_capacity=8,
    )
    reqs = _accept_stream(small_ds, n=9, seed=5)
    with pytest.raises(QueueFull):
        service.submit(reqs)
    assert service.queue_depth == 0  # no partial enqueue
    service.submit(reqs[:8])
    assert service.queue_depth == 8
    assert service.stats["queue_peak"] == 8
    out = service.drain()
    assert len(out) == 8 and service.queue_depth == 0
    # serve() waves respect the bound internally
    out = service.serve(reqs)
    assert len(out) == 9
    # p out of range rejected before enqueue
    bad = [QueryRequest(vector=small_ds.queries[0], p=3.0, k=5,
                        request_id=99)]
    with pytest.raises(ValueError):
        service.submit(bad)
    assert service.queue_depth == 0


def test_drain_failure_recovers_queue_and_partial_results(small_ds,
                                                          graphs_bulk):
    """A failing bucket re-queues every unserved request and hands back the
    already-computed responses via exc.partial_results; a retry drains the
    remainder (no request is ever lost or double-served)."""
    service = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), max_verify_batch=8)
    reqs = [QueryRequest(vector=small_ds.queries[i % 8], p=0.8, k=5,
                         request_id=i) for i in range(10)]  # 2 buckets
    service.submit(reqs)
    real_search = service.index.search
    calls = {"n": 0}

    def flaky(q, p, k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom")
        return real_search(q, p, k)

    service.index.search = flaky
    try:
        with pytest.raises(RuntimeError) as ei:
            service.drain()
        served = ei.value.partial_results
        assert len(served) == 8 and service.queue_depth == 2
    finally:
        service.index.search = real_search
    rest = service.drain()
    assert set(served) | set(rest) == set(range(10))
    assert not set(served) & set(rest)


def test_numpy_scalar_p_is_static(small_ds, graphs_bulk):
    """np.float32 / 0-d numpy p must hit the static specialization, not
    crash in the vector path (regression)."""
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=80))
    Q = jnp.asarray(small_ds.queries[:4])
    a, ad, _ = idx.search(Q, np.float32(0.8), 5)
    b, bd, _ = idx.search(Q, 0.8, 5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ad), np.asarray(bd))
    got = pairwise_lp(Q, Q, np.float32(1.5))
    want = pairwise_lp(Q, Q, 1.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_serve_with_prequeued_requests(small_ds, graphs_bulk):
    """serve() must tolerate a pre-populated queue: no spurious QueueFull,
    and the earlier submissions are served too (FIFO)."""
    service = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), queue_capacity=8)
    early = _accept_stream(small_ds, n=6, seed=7)
    for r in early:
        r.request_id += 1000
    service.submit(early)
    late = _accept_stream(small_ds, n=10, seed=8)  # 6 + 10 > capacity 8
    out = service.serve(late)
    assert {r.request_id for r in early} <= set(out)
    assert {r.request_id for r in late} <= set(out)
    assert service.queue_depth == 0


def test_scheduler_per_p_and_per_base_stats(small_ds, graphs_bulk):
    """The stats fix: Eq. 1 counters are attributable per base graph and
    per requested p, and agree with the aggregate."""
    service = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)))
    reqs = _accept_stream(small_ds, n=24, seed=6)
    service.serve(reqs)
    st = service.stats
    assert st["queries"] == 24
    per_p_q = sum(v["queries"] for v in st["per_p"].values())
    per_base_q = sum(v["queries"] for v in st["per_base"].values())
    assert per_p_q == per_base_q == 24
    assert st["per_base"]["G1"]["queries"] > 0  # 0.5 / 0.8 rows
    assert st["per_base"]["G2"]["queries"] > 0  # 1.25 / 2.0 rows
    np.testing.assert_allclose(
        sum(v["n_p"] for v in st["per_p"].values()), st["n_p"])
    np.testing.assert_allclose(
        sum(v["n_b"] for v in st["per_base"].values()), st["n_b"])
    # p == base metric rows ride the exact lane: no verification at all
    assert st["per_p"]["2"]["n_p"] == 0
    lat = service.latency_summary()
    assert lat["count"] == 24 and lat["p95"] >= lat["p50"] > 0
