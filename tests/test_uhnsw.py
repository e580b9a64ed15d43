"""Algorithm 1 (U-HNSW query) semantics + end-to-end recall."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hnsw import exact_topk
from repro.core.metrics import numpy_lp
from repro.core.uhnsw import UHNSW, UHNSWParams, recall, verify_candidates


def _reference_verify(Q, cand_ids, X, p, k, kappa, tau):
    """Literal NumPy transcription of paper Algorithm 1 lines 7-11."""
    out_ids, out_np = [], []
    for qi in range(Q.shape[0]):
        q = Q[qi]
        C = list(cand_ids[qi])
        dist = {c: float(numpy_lp(q[None], X[c][None], p, root=False)[0, 0]) for c in C[:k]}
        R = sorted(C[:k], key=lambda c: (dist[c], c))
        n_p = k
        i = k
        while i + kappa <= len(C):
            batch = C[i : i + kappa]
            i += kappa
            for c in batch:
                dist[c] = float(numpy_lp(q[None], X[c][None], p, root=False)[0, 0])
            n_p += kappa
            union = R + batch
            R_new = sorted(union, key=lambda c: (dist[c], c))[:k]
            inter = len(set(R_new) & set(R))
            R = R_new
            if inter / k >= tau:
                break
        out_ids.append(R)
        out_np.append(n_p)
    return np.array(out_ids), np.array(out_np)


def test_verify_matches_reference(small_ds, rng):
    """The jitted while_loop implements Algorithm 1 exactly."""
    X = small_ds.data
    Q = small_ds.queries[:6]
    k, kappa, tau, t = 10, 5, 0.9, 60
    cand = np.stack([rng.permutation(small_ds.n)[:t] for _ in range(len(Q))]).astype(np.int32)
    ids, dists, st = verify_candidates(
        jnp.asarray(Q), jnp.asarray(cand), jnp.asarray(X), 0.7, k, kappa, tau
    )
    n_p = st.n_p
    ref_ids, ref_np = _reference_verify(Q, cand, X, 0.7, k, kappa, tau)
    # same result *sets* (order may differ on exact ties)
    for i in range(len(Q)):
        assert set(np.asarray(ids)[i].tolist()) == set(ref_ids[i].tolist())
    np.testing.assert_array_equal(np.asarray(n_p), ref_np)


def test_early_termination_saves_work(small_ds, graphs_bulk):
    """tau < 1 must verify fewer candidates than exhaustive re-ranking."""
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=200))
    Q = jnp.asarray(small_ds.queries)
    _, _, stats = idx.search(Q, 0.8, 20)
    n_p = np.asarray(stats.n_p)
    assert (n_p <= 200).all()
    assert n_p.mean() < 150  # early termination really triggers
    assert (n_p >= 20).all()  # at least the initial K


@pytest.mark.parametrize("p", [0.5, 0.8, 1.2, 1.4, 1.7, 2.0])
def test_end_to_end_recall(p, small_ds, graphs_bulk):
    """Paper target: recall >= 0.9 across the universal p range."""
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=200))
    X = jnp.asarray(small_ds.data)
    Q = jnp.asarray(small_ds.queries)
    K = 20
    ids, dists, stats = idx.search(Q, p, K)
    true_ids, _ = exact_topk(X, Q, p, K)
    r = recall(ids, true_ids)
    assert r >= 0.9, f"p={p}: recall {r}"


def test_base_metric_shortcut(small_ds, graphs_bulk):
    """p == base metric skips verification entirely (N_p == 0)."""
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=150))
    Q = jnp.asarray(small_ds.queries[:8])
    for p in (1.0, 2.0):
        _, _, stats = idx.search(Q, p, 10)
        assert float(stats.n_p.sum()) == 0
        assert stats.base_p == p


def test_base_index_selection(graphs_bulk):
    idx = UHNSW(*graphs_bulk)
    assert idx.base_graph_for(0.5)[1] == 1.0
    assert idx.base_graph_for(1.4)[1] == 1.0
    assert idx.base_graph_for(1.5)[1] == 2.0
    assert idx.base_graph_for(2.0)[1] == 2.0


def test_returned_distances_are_exact_lp(small_ds, graphs_bulk):
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=150))
    Q = jnp.asarray(small_ds.queries[:4])
    p = 1.3
    ids, dists, _ = idx.search(Q, p, 10)
    ids, dists = np.asarray(ids), np.asarray(dists)
    for i in range(len(ids)):
        want = numpy_lp(small_ds.queries[i][None], small_ds.data[ids[i]], p)[0]
        np.testing.assert_allclose(dists[i], want, rtol=2e-4)


def test_modeled_cost_eq1(graphs_bulk, small_ds):
    """Eq. 1: T = N_b T_b + N_p T_p with T_p >> T_b for general p."""
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=150))
    Q = jnp.asarray(small_ds.queries[:8])
    _, _, stats = idx.search(Q, 0.8, 10)
    cost = idx.modeled_query_cost(stats, 0.8, small_ds.d)
    assert cost["T_p"] > 5 * cost["T_b"]
    assert cost["total"] == pytest.approx(
        cost["N_b"] * cost["T_b"] + cost["N_p"] * cost["T_p"]
    )
