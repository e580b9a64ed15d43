"""Level-0 merge: the frontier merged into the sorted beam against one sort.

Each hop of the level-0 beam loop (core/hnsw.py) merges its W*m0-entry
frontier into the ef-entry beam, which the previous hop left sorted. Up to
DENSE_MERGE_MAX_FRONTIER frontier entries it places every entry by ranks
counted with dense compares (`_merge_dense`), above that it sorts beam and
frontier together (`_merge_sort`). Both must give what one stable sort of
the concatenation gives, bit for bit:

  * the two helpers against `lax.sort(..., is_stable=True)[:ef]`, with
    integer-valued distances (ties between beam and frontier and within the
    frontier) and infs in both halves, at frontier widths 32 and 128 and at
    a small beam and the cells' 600;
  * a search run under each form returns equal ids, distances, N_b and
    hops, for W in {1, 4}, with and without the cross-segment threshold, on
    a graph over real-valued rows and on one over small integer rows, where
    distances tie;
  * the shapes the benchmark cells run (ef 600, W 1, m0 32) trace no sort
    at all in the search, and a frontier past the crossover keeps the sort.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hnsw
from repro.core.build import build_hnsw_bulk
from repro.core.hnsw import (
    DENSE_MERGE_MAX_FRONTIER,
    GraphArrays,
    _merge_dense,
    _merge_sort,
    knn_search,
)


def _draw(rng, n, ties):
    if ties:
        v = rng.integers(0, 5, size=n).astype(np.float32)
    else:
        v = rng.exponential(size=n).astype(np.float32)
    v[rng.random(n) < 0.3] = np.inf
    return v


def _stable_reference(beam, front):
    ef = beam[0].shape[0]
    cat = tuple(jnp.concatenate([b, f]) for b, f in zip(beam, front))
    out = jax.lax.sort(cat, num_keys=1, is_stable=True)
    return [np.asarray(x[:ef]) for x in out]


@pytest.mark.parametrize("ties", [True, False])
@pytest.mark.parametrize("f", [32, 128])
@pytest.mark.parametrize("ef", [12, 600])
def test_merge_helpers_equal_stable_sort(ef, f, ties):
    """Both helpers give the stable sort's first ef entries: the beam
    first among equal distances, the frontier in its own order after."""
    rng = np.random.default_rng(ef * 1000 + f + ties)
    for _ in range(4):
        bd = np.sort(_draw(rng, ef, ties))
        fd = _draw(rng, f, ties)
        beam = (jnp.asarray(bd),
                jnp.asarray(rng.permutation(4 * ef)[:ef].astype(np.int32)),
                jnp.asarray(np.where(np.isinf(bd), 1,
                                     rng.integers(0, 2, ef)).astype(np.int32)))
        front = (jnp.asarray(fd),
                 jnp.asarray(rng.permutation(4 * f)[:f].astype(np.int32)),
                 jnp.asarray(np.isinf(fd).astype(np.int32)))
        want = _stable_reference(beam, front)
        for form in (_merge_dense, _merge_sort):
            got = jax.jit(form)(beam, front)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), w)


def _search(arrays, X, Q, **kw):
    ids, dists, nb, hops = knn_search(arrays, X, Q, **kw)
    return tuple(np.asarray(a) for a in (ids, dists, nb, hops))


def _search_under(monkeypatch, limit, *args, **kw):
    """The search traced with the crossover at `limit` frontier entries."""
    monkeypatch.setattr(hnsw, "DENSE_MERGE_MAX_FRONTIER", limit)
    knn_search.clear_cache()
    try:
        return _search(*args, **kw)
    finally:
        knn_search.clear_cache()


@pytest.fixture(scope="module")
def graph_real(small_ds):
    data = small_ds.data[:500]
    g = build_hnsw_bulk(data, 1.0, m=8, seed=3)
    return GraphArrays.from_graph(g), jnp.asarray(data), jnp.asarray(
        small_ds.queries[:6])


@pytest.fixture(scope="module")
def graph_ties():
    """Rows on a small integer grid: L1 distances are small integers, so
    beam and frontier tie on nearly every hop."""
    rng = np.random.default_rng(5)
    data = rng.integers(0, 3, size=(400, 6)).astype(np.float32)
    g = build_hnsw_bulk(data, 1.0, m=8, seed=4)
    Q = rng.integers(0, 3, size=(6, 6)).astype(np.float32)
    return GraphArrays.from_graph(g), jnp.asarray(data), jnp.asarray(Q)


@pytest.mark.parametrize("graph", ["graph_real", "graph_ties"])
@pytest.mark.parametrize("threshed", [False, True])
@pytest.mark.parametrize("w", [1, 4])
def test_search_identical_across_forms(request, monkeypatch, graph, w,
                                       threshed):
    """The dense merge (crossover above the frontier) and the sort
    (crossover 0) give the same search, N_b and hops included."""
    arrays, X, Q = request.getfixturevalue(graph)
    kw = dict(ef=48, t=16, expand_width=w)
    if threshed:
        # each query's 6th-best base distance: the admission cut engages
        _, d, _, _ = knn_search(arrays, X, Q, ef=48, t=16)
        kw["thresh"] = d[:, 5]
    front = w * arrays.adj0.shape[1]
    dense = _search_under(monkeypatch, front, arrays, X, Q, **kw)
    sort = _search_under(monkeypatch, 0, arrays, X, Q, **kw)
    for a, b in zip(dense, sort):
        np.testing.assert_array_equal(a, b)
    if graph == "graph_ties":  # the ties this graph is for did happen
        d = dense[1]
        assert ((d[:, 1:] == d[:, :-1]) & np.isfinite(d[:, 1:])).any()


def _sort_widths(jaxpr) -> list[int]:
    """Lengths of the arrays every `sort` in a jaxpr (and the jaxprs it
    holds: loop bodies, branches, inner jits) sorts."""
    widths = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            widths.append(eqn.invars[0].aval.shape[-1])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    widths += _sort_widths(inner)
    return widths


def _traced_sort_widths(n: int, w: int) -> list[int]:
    m0, d, ef = 32, 8, 600
    arrays = GraphArrays(
        adj0=jax.ShapeDtypeStruct((n, m0), jnp.int32), upper_adj=(),
        upper_g2l=(), entry=jax.ShapeDtypeStruct((), jnp.int32), n=n,
        metric_p=1.0)
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    Q = jax.ShapeDtypeStruct((4, d), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, x, q: knn_search(
        a, x, q, ef=ef, t=300, expand_width=w))(arrays, X, Q)
    return _sort_widths(jaxpr.jaxpr)


@pytest.mark.parametrize("n,w", [(16384, 1), (32768, 1), (8192, 1),
                                 (16384, 4), (16384, 8)])
def test_form_chosen_by_frontier_width(n, w):
    """The cells' segments (deep 16,384 rows, glove 32,768, trevi 8,192)
    at ef 600, W 1, m0 32 trace no sort at all: no 632-entry merge sort
    and, at W 1, no dedup sort. Past the crossover the (600 + W*32)-entry
    merge sort comes back, beside the W > 1 dedup sort of W*32 ids."""
    front = w * 32
    widths = _traced_sort_widths(n, w)
    assert (600 + front in widths) == (front > DENSE_MERGE_MAX_FRONTIER)
    assert (front in widths) == (w > 1)
    if w == 1:
        assert DENSE_MERGE_MAX_FRONTIER >= front and widths == []
