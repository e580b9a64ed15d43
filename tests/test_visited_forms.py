"""Visited-bitmask test-and-set: dense compares against indexed gather/scatter.

The level-0 beam loop (core/hnsw.py) tests and sets each hop's neighbours in
a per-query uint32 bitmask. Up to DENSE_VISITED_MAX_WORDS words it does so
with dense compares over the word axis, above that with an indexed gather
and scatter-add. The two are the same integer arithmetic, so a search must
come out bit for bit the same whichever form its bitmask size selects:

  * the two helpers agree on random inputs, duplicates included;
  * one graph searched at its own size (dense) and padded with phantom rows
    to bitmasks at and past the crossover (dense at the boundary, then
    indexed) returns equal ids, distances, N_b and hops, for W in {1, 4}
    and with and without the cross-segment threshold;
  * the same holds on the all-to-all graph, where the W lists share every
    neighbour;
  * the segment sizes the benchmark cells run (16,384 and 8,192 rows) trace
    no scatter-add, and a monolithic 262,144-row index still does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.build import build_hnsw_bulk
from repro.core.hnsw import (
    DENSE_VISITED_MAX_WORDS,
    GraphArrays,
    _visited_dense,
    _visited_scatter,
    knn_search,
)

# bitmask sizes (rows) that straddle the crossover: the last dense size and
# the first indexed one
N_AT_CROSSOVER = DENSE_VISITED_MAX_WORDS * 32
N_PAST_CROSSOVER = N_AT_CROSSOVER + 32


def _pad(arrays: GraphArrays, X, n_pad: int):
    sizes = tuple(a.shape[0] for a in arrays.upper_adj)
    padded = arrays.pad_to(n_pad, len(sizes), sizes)
    Xp = jnp.concatenate(
        [X, jnp.zeros((n_pad - X.shape[0], X.shape[1]), X.dtype)], axis=0)
    return padded, Xp


def _search(arrays, X, Q, **kw):
    """knn_search with the padding sentinel mapped to -1, as numpy."""
    ids, dists, nb, hops = knn_search(arrays, X, Q, **kw)
    ids = np.asarray(ids)
    return (np.where(ids < arrays.n, ids, -1), np.asarray(dists),
            np.asarray(nb), np.asarray(hops))


def _assert_same(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("words,j", [(1, 8), (7, 32), (64, 32), (300, 128)])
def test_forms_identical_on_random_inputs(words, j):
    """Same (new, visited) from both helpers, with repeated ids among the
    eligible ones, where the sums carry in both forms alike."""
    rng = np.random.default_rng(words)
    visited = jnp.asarray(
        rng.integers(0, 2**32, size=words, dtype=np.uint64).astype(np.uint32)
        & rng.integers(0, 2**32, size=words, dtype=np.uint64).astype(np.uint32))
    ids = rng.integers(0, words * 32, size=j)
    ids[: j // 4] = ids[j // 4: j // 2]  # repeats
    word = jnp.asarray((ids >> 5).astype(np.int32))
    bit = jnp.asarray((1 << (ids & 31)).astype(np.uint32))
    eligible = jnp.asarray(rng.random(j) < 0.8)
    new_d, vis_d = jax.jit(_visited_dense)(visited, word, bit, eligible)
    new_s, vis_s = jax.jit(_visited_scatter)(visited, word, bit, eligible)
    np.testing.assert_array_equal(np.asarray(new_d), np.asarray(new_s))
    np.testing.assert_array_equal(np.asarray(vis_d), np.asarray(vis_s))


@pytest.fixture(scope="module")
def graph_500(small_ds):
    data = small_ds.data[:500]
    g = build_hnsw_bulk(data, 1.0, m=8, seed=3)
    return GraphArrays.from_graph(g), jnp.asarray(data)


@pytest.mark.parametrize("threshed", [False, True])
@pytest.mark.parametrize("w", [1, 4])
def test_search_identical_across_forms(graph_500, small_ds, w, threshed):
    """The graph at 500 rows (16 words), padded to the last dense bitmask
    and to the first indexed one: the same search on the same rows."""
    arrays, X = graph_500
    Q = jnp.asarray(small_ds.queries[:6])
    kw = dict(ef=48, t=16, expand_width=w)
    if threshed:
        # each query's 6th-best base distance: the admission cut engages
        _, d, _, _ = knn_search(arrays, X, Q, ef=48, t=16)
        kw["thresh"] = d[:, 5]
    dense = _search(arrays, X, Q, **kw)
    for n_pad in (N_AT_CROSSOVER, N_PAST_CROSSOVER):
        padded, Xp = _pad(arrays, X, n_pad)
        _assert_same(dense, _search(padded, Xp, Q, **kw))
    if threshed:  # the cut left slots unfilled, so the sentinel was mapped
        assert (dense[0] == -1).any()


@pytest.mark.parametrize("w", [1, 4])
def test_all_to_all_identical_across_forms(w):
    """The graph of test_nb_exact_under_cross_list_duplication: every W
    lists share every neighbour, so the first-occurrence mask does all the
    dedup; dense (2 words) and indexed forms agree, and N_b is still n."""
    n, d = 64, 16
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    adj0 = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (n, n))
    arrays = GraphArrays(adj0=adj0, upper_adj=(), upper_g2l=(),
                         entry=jnp.int32(0), n=n, metric_p=1.0)
    Q = jnp.asarray(rng.normal(size=(4, d)).astype(np.float32))
    kw = dict(ef=n, t=n, expand_width=w)
    dense = _search(arrays, X, Q, **kw)
    np.testing.assert_array_equal(dense[2], n)
    padded, Xp = _pad(arrays, X, N_PAST_CROSSOVER)
    _assert_same(dense, _search(padded, Xp, Q, **kw))


def _traces_scatter_add(n: int) -> bool:
    m0, d = 32, 8
    arrays = GraphArrays(
        adj0=jax.ShapeDtypeStruct((n, m0), jnp.int32), upper_adj=(),
        upper_g2l=(), entry=jax.ShapeDtypeStruct((), jnp.int32), n=n,
        metric_p=1.0)
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    Q = jax.ShapeDtypeStruct((4, d), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, x, q: knn_search(a, x, q, ef=64, t=10))(arrays, X, Q)
    return "scatter-add" in str(jaxpr)


@pytest.mark.parametrize("n,dense", [(16384, True), (8192, True),
                                     (262144, False)])
def test_form_chosen_by_bitmask_size(n, dense):
    """The benchmark cells' segments (deep 16,384 rows, trevi 8,192) run
    the dense form: their search traces no scatter-add. A 262,144-row
    monolithic index keeps the indexed form."""
    assert ((n + 31) // 32 <= DENSE_VISITED_MAX_WORDS) == dense
    assert _traces_scatter_add(n) == (not dense)
