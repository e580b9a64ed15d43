"""The level-0 loop's fetch-and-score of new neighbour rows.

Where rows are whole DMA tiles (d % BEAM_FETCH_ROW_ELEMS == 0), each trip
of the level-0 beam loop scores its frontier with one Pallas kernel call
(kernels/beam_fetch.py) that reads only the rows its visited test marks
new, in place of an XLA gather of every frontier row. Here, on the CPU
with the kernel in interpret mode:

  * the kernel against `_base_dist(q, X[ids], p)` on the new rows
    (relative error <= 1e-6) and +inf on every other entry: lanes with
    every entry new and lanes with none, sentinel ids, W = 2 frontiers
    after the dedupe sort, p in {1, 2};
  * lanes whose counts sit at the edges of the kernel's 8-row score
    groups (0, 1, 7, 8, 9, 31, 32, and past 32 at W = 2), at d 1024 and
    4096: whole rows scored, +inf past the count even where the ring
    slot still holds an earlier lane's rows;
  * a nested vmap (segments x queries) gives the per-lane calls' values;
  * the segmented search's level-0 loop holds exactly one kernel call,
    whose lane axis is segments x queries, at d = 1024, and none at
    d in {100, 256};
  * a segmented and a monolithic search on the kernel path against the
    XLA path: the same candidates, N_b and hops;
  * the kernel's row source is made again after a write of the rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hnsw
from repro.core.hnsw import _base_dist
from repro.core.uhnsw import UHNSW, UHNSWParams
from repro.index.sharded import ShardedUHNSW
from repro.kernels import beam_fetch as bf

M0 = 32


def _frontier(rng, lanes: int, n: int, w: int):
    """(ids, new) of `lanes` frontiers of W*m0 entries as the loop builds
    them: W adjacency rows with sentinels (n), sorted and first-occurrence
    masked for W > 1, some entries already visited. Lane 0 has every
    valid entry new, lane 1 none."""
    ids = rng.integers(0, n + n // 4, (lanes, w * M0)).astype(np.int32)
    ids = np.where(ids >= n, n, ids)
    first = np.ones_like(ids, bool)
    if w > 1:
        ids = np.sort(ids, axis=1)
        first[:, 1:] = ids[:, 1:] != ids[:, :-1]
    new = (ids < n) & first & (rng.random(ids.shape) < 0.3)
    new[0] = (ids[0] < n) & first[0]
    new[1] = False
    return ids, new


def _reference(q, x, ids, new, p):
    n = x.shape[0]
    dv = _base_dist(q[:, None, :], x[np.clip(ids, 0, n - 1)], p)
    return np.where(new, np.asarray(dv), np.inf)


@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("w", [1, 2])
def test_kernel_scores_only_the_new_rows(p, w):
    rng = np.random.default_rng(int(10 * p + w))
    n, d, lanes = 90, 1024, 6
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((lanes, d)).astype(np.float32)
    ids, new = _frontier(rng, lanes, n, w)
    got = np.asarray(bf.fetch_score_lanes(
        jnp.asarray(q).reshape(lanes, d // 128, 128), jnp.asarray(ids),
        jnp.asarray(new), jnp.zeros((lanes,), jnp.int32),
        bf.beam_rows(jnp.asarray(x)), p=p, interpret=True))
    want = _reference(q, x, ids, new, p)
    np.testing.assert_array_equal(np.isinf(got), ~new)
    assert np.isinf(got[1]).all()
    rel = np.abs(got[new] - want[new]) / want[new]
    assert rel.max() <= 1e-6, rel.max()


# per-lane new-row counts at the edges of the kernel's 8-row score groups;
# with DEPTH 4, lane l + 4 reuses lane l's ring slot, so each lane here
# with fewer rows than the lane four before it finds that lane's rows
# still in the slot past its own count
_EDGE_COUNTS = {12: [12, 9, 11, 8, 7, 1, 0, 3, 0],
                32: [32, 9, 31, 8, 7, 1, 0, 0, 1, 0, 9, 7],
                64: [64, 57, 63, 33, 31, 9, 8, 32, 7, 1, 0, 64, 0]}


@pytest.mark.parametrize("f", [12, 32, 64])
@pytest.mark.parametrize("d", [1024, 4096])
@pytest.mark.parametrize("p", [1.0, 2.0])
def test_kernel_group_edges_score_whole_rows_and_nothing_stale(p, d, f):
    """Lane counts 0, 1, 7, 8, 9, 31 and 32 (and past 32 at F = 64, W = 2;
    F = 12, m0 = 6, is not whole groups): each lane's first `count` outputs
    are `_base_dist` of its rows; every output past its count is +inf,
    whatever the slot held before."""
    counts = np.array(_EDGE_COUNTS[f], np.int32)
    lanes, n = len(counts), 3 * f
    rng = np.random.default_rng(d + f + int(p))
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((5, d)).astype(np.float32)
    rows = rng.integers(0, n, (lanes, f)).astype(np.int32)
    got = np.asarray(bf.fetch_kernel_call(
        jnp.asarray(counts), jnp.asarray(rows.reshape(-1)),
        jnp.asarray(q).reshape(5, d // 128, 128), bf.beam_rows(jnp.asarray(x)),
        p=p, interpret=True))
    live = np.arange(f)[None, :] < counts[:, None]
    np.testing.assert_array_equal(np.isinf(got), ~live)
    want = np.asarray(_base_dist(q[np.arange(lanes) % 5][:, None, :],
                                 x[rows], p))
    rel = np.abs(got[live] - want[live]) / want[live]
    assert rel.max() <= 1e-6, rel.max()


def test_lanes_past_one_calls_ids_split_into_calls(monkeypatch):
    """More row ids than one call holds in SMEM: the lanes split over
    several calls, each lane still reading its own query."""
    rng = np.random.default_rng(3)
    n, d, lanes = 60, 1024, 6
    x = bf.beam_rows(jnp.asarray(rng.standard_normal((n, d)), jnp.float32))
    q = jnp.asarray(rng.standard_normal((3, d // 128, 128)), jnp.float32)
    ids, new = _frontier(rng, lanes, n, 1)
    args = (q, jnp.asarray(ids), jnp.asarray(new),
            jnp.zeros((lanes,), jnp.int32), x)
    whole = bf.fetch_score_lanes(*args, p=1.0, interpret=True)
    monkeypatch.setattr(bf, "MAX_CALL_IDS", 4 * M0)  # 4 lanes a call
    split = jax.make_jaxpr(lambda *a: bf.fetch_score_lanes(
        *a, p=1.0, interpret=True))(*args)
    assert str(split).count("pallas_call") == 2
    np.testing.assert_array_equal(
        np.asarray(bf.fetch_score_lanes(*args, p=1.0, interpret=True)),
        np.asarray(whole))


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_nested_vmap_equals_per_lane_calls(p):
    """Segments x queries under two vmaps, each segment at its offset in
    one flat row source, equal one call per lane."""
    rng = np.random.default_rng(7)
    s, n, d, b = 3, 40, 2048, 4
    x = rng.standard_normal((s, n, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
    ids, new = _frontier(rng, s * b, n, 1)
    ids, new = ids.reshape(s, b, M0), new.reshape(s, b, M0)
    src = bf.beam_rows(jnp.asarray(x))
    row0 = jnp.arange(s, dtype=jnp.int32) * n

    def lane(qi, i, m, r0):
        return bf.fetch_score(bf.query_tiles(qi), i, m, r0, src, p)

    batched = jax.jit(jax.vmap(lambda i2, m2, r0: jax.vmap(
        lambda qi, i, m: lane(qi, i, m, r0))(q, i2, m2)))(
        jnp.asarray(ids), jnp.asarray(new), row0)
    for si in range(s):
        for bi in range(b):
            one = lane(q[bi], jnp.asarray(ids[si, bi]),
                       jnp.asarray(new[si, bi]), row0[si])
            np.testing.assert_array_equal(np.asarray(batched[si, bi]),
                                          np.asarray(one))
        want = _reference(np.asarray(q), x[si], ids[si], new[si], p)
        live = new[si]
        np.testing.assert_allclose(np.asarray(batched[si])[live], want[live],
                                   rtol=1e-6)


def _index(d: int, n_seg: int = 2, rows: int = 240, seed: int = 0):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n_seg * rows, d)).astype(np.float32)
    idx = ShardedUHNSW.build(data, num_segments=n_seg, m=8, seed=seed,
                             params=UHNSWParams(t=16, ef=32))
    q = data[:8] + 0.05 * rng.standard_normal((8, d)).astype(np.float32)
    return idx, q


def _kernel_calls(jaxpr, in_loop=False) -> list:
    """(lane axis, inside a while body) of every kernel call in a jaxpr
    and the jaxprs it holds."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.outvars[0].aval.shape[0], in_loop))
        inner_loop = in_loop or eqn.primitive.name == "while"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    calls += _kernel_calls(inner, inner_loop)
    return calls


@pytest.mark.parametrize("d", [100, 256, 1024])
def test_loop_holds_one_kernel_call_over_every_lane(d):
    idx, q = _index(d)
    s = idx.num_segments
    seg = idx.segments
    stack = (seg.arrays1, seg.X, seg.node_ids, np.arange(s))
    jaxpr = jax.make_jaxpr(lambda qq: idx._stack_search(
        stack, qq, 1, ef=32, t=16, max_hops=4096))(jnp.asarray(q))
    calls = _kernel_calls(jaxpr.jaxpr)
    if hnsw.beam_fetch_on(d):
        assert calls == [(s * len(q), True)]
    else:
        assert calls == []


def _candidates(idx, q, base_p):
    c = idx.search_stage_candidates(q, base_p, k=10)
    return [np.asarray(a) for a in (c.ids, c.n_b, c.hops, c.rows_read)]


@pytest.mark.parametrize("kind,base_p", [("sharded", 1.0), ("sharded", 2.0),
                                         ("monolithic", 2.0)])
def test_kernel_path_search_matches_xla_path(monkeypatch, kind, base_p):
    idx, q = _index(1024, seed=int(base_p))
    arrays = idx.segments.arrays1
    if kind == "monolithic":
        idx = UHNSW(idx.segments.graphs1[0], idx.segments.graphs2[0],
                    idx.params)
        arrays = idx.arrays1
    ids_k, nb_k, hops_k, rows_k = _candidates(idx, q, base_p)
    monkeypatch.setattr(hnsw, "BEAM_FETCH_ROW_ELEMS", 3)  # 1024 % 3 != 0
    ids_x, nb_x, hops_x, rows_x = _candidates(idx, q, base_p)
    overlap = np.mean([len(set(a) & set(b)) / len(a)
                       for a, b in zip(ids_k, ids_x)])
    assert overlap >= 0.99, overlap
    np.testing.assert_allclose(nb_k, nb_x, rtol=0.01)
    np.testing.assert_allclose(hops_k, hops_x, rtol=0.01)
    # the kernel reads the new rows only; the gather every frontier row
    np.testing.assert_array_equal(rows_x, hops_x * arrays.adj0.shape[-1])
    assert (0 < rows_k).all() and (rows_k < nb_k).all()
    assert (rows_k < rows_x).all()


def test_row_source_follows_every_write_of_the_rows():
    """The fetch kernel's copy of the segment rows is made once and made
    again after any write of X (a poisoned or restored segment, a
    placement), so the loop never scores stale rows."""
    idx, _ = _index(1024)
    seg = idx.segments
    src = seg.beam_src()
    assert seg.beam_src() is src
    seg.X = seg.X.at[1, 0].set(7.0)
    fresh = np.asarray(seg.beam_src()[seg.X.shape[1]])
    np.testing.assert_array_equal(fresh.reshape(-1), np.full(1024, 7.0))
