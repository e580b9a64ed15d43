"""Early-abandoning blocked-dimension verification: exactness (DESIGN.md §8).

The subsystem's contract is that abandonment is *free* in result space:
a candidate is abandoned only when a monotone lower bound on its final
root-free power sum (its partial sum over scanned dimension blocks, or
the base-distance entry/suffix bound) already exceeds the running
k-th-best, so the returned top-k (ids AND distances) must be identical
to the full-dimension verification at matched (t, kappa, tau).

Layers pinned here:

  * bound validity — `lp_entry_bound` / `lp_suffix_bound` never exceed
    the true power sum (the property exactness rests on);
  * kernel parity — `lp_gather_abandon` interpret=True vs the blocked
    jnp reference, bitwise, including the scanned-dim counts;
  * scalar-vs-vector p — one traced program rows == per-p programs;
  * verification — abandoning vs full-dimension `verify_candidates`:
    identical ids and n_p, distances to 1-ulp-class tolerance (the
    blocked scan reassociates the d-axis sum; single-block shapes are
    bitwise);
  * the `abandon=False` escape hatch — bit-parity with the legacy
    sort-merge loop, including n_dim_frac == 1;
  * end-to-end — UHNSW / ShardedUHNSW (+ delta tier) searches with
    abandonment on vs off return identical ids at every p, while
    n_dim_frac < 1 when the workload actually abandons.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.lp_ops import lp_entry_bound, lp_suffix_bound
from repro.core.metrics import lp_distance
from repro.core.uhnsw import UHNSW, UHNSWParams, verify_candidates
from repro.kernels.ops import (
    lp_gather_abandon,
    lp_gather_distance,
    pick_abandon_block_d,
)

P_GRID = [0.5, 0.8, 1.25, 1.5, 1.7]


def _close_with_inf(got, want, err=""):
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want), err_msg=err)
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-6, err_msg=err)


def _case(seed=0, b=6, c=40, n=250, d=64):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32) * 2)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) * 2)
    ids = rng.integers(-1, n + 2, size=(b, c)).astype(np.int32)
    return q, x, jnp.asarray(ids), rng


def _base_power(q, x, ids, base_p):
    """True base-metric power sums for the candidate block (inf padding)."""
    n = x.shape[0]
    valid = (np.asarray(ids) >= 0) & (np.asarray(ids) < n)
    d = np.asarray(lp_distance(q[:, None, :],
                               x[np.clip(np.asarray(ids), 0, n - 1)],
                               base_p, root=False))
    return jnp.asarray(np.where(valid, d, np.inf).astype(np.float32))


# ---------------------------------------------------------------------------
# bound validity: the inequalities exactness rests on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("base_p", [1.0, 2.0])
def test_entry_bound_never_exceeds_true_power(p, base_p):
    rng = np.random.default_rng(3)
    for d in (8, 96, 300):
        v = rng.standard_t(3.0, size=(200, d)).astype(np.float32) * \
            np.exp(rng.standard_normal(d).astype(np.float32))
        true_p = np.asarray(lp_distance(jnp.asarray(v), 0.0, p, root=False))
        sb = np.asarray(lp_distance(jnp.asarray(v), 0.0, base_p,
                                    root=False))
        lb = np.asarray(lp_entry_bound(jnp.asarray(sb), base_p, p, d))
        assert np.all(lb <= true_p * (1 + 1e-5)), (
            f"entry bound exceeds true power sum: p={p} base={base_p} d={d} "
            f"worst={(lb / np.maximum(true_p, 1e-30)).max()}")


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("base_p", [1.0, 2.0])
def test_suffix_bound_never_exceeds_true_power(p, base_p):
    rng = np.random.default_rng(4)
    d_rem = 40
    v = rng.standard_t(3.0, size=(300, d_rem)).astype(np.float32) * 3
    true_p = np.asarray(lp_distance(jnp.asarray(v), 0.0, p, root=False))
    r = np.asarray(lp_distance(jnp.asarray(v), 0.0, base_p, root=False))
    lb = np.asarray(lp_suffix_bound(jnp.asarray(r), base_p, p,
                                    float(d_rem)))
    assert np.all(lb <= true_p * (1 + 1e-5))


# ---------------------------------------------------------------------------
# kernel layer: dispatch semantics + interpret parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", [0.8, 1.25])
def test_abandon_inf_threshold_equals_full_scan(p):
    """thresh=+inf scans everything: must equal the full-dimension path
    (bitwise here — the block widths divide d, and XLA:CPU reduces the
    32-wide blocks exactly like the fused d-axis sum at these shapes)."""
    q, x, ids, _ = _case(d=64)
    full = np.asarray(lp_gather_distance(q, ids, x, p, root=False))
    thr = jnp.full((q.shape[0],), jnp.inf)
    sb = jnp.zeros(ids.shape, jnp.float32)
    out, nd = lp_gather_abandon(q, ids, x, thr, sb, p, base_p=1.0)
    valid = (np.asarray(ids) >= 0) & (np.asarray(ids) < x.shape[0])
    np.testing.assert_array_equal(np.asarray(out)[valid], full[valid])
    assert np.all(np.asarray(nd)[valid] == q.shape[1])
    assert np.all(np.isinf(np.asarray(out)[~valid]))
    assert np.all(np.asarray(nd)[~valid] == 0)


@pytest.mark.parametrize("p", [0.5, 0.8, 1.25, 1.5])
@pytest.mark.parametrize("base_p", [1.0, 2.0])
def test_abandon_exactness_vs_threshold(p, base_p):
    """Everything the full path scores <= thresh must survive with its
    exact full-path value; everything abandoned must truly exceed thresh."""
    q, x, ids, rng = _case(seed=11, d=96)
    full = np.asarray(lp_gather_distance(q, ids, x, p, root=False))
    valid = (np.asarray(ids) >= 0) & (np.asarray(ids) < x.shape[0])
    thr_v = np.nanquantile(np.where(valid, full, np.nan), 0.4,
                           axis=1).astype(np.float32)
    sb = _base_power(q, x, ids, base_p)
    out, nd = lp_gather_abandon(q, ids, x, jnp.asarray(thr_v), sb, p,
                                base_p=base_p)
    out = np.asarray(out)
    # blocked (3 x 32) association differs from the fused d=96 sum by ~1
    # ulp, so near-threshold comparisons carry a 1e-6 relative margin;
    # clear keepers must survive with their blocked value, clear losers
    # must be provably over the bound.
    must_survive = valid & (full <= thr_v[:, None] * (1 - 1e-6))
    assert np.isfinite(out[must_survive]).all(), "abandoned a keeper"
    np.testing.assert_allclose(out[must_survive], full[must_survive],
                               rtol=1e-6)
    abandoned = valid & np.isinf(out)
    assert np.all(full[abandoned] > thr_v[:, None].repeat(
        out.shape[1], 1)[abandoned] * (1 - 1e-6)), \
        "abandoned candidate was competitive"
    # savings exist at this threshold for p > 1: the Jensen entry bound
    # d^(1-p)*S1^p (or S2^(p/2)) kills clear losers before any block.
    # For p <= 1 on i.i.d. data no aggregate bound can bite (power sums
    # of spread vectors concentrate), so only exactness is asserted.
    if p > 1.0:
        assert np.asarray(nd)[valid].mean() < q.shape[1]


@pytest.mark.parametrize("p", [0.8, 1.25])
@pytest.mark.parametrize("d", [32, 64, 96])
def test_abandon_kernel_interpret_matches_ref(p, d):
    """interpret=True Pallas kernel vs the blocked jnp reference: bitwise
    on distances AND scanned-dim counts, scalar and vector p."""
    q, x, ids, rng = _case(seed=5, d=d)
    thr = jnp.asarray(rng.uniform(20, 200, size=q.shape[0]).astype(
        np.float32))
    sb = _base_power(q, x, ids, 1.0)
    r_out, r_nd = lp_gather_abandon(q, ids, x, thr, sb, p, base_p=1.0)
    k_out, k_nd = lp_gather_abandon(q, ids, x, thr, sb, p, base_p=1.0,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(r_out), np.asarray(k_out))
    np.testing.assert_array_equal(np.asarray(r_nd), np.asarray(k_nd))
    ps = jnp.full((q.shape[0],), p, jnp.float32)
    v_out, v_nd = lp_gather_abandon(q, ids, x, thr, sb, ps, base_p=1.0,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(r_out), np.asarray(v_out))
    np.testing.assert_array_equal(np.asarray(r_nd), np.asarray(v_nd))


@pytest.mark.parametrize("p", [0.8, 1.25, "vector"])
@pytest.mark.parametrize("c,block_c", [(5, None), (10, None), (300, 128)])
def test_abandon_kernel_copies_only_scored_rows(c, block_c, p):
    """The engine's batches (c = 5 and 10 in one grid step of TC = 128)
    and C = 300 over three steps, with the sentinels -1 and n among the
    real ids and X's rows 0 and n - 1 NaN (where a padding slot's id
    would clamp to): the interpret kernel is bitwise the blocked
    reference on distances and scanned-dim counts, padding slots score
    +inf and scan 0 dimensions."""
    b, n, d = 6, 203, 64
    rng = np.random.default_rng(c)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32) * 2)
    xs = rng.normal(size=(n, d)).astype(np.float32) * 2
    xs[[0, n - 1]] = np.nan
    x = jnp.asarray(xs)
    ids = rng.integers(1, n - 1, size=(b, c)).astype(np.int32)
    ids[:, ::3] = -1
    ids[:, 1::4] = n
    valid = (ids >= 0) & (ids < n)
    if p == "vector":
        p = jnp.asarray(rng.choice(P_GRID, size=b).astype(np.float32))
    sb = _base_power(q, x, jnp.asarray(ids), 1.0)
    full = np.asarray(lp_gather_distance(q, jnp.asarray(ids), x, p))
    thr = jnp.asarray(np.nanquantile(np.where(valid, full, np.nan), 0.6,
                                     axis=1).astype(np.float32))
    out, nd = (np.asarray(a) for a in lp_gather_abandon(
        q, jnp.asarray(ids), x, thr, sb, p, base_p=1.0, interpret=True,
        block_c=block_c))
    r_out, r_nd = (np.asarray(a) for a in lp_gather_abandon(
        q, jnp.asarray(ids), x, thr, sb, p, base_p=1.0))
    np.testing.assert_array_equal(out, r_out)
    np.testing.assert_array_equal(nd, r_nd)
    assert np.isinf(out[~valid]).all() and (nd[~valid] == 0).all()
    assert np.isfinite(out[valid]).any() and (nd > 0).any(axis=1).all()


def test_abandon_vector_p_rows_match_scalar():
    """One traced mixed-p program == per-p scalar programs, row by row."""
    q, x, ids, rng = _case(seed=9, d=64)
    ps = rng.choice(P_GRID, size=q.shape[0]).astype(np.float32)
    thr = jnp.asarray(rng.uniform(20, 300, size=q.shape[0]).astype(
        np.float32))
    sb = _base_power(q, x, ids, 1.0)
    v_out, v_nd = lp_gather_abandon(q, ids, x, thr, sb, jnp.asarray(ps),
                                    base_p=1.0)
    for i, p in enumerate(ps):
        s_out, s_nd = lp_gather_abandon(q[i:i + 1], ids[i:i + 1], x,
                                        thr[i:i + 1], sb[i:i + 1],
                                        float(p), base_p=1.0)
        np.testing.assert_array_equal(np.asarray(v_out)[i],
                                      np.asarray(s_out)[0], err_msg=f"p={p}")
        np.testing.assert_array_equal(np.asarray(v_nd)[i],
                                      np.asarray(s_nd)[0], err_msg=f"p={p}")


def test_pick_abandon_block_d():
    assert pick_abandon_block_d(96) == 32
    assert pick_abandon_block_d(256) == 32
    assert pick_abandon_block_d(48) == 16
    assert pick_abandon_block_d(40) == 8
    assert pick_abandon_block_d(100) == 32  # ragged: 4 blocks, the last 4 wide


# ---------------------------------------------------------------------------
# verification layer: abandoning loop vs full-dimension loop
# ---------------------------------------------------------------------------


def _verify_case(seed=23, b=8, t=60, n=300, d=32, base_p=1.0):
    """Candidates sorted ascending by base distance (the beam contract),
    with trailing padding, plus their true base power sums."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    base = np.asarray(lp_distance(q[:, None, :], x[None, :, :], base_p,
                                  root=False))
    order = np.argsort(base, axis=1)[:, :t].astype(np.int32)
    cand_base = np.take_along_axis(base, order, axis=1).astype(np.float32)
    order[:, -2:] = -1
    cand_base[:, -2:] = np.inf
    return q, x, jnp.asarray(order), jnp.asarray(cand_base)


@pytest.mark.parametrize("p", P_GRID)
def test_verify_abandon_matches_full_scalar(p):
    """ids and n_p identical at matched (t, kappa, tau); dists to 1-ulp.

    The abandoning scan reduces (d, TC)-transposed blocks (the layout
    that makes dimension blocks TPU sublane slices, DESIGN.md §8) while
    the legacy path reduces the (B, C, d) last axis — XLA:CPU
    reassociates the two by <= 1 ulp on some elements (max measured
    rel diff 1.8e-7 at p=1.5), exactly the wobble class pinned for the
    pairwise vector-p kernel in test_kernels. Selection is tie-free at
    that scale on continuous data, so ids and N_p stay bitwise.
    """
    q, x, cand, cand_base = _verify_case(d=32)
    k, kappa, tau = 10, 25, 0.95
    a = verify_candidates(q, cand, x, p, k, kappa, tau, cand_base=cand_base,
                          base_p=1.0, abandon=True)
    f = verify_candidates(q, cand, x, p, k, kappa, tau, abandon=False)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(f[0]))
    _close_with_inf(np.asarray(a[1]), np.asarray(f[1]))
    np.testing.assert_array_equal(np.asarray(a[2].n_p), np.asarray(f[2].n_p))
    assert np.all(np.asarray(f[2].n_dim_frac) == 1.0)


@pytest.mark.parametrize("p", [0.8, 1.25])
@pytest.mark.parametrize("base_p", [1.0, 2.0])
def test_verify_abandon_matches_full_multiblock(p, base_p):
    """Multi-block d: identical ids/n_p, dists within reassociation ulp,
    and the scanned fraction actually drops (the savings are real)."""
    q, x, cand, cand_base = _verify_case(d=96, base_p=base_p)
    k, kappa, tau = 10, 25, 1.0  # tau=1: scan deep into the junk tail
    a = verify_candidates(q, cand, x, p, k, kappa, tau, cand_base=cand_base,
                          base_p=base_p, abandon=True)
    f = verify_candidates(q, cand, x, p, k, kappa, tau, abandon=False)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(f[0]))
    _close_with_inf(np.asarray(a[1]), np.asarray(f[1]))
    np.testing.assert_array_equal(np.asarray(a[2].n_p), np.asarray(f[2].n_p))
    frac = np.asarray(a[2].n_dim_frac)
    assert np.all(frac <= 1.0) and np.all(frac > 0.0)
    assert frac.mean() < 1.0, "no dimension work was saved"


@pytest.mark.parametrize("interpret", [None, True])
def test_verify_abandon_vector_p_matches_scalar(interpret):
    """Mixed-batch abandoning verification: each row == the scalar-p call
    (ids/n_p/n_dim_frac bitwise, dists to cross-program tolerance)."""
    q, x, cand, cand_base = _verify_case(d=64)
    k, kappa = 10, 10
    rng = np.random.default_rng(1)
    ps = rng.choice(P_GRID, size=q.shape[0]).astype(np.float32)
    mv = verify_candidates(q, cand, x, jnp.asarray(ps), k, kappa, 0.92,
                           interpret=interpret, cand_base=cand_base,
                           base_p=1.0, abandon=True)
    for i, p in enumerate(ps):
        sv = verify_candidates(q[i:i + 1], cand[i:i + 1], x, float(p),
                               k, kappa, 0.92, interpret=interpret,
                               cand_base=cand_base[i:i + 1], base_p=1.0,
                               abandon=True)
        np.testing.assert_array_equal(np.asarray(mv[0])[i],
                                      np.asarray(sv[0])[0], err_msg=f"p={p}")
        np.testing.assert_allclose(np.asarray(mv[1])[i],
                                   np.asarray(sv[1])[0], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(mv[2].n_p)[i],
                                      np.asarray(sv[2].n_p)[0])
        np.testing.assert_allclose(np.asarray(mv[2].n_dim_frac)[i],
                                   np.asarray(sv[2].n_dim_frac)[0], rtol=1e-6)


def test_verify_abandon_padding_rows():
    """Sentinel candidate ids (-1 / n) can never enter the result set."""
    q, x, cand, cand_base = _verify_case(d=32)
    n = x.shape[0]
    cand = np.asarray(cand).copy()
    cand[:, 15:] = np.where(np.arange(cand.shape[1] - 15)[None, :] % 2 == 0,
                            -1, n)
    cand_base = np.asarray(cand_base).copy()
    cand_base[:, 15:] = np.inf
    ids, dists, _ = verify_candidates(
        q, jnp.asarray(cand), x, 0.8, 10, 5, 0.92,
        cand_base=jnp.asarray(cand_base), base_p=1.0, abandon=True)
    assert np.all(np.asarray(ids) >= 0) and np.all(np.asarray(ids) < n)
    assert np.isfinite(np.asarray(dists)).all()


@pytest.mark.parametrize("p", [0.8, 1.5, "vector"])
def test_verify_rows_count_every_live_batch_without_bounds(p):
    """Without base sums no entry bound kills a candidate, so a row runs
    its scan in every batch it verifies: `n_verify_rows` is the valid ids
    of its first k and of its first (n_p - k) / kappa batches (the case's
    last two columns are -1 sentinels, which copy nothing)."""
    q, x, cand, _ = _verify_case(d=64)
    k, kappa = 10, 5
    if p == "vector":
        p = jnp.asarray(np.resize(np.float32(P_GRID), q.shape[0]))
    _, _, st = verify_candidates(q, cand, x, p, k, kappa, 0.92,
                                 abandon=True)
    n_p = np.asarray(st.n_p)
    valid = np.asarray(cand) >= 0
    want = [valid[i, :n_p[i]].sum() for i in range(len(n_p))]
    np.testing.assert_array_equal(np.asarray(st.n_verify_rows), want)
    assert (n_p < cand.shape[1]).any()  # some rows stop before the tail


def test_verify_rows_on_an_index(monolithic_index, small_ds):
    """On a small index at kappa = k / 2 = 5, with no sentinel ids among
    the candidates: each row copied its first k rows plus 5 for every
    batch whose scan ran, at most one per batch it verified; a query
    whose p is its base metric verifies nothing and copies nothing."""
    k = 10
    idx = monolithic_index
    q = jnp.asarray(small_ds.queries)
    cands = idx.search_stage_candidates(q, 1.0, k=k)
    assert (np.asarray(cands.ids) >= 0).all()
    for p in (0.5, 0.8, 1.25):
        _, _, st = idx.search_stage_finish(q, cands, p, k)
        rows, n_p = np.asarray(st.n_verify_rows), np.asarray(st.n_p)
        assert ((rows - k) % 5 == 0).all() and (rows >= k).all(), p
        assert (rows <= n_p).all(), p
    for base_p in (1.0, 2.0):
        _, _, st = idx.search(q, base_p, k)
        assert np.all(np.asarray(st.n_verify_rows) == 0)


def test_verify_abandon_false_is_legacy_bitwise():
    """The escape hatch: abandon=False must be the pre-abandonment loop
    bit-for-bit (pinned against a hand-rolled sort-merge reference)."""
    q, x, cand, _ = _verify_case(d=32)
    k, kappa, tau, p = 10, 5, 0.92, 0.8
    ids, dists, st = verify_candidates(
        q, cand, x, p, k, kappa, tau, abandon=False)
    n_p, frac = st.n_p, st.n_dim_frac
    assert np.all(np.asarray(frac) == 1.0)
    # reference: the legacy loop in numpy (full-dimension, lax.sort merge)
    full = np.asarray(lp_gather_distance(q, cand, x, p, root=False))
    B, t = cand.shape
    for i in range(B):
        order = np.argsort(full[i, :k], kind="stable")
        r_ids = np.asarray(cand)[i, :k][order]
        r_d = full[i, :k][order]
        j = 0
        while j < (t - k) // kappa:
            s = k + j * kappa
            b_ids = np.asarray(cand)[i, s:s + kappa]
            b_d = full[i, s:s + kappa]
            all_d = np.concatenate([r_d, b_d])
            all_i = np.concatenate([r_ids, b_ids])
            oo = np.argsort(all_d, kind="stable")[:k]
            inter = len(set(all_i[oo]) & set(r_ids))
            r_ids, r_d = all_i[oo], all_d[oo]
            j += 1
            if inter / k >= tau:
                break
        np.testing.assert_array_equal(np.asarray(ids)[i], r_ids)
        np.testing.assert_array_equal(np.asarray(n_p)[i], k + j * kappa)


# ---------------------------------------------------------------------------
# end-to-end: index layers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def abandon_index(small_ds):
    params = UHNSWParams(t=120, kappa=32, abandon=True)
    return UHNSW.build(small_ds.data, m=12, method="bulk", params=params)


@pytest.mark.parametrize("p", [0.5, 0.8, 1.25, 1.5])
def test_index_search_abandon_identical_ids(abandon_index, small_ds, p):
    from dataclasses import replace

    idx = abandon_index
    Q = jnp.asarray(small_ds.queries)
    idx.params = replace(idx.params, abandon=True)
    ia, da, sa = idx.search(Q, p, 10)
    idx.params = replace(idx.params, abandon=False)
    if_, df, sf = idx.search(Q, p, 10)
    idx.params = replace(idx.params, abandon=True)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(if_))
    _close_with_inf(np.asarray(da), np.asarray(df))
    np.testing.assert_array_equal(np.asarray(sa.n_p), np.asarray(sf.n_p))
    frac = np.asarray(sa.n_dim_frac)
    assert np.all((frac > 0) & (frac <= 1.0))
    assert np.all(np.asarray(sf.n_dim_frac) == 1.0)


def test_sharded_with_delta_abandon_identical(small_ds, make_sharded):
    from dataclasses import replace

    # fresh wrapper over the session's frozen 4-segment build (this test
    # mutates params and the delta tier, so no sharing with sharded_index)
    idx = make_sharded(params=UHNSWParams(t=120, abandon=True),
                       delta_capacity=128)
    rng = np.random.default_rng(2)
    for _ in range(30):
        idx.add(rng.normal(size=small_ds.data.shape[1]).astype(np.float32))
    Q = jnp.asarray(small_ds.queries)
    ps = np.asarray([0.5, 0.8, 1.25, 1.5, 2.0, 1.0] * 4, np.float32)
    i1, d1, s1 = idx.search(Q, ps, 10)
    idx.params = replace(idx.params, abandon=False)
    i2, d2, s2 = idx.search(Q, ps, 10)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    _close_with_inf(np.asarray(d1), np.asarray(d2))
    frac = np.asarray(s1.n_dim_frac)
    assert np.all((frac > 0) & (frac <= 1.0))
    # the delta scan abandons against the verified k-th best: with junk
    # inserts present, some rows must actually skip dimension work
    assert frac.mean() < 1.0
