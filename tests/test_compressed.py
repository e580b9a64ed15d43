"""Compressed storage band (DESIGN.md §10): certified bounds, two-band parity.

The band's contract is *certification*, not approximation — every test
here pins one leg of it:

  * admissibility — the (deflated) compressed lower bound never exceeds
    the true f32 power sum, on random corpora AND on adversarial rows
    parked at quantization midpoints (the worst dequant error);
  * screen soundness — a candidate the screen kills provably could not
    enter the top-k (its true power sum exceeds the threshold), and
    padding ids never survive;
  * dispatch parity — the Pallas screen kernel (interpret mode) is
    bitwise the blocked jnp reference;
  * two-band exactness — `verify_candidates(band=...)` returns ids AND
    distances bitwise-identical to the uncompressed path at every p,
    scalar and vector, and end-to-end through `UHNSW.search`;
  * energy order — the permutation is a bijection, variance-sorted, and
    search under `energy_perm=True` returns the same ids;
  * persistence — a snapshot carries the band byte-for-byte (codes,
    scales, radii, manifest-authoritative perm) through save/load.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lp_ops import BOUND_SLACK
from repro.core.uhnsw import UHNSW, UHNSWParams, verify_candidates
from repro.index.compressed import (
    CompressedBand,
    build_band,
    compressed_lower_bound,
    energy_order,
)
from repro.index.persist import load_snapshot, read_manifest, save_snapshot
from repro.index.sharded import ShardedUHNSW
from repro.kernels.ops import lp_gather_distance, lp_gather_screen

P_GRID = (0.5, 0.8, 1.25, 2.0)


def _corpus(n=300, d=48, seed=0, nq=6):
    """Heterogeneous per-coordinate energy (the regime the band targets)."""
    rng = np.random.default_rng(seed)
    dim_scale = np.exp(rng.standard_normal(d) * 0.8).astype(np.float32)
    X = (rng.standard_normal((n, d)) * dim_scale).astype(np.float32)
    Q = (rng.standard_normal((nq, d)) * dim_scale).astype(np.float32)
    return X, Q


def _true_power_sums(Q, X, p):
    """f32 true Lp power sums (B, n) — the quantity the bound certifies."""
    return np.asarray(
        lp_gather_distance(
            jnp.asarray(Q),
            jnp.broadcast_to(jnp.arange(X.shape[0], dtype=jnp.int32),
                             (Q.shape[0], X.shape[0])),
            jnp.asarray(X), p, root=False))


def _midpoint_corpus(d=32, seed=3):
    """Rows parked exactly at quantization midpoints: scale * (k + 0.5).

    round() moves each coordinate by half a step — the maximum possible
    dequant error — so radii are as large as the scheme ever makes them
    and the max(|q - x̂| - radius, 0) clamp is exercised at its boundary.
    """
    rng = np.random.default_rng(seed)
    # a carrier row pins absmax (hence scale); midpoint rows ride inside
    carrier = (np.exp(rng.standard_normal(d) * 0.5) * 127).astype(np.float32)
    scale = np.maximum(np.abs(carrier), 1e-12) / 127.0
    ks = rng.integers(-126, 126, size=(64, d)).astype(np.float32)
    mids = ((ks + 0.5) * scale).astype(np.float32)
    X = np.concatenate([carrier[None, :], -carrier[None, :], mids])
    Q = (rng.standard_normal((4, d)) * scale * 64).astype(np.float32)
    return X.astype(np.float32), Q


# ---------------------------------------------------------------------------
# admissibility of the certified lower bound
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", P_GRID)
def test_bound_admissible_random(p):
    X, Q = _corpus()
    band = build_band(X)
    Qp = jnp.take(jnp.asarray(Q), band.perm, axis=1)
    lb = np.asarray(compressed_lower_bound(Qp, band.codes, band.scale,
                                           band.radius, p))
    true = _true_power_sums(Q, X, p)
    # the scan compares the BOUND_SLACK-deflated bound; that deflation is
    # what absorbs accumulated f32 rounding on both sides
    assert np.all(lb * (1.0 - BOUND_SLACK) <= true), \
        f"bound violation at p={p}: max excess " \
        f"{float((lb * (1 - BOUND_SLACK) - true).max())}"


@pytest.mark.parametrize("p", P_GRID)
def test_bound_admissible_midpoint_adversary(p):
    X, Q = _midpoint_corpus()
    band = build_band(X)
    Qp = jnp.take(jnp.asarray(Q), band.perm, axis=1)
    lb = np.asarray(compressed_lower_bound(Qp, band.codes, band.scale,
                                           band.radius, p))
    true = _true_power_sums(Q, X, p)
    assert np.all(lb * (1.0 - BOUND_SLACK) <= true)
    # the adversary really does sit at max dequant error: radii ~ scale/2
    r = np.asarray(band.radius)
    s = np.asarray(band.scale)
    assert np.all(r >= 0.49 * s), "midpoint rows failed to maximize radii"


def test_bound_admissible_vector_p():
    X, Q = _corpus(seed=7)
    band = build_band(X)
    Qp = jnp.take(jnp.asarray(Q), band.perm, axis=1)
    ps = np.resize(np.asarray(P_GRID, np.float32), Q.shape[0])
    lb = np.asarray(compressed_lower_bound(Qp, band.codes, band.scale,
                                           band.radius, jnp.asarray(ps)))
    for i, p in enumerate(ps):
        true = _true_power_sums(Q[i:i + 1], X, float(p))
        assert np.all(lb[i] * (1.0 - BOUND_SLACK) <= true[0]), f"p={p}"


def test_bound_tightness_not_vacuous():
    """The bound must actually bite (> 90% of the true sum on smooth
    data), else the screen never kills anything and the band is dead
    weight that the parity tests would never notice."""
    X, Q = _corpus(seed=2)
    band = build_band(X)
    Qp = jnp.take(jnp.asarray(Q), band.perm, axis=1)
    for p in (0.5, 2.0):
        lb = np.asarray(compressed_lower_bound(Qp, band.codes, band.scale,
                                               band.radius, p))
        true = _true_power_sums(Q, X, p)
        ratio = lb / np.maximum(true, 1e-20)
        assert float(np.median(ratio)) > 0.9, f"vacuous bound at p={p}"


# ---------------------------------------------------------------------------
# the blocked screen: soundness + kernel/reference parity
# ---------------------------------------------------------------------------


def _screen_case(p, d=32, c=64, seed=5):
    X, Q = _corpus(n=200, d=d, seed=seed, nq=4)
    band = build_band(X)
    Qp = jnp.take(jnp.asarray(Q), band.perm, axis=1)
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.permutation(X.shape[0])[:c] for _ in Q])
    ids[:, -3:] = [-1, X.shape[0], -1]          # padding must die
    ids = jnp.asarray(ids.astype(np.int32))
    true = _true_power_sums(Q, X, p if np.isscalar(p) else 1.0)
    if np.isscalar(p):
        # a mid-quantile threshold: some kills, some survivors
        thr = jnp.asarray(np.quantile(true, 0.25, axis=1).astype(np.float32))
    else:
        thr = jnp.full((Q.shape[0],), jnp.inf)
    sb = jnp.zeros(ids.shape, jnp.float32)      # no base bounds: screen only
    return X, Q, band, Qp, ids, thr, sb


@pytest.mark.parametrize("p", [0.5, 0.8, 1.25, 2.0])
def test_screen_kills_are_certified(p):
    X, Q, band, Qp, ids, thr, sb = _screen_case(p)
    keep, nd = lp_gather_screen(Qp, ids, band.codes, band.scale, band.radius,
                                thr, sb, p)
    keep = np.asarray(keep)
    ids_np = np.asarray(ids)
    valid = (ids_np >= 0) & (ids_np < X.shape[0])
    assert not np.any(keep & ~valid), "padding survived the screen"
    assert keep[valid].any(), "screen killed everything: thresholds bogus"
    true = _true_power_sums(Q, X, p)
    thr_np = np.asarray(thr)
    for b in range(ids_np.shape[0]):
        killed = ids_np[b][valid[b] & ~keep[b]]
        # soundness: every certified kill truly exceeds the threshold
        assert np.all(true[b, killed] > thr_np[b]), f"unsound kill row {b}"
    assert np.all(np.asarray(nd) >= 0)


@pytest.mark.parametrize("vec_p", [False, True])
def test_screen_kernel_matches_reference(vec_p):
    """interpret-mode Pallas screen == blocked jnp reference, bitwise."""
    p = jnp.asarray(np.resize([0.8, 2.0, 1.25, 0.5], 4).astype(np.float32)) \
        if vec_p else 0.8
    X, Q, band, Qp, ids, thr, sb = _screen_case(1.0 if vec_p else p, d=32)
    ref = lp_gather_screen(Qp, ids, band.codes, band.scale, band.radius,
                           thr, sb, p)                       # off-TPU ref
    ker = lp_gather_screen(Qp, ids, band.codes, band.scale, band.radius,
                           thr, sb, p, interpret=True)       # Pallas path
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(ker[0]))
    np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(ker[1]))


# ---------------------------------------------------------------------------
# two-band verification: bitwise parity with the uncompressed path
# ---------------------------------------------------------------------------


def _verify_case(d=48, seed=9):
    X, Q = _corpus(n=400, d=d, seed=seed, nq=5)
    rng = np.random.default_rng(seed)
    t = 60
    cand = np.stack([rng.permutation(X.shape[0])[:t] for _ in Q])
    # sort by L1 base distance, like the beam hands candidates over
    base = np.abs(Q[:, None, :] - X[cand]).sum(-1)
    order = np.argsort(base, axis=1, kind="stable")
    cand = np.take_along_axis(cand, order, axis=1).astype(np.int32)
    base = np.take_along_axis(base, order, axis=1).astype(np.float32)
    return (jnp.asarray(Q), jnp.asarray(X), jnp.asarray(cand),
            jnp.asarray(base))


@pytest.mark.parametrize("p", P_GRID)
def test_two_band_bitwise_parity_scalar(p):
    Q, X, cand, base = _verify_case()
    band = build_band(X)
    k, kappa, tau = 10, 16, 0.92
    c = verify_candidates(Q, cand, X, p, k, kappa, tau, cand_base=base,
                          base_p=1.0, band=band)
    f = verify_candidates(Q, cand, X, p, k, kappa, tau, abandon=False)
    np.testing.assert_array_equal(np.asarray(c[0]), np.asarray(f[0]),
                                  err_msg=f"ids differ at p={p}")
    np.testing.assert_array_equal(np.asarray(c[1]), np.asarray(f[1]),
                                  err_msg=f"dists differ at p={p}")
    np.testing.assert_array_equal(np.asarray(c[2].n_p), np.asarray(f[2].n_p))
    # the screen actually saved f32 gathers, and band traffic is counted
    assert float(np.mean(np.asarray(c[2].n_f32_rows_frac))) < 1.0
    assert float(np.mean(np.asarray(c[2].n_band_frac))) > 0.0
    assert np.all(np.asarray(f[2].n_f32_rows_frac) == 1.0) \
        and np.all(np.asarray(f[2].n_band_frac) == 0.0)


def test_two_band_bitwise_parity_vector_p():
    Q, X, cand, base = _verify_case(seed=11)
    band = build_band(X)
    ps = np.resize(np.asarray(P_GRID, np.float32), Q.shape[0])
    k, kappa, tau = 10, 16, 0.92
    c = verify_candidates(Q, cand, X, jnp.asarray(ps), k, kappa, tau,
                          cand_base=base, base_p=1.0, band=band)
    f = verify_candidates(Q, cand, X, jnp.asarray(ps), k, kappa, tau,
                          abandon=False)
    np.testing.assert_array_equal(np.asarray(c[0]), np.asarray(f[0]))
    np.testing.assert_array_equal(np.asarray(c[1]), np.asarray(f[1]))


@pytest.mark.parametrize("p", P_GRID)
def test_uhnsw_search_parity_end_to_end(p, small_ds, graphs_bulk):
    on = UHNSW(*graphs_bulk, UHNSWParams(t=120, kappa=32,
                                         compressed_band=True))
    off = UHNSW(*graphs_bulk, UHNSWParams(t=120, kappa=32, abandon=False))
    Q = jnp.asarray(small_ds.queries[:8])
    ids_c, d_c, st_c = on.search(Q, p, 10)
    ids_f, d_f, st_f = off.search(Q, p, 10)
    np.testing.assert_array_equal(np.asarray(ids_c), np.asarray(ids_f))
    np.testing.assert_array_equal(np.asarray(d_c), np.asarray(d_f))
    if p != 2.0:  # p == base metric takes the exact skip: nothing verifies
        assert float(np.mean(np.asarray(st_c.n_f32_rows_frac))) < 1.0
    else:
        assert float(np.sum(np.asarray(st_c.n_p))) == 0.0


def test_energy_perm_search_same_ids(small_ds, graphs_bulk):
    on = UHNSW(*graphs_bulk, UHNSWParams(t=120, kappa=32, energy_perm=True))
    off = UHNSW(*graphs_bulk, UHNSWParams(t=120, kappa=32))
    Q = jnp.asarray(small_ds.queries[:8])
    for p in (0.8, 1.5):
        ids_e, _, _ = on.search(Q, p, 10)
        ids_o, _, _ = off.search(Q, p, 10)
        np.testing.assert_array_equal(np.asarray(ids_e), np.asarray(ids_o),
                                      err_msg=f"p={p}")


# ---------------------------------------------------------------------------
# energy order: bijection, variance-sorted, round-trip identity
# ---------------------------------------------------------------------------


def test_energy_order_roundtrip_identity():
    X, _ = _corpus(seed=13)
    perm = energy_order(X)
    assert sorted(perm.tolist()) == list(range(X.shape[1]))
    var = np.var(np.asarray(X, np.float64), axis=0)[perm]
    assert np.all(np.diff(var) <= 1e-12), "not in decreasing-variance order"
    inv = np.argsort(perm)
    np.testing.assert_array_equal(X[:, perm][:, inv], X)
    # deterministic, and build_band derives the same ordering
    np.testing.assert_array_equal(perm, energy_order(X))
    np.testing.assert_array_equal(np.asarray(build_band(X).perm), perm)


def test_build_band_deterministic():
    X, _ = _corpus(seed=17)
    a, b = build_band(X), build_band(X)
    np.testing.assert_array_equal(np.asarray(a.codes), np.asarray(b.codes))
    np.testing.assert_array_equal(np.asarray(a.scale), np.asarray(b.scale))
    np.testing.assert_array_equal(np.asarray(a.radius), np.asarray(b.radius))


# ---------------------------------------------------------------------------
# persistence: the band rides the snapshot byte-for-byte
# ---------------------------------------------------------------------------


def test_snapshot_band_roundtrip(tmp_path):
    X, Q = _corpus(n=240, d=24, seed=19)
    params = UHNSWParams(t=80, kappa=32, compressed_band=True)
    idx = ShardedUHNSW.build(X, num_segments=2, m=12, seed=3, params=params)
    band = idx.compressed_band()            # materialize before snapshot
    path = save_snapshot(idx, tmp_path)
    man = read_manifest(path)
    assert man["band"] is not None
    np.testing.assert_array_equal(np.asarray(band.perm),
                                  np.asarray(man["band"]["perm"]))
    back = load_snapshot(path)
    assert isinstance(back._band, CompressedBand)
    np.testing.assert_array_equal(np.asarray(back._band.codes),
                                  np.asarray(band.codes))
    np.testing.assert_array_equal(np.asarray(back._band.scale),
                                  np.asarray(band.scale))
    np.testing.assert_array_equal(np.asarray(back._band.radius),
                                  np.asarray(band.radius))
    np.testing.assert_array_equal(np.asarray(back._band.perm),
                                  np.asarray(band.perm))
    Qj = jnp.asarray(Q)
    for p in (0.5, 1.25):
        a_ids, a_d, _ = idx.search(Qj, p, 10)
        b_ids, b_d, _ = back.search(Qj, p, 10)
        np.testing.assert_array_equal(np.asarray(a_ids), np.asarray(b_ids))
        np.testing.assert_array_equal(np.asarray(a_d), np.asarray(b_d))


def test_snapshot_without_band_has_null_manifest_entry(tmp_path):
    X, _ = _corpus(n=150, d=16, seed=23)
    idx = ShardedUHNSW.build(X, num_segments=2, m=12, seed=3)
    path = save_snapshot(idx, tmp_path)
    assert read_manifest(path)["band"] is None
    assert load_snapshot(path)._band is None
