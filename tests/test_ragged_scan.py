"""The blocked scan at widths that are not a multiple of 8 (GloVe's 100).

`pick_abandon_block_d` gives such a width 32-dimension blocks; the scan
runs ceil(d / 32) of them and the last is ragged, its columns past d the
zero lane padding of `kernels.ops.kernel_rows`. Pinned here, on seeded
random corpora at d in {100, 36}, with the row source laid out as on the
chip (lane-padded) and the kernels in interpret mode:

  * the abandon and screen kernels against the blocked oracles of
    kernels/ref.py, which read the unpadded rows: the same `nd`, the same
    partial sums (bitwise), the same survivors;
  * the abandon kernel against exact float32 Lp distances in plain
    `jax.numpy`: top-k ids equal to the full-dimension path, distances
    within 1e-5 relative, and every abandoned candidate truly beaten;
  * `nd` counts real dimensions only: d for a candidate scanned whole.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.compressed import build_band
from repro.kernels import ops

WIDTHS = [100, 36]
P_CASES = [0.5, 0.8, 1.5, 2.0, "rows"]
ROW_PS = np.array([0.5, 0.8, 1.5, 2.0, 0.7, 1.9], np.float32)
B, C, N, K = 6, 40, 250, 5


def _p(p):
    return jnp.asarray(ROW_PS) if p == "rows" else p


def _p_rows(p):
    return ROW_PS[:, None] if p == "rows" else np.float32(p)


def _chip_rows(x, monkeypatch):
    """x in the lane-padded layout the index lays out on the chip."""
    with monkeypatch.context() as m:
        m.setattr(ops, "_on_tpu", lambda: True)
        rows = ops.kernel_rows(x)
    assert rows.shape[1] == 128
    return rows


def _case(d, seed=0):
    rng = np.random.default_rng(seed + d)
    q = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32) * 2)
    x = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32) * 2)
    ids = rng.integers(0, N, size=(B, C)).astype(np.int32)
    ids[:, -2:] = [-1, N]                       # padding scores +inf
    return q, x, jnp.asarray(ids)


def _exact(q, x, ids, p):
    """Exact float32 power sums sum_j |q_j - x_j|^p, plain jnp; +inf for
    padding ids."""
    ids = np.asarray(ids)
    valid = (ids >= 0) & (ids < N)
    rows = jnp.asarray(x)[np.clip(ids, 0, N - 1)]
    s = jnp.sum(jnp.abs(jnp.asarray(q)[:, None, :] - rows)
                ** jnp.asarray(_p_rows(p))[..., None], axis=-1)
    return np.where(valid, np.asarray(s), np.inf)


@pytest.mark.parametrize("p", P_CASES)
@pytest.mark.parametrize("d", WIDTHS)
def test_ragged_abandon_kernel_matches_ref_and_exact(d, p, monkeypatch):
    assert ops.pick_abandon_block_d(d) == 32
    q, x, ids = _case(d)
    exact = _exact(q, x, ids, p)
    thr = jnp.asarray(np.median(exact[:, :-2], axis=1).astype(np.float32))
    sb = jnp.zeros(ids.shape, jnp.float32)
    ker, nd = ops.lp_gather_abandon(q, ids, _chip_rows(x, monkeypatch), thr,
                                    sb, _p(p), interpret=True)
    ref, nd_ref = ops.lp_gather_abandon(q, ids, x, thr, sb, _p(p))
    ker, nd, ref, nd_ref = map(np.asarray, (ker, nd, ref, nd_ref))
    np.testing.assert_array_equal(nd, nd_ref)
    np.testing.assert_array_equal(ker, ref)     # same blocks, same sums
    alive = np.isfinite(ker)
    assert alive.any() and (~alive[:, :-2]).any()
    assert nd.max() == d and np.all(nd[alive] == d)
    # some candidates die mid-scan: after a whole block, before the end
    assert np.any((nd > 0) & (nd < d))
    np.testing.assert_allclose(ker[alive], exact[alive], rtol=1e-5)
    assert np.all(exact[~alive] > np.asarray(thr)[:, None]
                  .repeat(C, 1)[~alive] * (1 - 1e-6))
    top = np.argsort(ker, axis=1, kind="stable")[:, :K]
    want = np.argsort(exact, axis=1, kind="stable")[:, :K]
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(ids), top, 1),
                                  np.take_along_axis(np.asarray(ids), want, 1))


@pytest.mark.parametrize("p", P_CASES)
@pytest.mark.parametrize("d", WIDTHS)
def test_ragged_scan_unbounded_is_the_exact_distance(d, p, monkeypatch):
    """With no threshold every candidate is scanned whole: the blocked sum
    over the ragged blocks is the exact float32 Lp power sum."""
    q, x, ids = _case(d, seed=1)
    thr = jnp.full((B,), jnp.inf)
    sb = jnp.zeros(ids.shape, jnp.float32)
    ker, nd = ops.lp_gather_abandon(q, ids, _chip_rows(x, monkeypatch), thr,
                                    sb, _p(p), interpret=True)
    ker, nd = np.asarray(ker), np.asarray(nd)
    valid = (np.asarray(ids) >= 0) & (np.asarray(ids) < N)
    np.testing.assert_array_equal(nd, np.where(valid, d, 0))
    np.testing.assert_allclose(ker[valid], _exact(q, x, ids, p)[valid],
                               rtol=1e-5)


@pytest.mark.parametrize("p", P_CASES)
@pytest.mark.parametrize("d", WIDTHS)
def test_ragged_screen_kernel_matches_ref(d, p, monkeypatch):
    q, x, ids = _case(d, seed=2)
    band = build_band(x)
    qp = jnp.take(q, band.perm, axis=1)
    exact = _exact(q, x, ids, p)
    thr = jnp.asarray(np.quantile(exact[:, :-2], 0.4, axis=1)
                      .astype(np.float32))
    sb = jnp.zeros(ids.shape, jnp.float32)
    keep, nd = ops.lp_gather_screen(
        qp, ids, _chip_rows(band.codes, monkeypatch), band.scale,
        band.radius, thr, sb, _p(p), interpret=True)
    keep_r, nd_r = ops.lp_gather_screen(qp, ids, band.codes, band.scale,
                                        band.radius, thr, sb, _p(p))
    keep, nd = np.asarray(keep), np.asarray(nd)
    np.testing.assert_array_equal(keep, np.asarray(keep_r))
    np.testing.assert_array_equal(nd, np.asarray(nd_r))
    valid = (np.asarray(ids) >= 0) & (np.asarray(ids) < N)
    assert keep[valid].any() and not keep[~valid].any()
    assert nd.max() == d and np.all(nd[keep] == d)
    killed = valid & ~keep
    assert killed.any()
    # a certified kill: the true power sum exceeds the threshold
    assert np.all(exact[killed] > np.asarray(thr)[:, None]
                  .repeat(C, 1)[killed])


# ---------------------------------------------------------------------------
# the engine's scan_blocks_w counter at a ragged width
# ---------------------------------------------------------------------------


def _blocks_corpus(d=36):
    """q = 0 and three rows in ascending L1 order: x0 spread evenly (L1
    3.6), x1 all in one coordinate (L1 3.7, but nearer under p = 0.8, so
    it is scanned whole), x2 far (L1 360: its entry bound beats it)."""
    x = np.zeros((3, d), np.float32)
    x[0] = 0.1
    x[1, 0] = 3.7
    x[2] = 10.0
    return x


@pytest.mark.parametrize("interpret", [None, True])
def test_engine_scan_blocks_count_entered_blocks(interpret):
    """scan_blocks_w sums, over verified candidates, the dimension blocks
    the abandoning scan entered: ceil(d / 32) = 2 at d = 36 for a
    candidate scored whole (the first-k row and x1), 0 for one abandoned
    at entry (x2: adding it adds a verified candidate and no block)."""
    from repro.core.uhnsw import UHNSW, UHNSWParams
    from repro.retrieval.service import QueryRequest, UniversalVectorService

    x = _blocks_corpus()
    got = {}
    for t in (2, 3):
        idx = UHNSW.build(x, m=4, ef_construction=8, seed=0,
                          params=UHNSWParams(t=t, kappa=1, tau=0.92,
                                             ef=8, interpret=interpret))
        svc = UniversalVectorService(index=idx, max_batch=8, min_bucket=8)
        out = svc.serve([QueryRequest(vector=np.zeros(36, np.float32),
                                      p=0.8, k=1, request_id=0)])
        assert int(out[0][0][0]) == 1     # x1 is the p = 0.8 neighbour
        got[t] = (svc.stats["n_p"], svc.stats["scan_blocks_w"])
    # scan_blocks_w is n_p times a per-row mean: a float's rounding apart
    assert got[2] == pytest.approx((2.0, 4.0))  # x0, x1: 2 blocks each
    assert got[3] == pytest.approx((3.0, 4.0))  # x2 abandoned at entry
