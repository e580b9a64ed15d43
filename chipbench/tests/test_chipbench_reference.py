"""The plain reference and the comparison, against NumPy in float64."""

import jax.numpy as jnp
import numpy as np

from chipbench import compare, reference


def _brute(x, q, ps, k):
    d = (np.abs(q[:, None, :].astype(np.float64) - x[None].astype(np.float64))
         ** ps[:, None, None]).sum(-1) ** (1.0 / ps[:, None])
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


def test_exact_topk_matches_float64_brute_force():
    rng = np.random.default_rng(0)
    x = rng.standard_t(3, (1000, 48)).astype(np.float32)
    q = rng.standard_t(3, (40, 48)).astype(np.float32)
    ps = rng.choice([0.5, 0.7, 1.0, 1.3, 1.9, 2.0], 40).astype(np.float32)
    want_i, want_d = _brute(x, q, ps.astype(np.float64), 10)
    got_i, got_d = reference.exact_topk(jnp.asarray(x), q, ps, 10)
    assert got_i.shape == (40, 10) and got_d.dtype == np.float32
    np.testing.assert_allclose(got_d, want_d, rtol=1e-5)
    assert (got_i == want_i).mean() > 0.99  # near-ties may swap
    dd = reference.distances_of(jnp.asarray(x), q, ps, want_i)
    np.testing.assert_allclose(dd, want_d, rtol=1e-5)


def test_bfloat16_reference_departs_from_float32():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_t(3, (512, 64)).astype(np.float32))
    q = rng.standard_t(3, (8, 64)).astype(np.float32)
    ps = np.full(8, 0.7, np.float32)
    ids, _ = reference.exact_topk(x, q, ps, 10)
    f32 = reference.distances_of(x, q, ps, ids)
    bf16 = reference.distances_of(x, q, ps, ids, dtype=jnp.bfloat16)
    assert compare.dist_gap(bf16, f32) > 1e-3


def test_bad_answer_rows():
    ids = np.array([[0, 1, 2], [0, 0, 1], [0, 1, 9], [2, 1, 0], [1, 2, 3]])
    dists = np.array([[1, 2, 3], [1, 2, 3], [1, 2, 3], [1, 2, 3],
                      [3, 2, 1]], np.float32)
    bad = compare.bad_answer_rows(ids, dists, np.full(5, 5))
    assert bad.tolist() == [False, True, True, False, True]


def test_recall_counts_newer_rows_that_are_close_enough():
    true_ids = np.array([[0, 1, 2]])
    true_d = np.array([[1.0, 2.0, 3.0]])
    got = np.array([[0, 1, 7]])
    assert compare.recall(got, np.array([[1.0, 2.0, 2.5]]), true_ids, true_d,
                          np.array([5]))[0] == 1.0
    assert compare.recall(got, np.array([[1.0, 2.0, 3.5]]), true_ids, true_d,
                          np.array([5]))[0] == 2 / 3
