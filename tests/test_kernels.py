"""Pallas kernel vs pure-jnp oracle: shape/dtype/p sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests_hypothesis_compat import given, settings, st  # optional dep shim

from repro.kernels.ops import (
    lp_gather_distance,
    pallas_pairwise_lp,
    pallas_rowwise_lp,
)
from repro.kernels.ref import pairwise_lp_ref, rowwise_lp_ref

P_GRID = [0.5, 0.8, 1.0, 1.3, 1.5, 2.0]
SHAPES_PW = [(1, 1, 8), (3, 130, 32), (17, 333, 96), (128, 512, 128), (9, 1000, 760)]
SHAPES_RW = [(1, 1, 8), (5, 33, 64), (16, 300, 128), (8, 257, 960)]


def _rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b) / (jnp.abs(b) + 1e-5)))


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("shape", SHAPES_PW)
def test_pairwise_kernel_matches_ref(p, shape):
    b, n, d = shape
    kq, kx = jax.random.split(jax.random.PRNGKey(b * 31 + n))
    q = jax.random.normal(kq, (b, d), dtype=jnp.float32) * 3
    x = jax.random.normal(kx, (n, d), dtype=jnp.float32) * 3
    got = pallas_pairwise_lp(q, x, p)
    want = pairwise_lp_ref(q, x, p)
    assert got.shape == want.shape
    assert _rel_err(got, want) < 3e-5


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("shape", SHAPES_RW)
def test_rowwise_kernel_matches_ref(p, shape):
    b, c, d = shape
    kq, kc = jax.random.split(jax.random.PRNGKey(b * 17 + c))
    q = jax.random.normal(kq, (b, d), dtype=jnp.float32) * 3
    cands = jax.random.normal(kc, (b, c, d), dtype=jnp.float32) * 3
    got = pallas_rowwise_lp(q, cands, p)
    want = rowwise_lp_ref(q, cands, p)
    assert got.shape == want.shape
    assert _rel_err(got, want) < 3e-5


@pytest.mark.parametrize("p", [0.5, 1.0, 2.0])
def test_pairwise_kernel_bf16_inputs(p):
    kq, kx = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (8, 64), dtype=jnp.bfloat16)
    x = jax.random.normal(kx, (100, 64), dtype=jnp.bfloat16)
    got = pallas_pairwise_lp(q, x, p)
    want = pairwise_lp_ref(q.astype(jnp.float32), x.astype(jnp.float32), p)
    assert got.dtype == jnp.float32  # kernels accumulate in f32
    assert _rel_err(got, want) < 2e-2  # bf16 input quantization


@pytest.mark.parametrize("p", P_GRID)
def test_root_free_variant(p):
    kq, kx = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(kq, (4, 48))
    x = jax.random.normal(kx, (77, 48))
    got = pallas_pairwise_lp(q, x, p, root=False)
    want = pairwise_lp_ref(q, x, p, root=False)
    assert _rel_err(got, want) < 3e-5


def test_explicit_tile_override():
    q = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 32))
    a = pallas_pairwise_lp(q, x, 1.0, block_b=8, block_n=128)
    b = pallas_pairwise_lp(q, x, 1.0, block_b=16, block_n=256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(1, 9),
    n=st.integers(1, 150),
    d=st.integers(2, 80),
    p=st.sampled_from(P_GRID),
)
def test_pairwise_kernel_property(b, n, d, p):
    """Any (B, N, d) — including awkward non-tile-multiples — matches ref."""
    kq, kx = jax.random.split(jax.random.PRNGKey(b * 1000 + n * 10 + d))
    q = jax.random.normal(kq, (b, d), dtype=jnp.float32)
    x = jax.random.normal(kx, (n, d), dtype=jnp.float32)
    got = pallas_pairwise_lp(q, x, p)
    want = pairwise_lp_ref(q, x, p)
    assert _rel_err(got, want) < 5e-5


def test_zero_distance_diagonal():
    """d(x, x) == 0 exactly for the general-p path (log-singularity guard)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (12, 40))
    for p in (0.7, 1.3):
        d = pallas_pairwise_lp(x, x, p)
        np.testing.assert_allclose(np.asarray(jnp.diag(d)), 0.0, atol=1e-5)


def _ulp_diff(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """f32 ulp distance via the monotone int32 bit-pattern view."""
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    return np.abs(ai - bi)


@pytest.mark.parametrize("shape", [(9, 37, 24), (16, 256, 64), (17, 333, 96)])
@pytest.mark.parametrize("p", [0.5, 0.8, 1.0, 1.5, 2.0])
def test_pairwise_vector_p_vs_scalar_ulp_pinned(shape, p):
    """Scalar-p vs vector-p *pairwise kernel* parity, pinned to <= 4 ulp.

    This is the known wobble (CHANGES.md PR-3), pinned with an explicit ulp
    tolerance rather than bit-equality. Divergence point: both kernels sum
    |q-x|^p over the d axis, but the vector-p body evaluates every family's
    op sequence and where-selects per element (core/lp_ops), and at tile
    shapes where d is not lane-aligned XLA:CPU reassociates that reduction
    differently from the scalar body's single-family sum — observed only
    for p=1.5 (the a*sqrt(a) family), max 2 ulp pre-root on the pinned
    toolchain; the bound of 4 leaves one extra reassociation of headroom.
    The selected *values* are identical (a select returns the chosen
    operand's bits) — only the summation order wobbles, which is why the
    serving path's gather/rowwise entry points (hard bit-parity contract,
    tests/test_mixed_p.py) are unaffected: their kernels loop query rows
    and never fuse across the family select.

    root=False on purpose: the root is applied outside the kernel by the
    same lp_root on both paths, so any post-root difference is just this
    pre-root wobble amplified by s^(1/p).
    """
    b, n, d = shape
    rng = np.random.default_rng(b * 7 + d)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32) * 3)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) * 3)
    scalar = np.asarray(
        pallas_pairwise_lp(q, x, p, root=False, interpret=True))
    vector = np.asarray(pallas_pairwise_lp(
        q, x, jnp.full((b,), p, dtype=jnp.float32), root=False,
        interpret=True))
    worst = int(_ulp_diff(scalar, vector).max())
    assert worst <= 4, (
        f"pairwise scalar-vs-vector p={p} wobble grew to {worst} ulp "
        f"at shape {shape} — the 1-2 ulp reassociation pin has drifted")


# ---------------------------------------------------------------------------
# fused gather+distance kernel (the verification hot path)
# ---------------------------------------------------------------------------

P_GATHER = [0.5, 0.8, 1.25, 2.0]


def _gather_case(seed, b, c, n, d, sentinels=True):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32) * 3)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) * 3)
    ids = rng.integers(0, n, size=(b, c)).astype(np.int32)
    if sentinels:
        # the padding vocabulary of the query path: -1 (merge pad),
        # n (beam sentinel), and a stray overflow value
        ids[0, 0] = -1
        ids[min(1, b - 1), c // 2] = n
        ids[:, c - 1] = n + 7
    return q, jnp.asarray(ids), x, ids


@pytest.mark.parametrize("p", P_GATHER)
@pytest.mark.parametrize("root", [False, True])
def test_gather_kernel_matches_rowwise_ref(p, root):
    """Fused kernel == gather-then-rowwise_lp, with padding ids -> inf."""
    q, ids, x, ids_np = _gather_case(11, b=6, c=37, n=200, d=48)
    n = x.shape[0]
    got = np.asarray(lp_gather_distance(q, ids, x, p, root=root,
                                        interpret=True))
    valid = (ids_np >= 0) & (ids_np < n)
    want = np.asarray(rowwise_lp_ref(q, x[np.clip(ids_np, 0, n - 1)], p,
                                     root=root))
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.isinf(got), ~valid)
    err = np.max(np.abs(got[valid] - want[valid]) /
                 (np.abs(want[valid]) + 1e-5))
    assert err < 3e-5, (p, root, err)


@pytest.mark.parametrize("p", P_GATHER)
def test_gather_dispatch_paths_agree(p):
    """Backend-aware fallback (jnp reference) == forced interpret kernel."""
    q, ids, x, _ = _gather_case(7, b=5, c=130, n=90, d=33)
    auto = np.asarray(lp_gather_distance(q, ids, x, p))  # CPU -> reference
    kern = np.asarray(lp_gather_distance(q, ids, x, p, interpret=True))
    np.testing.assert_array_equal(np.isinf(auto), np.isinf(kern))
    finite = np.isfinite(auto)
    np.testing.assert_allclose(auto[finite], kern[finite], rtol=5e-5)


def test_gather_all_padding_row():
    """A fully-padded id row (underfilled beam) scores inf everywhere."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(50, 16)).astype(np.float32))
    ids = jnp.concatenate([
        jnp.full((1, 12), -1, jnp.int32),
        jnp.full((1, 12), 50, jnp.int32),
    ])
    for interpret in (None, True):
        out = np.asarray(lp_gather_distance(q, ids, x, 1.25,
                                            interpret=interpret))
        assert np.isinf(out).all()


@pytest.mark.parametrize("p", [0.8, 2.0])
def test_gather_shared_ids_matches_broadcast(p):
    """1-D ids (the delta-scan shape) == the same ids broadcast per query."""
    rng = np.random.default_rng(17)
    b, c, n, d = 6, 23, 60, 32
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ids1d = rng.integers(-1, n + 1, size=(c,)).astype(np.int32)
    shared = np.asarray(lp_gather_distance(q, jnp.asarray(ids1d), x, p,
                                           root=True))
    bcast = np.asarray(lp_gather_distance(
        q, jnp.broadcast_to(jnp.asarray(ids1d)[None, :], (b, c)), x, p,
        root=True))
    np.testing.assert_array_equal(np.isinf(shared), np.isinf(bcast))
    finite = np.isfinite(shared)
    np.testing.assert_allclose(shared[finite], bcast[finite], rtol=5e-5)


def test_gather_explicit_tile_override():
    q, ids, x, _ = _gather_case(9, b=8, c=256, n=120, d=24, sentinels=False)
    a = lp_gather_distance(q, ids, x, 0.8, interpret=True,
                           block_b=2, block_c=128)
    b = lp_gather_distance(q, ids, x, 0.8, interpret=True,
                           block_b=8, block_c=256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("p", [0.8, 2.0, "vector"])
def test_lane_padded_row_source_matches_logical(p, monkeypatch):
    """A row source in `kernel_rows` layout (d=96 zero-padded to 128, as
    the index places it on a TPU) scores like the logical rows: the
    gather, abandon and screen kernels see only distance-neutral zero
    columns, and the blocked scans stop at the logical d."""
    from repro.index.compressed import build_band
    from repro.kernels import ops

    q, ids, x, ids_np = _gather_case(5, b=8, c=150, n=300, d=96)
    if p == "vector":
        p = jnp.asarray([0.5, 0.8, 1.0, 1.25, 1.5, 2.0, 0.8, 2.0],
                        jnp.float32)
    band = build_band(x)
    with monkeypatch.context() as m:
        m.setattr(ops, "_on_tpu", lambda: True)
        x_rows, codes_rows = ops.kernel_rows(x), ops.kernel_rows(band.codes)
    assert x_rows.shape == codes_rows.shape == (300, 128)

    def same(a, b):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(np.isinf(a), np.isinf(b))
        np.testing.assert_allclose(a[np.isfinite(a)], b[np.isfinite(b)],
                                   rtol=1e-6)

    same(lp_gather_distance(q, ids, x_rows, p, interpret=True),
         lp_gather_distance(q, ids, x, p, interpret=True))
    full = lp_gather_distance(q, ids, x, p, interpret=True)
    thr = jnp.asarray(np.nanmedian(
        np.where(np.isfinite(full), full, np.nan), axis=1))
    sb = jnp.zeros(ids.shape, jnp.float32)
    got, nd = ops.lp_gather_abandon(q, ids, x_rows, thr, sb, p,
                                    interpret=True)
    want, nd_want = ops.lp_gather_abandon(q, ids, x, thr, sb, p,
                                          interpret=True)
    same(got, want)
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(nd_want))
    assert int(np.asarray(nd).max()) == 96
    qp = jnp.take(q, band.perm, axis=1)
    keep, nd8 = ops.lp_gather_screen(qp, ids, codes_rows, band.scale,
                                     band.radius, thr, sb, p, interpret=True)
    keep_w, nd8_w = ops.lp_gather_screen(qp, ids, band.codes, band.scale,
                                         band.radius, thr, sb, p,
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(keep_w))
    np.testing.assert_array_equal(np.asarray(nd8), np.asarray(nd8_w))
