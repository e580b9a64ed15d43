"""Lp distance computation under universal p (paper §2.1).

The paper's key systems observation is a *hardware cost asymmetry*:

  p = 1, 2        -> basic arithmetic only (CPU: AVX-512 add/sub/mul; TPU: VPU
                     full-rate elementwise, and for p=2 the MXU matmul identity
                     ||x-y||^2 = ||x||^2 + ||y||^2 - 2<x,y>).
  p = 0.5, 1.5    -> adds a sqrt (CPU: _mm512_sqrt_ps; TPU: VPU transcendental).
  other p         -> needs |d|^p = exp(p*log|d|), two transcendentals per
                     element -> more than an order of magnitude slower.

This module provides the pure-jnp implementations (the Pallas kernels in
repro.kernels mirror these exactly; kernels/ref.py re-exports from here) plus
the analytic TPU op-cost model used by benchmarks/fig1_lp_distance_cost.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.lp_ops import EPS as _EPS  # noqa: F401  (back-compat export)
from repro.core.lp_ops import abs_pow, is_static_p, lp_root

# p-values whose Lp distance evaluates without transcendentals (fast family).
BASIC_PS = (1.0, 2.0)
# p-values that need only a sqrt on top of basic arithmetic (paper §2.1).
SQRT_PS = (0.5, 1.5)

# The op-sequence table lives in repro.core.lp_ops (shared with the Pallas
# kernel bodies); these aliases keep the historical private names alive.
_abs_diff_pow = abs_pow
_root = lp_root


def _as_p_vec(p) -> jax.Array:
    """Coerce a per-query p to a (B,) float32 array (the traced-p contract).

    A 0-d jax scalar becomes (1,) so it broadcasts as "one p for every
    row" instead of crashing the per-row indexing.
    """
    p = jnp.asarray(p, dtype=jnp.float32)
    return p[None] if p.ndim == 0 else p


@partial(jax.jit, static_argnames=("p", "root"))
def _lp_distance_s(x, y, p: float, root: bool):
    s = jnp.sum(_abs_diff_pow(x - y, p), axis=-1)
    return _root(s, p) if root else s


@partial(jax.jit, static_argnames=("root",))
def _lp_distance_v(x, y, p, root: bool):
    s = jnp.sum(_abs_diff_pow(x - y, p[..., None]), axis=-1)
    return _root(s, p) if root else s


def lp_distance(x: jax.Array, y: jax.Array, p, root: bool = True) -> jax.Array:
    """Lp distance between broadcast-compatible vectors along the last axis.

    p: Python float (one compiled program per p) or an array broadcastable
    to the *result* shape (per-element metric; one program for any p mix —
    DESIGN.md §6). With root=False returns sum(|x-y|^p) (same ordering,
    cheaper), which is what the search loops use internally.
    """
    if is_static_p(p):
        return _lp_distance_s(x, y, float(p), root)
    return _lp_distance_v(x, y, _as_p_vec(p), root)


def _matmul_t(q, x):
    """q @ x.T at full f32 precision (TPU's default f32 matmul is one bf16
    pass; the identity's cancellation needs every digit)."""
    return jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("p", "root"))
def _pairwise_lp_s(q, x, p: float, root: bool):
    if p == 2.0:
        qq = jnp.sum(q * q, axis=-1)
        xx = jnp.sum(x * x, axis=-1)
        s = qq[:, None] + xx[None, :] - 2.0 * _matmul_t(q, x)
        s = jnp.maximum(s, 0.0)  # clamp fp cancellation
        return jnp.sqrt(s) if root else s
    s = jnp.sum(_abs_diff_pow(q[:, None, :] - x[None, :, :], p), axis=-1)
    return _root(s, p) if root else s


@partial(jax.jit, static_argnames=("root",))
def _pairwise_lp_v(q, x, p, root: bool):
    # Elementwise family selection; rows with p == 2 additionally take the
    # MXU matmul-identity value so they match the scalar p=2 path bit-for-bit
    # (the elementwise diff^2 sum and the matmul identity round differently).
    s = jnp.sum(_abs_diff_pow(q[:, None, :] - x[None, :, :], p[:, None, None]),
                axis=-1)
    qq = jnp.sum(q * q, axis=-1)
    xx = jnp.sum(x * x, axis=-1)
    s2 = jnp.maximum(qq[:, None] + xx[None, :] - 2.0 * _matmul_t(q, x), 0.0)
    s = jnp.where(p[:, None] == 2.0, s2, s)
    return _root(s, p[:, None]) if root else s


def pairwise_lp(q: jax.Array, x: jax.Array, p, root: bool = True) -> jax.Array:
    """All-pairs Lp distances: q (B, d) f32 vs x (N, d) f32 -> (B, N) f32.

    p: Python float, or a (B,) array giving each query row its own metric
    (the mixed-p serving contract, DESIGN.md §6). For p=2 — the scalar
    specialization *and* vector rows equal to 2 — uses the MXU-friendly
    matmul identity (the TPU analogue of the paper's SIMD L2 fast path).
    Other p-values broadcast on the VPU.
    """
    if is_static_p(p):
        return _pairwise_lp_s(q, x, float(p), root)
    return _pairwise_lp_v(q, x, _as_p_vec(p), root)


@partial(jax.jit, static_argnames=("p", "root"))
def _rowwise_lp_s(q, c, p: float, root: bool):
    s = jnp.sum(_abs_diff_pow(q[:, None, :] - c, p), axis=-1)
    return _root(s, p) if root else s


@partial(jax.jit, static_argnames=("root",))
def _rowwise_lp_v(q, c, p, root: bool):
    s = jnp.sum(_abs_diff_pow(q[:, None, :] - c, p[:, None, None]), axis=-1)
    return _root(s, p[:, None]) if root else s


def rowwise_lp(q: jax.Array, c: jax.Array, p, root: bool = True) -> jax.Array:
    """Per-row candidate distances: q (B, d) f32 vs c (B, C, d) f32 -> (B, C).

    This is the verification-step shape: each query has its own gathered
    candidate block. p: Python float or (B,) array — row i is scored under
    p[i] (scalar-vs-vector contract, DESIGN.md §6).
    """
    if is_static_p(p):
        return _rowwise_lp_s(q, c, float(p), root)
    return _rowwise_lp_v(q, c, _as_p_vec(p), root)


# ---------------------------------------------------------------------------
# Analytic TPU op-cost model (reproduces the *shape* of paper Fig. 1).
#
# Costs are in VPU-lane-cycles per element. Calibrated against the public
# TPU ISA characterization: basic ALU ops are full rate (1), transcendentals
# (sqrt/exp/log) occupy the slow path (~7 cycle-equivalents per element).
# The MXU path for p=2 amortizes the d-dim reduction into a matmul running
# at ~128x the VPU flop rate for large candidate tiles.
# ---------------------------------------------------------------------------

VPU_BASIC = 1.0
VPU_TRANSCENDENTAL = 7.0
MXU_SPEEDUP = 64.0  # effective matmul advantage at the tile sizes we use


def lp_op_cost_per_element(p: float, use_mxu: bool = True) -> float:
    """Modelled per-element cost (VPU-cycle-equivalents) of |x-y|^p summation."""
    if p == 2.0:
        # sub, mul, add -- and the mul+add ride the MXU in pairwise form.
        return VPU_BASIC + 2.0 * VPU_BASIC / (MXU_SPEEDUP if use_mxu else 1.0)
    if p == 1.0:
        return 3.0 * VPU_BASIC  # sub, abs, add
    if p in SQRT_PS:
        extra = VPU_BASIC if p == 1.5 else 0.0  # p=1.5 also multiplies a*sqrt(a)
        return 3.0 * VPU_BASIC + VPU_TRANSCENDENTAL + extra
    # general p: sub, abs, log, mul, exp, add
    return 4.0 * VPU_BASIC + 2.0 * VPU_TRANSCENDENTAL


def lp_distance_cost_model(p: float, d: int, use_mxu: bool = True) -> float:
    """Modelled cost (VPU-cycle-equivalents) of one d-dim Lp Q2D distance."""
    per_elem = lp_op_cost_per_element(p, use_mxu=use_mxu)
    # the outer root is O(1) per distance; include it for completeness
    root_cost = 0.0 if p == 1.0 else VPU_TRANSCENDENTAL
    return per_elem * d + root_cost


def transcendental_op_count(p: float, d: int) -> int:
    """Exact transcendental-op count of one d-dim Lp distance (root excluded)."""
    if p in BASIC_PS:
        return 0
    if p in SQRT_PS:
        return d
    return 2 * d  # log + exp per element


def base_metric_for(p, cutoff: float = 1.4):
    """U-HNSW base-index selection rule (paper Alg. 1 line 3): G1 iff p <= 1.4.

    Scalar p -> scalar 1.0/2.0. Array p (a mixed-p batch) -> same-shape f32
    array: the *two-way* G1/G2 partition of the batch (DESIGN.md §6) — the
    number of distinct p values never matters, only which side of the
    cutoff each row falls on.
    """
    import numpy as np

    pa = np.asarray(p, dtype=np.float32)
    # NaN must fail too, so phrase the check as "all inside", not "any outside"
    if not np.all((pa >= 0.5) & (pa <= 2.0)):
        raise ValueError(f"p={p} outside the supported universal range [0.5, 2]")
    if pa.ndim == 0:
        return 1.0 if float(pa) <= cutoff else 2.0
    return np.where(pa <= cutoff, np.float32(1.0), np.float32(2.0))


def numpy_lp(q, x, p: float, root: bool = True):
    """NumPy oracle (no jit) used by tests and the CPU-side graph builder."""
    import numpy as np

    diff = np.abs(np.asarray(q)[..., None, :] - np.asarray(x)[None, :, :])
    s = (diff**p).sum(axis=-1)
    return s ** (1.0 / p) if root else s
