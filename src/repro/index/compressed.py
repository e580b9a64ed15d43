"""Compressed storage band with certified Lp lower bounds (DESIGN.md §10).

The verification stage gathers full f32 rows for every candidate a kappa
batch offers. Because Lp is coordinate-separable, an int8 replica of the
corpus admits *exact per-coordinate* error bounds: with dequantized value
x̂_j = scale_j * code_j and a per-coordinate radius

    radius_j >= |x_j - x̂_j|   for every row x in the corpus,

the reverse triangle inequality gives, coordinate by coordinate,

    |q_j - x_j| >= max(|q_j - x̂_j| - radius_j, 0),

and monotonicity of t -> t^p lifts the inequality through the power sum —
so a blocked power sum over compressed rows minus the accumulated radius
term is a certified lower bound on the true f32 power-sum distance (the
same admissibility style as `lp_entry_bound`/`lp_suffix_bound`, applied
to a storage tier). The two-band scan (core/uhnsw._verify_two_band_impl)
screens candidates against the running k-th best using this bound and
gathers f32 rows only for survivors.

Coordinates are stored in *energy order* (decreasing per-coordinate
variance): Lp is coordinate-separable, so a fixed permutation is bit-exact
after unpermuting, and front-loading the mass makes both the compressed
screen and the PR-5 suffix bounds go dead after fewer blocks at small p.

Quantization is symmetric per-coordinate affine: one f32 scale per
coordinate, codes in [-127, 127]. Radii are computed *exactly* in f32 as
the max dequantization error over the corpus — the scan evaluates the
identical dequant expression `codes.astype(f32) * scale`, so the radius
covers every row bit-for-bit; accumulated f32 rounding in the blocked sum
is dwarfed by the BOUND_SLACK deflation applied at comparison time.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lp_ops import is_static_p, pow_from_abs


@dataclass(frozen=True)
class CompressedBand:
    """Device-resident int8 replica of a frozen corpus, in energy order.

    Attributes:
      codes: (n, d) int8 — quantized corpus, coordinate j of the band is
        original coordinate `perm[j]` (energy order).
      scale: (d,) f32 — per-coordinate dequant scales (band order);
        x̂ = codes.astype(f32) * scale.
      radius: (d,) f32 — exact per-coordinate max dequant error over the
        corpus (band order): max_i |Xp[i, j] - scale[j] * codes[i, j]|.
      perm: (d,) int32 — band coord j = original coord perm[j]. Queries
        enter the screen as Q[:, perm]; results never need unpermuting
        (the screen emits keep decisions, not distances).
      rows: the screen kernel's row source — `codes` itself unless the
        TPU needs the feature axis lane-padded (kernels.ops.kernel_rows);
        derived from `codes` at construction.
    """

    codes: jax.Array
    scale: jax.Array
    radius: jax.Array
    perm: jax.Array
    rows: jax.Array = field(init=False, repr=False, compare=False)

    def __post_init__(self):
        from repro.kernels.ops import kernel_rows

        object.__setattr__(self, "rows", kernel_rows(self.codes))

    @property
    def n(self) -> int:
        return int(self.codes.shape[0])

    @property
    def d(self) -> int:
        return int(self.codes.shape[1])

    def nbytes(self) -> int:
        """Band storage footprint (codes + scales + radii + perm)."""
        return self.n * self.d + 3 * 4 * self.d


def energy_order(X) -> np.ndarray:
    """(d,) int32 permutation: coordinates by decreasing variance.

    Stable (ties keep their original order), computed on host in f64 so
    the ordering is deterministic across backends. Constant coordinates
    (zero variance) sink to the tail, where the suffix bounds lose
    nothing by scanning them last.
    """
    var = np.var(np.asarray(X, dtype=np.float64), axis=0)
    # argsort of -var is stable under kind="stable": equal-variance coords
    # keep ascending original index, matching jnp.take round-trip tests
    return np.argsort(-var, kind="stable").astype(np.int32)


def build_band(X, perm: np.ndarray | None = None) -> CompressedBand:
    """Quantize a frozen corpus into its compressed band.

    X: (n, d) f32 (host or device). perm: optional (d,) coordinate
    permutation; None derives the energy order. Returns a device-resident
    CompressedBand whose radii are exact f32 maxima of the dequant error,
    so the screen's per-coordinate bound is admissible for every row.

    Deterministic: same X -> bit-identical band (compaction and snapshot
    recovery rebuild it and land on the same bytes).
    """
    Xh = np.ascontiguousarray(np.asarray(X), dtype=np.float32)
    n, d = Xh.shape
    if perm is None:
        perm = energy_order(Xh)
    perm = np.asarray(perm, dtype=np.int32)
    assert perm.shape == (d,), (perm.shape, d)
    Xp = np.ascontiguousarray(Xh[:, perm])
    # symmetric per-coordinate affine quantization:
    # scale = max|col| / 127, codes = round(col / scale) in [-127, 127]
    absmax = np.abs(Xp).max(axis=0) if n else np.zeros(d, np.float32)
    scale = (np.maximum(absmax, 1e-12) / 127.0).astype(np.float32)
    codes = np.clip(np.round(Xp / scale), -127, 127).astype(np.int8)
    # exact f32 radii over the SAME dequant expression the scan evaluates
    dequant = (codes.astype(np.float32) * scale).astype(np.float32)
    err = np.abs(Xp - dequant)
    radius = (err.max(axis=0) if n else np.zeros(d)).astype(np.float32)
    return CompressedBand(
        codes=jnp.asarray(codes),
        scale=jnp.asarray(scale),
        radius=jnp.asarray(radius),
        perm=jnp.asarray(perm),
    )


def compressed_lower_bound(qp: jax.Array, codes: jax.Array,
                           scale: jax.Array, radius: jax.Array,
                           p) -> jax.Array:
    """Certified lower bound on the f32 Lp power sum, full-dimension form.

    qp: (B, d) queries in band (permuted) coordinate order; codes: (C, d)
    int8 band rows; scale/radius: (d,) f32. p: Python float or (B,)
    per-row array (the scalar-vs-vector contract, DESIGN.md §6). Returns
    (B, C) f32 — the un-deflated bound sum_j max(|q_j - x̂_j| - r_j, 0)^p,
    which real-arithmetic admissibility puts at or below the true power
    sum (the scan deflates by BOUND_SLACK before comparing, absorbing the
    accumulated f32 rounding of both sides).

    This is the property-test oracle for the blocked screen (kernels/
    ref.gather_lp_screen_ref accumulates exactly these per-block terms).
    """
    xh = codes.astype(jnp.float32) * scale[None, :]        # (C, d)
    a = jnp.abs(qp[:, None, :] - xh[None, :, :])           # (B, C, d)
    a = jnp.maximum(a - radius[None, None, :], 0.0)
    p_b = float(p) if is_static_p(p) else jnp.asarray(p)[:, None, None]
    return jnp.sum(pow_from_abs(a, p_b), axis=-1)
