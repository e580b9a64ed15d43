"""Pure-jnp oracles for the Lp distance kernels.

The single source of truth for Lp semantics is repro.core.metrics; the
kernels must match these to float tolerance across all shapes/dtypes/p.
Like the kernels, the oracles accept p as a Python float or as a (B,)
per-query-row array (the mixed-p contract, DESIGN.md §6) — so every
vector-p kernel has a vector-p oracle with identical semantics.

`gather_lp_abandon_ref` is additionally the *off-TPU production path* for
the early-abandoning blocked verification (DESIGN.md §8): XLA:CPU cannot
skip masked work, so it computes every block and masks — the scanned-dim
accounting (`nd`) still reports exactly what the TPU kernel would skip.
"""

import jax.numpy as jnp
from jax import lax

from repro.core.lp_ops import (
    BOUND_SLACK,
    is_static_p,
    lp_entry_bound,
    lp_suffix_bound,
    pow_from_abs,
)
from repro.core.metrics import (  # noqa: F401
    lp_distance,
    numpy_lp,
    pairwise_lp,
    rowwise_lp,
)

# Aliases matching the kernel entry points one-to-one.
pairwise_lp_ref = pairwise_lp
rowwise_lp_ref = rowwise_lp


def _pad_blocks(t, block_d: int):
    """A (B, d, C) tile zero-padded along d to whole blocks of block_d, as
    the kernels read it from their lane-padded scratch. The barrier keeps
    XLA from folding the zero rows out of the last block's sum, which
    would reassociate it against the kernel's sum over the padded block."""
    short = -t.shape[1] % block_d
    if not short:
        return t
    return lax.optimization_barrier(jnp.pad(t, ((0, 0), (0, short), (0, 0))))


def gather_lp_abandon_ref(
    q: jnp.ndarray,       # (B, d) f32
    ids: jnp.ndarray,     # (B, C) int32; out-of-range = padding
    x: jnp.ndarray,       # (n, d) f32
    thresh: jnp.ndarray,  # (B,) abandon bound, power-sum space
    sb: jnp.ndarray,      # (B, C) base-metric power sums (0 = no bound)
    p,                    # Python float or (B,) f32
    base_p: float,
    block_d: int,
):
    """Blocked early-abandoning oracle for `gather_lp_abandon_kernel_call`.

    Identical scan semantics to the kernel — same block order (a candidate
    that is abandoned mid-scan has exactly the same partial sum on both
    paths), same entry/suffix bounds (shared helpers in core/lp_ops), same
    `(dists, nd)` outputs; abandoned and padding candidates score +inf and
    dims scanned after a candidate dies are not counted. The per-block
    reduction mirrors the kernel's transposed (block_d, TC) axis-0 sum.
    Where block_d does not divide d the last of the ceil(d / block_d)
    blocks is ragged: zero-padded to block_d columns, as the kernel reads
    it from its lane-padded scratch, with `nd` counting its real ones.
    """
    n, d = x.shape
    nb = -(-d // block_d)
    valid = (ids >= 0) & (ids < n)
    diff = x[jnp.clip(ids, 0, n - 1)] - q[:, None, :]   # (B, C, d)
    dt = _pad_blocks(jnp.swapaxes(diff, 1, 2), block_d)  # (B, nb*bd, C)
    if is_static_p(p):
        p_blk = p_row = p
    else:
        p_blk = p[:, None, None]
        p_row = p[:, None]
    thr = thresh[:, None]
    lb = lp_entry_bound(sb, base_p, p_row, d)
    alive = valid & (lb <= thr)
    s = jnp.zeros_like(sb)
    sbase = jnp.zeros_like(sb)
    nd = jnp.zeros(sb.shape, jnp.int32)
    for b in range(nb):
        blk = lax.slice_in_dim(dt, b * block_d, (b + 1) * block_d, axis=1)
        a = jnp.abs(blk)
        bs = jnp.sum(pow_from_abs(a, p_blk), axis=1)
        bb = jnp.sum(a if base_p == 1.0 else a * a, axis=1)
        s = jnp.where(alive, s + bs, s)
        sbase = jnp.where(alive, sbase + bb, sbase)
        nd = nd + jnp.where(alive, min(block_d, d - b * block_d), 0)
        dead = s > thr
        d_rem = d - (b + 1) * block_d
        if d_rem > 0:
            rem = lp_suffix_bound(sb - sbase, base_p, p_row,
                                  float(d_rem))
            dead = dead | (s + rem > thr)
        alive = alive & ~dead
    return jnp.where(alive, s, jnp.inf), nd


def gather_lp_screen_ref(
    q: jnp.ndarray,       # (B, d) f32 queries, band (permuted) coord order
    ids: jnp.ndarray,     # (B, C) int32; out-of-range = padding
    codes: jnp.ndarray,   # (n, d) int8 compressed band (band coord order)
    scale: jnp.ndarray,   # (d,) f32 per-coordinate dequant scales
    radius: jnp.ndarray,  # (d,) f32 per-coordinate max dequant error
    thresh: jnp.ndarray,  # (B,) screen bound, power-sum space
    sb: jnp.ndarray,      # (B, C) base-metric power sums (0 = no bound)
    p,                    # Python float or (B,) f32
    base_p: float,
    block_d: int,
):
    """Blocked compressed-band screen oracle (DESIGN.md §10) for
    `gather_lp_screen_kernel_call`.

    Accumulates the certified per-coordinate lower bound
    max(|q_j - x̂_j| - radius_j, 0)^p over dimension blocks and kills a
    candidate as soon as the deflated running bound exceeds the per-query
    threshold — such a candidate's *true* f32 power sum provably exceeds
    the running k-th best, so the two-band scan never gathers its f32
    row. Unlike `gather_lp_abandon_ref` the accumulated sum is a float-
    evaluated *bound*, not an exact partial of the true distance, so the
    kill comparison deflates by BOUND_SLACK (the same slack the entry/
    suffix bounds carry); the mid-scan suffix bound uses the remaining
    base mass net of the accumulated per-coordinate *upper* bounds
    (|q_j - x̂_j| + radius_j), keeping the remainder an underestimate.

    Returns (keep (B, C) bool — True iff the candidate survived the
    screen (padding never survives), nd (B, C) int32 band dimensions
    scanned; like the abandon oracle this computes-then-masks off TPU
    while reporting exactly what the TPU kernel would skip). A ragged
    last block is zero-padded as in `gather_lp_abandon_ref`.
    """
    n, d = codes.shape
    nb = -(-d // block_d)
    valid = (ids >= 0) & (ids < n)
    xh = codes[jnp.clip(ids, 0, n - 1)].astype(jnp.float32) \
        * scale[None, None, :]                              # (B, C, d)
    a0 = jnp.abs(xh - q[:, None, :])
    al = jnp.maximum(a0 - radius[None, None, :], 0.0)       # lower bounds
    au = a0 + radius[None, None, :]                         # upper bounds
    alt = _pad_blocks(jnp.swapaxes(al, 1, 2), block_d)      # (B, nb*bd, C)
    aut = _pad_blocks(jnp.swapaxes(au, 1, 2), block_d)
    if is_static_p(p):
        p_blk = p_row = p
    else:
        p_blk = p[:, None, None]
        p_row = p[:, None]
    thr = thresh[:, None]
    lb = lp_entry_bound(sb, base_p, p_row, d)
    alive = valid & (lb <= thr)
    s = jnp.zeros_like(sb)
    sbase = jnp.zeros_like(sb)
    nd = jnp.zeros(sb.shape, jnp.int32)
    deflate = 1.0 - BOUND_SLACK
    for b in range(nb):
        blk = lax.slice_in_dim(alt, b * block_d, (b + 1) * block_d, axis=1)
        ublk = lax.slice_in_dim(aut, b * block_d, (b + 1) * block_d, axis=1)
        bs = jnp.sum(pow_from_abs(blk, p_blk), axis=1)
        bb = jnp.sum(ublk if base_p == 1.0 else ublk * ublk, axis=1)
        s = jnp.where(alive, s + bs, s)
        sbase = jnp.where(alive, sbase + bb, sbase)
        nd = nd + jnp.where(alive, min(block_d, d - b * block_d), 0)
        dead = s * deflate > thr
        d_rem = d - (b + 1) * block_d
        if d_rem > 0:
            rem = lp_suffix_bound(sb - sbase, base_p, p_row,
                                  float(d_rem))
            dead = dead | ((s + rem) * deflate > thr)
        alive = alive & ~dead
    return alive, nd
