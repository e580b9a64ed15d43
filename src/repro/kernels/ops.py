"""Jit'd dispatching wrappers over the Pallas Lp distance kernels.

Responsibilities:
  * VMEM-aware tile-size selection (the BlockSpec working set must fit VMEM);
  * padding arbitrary (B, N, C) up to tile multiples and slicing the result;
  * operand layout for Mosaic: per-row p and thresholds go in as
    pre-padded (B, 1) f32 columns the kernels block into SMEM; candidate
    ids as (B, C) int32, -1-padded; the compiled gather family reads its
    row source (corpus, band codes) in `kernel_rows` layout — the feature
    axis zero-padded to a lane multiple once, by the index that owns it —
    and pads only the queries to match;
  * interpret-mode fallback on non-TPU backends (tests run on the CPU with
    the kernel bodies in interpret mode or the jnp reference; on a TPU the
    same code lowers to Mosaic);
  * `lp_gather_distance` — the single entry point for exact-Lp candidate
    scoring in the query path (verify_candidates, delta scans). On TPU it
    runs the fused gather+distance kernel (rows gathered tile-by-tile in
    VMEM, no (B, C, d) HBM intermediate); off-TPU it falls back to the
    plain jnp reference, which XLA:CPU handles better than an interpreted
    per-row DMA loop;
  * scalar-vs-vector p (DESIGN.md §6): every wrapper takes p as a Python
    float (compile-time per-p specialization) or a (B,) array (one traced
    program serves a mixed-p batch, each row bit-identical to its scalar
    specialization). Scalar p stays on the original static-argname jits,
    so existing per-p callers compile exactly as before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lp_ops import is_static_p, lp_root
from repro.core.metrics import rowwise_lp
from repro.kernels import lp_distance as _k

# VMEM budget we allow a single kernel instance to claim (bytes). v5e has
# ~16 MiB per core; leave room for double-buffering of input tiles.
_VMEM_BUDGET = 6 * 1024 * 1024
_LANE = 128  # TPU lane width: last-dim tiles should be multiples of this


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_tiles_pairwise(b: int, n: int, d: int) -> tuple[int, int]:
    """Choose (TB, TN). Working set ~ 4*(TB*d + 2*TN*d + TB*TN) bytes."""
    # Start from the preferred MXU-aligned tiles and shrink TN for large d.
    tb = min(128, _round_up(b, 8))
    tn = 512
    while tn > _LANE and 4 * (tb * d + 2 * tn * d + tb * tn) > _VMEM_BUDGET:
        tn //= 2
    while tb > 8 and 4 * (tb * d + 2 * tn * d + tb * tn) > _VMEM_BUDGET:
        tb //= 2
    return max(tb, 8), max(tn, _LANE)


def _pick_tiles_rowwise(b: int, c: int, d: int) -> tuple[int, int]:
    """Choose (TB, TC). Working set ~ 4*(TB*d + 2*TB*TC*d) bytes."""
    tb = min(8, _round_up(b, 1))
    tc = min(512, _round_up(c, _LANE))
    while tc > _LANE and 4 * (tb * d + 2 * tb * tc * d) > _VMEM_BUDGET:
        tc //= 2
    while tb > 1 and 4 * (tb * d + 2 * tb * tc * d) > _VMEM_BUDGET:
        tb //= 2
    return max(tb, 1), max(tc, _LANE)


def _pad_axis(a: jax.Array, axis: int, to: int, fill: float) -> jax.Array:
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=fill)


def _pad_p_col(p: jax.Array, to: int) -> jax.Array:
    """(B,) per-row p -> pre-padded (to, 1) f32 kernel operand (SMEM).

    Padding rows get p=1.0 — the cheapest family; their outputs are sliced
    off, so any valid p would do.
    """
    p = jnp.asarray(p, dtype=jnp.float32).reshape(-1)
    return _pad_axis(p, 0, to, 1.0)[:, None]


def _p_rows(p, b: int):
    """Scalar p stays a Python float; vector p broadcasts to (B,) — a (1,)
    array means "one p for every row"."""
    return p if is_static_p(p) else jnp.broadcast_to(p, (b,))


def _p_operand(p, bp: int):
    """Kernel p operand: the static float, or the padded (bp, 1) column."""
    return p if is_static_p(p) else _pad_p_col(p, bp)


def _root_rows(out: jax.Array, p, root: bool) -> jax.Array:
    """Apply the Lp root to a (B, ...) result under scalar or per-row p."""
    if not root:
        return out
    return lp_root(out, p if is_static_p(p) else p[:, None])


def kernel_rows(x: jax.Array) -> jax.Array:
    """x (n, d) in the row layout the compiled gather-family kernels read.

    Mosaic DMAs whole 128-lane rows, so on a TPU a feature width that is
    not a lane multiple is zero-padded to one (zero columns are
    distance-neutral: |0 - 0|^p = 0). Owners of a row source (the index's
    corpus, the compressed band's codes, the delta buffer) call this once
    when they place it on the device; the kernel wrappers never pad a row
    source per call. Off the TPU, or when d is already a lane multiple,
    x is returned as is (no copy).
    """
    if not _on_tpu() or x.shape[-1] % _LANE == 0:
        return x
    return _pad_axis(x, x.ndim - 1, _round_up(x.shape[-1], _LANE), 0)


def _match_rows(q: jax.Array, x: jax.Array, interpret: bool):
    """Zero-pad queries (B, d) to the width of a `kernel_rows` row source
    (n, dx >= d). The compiled kernels need dx % 128 == 0: a row source
    that is not in `kernel_rows` layout is refused rather than copied."""
    dx = x.shape[-1]
    if not interpret and dx % _LANE:
        raise ValueError(
            f"compiled gather kernels need a lane-aligned row source, got "
            f"width {dx}; place it with kernels.ops.kernel_rows first")
    return _pad_axis(q, 1, dx, 0.0)


def _pairwise_impl(q, x, p, root=True, interpret=None, block_b=None,
                   block_n=None):
    if interpret is None:
        interpret = not _on_tpu()
    b, d = q.shape
    n, _ = x.shape
    p = _p_rows(p, b)
    tb, tn = _pick_tiles_pairwise(b, n, d)
    tb, tn = block_b or tb, block_n or tn
    bp, np_ = _round_up(b, tb), _round_up(n, tn)
    qp = _pad_axis(q, 0, bp, 0.0)
    xp = _pad_axis(x, 0, np_, 0.0)
    # root applied *outside* the kernel (like the gather entry point): the
    # in-kernel static-p root const-folds its division while a traced-p
    # kernel divides at runtime — rooting on the (B, N) result with the
    # barriered lp_root keeps static-p and vector-p wrappers bit-consistent.
    out = _k.pairwise_lp_kernel_call(
        qp, xp, _p_operand(p, bp), root=False, block_b=tb, block_n=tn,
        interpret=interpret,
    )[:b, :n]
    return _root_rows(out, p, root)


_PW_STATIC = ("root", "interpret", "block_b", "block_n")
_pallas_pairwise_lp_s = jax.jit(_pairwise_impl,
                                static_argnames=("p",) + _PW_STATIC)
_pallas_pairwise_lp_v = jax.jit(_pairwise_impl, static_argnames=_PW_STATIC)


def _dispatch(fn_s, fn_v, *args, p, **kw):
    """Call the static-p jit with float(p), or the traced-p jit with a
    (B,)/(1,) f32 array — the scalar-vs-vector contract (DESIGN.md §6)."""
    if is_static_p(p):
        return fn_s(*args, p=float(p), **kw)
    return fn_v(*args, p=jnp.atleast_1d(jnp.asarray(p, jnp.float32)), **kw)


def pallas_pairwise_lp(
    q: jax.Array,
    x: jax.Array,
    p,
    root: bool = True,
    interpret: bool | None = None,
    block_b: int | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """Pairwise Lp distances (B, d) x (N, d) -> (B, N) via the Pallas kernel.

    p: Python float (per-p compiled program) or a (B,) array scoring each
    query row under its own metric (one compiled program for any p mix —
    DESIGN.md §6).
    """
    return _dispatch(_pallas_pairwise_lp_s, _pallas_pairwise_lp_v, q, x,
                     p=p, root=root, interpret=interpret, block_b=block_b,
                     block_n=block_n)


def _rowwise_impl(q, c, p, root=True, interpret=None, block_b=None,
                  block_c=None):
    if interpret is None:
        interpret = not _on_tpu()
    b, d = q.shape
    _, cc, _ = c.shape
    p = _p_rows(p, b)
    tb, tc = _pick_tiles_rowwise(b, cc, d)
    tb, tc = block_b or tb, block_c or tc
    bp, cp = _round_up(b, tb), _round_up(cc, tc)
    qp = _pad_axis(q, 0, bp, 0.0)
    cpad = _pad_axis(_pad_axis(c, 1, cp, 0.0), 0, bp, 0.0)
    # root outside the kernel — see _pairwise_impl for why
    out = _k.rowwise_lp_kernel_call(
        qp, cpad, _p_operand(p, bp), root=False, block_b=tb, block_c=tc,
        interpret=interpret,
    )[:b, :cc]
    return _root_rows(out, p, root)


_RW_STATIC = ("root", "interpret", "block_b", "block_c")
_pallas_rowwise_lp_s = jax.jit(_rowwise_impl,
                               static_argnames=("p",) + _RW_STATIC)
_pallas_rowwise_lp_v = jax.jit(_rowwise_impl, static_argnames=_RW_STATIC)


def pallas_rowwise_lp(
    q: jax.Array,
    c: jax.Array,
    p,
    root: bool = True,
    interpret: bool | None = None,
    block_b: int | None = None,
    block_c: int | None = None,
) -> jax.Array:
    """Rowwise Lp distances (B, d) x (B, C, d) -> (B, C) via the Pallas kernel.

    p: Python float (per-p compiled program) or a (B,) array scoring each
    query row under its own metric (one compiled program for any p mix —
    DESIGN.md §6).
    """
    return _dispatch(_pallas_rowwise_lp_s, _pallas_rowwise_lp_v, q, c,
                     p=p, root=root, interpret=interpret, block_b=block_b,
                     block_c=block_c)


def lp_pairwise_distance(
    q: jax.Array,    # (B, d) f32
    x: jax.Array,    # (N, d) f32
    p,
    root: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Backend-aware pairwise Lp dispatch -> (B, N) f32.

    The all-pairs sibling of `lp_gather_distance` (same dispatch contract):
    on TPU the tiled Pallas pairwise kernel, off-TPU the jnp reference —
    which XLA:CPU compiles far better than an interpreted kernel body. Used
    by the bulk graph builder's chunked scoring passes (DESIGN.md §7);
    `interpret=True` forces the kernel in interpret mode for parity tests.

    p follows the scalar-vs-vector contract (DESIGN.md §6): a Python float
    or a (B,) array scoring each query row under its own metric.
    """
    if interpret is None and not _on_tpu():
        from repro.core.metrics import pairwise_lp

        return pairwise_lp(q, x, p, root=root)
    return pallas_pairwise_lp(q, x, p, root=root, interpret=interpret)


def _pick_tiles_gather(b: int, c: int, d: int) -> tuple[int, int]:
    """Choose (TB, TC) for the gather kernel.

    VMEM working set ~ 4*(TB*d + TB*TC + TC*d + TB*TC) bytes: the q tile,
    the ids tile, the (TC, d) gathered-row scratch, and the out tile — X
    itself stays in HBM, so d no longer multiplies TC*TB (the row-tile
    staging buffers add a fixed 2*8*d f32 words). TB stays a multiple of
    the 8-wide sublane (like the other pickers) so the tile refs lower
    cleanly on TPU.
    """
    tb = min(8, _round_up(b, 8))
    # tc is a power-of-two multiple of _LANE (128/256/512) so the halving
    # below can never leave the lane-aligned grid (e.g. 384 -> 192 would)
    tc = _LANE
    while tc < min(512, c):
        tc *= 2
    while tc > _LANE and 4 * (tb * d + tc * d + 2 * tb * tc) > _VMEM_BUDGET:
        tc //= 2
    return max(tb, 8), max(tc, _LANE)


def _gather_operands(q, ids, p, thresh, sb, tb: int, tc: int):
    """Pad the gather family's per-query operands to (TB, TC) multiples:
    q rows with zeros, ids with -1 (padding scores +inf), p via
    `_p_operand`, thresholds with -inf (padding rows die at entry, so the
    kernel skips their DMA gathers) and base sums with 0."""
    b, _ = q.shape
    _, cc = ids.shape
    bp, cp = _round_up(b, tb), _round_up(cc, tc)
    ops_ = [
        _pad_axis(q, 0, bp, 0.0),
        jnp.pad(ids.astype(jnp.int32), ((0, bp - b), (0, cp - cc)),
                constant_values=-1),
        _p_operand(p, bp),
    ]
    if thresh is not None:
        ops_.append(_pad_axis(thresh.astype(jnp.float32), 0, bp,
                              -jnp.inf)[:, None])
        ops_.append(_pad_axis(_pad_axis(sb.astype(jnp.float32), 1, cp, 0.0),
                              0, bp, 0.0))
    return ops_


def gather_rowwise_lp(q, ids, x, p, root: bool = False) -> jax.Array:
    """XLA gather of the candidate rows, then `rowwise_lp` -> (B, C) f32.

    The plain-XLA sibling of the fused gather kernel, with the same
    contract: ids (B, C) outside [0, x.shape[0]) are padding and score
    +inf; q (B, d) and x share one width. It materializes the gathered
    (B, C, d) block. `lp_gather_distance` runs it off the TPU, and the
    bulk graph builder scores its candidate blocks with it on every
    backend (DESIGN.md §7).
    """
    n = x.shape[0]
    p = _p_rows(p, q.shape[0])
    valid = (ids >= 0) & (ids < n)
    dd = rowwise_lp(q, x[jnp.clip(ids, 0, n - 1)], p, root=False)
    return _root_rows(jnp.where(valid, dd, jnp.inf), p, root)


def _gather_impl(q, ids, x, p, root=False, interpret=None, block_b=None,
                 block_c=None):
    n = x.shape[0]
    b, _ = q.shape
    p = _p_rows(p, b)
    if ids.ndim == 1:
        valid = (ids >= 0) & (ids < n)
        xs = x[jnp.clip(ids, 0, n - 1)]  # gathered once, shared by all rows
        dd = pallas_pairwise_lp(_pad_axis(q, 1, x.shape[1], 0.0), xs, p,
                                root=False, interpret=interpret)
        return _root_rows(jnp.where(valid[None, :], dd, jnp.inf), p, root)
    if interpret is None and not _on_tpu():
        return gather_rowwise_lp(q, ids, x, p, root)
    interpret = bool(interpret)
    q = _match_rows(q, x, interpret)
    _, cc = ids.shape
    tb, tc = _pick_tiles_gather(b, cc, q.shape[1])
    tb, tc = block_b or tb, block_c or tc
    qp, ip, pp = _gather_operands(q, ids, p, None, None, tb, tc)
    # apply the root *outside* the kernel on the (B, C) result: for root=True
    # callers this keeps the kernel body identical across root modes.
    out = _k.gather_lp_kernel_call(
        ip, qp, x, pp, root=False, block_b=tb, block_c=tc,
        interpret=interpret,
    )[:b, :cc]
    return _root_rows(out, p, root)


_G_STATIC = ("root", "interpret", "block_b", "block_c")
_lp_gather_distance_s = jax.jit(_gather_impl,
                                static_argnames=("p",) + _G_STATIC)
_lp_gather_distance_v = jax.jit(_gather_impl, static_argnames=_G_STATIC)


def pick_abandon_block_d(d: int) -> int:
    """Dimension-block width for the early-abandoning scan (DESIGN.md §8).

    32 dims = 4 native (8, 128) f32 vregs per block in the transposed
    (d, TC) layout — enough compute per block to amortize the per-block
    alive-mask branch, fine enough that a junk candidate dies after a
    small fraction of d. Falls back to 16/8 (sublane granularity floor)
    when they divide d, else a single full-width block: entry-bound-only
    abandonment, zero mid-scan checks.
    """
    for bd in (32, 16, 8):
        if d % bd == 0:
            return bd
    return d


def _pick_tiles_abandon(b: int, c: int, d: int) -> tuple[int, int]:
    """Choose (TB, TC) for the abandon and screen kernels.

    Like `_pick_tiles_gather` plus the transposed (d, TC) diff tile the
    blocked scan keeps live: ~ 4*(TB*d + 2*TC*d + 3*TB*TC) bytes. The
    screen's gathered int8 band rows are widened into the same f32
    (TC, d) scratch, so one picker serves both.
    """
    tb = min(8, _round_up(b, 8))
    tc = _LANE
    while tc < min(512, c):
        tc *= 2
    while tc > _LANE and 4 * (tb * d + 2 * tc * d + 3 * tb * tc) > _VMEM_BUDGET:
        tc //= 2
    return max(tb, 8), max(tc, _LANE)


def _abandon_impl(q, ids, x, thresh, sb, p, base_p, root=False,
                  interpret=None, block_b=None, block_c=None, block_d=None):
    b, d = q.shape
    p = _p_rows(p, b)
    bd = block_d or pick_abandon_block_d(d)
    if interpret is None and not _on_tpu():
        from repro.kernels.ref import gather_lp_abandon_ref

        out, nd = gather_lp_abandon_ref(q, ids, x, thresh, sb, p, base_p, bd)
        return _root_rows(out, p, root), nd
    interpret = bool(interpret)
    q = _match_rows(q, x, interpret)
    _, cc = ids.shape
    tb, tc = _pick_tiles_abandon(b, cc, q.shape[1])
    tb, tc = block_b or tb, block_c or tc
    qp, ip, pp, tp, sp = _gather_operands(q, ids, p, thresh, sb, tb, tc)
    out, nd = _k.gather_lp_abandon_kernel_call(
        ip, qp, tp, sp, x, pp, d=d, base_p=base_p, block_b=tb, block_c=tc,
        block_d=bd, interpret=interpret,
    )
    return _root_rows(out[:b, :cc], p, root), nd[:b, :cc]


_A_STATIC = ("base_p", "root", "interpret", "block_b", "block_c", "block_d")
_lp_gather_abandon_s = jax.jit(_abandon_impl,
                               static_argnames=("p",) + _A_STATIC)
_lp_gather_abandon_v = jax.jit(_abandon_impl, static_argnames=_A_STATIC)


def lp_gather_abandon(
    q: jax.Array,       # (B, d) f32 queries
    ids: jax.Array,     # (B, C) int32 candidate ids; out-of-range = padding
    x: jax.Array,       # (n, d) f32 dataset; `kernel_rows` layout on TPU
    thresh: jax.Array,  # (B,) per-query abandon bound (power-sum space;
                        # +inf = no abandonment, -inf = skip the whole row)
    sb: jax.Array,      # (B, C) base-metric power sums of the candidates
                        # (the beam's distances), or 0 to disable bounds
    p,
    base_p: float = 1.0,
    root: bool = False,
    interpret: bool | None = None,
    block_b: int | None = None,
    block_c: int | None = None,
    block_d: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Early-abandoning exact-Lp scoring (DESIGN.md §8) -> (dists, nd).

    The adaptive-T_p sibling of `lp_gather_distance`: per-query-row
    thresholds abandon candidates whose blocked partial power sum (or the
    base-distance entry/suffix lower bound, core/lp_ops) already exceeds
    the running k-th best — abandoned and padding candidates score +inf,
    which is exact for top-k purposes because a power sum only grows.
    `nd` (B, C) int32 counts the dimensions actually scanned per candidate
    (0 for entry-abandoned), the numerator of `SearchStats.n_dim_frac`.

    p follows the scalar-vs-vector contract (DESIGN.md §6); base_p (static
    1.0/2.0) names the metric of `sb`. Dispatch matches
    `lp_gather_distance`: fused Pallas kernel on TPU, the blocked jnp
    reference (kernels/ref.py — computes-then-masks, same `nd`
    accounting) off TPU, `interpret=True` for CPU kernel-parity tests.
    """
    return _dispatch(_lp_gather_abandon_s, _lp_gather_abandon_v,
                     q, ids, x, thresh, sb, p=p, base_p=float(base_p),
                     root=root, interpret=interpret, block_b=block_b,
                     block_c=block_c, block_d=block_d)


def _screen_impl(q, ids, codes, scale, radius, thresh, sb, p, base_p,
                 interpret=None, block_b=None, block_c=None, block_d=None):
    b, d = q.shape
    p = _p_rows(p, b)
    bd = block_d or pick_abandon_block_d(d)
    if interpret is None and not _on_tpu():
        from repro.kernels.ref import gather_lp_screen_ref

        return gather_lp_screen_ref(q, ids, codes, scale, radius, thresh,
                                    sb, p, base_p, bd)
    interpret = bool(interpret)
    q = _match_rows(q, codes, interpret)
    dx = q.shape[1]
    _, cc = ids.shape
    tb, tc = _pick_tiles_abandon(b, cc, dx)
    tb, tc = block_b or tb, block_c or tc
    qp, ip, pp, tp, sp = _gather_operands(q, ids, p, thresh, sb, tb, tc)
    # the (d,) scale and radius vectors are padded here: O(d), not O(n d)
    keep, nd = _k.gather_lp_screen_kernel_call(
        ip, qp, tp, sp, _pad_axis(scale, 0, dx, 0.0)[None, :],
        _pad_axis(radius, 0, dx, 0.0)[:, None], codes, pp, d=d,
        base_p=base_p, block_b=tb, block_c=tc, block_d=bd,
        interpret=interpret,
    )
    return keep[:b, :cc].astype(bool), nd[:b, :cc]


_S_STATIC = ("base_p", "interpret", "block_b", "block_c", "block_d")
_lp_gather_screen_s = jax.jit(_screen_impl, static_argnames=("p",) + _S_STATIC)
_lp_gather_screen_v = jax.jit(_screen_impl, static_argnames=_S_STATIC)


def lp_gather_screen(
    q: jax.Array,       # (B, d) f32 queries, band (permuted) coord order
    ids: jax.Array,     # (B, C) int32 candidate ids; out-of-range = padding
    codes: jax.Array,   # (n, d) int8 band codes (index/compressed.py),
                        # `kernel_rows` layout on TPU
    scale: jax.Array,   # (d,) f32 per-coordinate dequant scales
    radius: jax.Array,  # (d,) f32 per-coordinate max dequant error
    thresh: jax.Array,  # (B,) per-query screen bound (power-sum space;
                        # +inf = keep everything, -inf = screen out the row)
    sb: jax.Array,      # (B, C) base-metric power sums of the candidates
                        # (the beam's distances), or 0 to disable bounds
    p,
    base_p: float = 1.0,
    interpret: bool | None = None,
    block_b: int | None = None,
    block_c: int | None = None,
    block_d: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Compressed-band candidate screen (DESIGN.md §10) -> (keep, nd).

    The storage-side sibling of `lp_gather_abandon`: per-query thresholds
    kill candidates whose *certified lower bound* — the blocked power sum
    of max(|q_j - x̂_j| - radius_j, 0) over int8 band rows, deflated by
    BOUND_SLACK — already exceeds the running k-th best. `keep` (B, C)
    bool marks the survivors whose f32 rows the exact rerank must gather
    (padding never survives); `nd` (B, C) int32 counts band dimensions
    scanned (the int8 byte-traffic numerator of `SearchStats.n_band_frac`).

    q must be in the band's coordinate order (Q[:, band.perm]). p follows
    the scalar-vs-vector contract (DESIGN.md §6); base_p (static 1.0/2.0)
    names the metric of `sb`. Dispatch matches `lp_gather_abandon`: fused
    Pallas kernel on TPU, the blocked jnp reference (kernels/ref.py) off
    TPU, `interpret=True` for CPU kernel-parity tests.
    """
    return _dispatch(_lp_gather_screen_s, _lp_gather_screen_v,
                     q, ids, codes, scale, radius, thresh, sb, p=p,
                     base_p=float(base_p), interpret=interpret,
                     block_b=block_b, block_c=block_c, block_d=block_d)


def lp_gather_distance(
    q: jax.Array,    # (B, d) f32 queries
    ids: jax.Array,  # (B, C) int32 candidate ids; anything outside [0, n) is
                     # padding (-1 from merges, n from beam sentinels)
    x: jax.Array,    # (n, d) f32 dataset; `kernel_rows` layout on TPU
    p,
    root: bool = False,
    interpret: bool | None = None,
    block_b: int | None = None,
    block_c: int | None = None,
) -> jax.Array:
    """Exact-Lp distances for per-query candidate id blocks -> (B, C) f32.

    THE dispatch entry point for all exact-Lp scoring in the query path
    (DESIGN.md §2 "hot path"). Padding ids score +inf so they can never
    enter a result set.

    `p` — the scalar-vs-vector contract (DESIGN.md §6):

      * Python float — one compiled program per distinct p (the classic
        grouped-serving path);
      * (B,) array (f32) — row i is scored under p[i]; ONE compiled
        program serves any mix of p values, and each row's result is
        bit-identical to the scalar-p call with p = p[i] on the same path
        (the per-row op-sequence selection in core/lp_ops guarantees it).

    `interpret`:

      * None (default) — backend-aware: fused Pallas kernel on TPU, jnp
        reference (gather + rowwise powers) elsewhere;
      * True  — force the Pallas kernel in interpret mode (kernel-parity
        tests on CPU);
      * False — force the compiled Pallas kernel.

    ids may also be 1-D (C,): "every query scores the same candidate
    rows" (the delta-scan shape). That routes to the pairwise kernel on a
    once-gathered (C, d) block — no per-query re-gather, and p=2 keeps
    its MXU matmul — instead of broadcasting the id row B times.
    """
    return _dispatch(_lp_gather_distance_s, _lp_gather_distance_v,
                     q, ids, x, p=p, root=root, interpret=interpret,
                     block_b=block_b, block_c=block_c)
