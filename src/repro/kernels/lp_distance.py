"""Pallas TPU kernels for batched Q2D Lp distance (the paper's hot spot).

Hardware mapping (see DESIGN.md §2):

  * p = 2   — the MXU path. Inside each (TB, TN) output tile we compute
              ||q-x||^2 = ||q||^2 + ||x||^2 - 2 q @ x^T with a single VMEM-
              resident matmul (`jnp.dot` lowers onto the 128x128 systolic
              array). This is the TPU analogue of the paper's AVX-512 L2.
  * p = 1, 0.5, 1.5 — the VPU fast family: abs/add (+sqrt for the fractional
              pair), full-rate elementwise over a (TN, d) diff tile per query
              row, looped over the TB query rows with `lax.fori_loop` so the
              VMEM working set stays one diff-tile wide.
  * other p — the slow family: |d|^p = exp(p * log |d|) costs two
              transcendentals per element; same loop structure.

Tiling: grid is (B/TB, N/TN). Per grid step the kernel holds
  q tile (TB, d) + x tile (TN, d) + one (TN, d) diff scratch + out (TB, TN)
in VMEM; ops.py picks TB/TN so this fits the ~16 MiB v5e VMEM with headroom.
The query tile is reused across the whole row of candidate tiles (index_map
pins it per-i), amortizing its HBM read N/TN times — the VMEM analogue of
the paper keeping the query vector L1-cache-resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The per-p op-sequence table is shared with the jnp reference metrics
# (repro.core.lp_ops) so kernel and oracle cannot drift.
from repro.core.lp_ops import abs_pow as _abs_pow
from repro.core.lp_ops import (
    BOUND_SLACK,
    is_static_p,
    lp_entry_bound,
    lp_suffix_bound,
    pow_from_abs,
)
# Kernel bodies use the fold-friendly root: no optimization_barrier inside
# Mosaic-lowered code (traced per-row p takes runtime division regardless).
from repro.core.lp_ops import lp_root_folded as _root

# Every kernel here takes p either as a Python float (per-p compile-time
# specialization — the classic path) or as a per-query-row array (the
# mixed-p serving path, DESIGN.md §6). Vector p reaches the kernel as a
# pre-padded (B, 1) f32 operand blocked (TB, 1) into SMEM; the body reads one
# scalar per query row and the shared op-sequence table's where-select
# reproduces each row's scalar op sequence bit-for-bit (rows with p == 2
# additionally take the same MXU matmul-identity branch the scalar p=2
# kernel uses). The gather/rowwise vector-p kernels share
# `_row_dist_block` so the parity-critical op sequence cannot drift
# between entry points.
#
# Mosaic lowering rules the bodies follow: a traced index only ever
# indexes a *ref* (`q_ref[i, :]`, `dt_ref[pl.ds(off, bd), :]`), never a
# loaded value (that would be a `dynamic_slice`, which Pallas TPU cannot
# lower); per-row scalars (p, thresholds) and candidate ids used as DMA
# addresses live in SMEM and are read as scalars. The ids also ride a
# second, VMEM copy for the vectorized padding mask.


def _smem_rows(block_b: int) -> pl.BlockSpec:
    """(TB, 1) per-query-row scalars (p, thresholds) blocked into SMEM."""
    return pl.BlockSpec((block_b, 1), lambda i, j: (i, 0),
                        memory_space=pltpu.SMEM)


def _smem_ids(block_b: int, block_c: int) -> pl.BlockSpec:
    """(TB, TC) candidate ids blocked into SMEM: the gather's DMA
    addresses, read one scalar per row copy."""
    return pl.BlockSpec((block_b, block_c), lambda i, j: (i, j),
                        memory_space=pltpu.SMEM)


def _mxu_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32-exact MXU product for the p=2 identity (multi-pass on the chip;
    a one-pass bf16 product would cost ~3 significant digits)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _row_dist_block(qi: jax.Array, c: jax.Array, p) -> jax.Array:
    """One query row vs a (TC, d) candidate tile -> (TC,) power sums.

    Static p emits that p's op sequence only (p == 2: the MXU matmul
    identity with its cancellation clamp). Traced per-row p scores every
    family with the elementwise table and rows with p == 2 take the MXU
    identity value instead — the same expression the static p=2 path
    emits, so each row is bit-identical to its scalar specialization.
    """
    if is_static_p(p) and p != 2.0:
        return jnp.sum(_abs_pow(c - qi[None, :], p), axis=-1)
    s2 = jnp.sum(qi * qi) + jnp.sum(c * c, axis=-1) - 2.0 * _mxu_dot(c, qi)
    s2 = jnp.maximum(s2, 0.0)
    if is_static_p(p):
        return s2
    s = jnp.sum(_abs_pow(c - qi[None, :], p), axis=-1)
    return jnp.where(p == 2.0, s2, s)


# ---------------------------------------------------------------------------
# pairwise kernel: Q (B, d) x X (N, d) -> (B, N)
# ---------------------------------------------------------------------------


def _pairwise_l2_kernel(q_ref, x_ref, o_ref, *, root: bool):
    """MXU path: one matmul per output tile."""
    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    qq = jnp.sum(q * q, axis=-1)[:, None]
    xx = jnp.sum(x * x, axis=-1)[None, :]
    s = qq + xx - 2.0 * _mxu_dot(q, x.T)
    s = jnp.maximum(s, 0.0)
    o_ref[...] = (jnp.sqrt(s) if root else s).astype(o_ref.dtype)


def _pairwise_vpu_kernel(q_ref, x_ref, o_ref, *, p: float, root: bool):
    """VPU path: loop over query rows; one (TN, d) diff tile live at a time."""
    x = x_ref[...].astype(jnp.float32)
    tb = q_ref.shape[0]

    def body(i, _):
        qi = q_ref[i, :].astype(jnp.float32)
        s = jnp.sum(_abs_pow(x - qi[None, :], p), axis=-1)
        o_ref[i, :] = (_root(s, p) if root else s).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, body, 0)


def _pairwise_vec_kernel(p_ref, q_ref, x_ref, o_ref, s2_ref, *, root: bool):
    """Mixed-p path: per-row traced p; p==2 rows take the MXU identity.

    The identity term is hoisted as one (TB, TN) matmul — the same shape
    the scalar `_pairwise_l2_kernel` emits, so p==2 rows are bit-identical
    to the scalar p=2 kernel. It is parked in a VMEM scratch so the row
    loop reads it by ref (Mosaic cannot index a loaded value with a traced
    row). (The fast/slow VPU families match the scalar VPU kernel's op
    sequences exactly; XLA's fusion choices can still reassociate the
    d-axis sum by 1-2 ulp on non-lane-aligned tile shapes for p=1.5 —
    pinned with an explicit ulp tolerance in
    tests/test_kernels.py::test_pairwise_vector_p_vs_scalar_ulp_pinned —
    so only the gather/rowwise entry points — the serving hot path — carry
    the hard bit-parity contract.)
    """
    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    qq = jnp.sum(q * q, axis=-1)
    xx = jnp.sum(x * x, axis=-1)
    s2 = qq[:, None] + xx[None, :] - 2.0 * _mxu_dot(q, x.T)
    s2_ref[...] = jnp.maximum(s2, 0.0)
    tb = q.shape[0]

    def body(i, _):
        pi = p_ref[i, 0]
        qi = q_ref[i, :].astype(jnp.float32)
        s = jnp.sum(_abs_pow(x - qi[None, :], pi), axis=-1)
        s = jnp.where(pi == 2.0, s2_ref[i, :], s)
        o_ref[i, :] = (_root(s, pi) if root else s).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, body, 0)


def pairwise_lp_kernel_call(
    q: jax.Array,
    x: jax.Array,
    p,
    *,
    root: bool = True,
    block_b: int = 128,
    block_n: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Raw pallas_call for pre-padded inputs (B % block_b == N % block_n == 0).

    p: Python float, or a pre-padded (B, 1) f32 array (one metric per query
    row — the mixed-p contract described in the module preamble).
    """
    b, d = q.shape
    n, _ = x.shape
    assert b % block_b == 0 and n % block_n == 0, (b, n, block_b, block_n)

    if not is_static_p(p):
        assert p.shape == (b, 1), (p.shape, b)
        return pl.pallas_call(
            functools.partial(_pairwise_vec_kernel, root=root),
            grid=(b // block_b, n // block_n),
            in_specs=[
                _smem_rows(block_b),
                pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
                pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.float32)],
            interpret=interpret,
        )(p, q, x)

    if p == 2.0:
        kernel = functools.partial(_pairwise_l2_kernel, root=root)
    else:
        kernel = functools.partial(_pairwise_vpu_kernel, p=p, root=root)

    return pl.pallas_call(
        kernel,
        grid=(b // block_b, n // block_n),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), out_dtype),
        interpret=interpret,
    )(q, x)


# ---------------------------------------------------------------------------
# rowwise kernel: Q (B, d) x C (B, C, d) -> (B, C)
# (the verification-step shape: per-query gathered candidate blocks)
# ---------------------------------------------------------------------------


def _rowwise_l2_kernel(q_ref, c_ref, o_ref, *, root: bool):
    tb = q_ref.shape[0]

    def body(i, _):
        c = c_ref[i, :, :].astype(jnp.float32)  # (TC, d)
        qi = q_ref[i, :].astype(jnp.float32)
        s = jnp.sum(qi * qi) + jnp.sum(c * c, axis=-1) - 2.0 * _mxu_dot(c, qi)
        s = jnp.maximum(s, 0.0)
        o_ref[i, :] = (jnp.sqrt(s) if root else s).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, body, 0)


def _rowwise_vpu_kernel(q_ref, c_ref, o_ref, *, p: float, root: bool):
    tb = q_ref.shape[0]

    def body(i, _):
        qi = q_ref[i, :].astype(jnp.float32)
        c = c_ref[i, :, :].astype(jnp.float32)
        s = jnp.sum(_abs_pow(c - qi[None, :], p), axis=-1)
        o_ref[i, :] = (_root(s, p) if root else s).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, body, 0)


def _rowwise_vec_kernel(p_ref, q_ref, c_ref, o_ref, *, root: bool):
    """Mixed-p path: per-row traced p; p==2 rows take the MXU identity."""
    tb = q_ref.shape[0]

    def body(i, _):
        pi = p_ref[i, 0]
        qi = q_ref[i, :].astype(jnp.float32)
        c = c_ref[i, :, :].astype(jnp.float32)
        s = _row_dist_block(qi, c, pi)
        o_ref[i, :] = (_root(s, pi) if root else s).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, body, 0)


def rowwise_lp_kernel_call(
    q: jax.Array,
    c: jax.Array,
    p,
    *,
    root: bool = True,
    block_b: int = 8,
    block_c: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Raw pallas_call for pre-padded inputs (B % block_b == C % block_c == 0).

    p: Python float, or a pre-padded (B, 1) f32 array (one metric per query
    row — the mixed-p contract described in the module preamble).
    """
    b, d = q.shape
    b2, cc, _ = c.shape
    assert b == b2 and b % block_b == 0 and cc % block_c == 0

    if not is_static_p(p):
        assert p.shape == (b, 1), (p.shape, b)
        return pl.pallas_call(
            functools.partial(_rowwise_vec_kernel, root=root),
            grid=(b // block_b, cc // block_c),
            in_specs=[
                _smem_rows(block_b),
                pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
                pl.BlockSpec((block_b, block_c, d), lambda i, j: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((block_b, block_c), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b, cc), out_dtype),
            interpret=interpret,
        )(p, q, c)

    if p == 2.0:
        kernel = functools.partial(_rowwise_l2_kernel, root=root)
    else:
        kernel = functools.partial(_rowwise_vpu_kernel, p=p, root=root)

    return pl.pallas_call(
        kernel,
        grid=(b // block_b, cc // block_c),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, block_c, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, cc), out_dtype),
        interpret=interpret,
    )(q, c)


# ---------------------------------------------------------------------------
# fused gather + distance kernel: ids (B, C) + X (n, d) -> dists (B, C)
#
# The verification hot path (core/uhnsw.verify_candidates) scores per-query
# candidate id blocks against the frozen dataset. The un-fused route is
# X[ids] -> (B, C, d) in HBM, then the rowwise kernel — i.e. every gathered
# row makes an HBM round trip before it is read once. Here the gather happens
# *inside* the kernel: X stays HBM-resident (memory_space=ANY), and each
# (TB, TC) output tile DMAs its candidate rows one by one into a (TC, d)
# VMEM scratch, then runs one vectorized distance block over the scratch
# (MXU dot for p=2, VPU elementwise otherwise). The (B, C, d) intermediate
# never exists.
#
# Row DMAs follow X's HBM tiling: a DMA may only move whole layout tiles of
# rows (8 rows for 32-bit data, 32 for int8) and whole 128-lane rows, so
# each candidate fetches the aligned row tile that holds it into a small
# staging buffer and copies its one row out of VMEM; the compiled-path
# callers (kernels/ops.py) pad d to a lane multiple. Rows past the last
# whole tile (n % tile rows of them) ride a small f32 `tail` operand.
#
# Ids outside [0, n) are padding sentinels (-1 from merges, n from beams),
# and the wrappers pad the candidate axis past the caller's C to whole TC
# blocks: neither kind of slot copies a row, and both score +inf, so
# callers can pass padded id blocks straight through.
# ---------------------------------------------------------------------------


def _tile_rows(dtype) -> int:
    """Rows per HBM layout tile of a (n, d) array: the row granularity of
    a DMA out of it (8 for 32-bit, 32 for int8)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _row_source(x: jax.Array):
    """(in_specs, operands, scratch) of the tile-aligned row gather from
    x (n, d): x stays in HBM; its last n % tile-rows rows ride a padded f32
    `tail` block in VMEM; scratch is the x-dtype staging tile plus its f32
    widening (row picks index 32-bit refs only)."""
    n, d = x.shape
    rows = _tile_rows(x.dtype)
    n_al = n - n % rows
    tail = jnp.zeros((rows, d), jnp.float32)
    tail = tail.at[:n - n_al].set(x[n_al:].astype(jnp.float32))
    specs = [pl.BlockSpec(memory_space=pl.ANY),   # X stays in HBM
             pl.BlockSpec(memory_space=pltpu.VMEM)]  # tail, fetched once
    scratch = [pltpu.VMEM((rows, d), x.dtype), pltpu.VMEM((rows, d), jnp.float32)]
    return specs, (x, tail), scratch


def _slot_count(block_c: int, c: int):
    """This grid step's share of the call's `c` logical candidates:
    min(TC, c - j * TC) at candidate block j, a static c where one block
    holds them all. Read at the kernel's top level (interpret mode binds
    `program_id` only there)."""
    if c <= block_c:
        return c
    return jnp.minimum(block_c, c - pl.program_id(1) * block_c)


def _dma_gather_rows(ids_ref, i, x_hbm, tail_ref, stage_ref, wide_ref,
                     gx_ref, sem, *, n: int, slots):
    """Copy the candidate rows of query row i that the kernel scores into
    the (TC, d) f32 scratch `gx_ref`.

    The loop visits the first `slots` id columns (`_slot_count`), not the
    wrapper's padding past the call's candidates. Each slot reads its id as a
    scalar from the SMEM id block and copies a row only for an id in
    [0, n): the row's aligned HBM tile is DMA'd into `stage_ref` and the
    row picked out by ref, or a row of the ragged last tile comes from
    `tail_ref`. DMAs issue one at a time (start, then wait). A slot that
    copies nothing (a sentinel id, or a padding slot past c) keeps
    whatever its scratch row held; every kernel masks that lane's output
    (+inf, nd 0) and reduces only along a lane's own row, so a stale row
    never reaches a scored lane. Shared by the gather, abandon and screen
    kernels.
    """
    rows = stage_ref.shape[0]
    n_al = n - n % rows
    widen = stage_ref.dtype != jnp.float32
    pick_ref = wide_ref if widen else stage_ref

    def gather(j, _):
        idx = ids_ref[i, j]
        if n_al:
            @pl.when((idx >= 0) & (idx < n_al))
            def _():
                base = pl.multiple_of(idx // rows * rows, rows)
                cp = pltpu.make_async_copy(
                    x_hbm.at[pl.ds(base, rows), :], stage_ref, sem)
                cp.start()
                cp.wait()
                if widen:
                    wide_ref[...] = stage_ref[...].astype(jnp.float32)
                gx_ref[pl.ds(j, 1), :] = pick_ref[pl.ds(idx - base, 1), :]
        if n_al < n:
            @pl.when((idx >= n_al) & (idx < n))
            def _():
                gx_ref[pl.ds(j, 1), :] = tail_ref[pl.ds(idx - n_al, 1), :]
        return 0

    jax.lax.fori_loop(0, slots, gather, 0)


def _split_p(refs, p):
    """Kernel operand lists carry an SMEM (TB, 1) p block right after the
    query tile iff p is traced (p is None); scalar p is a static float."""
    if p is not None:
        return refs, lambda i: p
    head, p_ref, tail = refs[:3], refs[3], refs[4:]
    return head + tail, lambda i: p_ref[i, 0]


def _gather_lp_kernel(*refs, p, root: bool, n: int, block_c: int, c: int):
    """One (TB, TC) output tile.

    Per query row: a copy of each candidate row (HBM -> VMEM scratch), then
    one vectorized (TC, d) distance block. Scalar p (a static float) emits
    that p's op sequence only; traced p (p=None, per-row SMEM scalars)
    selects per row.
    """
    (ids_s, ids_v, q_ref, x_hbm, tail_ref, o_ref, stage_ref, wide_ref,
     gx_ref, sem), p_of = _split_p(refs, p)
    tb = q_ref.shape[0]
    slots = _slot_count(block_c, c)

    def per_query(i, _):
        _dma_gather_rows(ids_s, i, x_hbm, tail_ref, stage_ref, wide_ref,
                         gx_ref, sem, n=n, slots=slots)
        pi = p_of(i)
        qi = q_ref[i, :].astype(jnp.float32)
        s = _row_dist_block(qi, gx_ref[...], pi)
        val = _root(s, pi) if root else s
        ids_row = ids_v[i, :]
        ok = (ids_row >= 0) & (ids_row < n)
        o_ref[i, :] = jnp.where(ok, val, jnp.inf).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, per_query, 0)


def _gather_in_specs(block_b: int, block_c: int, d: int, vector_p: bool):
    """ids (SMEM + VMEM copies), query tile, [p] — the shared head of
    every gather-family kernel's operand list."""
    specs = [
        _smem_ids(block_b, block_c),
        pl.BlockSpec((block_b, block_c), lambda i, j: (i, j)),
        pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
    ]
    return specs + [_smem_rows(block_b)] if vector_p else specs


def gather_lp_kernel_call(
    ids: jax.Array,  # (B, C) int32 candidate ids; out-of-range = padding
    q: jax.Array,    # (B, d)
    x: jax.Array,    # (n, d) HBM-resident dataset
    p,
    *,
    root: bool = False,
    block_b: int = 8,
    block_c: int = 128,
    c: int,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Raw pallas_call for pre-padded inputs (B % block_b == C % block_c == 0;
    compiled for TPU, d % 128 == 0 — zero columns are distance-neutral).
    c: the caller's candidate count before its padding to C; id columns
    past it are padding, copy no row and score +inf.

    p: Python float, or a pre-padded (B, 1) f32 array (one metric per query
    row — the mixed-p contract described in the module preamble).
    """
    b, d = q.shape
    b2, cc = ids.shape
    n = x.shape[0]
    assert b == b2 and b % block_b == 0 and cc % block_c == 0, \
        (b, b2, cc, block_b, block_c)
    vector_p = not is_static_p(p)
    if vector_p:
        assert p.shape == (b, 1), (p.shape, b)
    src_specs, src_ops, src_scratch = _row_source(x)
    return pl.pallas_call(
        functools.partial(
            _gather_lp_kernel, p=None if vector_p else float(p), root=root,
            n=n, block_c=block_c, c=c,
        ),
        grid=(b // block_b, cc // block_c),
        in_specs=_gather_in_specs(block_b, block_c, d, vector_p) + src_specs,
        out_specs=pl.BlockSpec((block_b, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, cc), out_dtype),
        scratch_shapes=src_scratch + [
            pltpu.VMEM((block_c, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(ids, ids, q, *((p,) if vector_p else ()), *src_ops)


# ---------------------------------------------------------------------------
# early-abandoning gather + blocked-dimension distance kernel (DESIGN.md §8):
# ids (B, C) + thresholds (B, 1) + base sums (B, C) + X (n, d)
#   -> dists (B, C) power sums (+inf for abandoned), nd (B, C) scanned dims
#
# The adaptive-T_p hot path: root-free Lp power sums accumulate non-negative
# terms, so a candidate's partial sum over a prefix of dimension blocks is a
# monotone lower bound on its final distance — any candidate whose partial
# sum (or provable lower bound, core/lp_ops.lp_entry_bound/lp_suffix_bound)
# exceeds the per-query threshold is abandoned exactly, skipping all
# remaining blocks' transcendental work.
#
# Layout: the gathered (TC, d) rows minus the query are transposed ONCE
# into a (d, TC) VMEM scratch so dimension blocks are *sublane* slices
# (`pl.ds` on the scratch ref; granularity 8, block_d=32 default) while
# candidates occupy full 128-wide lanes — fine-grained abandonment checks
# without wasting lanes (a (TC, 32) lane-dim slice would run the VPU at
# 1/4 occupancy). Per block, `lax.cond` on the row's alive mask skips the
# transcendental family entirely once every candidate in the tile is dead;
# a row whose candidates are all dead at entry (threshold -inf = frozen
# query, or every entry bound beaten) skips its DMA gather too. The alive
# mask crosses loop and branch boundaries as int32 (Mosaic cannot carry
# i1 vectors through scf.if). `d` is the logical width: the scan runs
# ceil(d / block_d) blocks, and where block_d does not divide d the last
# block is ragged — its rows past d are the zero lane padding of the
# (dx, TC) scratch, which add 0 to every sum; lane padding past that last
# block is never scanned.
# ---------------------------------------------------------------------------


def _blocked_scan(i, ids_v, sb_ref, thr, pi, gather_tile, block_fn,
                  *, base_p: float, n: int, d: int, block_c: int,
                  block_d: int, deflate: float):
    """The alive-gated blocked scan shared by the abandon and screen
    kernels, for query row i. Returns (s, alive, nd) (TC,).

    `gather_tile()` fills the (d, TC) scratch for this row (skipped when
    every candidate is dead at entry); `block_fn(off)` returns the block
    at dimension offset `off` as (lower-bound terms, base-metric terms),
    both (block_d, TC) >= 0. A candidate dies when its deflated running
    sum, or that plus the suffix bound, exceeds `thr`. A ragged last block
    counts only its d % block_d real dimensions in `nd`, and the suffix
    bound counts the logical dimensions left, max(d - scanned, 0). Where
    block_d divides d, both reduce to the whole-block forms.
    """
    nb = -(-d // block_d)
    ragged = d % block_d != 0
    sb_row = sb_ref[i, :]
    ids_row = ids_v[i, :]
    valid = (ids_row >= 0) & (ids_row < n)
    alive0 = (valid & (lp_entry_bound(sb_row, base_p, pi, d) <= thr)
              ).astype(jnp.int32)
    zeros = jnp.zeros((block_c,), jnp.float32)
    nd0 = jnp.zeros((block_c,), jnp.int32)

    def scan_row(_):
        gather_tile()

        def compute(b, carry):
            s, sbase, alive_i, nd = carry
            alive = alive_i != 0
            al, au = block_fn(pl.multiple_of(b * block_d, block_d))
            bs = jnp.sum(pow_from_abs(al, pi), axis=0)
            bb = jnp.sum(au if base_p == 1.0 else au * au, axis=0)
            s = jnp.where(alive, s + bs, s)
            sbase = jnp.where(alive, sbase + bb, sbase)
            width = jnp.minimum(block_d, d - b * block_d) if ragged \
                else block_d
            nd = nd + jnp.where(alive, width, 0)
            dead = s * deflate > thr
            d_rem = d - (b + 1) * block_d
            if ragged:
                d_rem = jnp.maximum(d_rem, 0)
            d_rem = d_rem.astype(jnp.float32)
            rem = lp_suffix_bound(sb_row - sbase, base_p, pi, d_rem)
            dead = dead | ((d_rem > 0) & ((s + rem) * deflate > thr))
            return (s, sbase, (alive & ~dead).astype(jnp.int32), nd)

        def block_step(b, carry):
            return jax.lax.cond(jnp.max(carry[2]) > 0,
                                functools.partial(compute, b),
                                lambda c: c, carry)

        s, _, alive, nd = jax.lax.fori_loop(
            0, nb, block_step, (zeros, zeros, alive0, nd0))
        return s, alive, nd

    s, alive, nd = jax.lax.cond(jnp.max(alive0) > 0, scan_row,
                                lambda _: (zeros, nd0, nd0), 0)
    return s, alive != 0, nd


def _gather_abandon_kernel(*refs, p, base_p: float, n: int, d: int,
                           block_c: int, block_d: int, c: int):
    (ids_s, ids_v, q_ref, th_ref, sb_ref, x_hbm, tail_ref, o_ref, nd_ref,
     stage_ref, wide_ref, gx_ref, dt_ref, sem), p_of = _split_p(refs, p)
    tb = q_ref.shape[0]
    slots = _slot_count(block_c, c)

    def per_query(i, _):
        qi = q_ref[i, :].astype(jnp.float32)

        def gather_tile():
            _dma_gather_rows(ids_s, i, x_hbm, tail_ref, stage_ref, wide_ref,
                             gx_ref, sem, n=n, slots=slots)
            # one subtract + transpose; dimension blocks are sublane
            # slices of this (d, TC) diff tile
            dt_ref[...] = (gx_ref[...] - qi[None, :]).T

        def block_fn(off):
            a = jnp.abs(dt_ref[pl.ds(off, block_d), :])
            return a, a

        s, alive, nd = _blocked_scan(
            i, ids_v, sb_ref, th_ref[i, 0], p_of(i), gather_tile, block_fn,
            base_p=base_p, n=n, d=d, block_c=block_c, block_d=block_d,
            deflate=1.0)
        o_ref[i, :] = jnp.where(alive, s, jnp.inf).astype(o_ref.dtype)
        nd_ref[i, :] = nd
        return 0

    jax.lax.fori_loop(0, tb, per_query, 0)


def _blocked_call(kernel, ids, q, p, thresh, sb, src, extra, *, d: int,
                  block_b: int, block_c: int, block_d: int, c: int,
                  out_dtypes, interpret: bool, **kw):
    """pallas_call shared by the abandon and screen kernels: operands are
    the gather head (ids, q, [p]), SMEM thresholds, base sums, whole-array
    VMEM `extra` operands, then the row source `src` (n, dx); d <= dx is
    the logical width scanned, in ceil(d / block_d) blocks that dx must
    hold; c is the caller's candidate count before padding to C."""
    b, dx = q.shape
    b2, cc = ids.shape
    n = src.shape[0]
    assert b == b2 and b % block_b == 0 and cc % block_c == 0, \
        (b, b2, cc, block_b, block_c)
    assert -(-d // block_d) * block_d <= dx, (d, dx, block_d)
    assert thresh.shape == (b, 1), (thresh.shape, b)
    vector_p = not is_static_p(p)
    if vector_p:
        assert p.shape == (b, 1), (p.shape, b)
    src_specs, src_ops, src_scratch = _row_source(src)
    tile = pl.BlockSpec((block_b, block_c), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(
            kernel, p=None if vector_p else float(p), n=n, d=d,
            block_c=block_c, block_d=block_d, c=c, **kw,
        ),
        grid=(b // block_b, cc // block_c),
        in_specs=_gather_in_specs(block_b, block_c, dx, vector_p)
        + [_smem_rows(block_b), tile]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(extra) + src_specs,
        out_specs=(tile, tile),
        out_shape=tuple(jax.ShapeDtypeStruct((b, cc), dt)
                        for dt in out_dtypes),
        scratch_shapes=src_scratch + [
            pltpu.VMEM((block_c, dx), jnp.float32),
            pltpu.VMEM((dx, block_c), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(ids, ids, q, *((p,) if vector_p else ()), thresh, sb, *extra,
      *src_ops)


def gather_lp_abandon_kernel_call(
    ids: jax.Array,     # (B, C) int32 candidate ids; out-of-range = padding
    q: jax.Array,       # (B, dx)
    thresh: jax.Array,  # (B, 1) per-query abandon bound (power-sum space;
                        # -inf = row frozen, +inf = no abandonment)
    sb: jax.Array,      # (B, C) base-metric power sums (0 = no bound info)
    x: jax.Array,       # (n, dx) HBM-resident dataset
    p,
    *,
    d: int | None = None,
    base_p: float = 1.0,
    block_b: int = 8,
    block_c: int = 128,
    block_d: int = 32,
    c: int,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call for pre-padded inputs (B % block_b == C % block_c == 0,
    ceil(d / block_d) * block_d <= dx; d = logical width, default dx —
    compiled for TPU, dx % 128 == 0; c = the caller's candidate count
    before padding to C). Returns (dists (B, C) root-free power sums
    with +inf for abandoned/padding candidates, nd (B, C) int32 dimensions
    scanned).

    p: Python float, or a pre-padded (B, 1) f32 array (one metric per query
    row — the mixed-p contract in the module preamble). base_p (static 1.0
    or 2.0) names the metric of `sb` for the entry/suffix bounds.
    """
    return _blocked_call(
        _gather_abandon_kernel, ids, q, p, thresh, sb, x, (),
        d=q.shape[1] if d is None else d, block_b=block_b, block_c=block_c,
        block_d=block_d, c=c, out_dtypes=(out_dtype, jnp.int32),
        interpret=interpret, base_p=base_p)


# ---------------------------------------------------------------------------
# compressed-band screen kernel (DESIGN.md §10): ids (B, C) + thresholds
# (B, 1) + base sums (B, C) + int8 codes (n, d) + scale (1, d) / radius
# (d, 1) -> keep (B, C) int32 0/1, nd (B, C) int32 band dimensions scanned
#
# The storage-side sibling of the abandon kernel: instead of gathering f32
# rows and accumulating *exact* partial power sums, it gathers int8 band
# rows and accumulates the certified per-coordinate lower bound
# max(|q_j - x̂_j| - radius_j, 0)^p (index/compressed.py). A candidate
# whose deflated running bound exceeds the per-query threshold provably
# cannot enter the top-k, so the two-band scan never issues its f32
# gather — the screen's survivors are the only rows the exact rerank
# touches. Same transposed (d, TC) scratch layout, same per-block lax.cond
# alive gating, same entry/suffix bounds from the beam's base power sums
# as the abandon kernel (`_blocked_scan`); the suffix bound's scanned base
# mass accumulates the per-coordinate *upper* bounds (|q_j - x̂_j| +
# radius_j) so the remaining mass stays an underestimate. Because the
# accumulated sum is a float-evaluated bound (not an exact partial of the
# true distance), every kill comparison deflates by BOUND_SLACK. The
# dequant scale multiplies the gathered rows as a (1, d) row; the radius
# rides as a (d, 1) column so each dimension block slices it by ref. An
# int8 row tile is 32 rows, so on the chip a candidate's band fetch moves
# as many bytes as its f32 row fetch (8 rows) — the screen saves the
# rerank's gathers and transcendentals, not DMA bytes.
# ---------------------------------------------------------------------------


def _gather_screen_kernel(*refs, p, base_p: float, n: int, d: int,
                          block_c: int, block_d: int, c: int):
    (ids_s, ids_v, q_ref, th_ref, sb_ref, sc_ref, rad_ref, codes_hbm,
     tail_ref, keep_ref, nd_ref, stage_ref, wide_ref, gx_ref, dt_ref,
     sem), p_of = _split_p(refs, p)
    tb = q_ref.shape[0]
    slots = _slot_count(block_c, c)

    def per_query(i, _):
        qi = q_ref[i, :].astype(jnp.float32)

        def gather_tile():
            _dma_gather_rows(ids_s, i, codes_hbm, tail_ref, stage_ref,
                             wide_ref, gx_ref, sem, n=n, slots=slots)
            # dequant + subtract + transpose once; dimension blocks are
            # sublane slices of this (d, TC) |q - x̂| tile
            dt_ref[...] = jnp.abs(gx_ref[...] * sc_ref[...] - qi[None, :]).T

        def block_fn(off):
            a = dt_ref[pl.ds(off, block_d), :]
            r = rad_ref[pl.ds(off, block_d), :]
            return jnp.maximum(a - r, 0.0), a + r  # certified lower / upper

        _, alive, nd = _blocked_scan(
            i, ids_v, sb_ref, th_ref[i, 0], p_of(i), gather_tile, block_fn,
            base_p=base_p, n=n, d=d, block_c=block_c, block_d=block_d,
            deflate=1.0 - BOUND_SLACK)
        keep_ref[i, :] = alive.astype(jnp.int32)
        nd_ref[i, :] = nd
        return 0

    jax.lax.fori_loop(0, tb, per_query, 0)


def gather_lp_screen_kernel_call(
    ids: jax.Array,     # (B, C) int32 candidate ids; out-of-range = padding
    q: jax.Array,       # (B, dx) queries, band (permuted) coordinate order
    thresh: jax.Array,  # (B, 1) per-query screen bound (power-sum space;
                        # -inf = row frozen, +inf = keep everything)
    sb: jax.Array,      # (B, C) base-metric power sums (0 = no bound info)
    scale: jax.Array,   # (1, dx) f32 per-coordinate dequant scales
    radius: jax.Array,  # (dx, 1) f32 per-coordinate max dequant error
    codes: jax.Array,   # (n, dx) int8 HBM-resident compressed band
    p,
    *,
    d: int | None = None,
    base_p: float = 1.0,
    block_b: int = 8,
    block_c: int = 128,
    block_d: int = 32,
    c: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call for pre-padded inputs (B % block_b == C % block_c == 0,
    ceil(d / block_d) * block_d <= dx; d = logical width, default dx —
    compiled for TPU, dx % 128 == 0; c = the caller's candidate count
    before padding to C). Returns (keep (B, C) int32 — 1 iff the candidate
    survived the screen and its f32 row must be gathered for the exact
    rerank, nd (B, C) int32 band dimensions scanned).

    p: Python float, or a pre-padded (B, 1) f32 array (one metric per
    query row — the mixed-p contract in the module preamble). base_p
    (static 1.0 or 2.0) names the metric of `sb` for the entry/suffix
    bounds. scale/radius are whole-array VMEM operands (fetched once).
    """
    dx = q.shape[1]
    assert scale.shape == (1, dx) and radius.shape == (dx, 1), \
        (scale.shape, radius.shape, dx)
    return _blocked_call(
        _gather_screen_kernel, ids, q, p, thresh, sb, codes, (scale, radius),
        d=dx if d is None else d, block_b=block_b, block_c=block_c,
        block_d=block_d, c=c, out_dtypes=(jnp.int32, jnp.int32),
        interpret=interpret, base_p=base_p)
