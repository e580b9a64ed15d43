"""Fused fetch-and-score of the level-0 beam loop's new neighbour rows.

Each trip of the level-0 loop (core/hnsw.py::_beam_search_l0) scores the
W*m0 neighbours of the beam entries it expands, of which the visited test
marks only some new (about a quarter on the cells' segments). An XLA
gather moves every row of its fixed shape and the loop then masks the
visited ones, so most of the rows it reads are thrown away. This kernel
reads only the new rows: one Pallas call per trip over every lane of the
batched loop (segments x queries), each lane's new rows DMA'd out of an
HBM row source into VMEM and scored with the base metric's root-free
power sum, as `_base_dist` computes it.

Layout. A DMA out of HBM moves whole (8, 128) f32 layout tiles, so the row
source is a `(rows, d / 128, 128)` f32 view in which a row of d % 1024 == 0
is d / 1024 whole tiles (`beam_rows`). The caller (ShardedUHNSW) builds it
once per segment stack; each lane adds its segment's row offset to its ids.

Pipeline. Outside the kernel, each lane's new ids are compacted to the
front of its row (`fetch_score_lanes`); in the kernel, lane l's rows and
its query go to ring slot l % depth, and lanes l + 1 .. l + depth - 1 are
in flight while lane l is scored. A lane's rows are scored in groups of 8:
each group's copies count on a DMA semaphore of their own, so a group is
scored as soon as its rows are in, while the rows behind it still arrive,
and its 8 partial sums are one aligned (8, 128) store. Rows of the last
group past the lane's count are whatever the slot held before; they are
scored and masked, never fetched, since a padding row would cost its
bytes. Whole groups' copies are issued unrolled. On a TPU v5e this took
the kernel from about 110 ns a lane plus 46 ns per new row to about 220
ns a lane plus 22 ns per new row at d = 4096 (scoring alone 16 -> 4 ns a
row; PERF.md, "Findings"). The kernel returns
each lane's scores in compacted order, +inf past its count; the wrapper
puts them back in frontier order, +inf where the entry is not new.

Batching. The loop body is vmapped twice (queries in `knn_search`,
segments in `segmented_knn_search`); a vmapped `pallas_call` would run
one grid step per lane. The per-lane op is a `custom_vmap` whose rule
folds each new batch axis into the lane axis, so the traced loop holds
one kernel call whose lane axis is segments x queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lp_ops import abs_pow

_LANE = 128
# rows scored together, one (8, 128) f32 tile of their partial sums
_GROUP = 8
# lanes whose rows are in flight at once, the one being scored included:
# 4 and 8 read alike on a TPU v5e, 2 slower (PERF.md, "Findings")
DEPTH = 4
# most row ids one kernel call holds in SMEM (256 KB of the v5e's 1 MiB);
# more lanes than that take several calls
MAX_CALL_IDS = 1 << 16


def beam_rows(x: jax.Array) -> jax.Array:
    """(..., d) f32 rows -> the kernel's (rows, d / 128, 128) row source
    (the leading axes flattened, row i of the source the i-th row)."""
    d = x.shape[-1]
    assert d % (8 * _LANE) == 0, d  # a row is whole (8, 128) f32 tiles
    return x.reshape(-1, d // _LANE, _LANE).astype(jnp.float32)


def _fetch_kernel(cnt_ref, rows_ref, q_hbm, src_hbm, o_ref, buf, qbuf, acc,
                  sem, *, p: float, n_q: int, lane0: int, depth: int):
    """Every lane in turn: refill the ring slot of the lane `depth - 1`
    further on, then score this lane's rows a group of 8 at a time, each
    group as soon as its own rows are in. A lane with no new row fetches
    nothing, its query included."""
    lanes, f = o_ref.shape
    groups, s8 = f // _GROUP, buf.shape[2]

    def row_copy(lane, slot, r):
        # a group's rows count on that group's semaphore; the query on its
        # own, the slot's last
        row = src_hbm.at[rows_ref[lane * f + r]]
        return pltpu.make_async_copy(row, buf.at[slot, r],
                                     sem.at[slot, r // _GROUP])

    def query_copy(lane, slot):
        return pltpu.make_async_copy(q_hbm.at[(lane0 + lane) % n_q],
                                     qbuf.at[slot], sem.at[slot, groups])

    def wait_rows(slot, g, n):
        """Wait for n row copies of group g: a wait counts the bytes of
        its descriptor, so a whole group is one wait."""
        def rows_of(k):
            return pltpu.make_async_copy(src_hbm.at[pl.ds(0, k)],
                                         buf.at[slot, pl.ds(0, k)],
                                         sem.at[slot, g])

        @pl.when(n == _GROUP)
        def _():
            rows_of(_GROUP).wait()

        @pl.when(n < _GROUP)
        def _():
            def one(j, _):
                rows_of(1).wait()
                return 0

            jax.lax.fori_loop(0, n, one, 0)

    def start(lane):
        slot = lane % depth
        count = cnt_ref[lane]
        whole = count // _GROUP

        def group(g, _):
            for j in range(_GROUP):
                row_copy(lane, slot, g * _GROUP + j).start()
            return 0

        def one(r, _):
            row_copy(lane, slot, r).start()
            return 0

        # whole groups' copies unrolled, then the last group's one by one
        jax.lax.fori_loop(0, whole, group, 0)
        jax.lax.fori_loop(whole * _GROUP, count, one, 0)

        @pl.when(count > 0)
        def _():
            query_copy(lane, slot).start()

    for lane in range(min(depth - 1, lanes)):
        start(lane)

    def per_lane(lane, _):
        @pl.when(lane + depth - 1 < lanes)
        def _():
            start(lane + depth - 1)

        slot = lane % depth
        count = cnt_ref[lane]

        @pl.when(count > 0)
        def _():
            query_copy(lane, slot).wait()

        qv = qbuf[slot]

        def score(g, _):
            # the group's 8 rows -> one aligned (8, 128) tile of per-row
            # partial sums; rows past `count` hold an earlier lane's rows
            # and are masked out below
            r0 = pl.multiple_of(g * _GROUP, _GROUP)
            wait_rows(slot, g, jnp.minimum(count - r0, _GROUP))
            a = abs_pow(buf[slot, pl.ds(r0, _GROUP)] - qv[None], p)
            part = a[:, :8]
            for k in range(1, s8 // 8):
                part = part + a[:, 8 * k:8 * k + 8]
            acc[pl.ds(r0, _GROUP), :] = jnp.sum(part, axis=1)
            return 0

        jax.lax.fori_loop(0, (count + _GROUP - 1) // _GROUP, score, 0)
        s = jnp.sum(acc[...], axis=1)
        live = jax.lax.broadcasted_iota(jnp.int32, (f,), 0) < count
        o_ref[lane, :] = jnp.where(live, s, jnp.inf)
        return 0

    jax.lax.fori_loop(0, lanes, per_lane, 0)


def fetch_kernel_call(cnt, rows, q, src, *, p: float, interpret: bool,
                      lane0: int = 0, depth: int = DEPTH):
    """Raw pallas_call: cnt (L,) int32 new rows per lane; rows (L * F,)
    int32 source rows, lane l's first cnt[l] at [l * F, l * F + cnt[l]);
    q (Lq, d / 128, 128), lane l's query q[(lane0 + l) % Lq]; src (R,
    d / 128, 128). Returns (L, F) f32 compacted scores, +inf past each
    lane's count."""
    lanes = cnt.shape[0]
    f = rows.shape[0] // lanes
    fg = -(-f // _GROUP) * _GROUP  # whole score groups; the rest never read
    if fg != f:
        rows = jnp.pad(rows.reshape(lanes, f), ((0, 0), (0, fg - f)))
        rows = rows.reshape(-1)
    n_q, s8, lane_w = q.shape
    assert src.shape[1:] == (s8, lane_w), (q.shape, src.shape)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_fetch_kernel, p=float(p), n_q=n_q, lane0=lane0,
                          depth=depth),
        in_specs=[smem, smem, hbm, hbm],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((lanes, fg), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((depth, fg, s8, lane_w), jnp.float32),
            pltpu.VMEM((depth, s8, lane_w), jnp.float32),
            pltpu.VMEM((fg, lane_w), jnp.float32),
            pltpu.SemaphoreType.DMA((depth, fg // _GROUP + 1)),
        ],
        interpret=interpret,
        name="beam_fetch",
    )(cnt, rows, q, src)
    return out if fg == f else out[:, :f]


def fetch_score_lanes(q, ids, new, base, src, *, p: float, interpret: bool,
                      depth: int = DEPTH):
    """Base-metric power sums of every lane's new rows -> (L, F) f32.

    q (Lq, d / 128, 128), lane l's query q[l % Lq]; ids (L, F) int32 row
    ids within the lane's segment; new (L, F) bool; base (L,) int32 the
    lane's segment offset into src (R, d / 128, 128). Entries that are not
    new read +inf and are neither fetched nor scored. One kernel call
    unless L * F passes MAX_CALL_IDS.
    """
    lanes, f = ids.shape
    rank = jnp.cumsum(new, axis=1, dtype=jnp.int32) - 1
    cnt = rank[:, -1] + 1
    # hit[l, j, r]: frontier entry j is lane l's r-th new one
    hit = new[:, :, None] & (rank[:, :, None] == jnp.arange(f, dtype=jnp.int32))
    rows = jnp.where(hit, (ids + base[:, None])[:, :, None], 0).sum(
        axis=1, dtype=jnp.int32)
    step = max(1, MAX_CALL_IDS // f)
    out = jnp.concatenate([
        fetch_kernel_call(cnt[l0:l0 + step], rows[l0:l0 + step].reshape(-1),
                          q, src, p=p, interpret=interpret, lane0=l0,
                          depth=depth)
        for l0 in range(0, lanes, step)])
    dv = jnp.where(hit, out[:, None, :], -jnp.inf).max(axis=2)
    return jnp.where(new, dv, jnp.inf)


@functools.lru_cache(maxsize=None)
def _lane_op(p: float, interpret: bool):
    """`fetch_score_lanes` as a custom_vmap: a vmap of it is one call on
    the lanes of every batch axis."""

    @jax.custom_batching.custom_vmap
    def op(q, ids, new, base, src):
        return fetch_score_lanes(q, ids, new, base, src, p=p,
                                 interpret=interpret)

    @op.def_vmap
    def _(axis_size, in_batched, q, ids, new, base, src):
        q_b, ids_b, new_b, base_b, src_b = in_batched
        if src_b:
            raise NotImplementedError(
                "beam_fetch reads one row source for every lane")
        lanes, f = ids.shape[-2:]

        def fold(x, batched):
            x = x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((axis_size * lanes,) + x.shape[2:])

        if q_b:  # a query per new lane: lane a * L + l reads q[a, l % Lq]
            n_q = q.shape[1]
            q = jnp.broadcast_to(q[:, None], (axis_size, lanes // n_q)
                                 + q.shape[1:])
            q = q.reshape((axis_size * lanes,) + q.shape[3:])
        # unbatched q: (a * L + l) % Lq == l % Lq, since Lq divides L
        out = op(q, fold(ids, ids_b), fold(new, new_b), fold(base, base_b),
                 src)
        return out.reshape(axis_size, lanes, f), True

    return op


def _interpret() -> bool:
    """Interpret mode off the TPU (the kernel's default)."""
    return jax.default_backend() != "tpu"


def query_tiles(q: jax.Array) -> jax.Array:
    """A (d,) query in the row source's (d / 128, 128) layout: made once
    per search, outside the loop, since the relayout is a copy."""
    return q.reshape(q.shape[-1] // _LANE, _LANE)


def fetch_score(q, ids, new, base, src, p: float,
                interpret: bool | None = None):
    """One lane: q (d / 128, 128) its query (`query_tiles`); ids (F,)
    int32 row ids within the lane's segment; new (F,) bool; base () int32
    the segment's first row in src. Returns (F,) f32 base-metric power
    sums, +inf where not new. Under vmap every lane of every batch axis
    runs in one kernel call. `interpret` None: compiled on a TPU,
    interpret mode elsewhere."""
    if interpret is None:
        interpret = _interpret()
    return _lane_op(float(p), bool(interpret))(
        q[None], ids[None], new[None], jnp.reshape(base, (1,)), src)[0]
