"""Plain exact Lp search, the reference that decides `correct`.

Straightforward `jax.numpy` over the corpus in row blocks, with each
query row's own p: d(q, x) = (sum_j |q_j - x_j|^p)^(1/p). It imports
nothing of the program under test and takes nothing it made. `dtype`
names the arithmetic: float32 (the configuration's precision) for the
reference, bfloat16 for the control that must fail the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_CHUNK = 32
# rows per corpus block: keeps one block's (queries x rows x d) working set
# near 2^26 elements whatever the width
BLOCK_ELEMS = 1 << 26


def _lp_sums(q, x, p, dtype):
    """(B, R) sums of |q - x|^p in `dtype`; q (B, d), x (R, d), p (B,)."""
    diff = jnp.abs(q.astype(dtype)[:, None, :] - x.astype(dtype)[None, :, :])
    return jnp.sum(diff ** p.astype(dtype)[:, None, None], axis=-1,
                   dtype=dtype)


@functools.partial(jax.jit, static_argnames=("k", "block", "dtype"))
def _topk_chunk(x, q, p, *, k, block, dtype):
    """Exact top-k of one query chunk over all rows of x, block by block."""
    n = x.shape[0]
    nblocks = -(-n // block)
    xp = jnp.pad(x, ((0, nblocks * block - n), (0, 0)))

    def step(carry, b):
        best_d, best_i = carry
        start = b * block
        xb = jax.lax.dynamic_slice_in_dim(xp, start, block)
        s = _lp_sums(q, xb, p, dtype).astype(jnp.float32)
        ids = start + jnp.arange(block, dtype=jnp.int32)
        s = jnp.where(ids[None, :] < n, s, jnp.inf)
        all_d = jnp.concatenate([best_d, s], axis=1)
        all_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)],
                                axis=1)
        neg, pos = jax.lax.top_k(-all_d, k)
        return (-neg, jnp.take_along_axis(all_i, pos, axis=1)), None

    init = (jnp.full((q.shape[0], k), jnp.inf, jnp.float32),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(step, init, jnp.arange(nblocks))
    return i, d ** (1.0 / p[:, None])


@functools.partial(jax.jit, static_argnames=("dtype",))
def _row_dists(x, q, ids, p, *, dtype):
    """Rooted distance of each (query, id) pair: q (B, d), ids (B, k)."""
    rows = x[jnp.clip(ids, 0, x.shape[0] - 1)].astype(dtype)
    diff = jnp.abs(q.astype(dtype)[:, None, :] - rows)
    s = jnp.sum(diff ** p.astype(dtype)[:, None, None], axis=-1, dtype=dtype)
    return s.astype(jnp.float32) ** (1.0 / p[:, None])


def _chunks(total: int, size: int):
    for start in range(0, total, size):
        yield start, min(start + size, total)


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    if len(a) == rows:
        return a
    pad = np.repeat(a[-1:], rows - len(a), axis=0)
    return np.concatenate([a, pad])


def exact_topk(x, queries: np.ndarray, ps: np.ndarray, k: int,
               dtype=jnp.float32) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k ids (B, k) int32 and rooted distances (B, k) f32.

    `x` is the (n, d) device corpus, `queries` (B, d) and `ps` (B,) are
    host arrays. Queries run in fixed chunks so one program serves any B.
    """
    d = int(x.shape[1])
    block = max(8, min(int(x.shape[0]), BLOCK_ELEMS // (QUERY_CHUNK * d)))
    ids, dists = [], []
    with jax.default_matmul_precision("highest"):
        for lo, hi in _chunks(len(queries), QUERY_CHUNK):
            q = jnp.asarray(_pad_rows(queries[lo:hi], QUERY_CHUNK))
            p = jnp.asarray(_pad_rows(ps[lo:hi], QUERY_CHUNK), jnp.float32)
            i, dd = _topk_chunk(x, q, p, k=k, block=block, dtype=dtype)
            ids.append(np.asarray(i)[:hi - lo])
            dists.append(np.asarray(dd)[:hi - lo])
    return np.concatenate(ids), np.concatenate(dists)


def distances_of(x, queries: np.ndarray, ps: np.ndarray, ids: np.ndarray,
                 dtype=jnp.float32, chunk: int = 256) -> np.ndarray:
    """Rooted Lp distance of each returned id to its query, (B, k) f32."""
    out = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in _chunks(len(queries), chunk):
            q = jnp.asarray(_pad_rows(queries[lo:hi], chunk))
            p = jnp.asarray(_pad_rows(ps[lo:hi], chunk), jnp.float32)
            i = jnp.asarray(_pad_rows(ids[lo:hi], chunk))
            out.append(np.asarray(_row_dists(x, q, i, p, dtype=dtype))
                       [:hi - lo])
    return np.concatenate(out)
