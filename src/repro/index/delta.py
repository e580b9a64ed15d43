"""Mutable delta tier for streaming inserts (DESIGN.md §3).

Graph indexes are cheap to query but expensive to mutate; the standard
serving design is therefore frozen segments + a small mutable delta buffer.
`add()` is O(1) (append); queries brute-force the delta under *exact* Lp via
the Lp dispatch entry point (repro.kernels.ops.lp_gather_distance) — exact
distances, so delta hits need no verification pass and merge directly with
the verified graph top-k.
When the buffer reaches capacity it compacts: the owner (ShardedUHNSW)
builds a new frozen segment from the buffered vectors and clears the buffer.

Because the delta scan is exact, a freshly-added vector is findable at every
p immediately — there is no index-lag window.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class DeltaBuffer:
    """Append-only vector buffer with exact-Lp search.

    Global ids are assigned by the owner at add() time (`base_id + slot`)
    and stay stable across compaction — the compacted segment reuses them.
    """

    def __init__(self, d: int, capacity: int = 1024):
        assert capacity >= 1
        self.d = d
        self.capacity = capacity
        self._vecs: list[np.ndarray] = []
        self._ids: list[int] = []
        self._cache: jax.Array | None = None  # device copy, invalidated on add

    def __len__(self) -> int:
        return len(self._vecs)

    @property
    def full(self) -> bool:
        return len(self._vecs) >= self.capacity

    def add(self, vec: np.ndarray, global_id: int) -> int:
        v = np.asarray(vec, dtype=np.float32).reshape(-1)
        assert v.shape == (self.d,), (v.shape, self.d)
        self._vecs.append(v)
        self._ids.append(int(global_id))
        self._cache = None
        return global_id

    def vectors(self) -> np.ndarray:
        """(n_delta, d) snapshot (host)."""
        if not self._vecs:
            return np.zeros((0, self.d), dtype=np.float32)
        return np.stack(self._vecs)

    def ids(self) -> np.ndarray:
        return np.asarray(self._ids, dtype=np.int32)

    def restore(self, vecs: np.ndarray, ids: np.ndarray) -> None:
        """Bulk re-load buffered contents (snapshot recovery path).

        Appends in order with the saved global ids, so a restored buffer is
        indistinguishable from one that reached this state through `add`.
        """
        vecs = np.asarray(vecs, dtype=np.float32)
        assert len(vecs) == len(ids), (len(vecs), len(ids))
        for v, gid in zip(vecs, ids):
            self.add(v, int(gid))

    def drain(self) -> tuple[np.ndarray, np.ndarray]:
        """Return (vectors, ids) and empty the buffer (compaction step)."""
        vecs, ids = self.vectors(), self.ids()
        self._vecs, self._ids, self._cache = [], [], None
        return vecs, ids

    def search(self, Q: jax.Array, p, interpret: bool | None = None,
               thresh: jax.Array | None = None, block_d: int | None = None,
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Exact rooted Lp distances of every buffered vector to each query.

        Q: (B, d) f32. p: Python float or (B,) array — row i of a mixed-p
        batch is scored under p[i] (the scalar-vs-vector contract,
        DESIGN.md §6). Returns (ids (B, n_delta) int32 global, dists
        (B, n_delta) f32, nd (B, n_delta) int32 dimensions scanned).
        Empty buffer -> (B, 0) arrays, so callers can concatenate blindly.

        With `thresh` (per-query rooted k-th-best distances from the
        already-verified graph top-k) the scan routes through the
        early-abandoning blocked kernel (kernels/ops.lp_gather_abandon,
        DESIGN.md §8): buffered vectors whose partial power sum already
        exceeds the bound score +inf and skip their remaining dimension
        blocks — exact, since they provably cannot enter the top-k. The
        rooted threshold is un-rooted with a 1e-4 inflation so the
        root/power float round trip can never abandon a true top-k entry.

        Without `thresh` scoring stays on the exact-Lp dispatch entry
        point (kernels/ops.lp_gather_distance) in its 1-D shared-ids form,
        which runs as one pairwise block over the once-gathered buffer (no
        per-query re-gather; p=2 keeps its MXU matmul). `interpret`
        forwards to the dispatcher either way.
        """
        b = Q.shape[0]
        if not self._vecs:
            z = jnp.zeros((b, 0))
            return z.astype(jnp.int32), z, z.astype(jnp.int32)
        if self._cache is None:
            from repro.kernels.ops import kernel_rows

            self._cache = kernel_rows(jnp.asarray(self.vectors()))
        n_delta = len(self._vecs)
        d = self.d
        ids = jnp.broadcast_to(jnp.asarray(self.ids())[None, :],
                               (b, n_delta))
        if thresh is not None:
            from repro.core.lp_ops import pow_from_abs
            from repro.kernels.ops import lp_gather_abandon

            rows2d = jnp.broadcast_to(
                jnp.arange(n_delta, dtype=jnp.int32)[None, :], (b, n_delta))
            thr_pow = pow_from_abs(jnp.asarray(thresh, jnp.float32),
                                   jnp.asarray(p, jnp.float32)) * (1 + 1e-4)
            dists, nd = lp_gather_abandon(
                Q, rows2d, self._cache, thr_pow,
                jnp.zeros((b, n_delta), jnp.float32), p, root=True,
                interpret=interpret, block_d=block_d,
            )
            return ids, dists, nd
        from repro.kernels.ops import lp_gather_distance

        rows = jnp.arange(n_delta, dtype=jnp.int32)
        dists = lp_gather_distance(Q, rows, self._cache, p, root=True,
                                   interpret=interpret)
        return ids, dists, jnp.full((b, n_delta), d, jnp.int32)
