"""Dataset partitioning + per-segment graph construction (DESIGN.md §3).

A segment is an independently-built U-HNSW pair (G1 under L1, G2 under L2)
over a random subset of the corpus. Random (not clustered) partitioning is
deliberate: every segment is then a uniform sample of the data distribution,
so each per-segment top-t candidate list is an unbiased cover of the global
top-k and the merge loses no recall (cf. the sharded-HNSW recipe in the
graph-ANNS survey, PAPERS.md).

All segments are padded to one uniform shape (GraphArrays.pad_to) and
stacked on a leading (S,) axis (GraphArrays.stack) so the batched beam
search vmaps across segments as a single device program — same-shaped
segments are what turn S independent graph traversals into one SPMD kernel.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.build import HNSWGraph, build_hnsw, build_hnsw_bulk
from repro.core.hnsw import GraphArrays
from repro.kernels.beam_fetch import beam_rows

# below this size the sequential (faithful) builder is both faster to warm up
# and higher quality; above it the batched bulk builder wins
BULK_THRESHOLD = 512

# segment build methods (DESIGN.md §7): "bulk" is the device-side shared-pass
# builder (G1+G2 from one candidate-generation pass), "bulk_host" the older
# vectorized NumPy per-graph builder, "incremental" the paper-faithful
# sequential insertion.
BUILD_METHODS = ("incremental", "bulk", "bulk_host")


def partition_dataset(n: int, num_segments: int, seed: int = 0) -> list[np.ndarray]:
    """Random balanced partition of [0, n) into `num_segments` id arrays."""
    assert 1 <= num_segments <= n, (num_segments, n)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    return [np.sort(part).astype(np.int64) for part in
            np.array_split(perm, num_segments)]


def resolve_build_method(n: int, bulk: bool | None = None,
                         method: str | None = None) -> str:
    """Pick a segment build method.

    `method` (one of BUILD_METHODS) wins when given; else the legacy `bulk`
    flag maps True -> "bulk", False -> "incremental"; else auto by size
    (incremental below BULK_THRESHOLD, device bulk above).
    """
    if method is not None:
        if method not in BUILD_METHODS:
            raise ValueError(
                f"unknown build method {method!r} (options: {BUILD_METHODS})")
        return method
    if bulk is not None:
        return "bulk" if bulk else "incremental"
    return "bulk" if n >= BULK_THRESHOLD else "incremental"


def build_segment_pair(
    data: np.ndarray, m: int, seed: int, bulk: bool | None = None,
    method: str | None = None,
):
    """Build one segment's (G1, G2) over `data` (local ids)."""
    method = resolve_build_method(len(data), bulk=bulk, method=method)
    if method == "bulk":
        from repro.core.bulk_build import build_bulk_pair

        return build_bulk_pair(data, m=m, seed=seed)
    if method == "bulk_host":
        g1 = build_hnsw_bulk(data, 1.0, m=m, seed=seed)
        g2 = build_hnsw_bulk(data, 2.0, m=m, seed=seed + 1)
    else:
        efc = min(200, max(16, 4 * m))
        g1 = build_hnsw(data, 1.0, m=m, ef_construction=efc, seed=seed)
        g2 = build_hnsw(data, 2.0, m=m, ef_construction=efc, seed=seed + 1)
    return g1, g2


def _stack_uniform(graphs: list[HNSWGraph]) -> GraphArrays:
    """pad_to every graph to the common shape envelope, then stack."""
    arrays = [GraphArrays.from_graph(g) for g in graphs]
    n_pad = max(a.n for a in arrays)
    n_levels = max(len(a.upper_adj) for a in arrays)
    upper_m = max((g.m for g in graphs), default=0) or None
    level_sizes = tuple(
        max((a.upper_adj[l].shape[0] for a in arrays if l < len(a.upper_adj)),
            default=1)
        for l in range(n_levels)
    )
    padded = [a.pad_to(n_pad, n_levels, level_sizes, upper_m=upper_m)
              for a in arrays]
    return GraphArrays.stack(padded)


@dataclass
class SegmentedGraphs:
    """S frozen segments, stacked for vmapped traversal.

    Host-side state (graphs, global_ids) persists so new segments can join
    (delta compaction) — appending restacks the device arrays to the new
    shape envelope; the per-segment graphs themselves never rebuild.
    """

    graphs1: list[HNSWGraph]          # per-segment G1 (L1)
    graphs2: list[HNSWGraph]          # per-segment G2 (L2)
    global_ids: list[np.ndarray]      # per-segment local -> global id map
    # stacked device state (derived; rebuilt by _restack):
    arrays1: GraphArrays = field(init=False)
    arrays2: GraphArrays = field(init=False)
    node_ids: jax.Array = field(init=False)   # (S, n_pad) int32, -1 pad

    def __post_init__(self):
        self._restack()

    @property
    def num_segments(self) -> int:
        return len(self.graphs1)

    @property
    def n_pad(self) -> int:
        return self.arrays1.n

    @property
    def X(self) -> jax.Array:
        """(S, n_pad, d) f32 segment data."""
        return self._X

    @X.setter
    def X(self, value: jax.Array) -> None:
        # every write of the rows (restack, placement, a poisoned or
        # restored segment) drops the fetch kernel's copy of them
        self._X = value
        self._beam_src = None

    def _restack(self):
        self.arrays1 = _stack_uniform(self.graphs1)
        self.arrays2 = _stack_uniform(self.graphs2)
        n_pad = max(self.arrays1.n, self.arrays2.n)
        d = self.graphs1[0].d
        s = self.num_segments
        X = np.zeros((s, n_pad, d), dtype=np.float32)
        node_ids = np.full((s, n_pad), -1, dtype=np.int32)
        for i, (g, ids) in enumerate(zip(self.graphs1, self.global_ids)):
            X[i, : g.n] = g.data
            node_ids[i, : g.n] = ids
        self.X = jnp.asarray(X)
        self.node_ids = jnp.asarray(node_ids)

    def beam_src(self) -> jax.Array:
        """X as the level-0 fetch kernel's row source (kernels.beam_fetch):
        (S * n_pad, d / 128, 128) f32, segment s's rows from s * n_pad.
        Made on first use, by the searches whose rows are whole DMA tiles
        (hnsw.beam_fetch_on), and again after every write of X."""
        if self._beam_src is None:
            self._beam_src = beam_rows(self.X)
        return self._beam_src

    def append(self, g1: HNSWGraph, g2: HNSWGraph, global_ids: np.ndarray):
        """Add a frozen segment (delta compaction) and restack."""
        assert g1.n == g2.n == len(global_ids)
        self.graphs1.append(g1)
        self.graphs2.append(g2)
        self.global_ids.append(np.asarray(global_ids, dtype=np.int64))
        self._restack()

    def index_size_bytes(self) -> int:
        return sum(g.index_size_bytes() for g in self.graphs1 + self.graphs2)


def build_segments(
    data: np.ndarray,
    num_segments: int = 4,
    m: int = 16,
    seed: int = 0,
    bulk: bool | None = None,
    method: str | None = None,
) -> SegmentedGraphs:
    """Partition `data` and build every segment's G1/G2 pair.

    Per-segment builds are independent (parallelizable across hosts at
    production scale — the sequential global insert order of monolithic HNSW
    is the scaling bottleneck this removes). `method` / `bulk` select the
    per-segment builder (see `resolve_build_method`); the device bulk path
    additionally builds each segment's G1 and G2 from one shared
    candidate-generation pass (DESIGN.md §7).
    """
    data = np.ascontiguousarray(data, dtype=np.float32)
    parts = partition_dataset(len(data), num_segments, seed=seed)
    graphs1, graphs2, global_ids = [], [], []
    for i, ids in enumerate(parts):
        g1, g2 = build_segment_pair(data[ids], m=m, seed=seed + 17 * i,
                                    bulk=bulk, method=method)
        graphs1.append(g1)
        graphs2.append(g2)
        global_ids.append(ids)
    return SegmentedGraphs(graphs1=graphs1, graphs2=graphs2,
                           global_ids=global_ids)
