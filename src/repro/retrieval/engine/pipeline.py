"""Two-stage search/verify pipeline over the staged index API.

The index (UHNSW / ShardedUHNSW) exposes the query path as two device
stages (DESIGN.md §6):

    search_stage_candidates(Q, base_p)      -> CandidateSet   (stage A)
    search_stage_finish(Q, cands, p, k)     -> ids/dists/stats (stage B)

Both stages are *async dispatches* under JAX: they enqueue device work
and return device arrays without blocking. The pipeline exploits that by
dispatching wave N+1's stage A before materializing wave N's stage B —
the dispatch order is

    A1, B1, A2, <collect B1>, B2, A3, <collect B2>, B3, ...

so on an accelerator the next wave's base-graph beam search overlaps the
previous wave's general-p verification; the only blocking point is the
`np.asarray` collection of a wave whose successor is already in flight.
`search` composes exactly these two stage methods, so pipelined results
are bitwise-identical to the fused call — and batch-composition
invariance (tests/test_mixed_p.py) makes them bitwise-identical to
`serve_grouped` regardless of how the scheduler chunked the stream.

A `Wave` is one device-call unit: a ladder-sized, padded, homogeneous
(base, k, exact) slice of a scheduler flush. Its query tensor and
candidate set stay device-resident between the stages.

Each stage runs inside a `jax.profiler.TraceAnnotation` named for it
(`engine.dispatch_search`, `engine.dispatch_finish`, and
`engine.collect.wait` around the blocking read), tagged with the wave's
sequence number and padded size, so a profile puts the engine's host
work on the device trace's clock wave by wave. Outside a profiling
session an annotation does nothing.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
from jax.profiler import TraceAnnotation

from repro.retrieval.engine.request import (
    DONE,
    SEARCHING,
    VERIFYING,
    EngineRequest,
)
from repro.retrieval.engine.scheduler import Flush, chunk_plan


@dataclass
class Wave:
    """One ladder-sized device batch flowing through the two stages."""

    base: float
    k: int
    exact: bool
    reason: str                      # the flush reason that released it
    requests: list[EngineRequest]    # n_real entries
    size: int                        # padded device batch size (ladder)
    q: np.ndarray                    # (size, d) f32, rows >= n_real padded
    p_vec: np.ndarray | None         # (size,) f32 for the verify lane
    cands: object = None             # CandidateSet (device) after stage A
    result: tuple | None = None      # (ids, dists, stats) after stage B
    attempt: int = 0                 # failed executions so far (retry budget)
    health_gen: int | None = None    # health generation at stage-A dispatch
    seq: int = 0                     # the engine's wave sequence number

    def span(self, name: str) -> TraceAnnotation:
        """A profiler annotation of one of this wave's stages."""
        return TraceAnnotation(name, wave=self.seq, rows=self.size)

    @property
    def n_real(self) -> int:
        return len(self.requests)

    @property
    def padded_rows(self) -> int:
        return self.size - self.n_real


def make_waves(flush: Flush, ladder: list[int],
               first_seq: int) -> list[Wave]:
    """Cut one flush into exact-fit ladder waves (greedy largest-first).

    Padding rows replicate row 0 of their wave (same base graph, any p is
    valid there) and are sliced off before results or stats are read —
    identical to the v1 scheduler's padding contract. The waves are
    numbered from `first_seq` on (the engine passes its running count).
    """
    reqs = flush.requests
    waves = []
    start = 0
    for i, size in enumerate(chunk_plan(len(reqs), ladder)):
        chunk = reqs[start:start + min(size, len(reqs) - start)]
        start += len(chunk)
        q = np.stack([np.asarray(r.vector, np.float32).reshape(-1)
                      for r in chunk])
        if size > len(chunk):
            q = np.concatenate(
                [q, np.repeat(q[:1], size - len(chunk), axis=0)])
        p_vec = None
        if not flush.exact:
            p_vec = np.array([float(r.p) for r in chunk], np.float32)
            if size > len(chunk):
                p_vec = np.concatenate(
                    [p_vec, np.repeat(p_vec[:1], size - len(chunk))])
        waves.append(Wave(base=flush.base, k=flush.k, exact=flush.exact,
                          reason=flush.reason, requests=chunk, size=size,
                          q=q, p_vec=p_vec, seq=first_seq + i))
    return waves


@dataclass
class TwoStagePipeline:
    """Dispatch/collect the two index stages for a stream of waves.

    The pipeline itself is stateless about ordering — the engine owns the
    one-wave lookahead (`ServingEngine._inflight`) and the failure
    recovery; this class just knows how to run one wave's stages and
    materialize its results.
    """

    index: object  # UHNSW | ShardedUHNSW (any object with the stage API)

    def dispatch_search(self, wave: Wave) -> None:
        """Stage A: async-dispatch base-graph candidate generation."""
        with wave.span("engine.dispatch_search"):
            wave.cands = self.index.search_stage_candidates(
                wave.q, wave.base, k=wave.k)
        for r in wave.requests:
            r.stage = SEARCHING

    def dispatch_finish(self, wave: Wave) -> None:
        """Stage B: async-dispatch verification (or the exact-base skip).

        The exact lane passes the scalar base metric (the skip path: no
        verification program at all); the verify lane passes the per-row
        p vector — the same traced-p program `serve_grouped` runs, which
        is what makes engine results bitwise-equal to the baselines.
        """
        p_arg = wave.base if wave.exact else wave.p_vec
        with wave.span("engine.dispatch_finish"):
            wave.result = self.index.search_stage_finish(
                wave.q, wave.cands, p_arg, wave.k)
        wave.cands = None  # device buffers free as soon as B consumes them
        for r in wave.requests:
            r.stage = VERIFYING

    def collect(self, wave: Wave):
        """Materialize one wave on host (the pipeline's only blocking
        point). Returns (ids, dists, stats) sliced to the real rows:
        `stats` is the wave's SearchStats as host rows
        (`SearchStats.host_rows`) — every per-row counter as float64
        rows, `hops_max` (the trip count of the beam loop behind each
        segment lane) flat, `coverage_frac` the exact alive-coverage
        fraction the wave was served at (DESIGN.md §11).
        """
        ids, dists, st = wave.result
        n = wave.n_real
        with wave.span("engine.collect.wait"):
            ids = np.asarray(ids)[:n]
        dists = np.asarray(dists)[:n]
        rows = st.host_rows(n)
        wave.result = None
        for r in wave.requests:
            r.stage = DONE
        return ids, dists, rows
