"""ShardedUHNSW: segmented U-HNSW with one merged verification pass.

Query path (DESIGN.md §3):

  1. Candidate generation — policy-dependent (`ShardedParams.policy`):

     * "independent" (default): every segment runs a fully independent
       beam (the pre-threshold behavior; the exhaustive reference the
       other policies are measured against).
     * "two_phase": phase A probes a prior-ordered subset of
       segments (largest/oldest first, `probe` of them) with the full
       beam; its merged k-th-best base distance becomes the *inherited
       pruning threshold* for phase B, which searches the remaining
       segments with a shrunken beam whose admission is cut at the bound
       (core/hnsw.knn_search `thresh`). Pruning is admissible for the
       merged top-t whenever the threshold rank r satisfies
       (S / probe) * r >= t — the bound then upper-bounds the global
       t-th-best, so no pruned candidate could have entered the merged
       list (`resolve_thresh_rank` picks r accordingly).
     * "round_robin": single-phase cascade — every segment takes its turn
       in prior order with the full beam, inheriting the running merged
       k-th-best of all earlier turns as its threshold (first turn
       unthresholded). Maximum pruning, S sequential device calls.

     Per-segment searches `jax.vmap` over the stacked (S,) segment axis of
     the selected base graph (G1 for p <= 1.4, G2 otherwise); the segment
     axis shards over the mesh's data axes (`shard_over`).
  2. Merge — per-segment top-t lists (already ascending) concatenate and a
     single `lax.sort` keeps the global top-t under the base metric.
     Segments hold disjoint ids, so no dedup is needed.
  3. Verification — ONE `verify_candidates` pass over the merged list.
     Running verification after the merge (not per segment) preserves the
     paper's early-termination N_p savings end-to-end: the convergence test
     sees the same globally-ordered candidate stream a monolithic index
     would produce.
  4. Delta merge — exact rooted-Lp distances for the mutable delta buffer
     (repro.index.delta) sort-merge into the verified top-k. Exactness means
     no verification is owed for delta hits; with abandonment on, the scan
     inherits the verified k-th-best as its threshold (DESIGN.md §8).

Streaming inserts: `add()` appends to the delta buffer; at capacity the
buffer compacts into a new frozen segment — built with the index's build
method (DESIGN.md §7; by default the batched bulk builder once the buffer
holds >= BULK_THRESHOLD vectors), stacks re-pad — and the cycle repeats.
Ids are assigned once and never change.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.core.hnsw import GraphArrays, beam_fetch_on, knn_search
from repro.core.metrics import base_metric_for
from repro.core.uhnsw import (
    CandidateSet,
    SearchStats,
    UHNSWParams,
    finish_candidates,
    modeled_query_cost,
    two_way_mixed_search,
)
from repro.index.delta import DeltaBuffer
from repro.index.health import SegmentHealthTracker
from repro.index.segment import SegmentedGraphs, build_segment_pair, build_segments
from repro.kernels.ops import kernel_rows, pick_abandon_block_d


@dataclass(frozen=True)
class ShardedParams:
    """Cross-segment search policy knobs (DESIGN.md §3).

    Frozen dataclass; invalid values raise ValueError at construction
    (`__post_init__`), never at query time.

    Attributes:
      policy: str — one of POLICIES. "independent" (the default — no
        cross-segment state; every segment runs a fully independent beam,
        the exhaustive reference the bench's ids-equal gate compares
        against), "two_phase" (probe + threshold-pruned spill — the
        cheap cross-segment policy the bench flags), or "round_robin"
        (single-phase cascade, every turn inherits the running bound).
        The default stays exhaustive because threshold pruning trades a
        bounded recall loss for N_b; deployments opt in per index
        (benchmarks/sharded_index.py quantifies the trade). Any other
        string raises ValueError.
      probe: int >= 1 — number of prior-ordered segments phase A
        searches with the full beam (two_phase only; the prior order is
        `ShardedUHNSW._probe_order`, largest segments first). Clamped to
        [1, S-1] at query time; with S == 1 or probe >= S every policy
        degenerates to independent. probe < 1 raises ValueError.
      ef_shrink: float in (0, 1] — phase-B beam-width multiplier,
        floored at the spill t (two_phase only — round_robin keeps the
        full beam every turn and relies on the threshold admission cut
        alone). Out-of-range raises ValueError.
      thresh_rank: int | None — rank r of the inherited running k-th
        best used as the pruning bound; None derives
        max(k, ceil(t * probe / S)) — the smallest rank that keeps
        pruning admissible for the merged top-t (see the module
        docstring) while never pruning inside the caller's top-k.
        Clamped to [1, t] by `resolve_thresh_rank`.
    """

    policy: str = "independent"
    probe: int = 1
    ef_shrink: float = 0.5
    thresh_rank: int | None = None

    POLICIES = ("two_phase", "round_robin", "independent")

    def __post_init__(self):
        if self.policy not in self.POLICIES:
            raise ValueError(
                f"unknown policy {self.policy!r} (options: {self.POLICIES})")
        if not self.probe >= 1:
            raise ValueError(f"probe must be >= 1, got {self.probe}")
        if not 0.0 < self.ef_shrink <= 1.0:
            raise ValueError(
                f"ef_shrink must be in (0, 1], got {self.ef_shrink}")

    def resolve_thresh_rank(self, t: int, num_segments: int,
                            k: int | None) -> int:
        """The rank whose running best becomes the inherited bound."""
        if self.thresh_rank is not None:
            return max(1, min(self.thresh_rank, t))
        probe = max(1, min(self.probe, num_segments))
        admissible = -(-t * probe // num_segments)  # ceil(t*probe/S)
        return max(1, min(max(k or 1, admissible), t))

    def validate_for(self, num_segments: int, t: int) -> None:
        """Instance-dependent bounds, checked where the index is built.

        `__post_init__` can only see the params themselves; these two
        constraints involve the index (segment count, candidate width) and
        used to surface as shape errors deep inside
        `segmented_knn_search`. ShardedUHNSW calls this at construction so
        they fail immediately, with a fix attached. probe == num_segments
        stays legal (the policy degenerates to independent).
        """
        if self.probe > num_segments:
            raise ValueError(
                f"ShardedParams.probe={self.probe} exceeds the index's "
                f"{num_segments} segments — phase A cannot probe more "
                f"segments than exist; lower probe to <= {num_segments} "
                f"or build with more segments")
        if self.thresh_rank is not None and self.thresh_rank > t:
            raise ValueError(
                f"ShardedParams.thresh_rank={self.thresh_rank} exceeds the "
                f"candidate width t={t} — the running rank-r best only "
                f"exists for r <= t; lower thresh_rank or raise "
                f"UHNSWParams.t")


@functools.partial(
    jax.jit, static_argnames=("ef", "t", "max_hops", "expand_width")
)
def segmented_knn_search(
    arrays: GraphArrays,   # stacked, leading (S,) axis, n = n_pad
    X: jax.Array,          # (S, n_pad, d)
    node_ids: jax.Array,   # (S, n_pad) local -> global, -1 pad
    Q: jax.Array,          # (B, d)
    ef: int,
    t: int,
    max_hops: int = 4096,
    expand_width: int = 1,
    thresh: jax.Array | None = None,
    alive: jax.Array | None = None,
    fetch_rows: tuple | None = None,
):
    """Vmapped per-segment base-metric search + one-sort global merge.

    `thresh` (optional (B,) root-free base-metric bounds, shared by every
    segment in the stack) routes each per-segment beam through the
    admission early-cut (core/hnsw.knn_search): evaluations past a query's
    bound count toward n_b but are never admitted, so pruned segments
    terminate as soon as their sub-threshold region is exhausted. None
    compiles the unmodified exhaustive program.

    `alive` (optional (S,) bool, *traced* — one compiled program serves
    every mask) implements degraded-coverage search (DESIGN.md §11): dead
    segments still run inside the vmap (the stacked shape is fixed) but
    their outputs are masked to the padding encoding (-1 ids, inf dists,
    zero counters) before the merge, which makes the merged result
    bitwise identical to a search over an index holding only the alive
    segments. None compiles the unmasked program.

    Every gathered per-segment distance also passes a NaN/inf guard: a
    candidate with a real id but a non-finite base distance (poisoned
    rows, a corrupt gather) is masked to padding — it can never reach a
    top-k — and raises that query's `poisoned` flag so the serving engine
    can bisect the poison back to a segment. Because a beam never
    *admits* a NaN distance (every comparison against it is false), a
    fully poisoned segment would otherwise return only sentinels and slip
    past a final-list check — so the guard additionally recomputes each
    query's base distance to the segment's entry-point row (one O(B*d)
    evaluation per segment, the row every beam must gather first) and
    flags non-finite entry distances too.

    `fetch_rows` (optional (src, base): `SegmentedGraphs.beam_src`, the
    stack's rows in the layout of `kernels.beam_fetch`, and (S,) int32 each
    stacked segment's first row in it) makes every level-0 trip read only
    the neighbour rows its visited test marks new, in one kernel call over
    all (segment, row) lanes. None compiles the XLA gather of every
    frontier row.

    Returns (gids (B, t) int32 global ids (-1 past the end of real data),
    dists (B, t) base-metric root-free distances, n_b (B,), hops (B,),
    poisoned (B,) bool, hops_max ()), and with `fetch_rows` also
    rows_read (B,), the corpus rows the level-0 loops read. `hops` sums
    each row's level-0 trips over its segment lanes; `hops_max`, the
    largest trip count of any (segment, row) lane, is the trip count of
    the one batched loop that runs them all — every lane is held for that
    many trips.
    """
    n_pad = arrays.n
    base_p = arrays.metric_p

    def per_segment(arr, x, ni, al, row0):
        fetch = None if row0 is None else (fetch_rows[0], row0)
        ids, dists, nb, hops, *rows = knn_search(
            arr, x, Q, ef=ef, t=t, max_hops=max_hops,
            expand_width=expand_width, thresh=thresh, fetch_rows=fetch,
        )
        valid = ids < n_pad
        g = jnp.where(valid, ni[jnp.clip(ids, 0, n_pad - 1)], -1)
        d = jnp.where(valid & (g >= 0), dists, jnp.inf)
        # NaN/inf guard: non-finite distance on a real id -> padding
        bad = (g >= 0) & ~jnp.isfinite(d)
        pois = bad.any(axis=1)
        g = jnp.where(bad, -1, g)
        d = jnp.where(bad, jnp.inf, d)
        # entry-row probe: catches a fully poisoned segment whose beam
        # admitted nothing (docstring) — base_p is 1 or 2, so the power
        # sum needs no transcendentals
        diff = jnp.abs(Q - x[jnp.clip(arr.entry, 0, n_pad - 1)][None, :])
        entry_d = (diff if base_p == 1.0 else diff * diff).sum(axis=1)
        pois = pois | ~jnp.isfinite(entry_d)
        if al is not None:  # degraded mask: dead segment -> all padding
            g = jnp.where(al, g, -1)
            d = jnp.where(al, d, jnp.inf)
            nb = jnp.where(al, nb, jnp.zeros_like(nb))
            hops = jnp.where(al, hops, jnp.zeros_like(hops))
            rows = [jnp.where(al, r, jnp.zeros_like(r)) for r in rows]
            pois = pois & al
        return (g, d, nb, hops, pois, *rows)

    row0 = None if fetch_rows is None else fetch_rows[1]
    if alive is None:
        g, d, nb, hops, pois, *rows = jax.vmap(
            lambda arr, x, ni, r0: per_segment(arr, x, ni, None, r0)
        )(arrays, X, node_ids, row0)
    else:
        g, d, nb, hops, pois, *rows = jax.vmap(per_segment)(
            arrays, X, node_ids, alive, row0)
    b = Q.shape[0]
    g = jnp.moveaxis(g, 0, 1).reshape(b, -1)  # (B, S*t)
    d = jnp.moveaxis(d, 0, 1).reshape(b, -1)
    sd, si = jax.lax.sort((d, g), num_keys=1)
    return (si[:, :t], sd[:, :t], nb.sum(axis=0), hops.sum(axis=0),
            pois.any(axis=0), hops.max()) + tuple(r.sum(axis=0) for r in rows)


@functools.partial(jax.jit, static_argnames=("t",))
def merge_phase_lists(g_a, d_a, g_b, d_b, t: int):
    """Sort-merge probe (flag 0) and spill (flag 1) candidate lists.

    g_a/d_a are phase-A (probe) global ids and base distances, g_b/d_b the
    phase-B (spill) lists; widths may differ. Returns (gids (B, t), dists
    (B, t), flags (B, t)) — flags mark each survivor's phase for the
    per-phase N_p attribution.
    """
    g = jnp.concatenate([g_a, g_b], axis=1)
    d = jnp.concatenate([d_a, d_b], axis=1)
    flag = jnp.concatenate(
        [jnp.zeros_like(g_a), jnp.ones_like(g_b)], axis=1)
    sd, sg, sf = jax.lax.sort((d, g, flag), num_keys=1)
    return sg[:, :t], sd[:, :t], sf[:, :t]


@functools.partial(jax.jit, static_argnames=("t",))
def merge_tagged_lists(g, d, f, g_new, d_new, t: int):
    """One round_robin cascade step: merge a flag-carrying running list
    with a new segment's (spill, flag 1) list, keeping the top-t."""
    ga = jnp.concatenate([g, g_new], axis=1)
    da = jnp.concatenate([d, d_new], axis=1)
    fa = jnp.concatenate([f, jnp.ones_like(g_new)], axis=1)
    sd, sg, sf = jax.lax.sort((da, ga, fa), num_keys=1)
    return sg[:, :t], sd[:, :t], sf[:, :t]


class ShardedUHNSW:
    """Segmented U-HNSW index with streaming inserts.

    Drop-in for UHNSW at the serving layer: `search(Q, p, k)` has the same
    contract — Q (B, d) f32; p a Python float or a (B,) array (each query
    row under its own metric, DESIGN.md §6); returns (ids (B, k) int32,
    rooted dists (B, k) f32, SearchStats with per-row n_b/n_p/hops). Adds
    `add(vec)` for online insertion (O(1), delta tier; DESIGN.md §3) and
    `shard_over(rt)` for multi-device placement (segment axis over the
    mesh's data axes).

    Mixed-p batches partition two ways by base graph (G1/G2) — never one
    group per distinct p — and each side runs one traced-p program whose
    per-row results are bit-identical to the scalar-p call at that row's p.
    """

    def __init__(
        self,
        segments: SegmentedGraphs,
        data: np.ndarray,
        params: UHNSWParams | None = None,
        delta_capacity: int = 1024,
        sharded_params: "ShardedParams | None" = None,
    ):
        self.segments = segments
        self.params = params or UHNSWParams()
        self.sharded_params = sharded_params or ShardedParams()
        self.sharded_params.validate_for(segments.num_segments,
                                         self.params.t)
        # per-segment failure state machine (DESIGN.md §11): quarantined
        # segments drop out of `_alive_segments()` and every search
        # reports the exact coverage it served at
        self.health = SegmentHealthTracker(segments.num_segments)
        # per-(base graph, probe count, alive set) device sub-stacks for
        # the phase split; invalidated whenever the segment set restacks
        # (compaction) or placement changes (shard_over)
        self._phase_cache: dict = {}
        # _X_host holds only *frozen* rows (segment members); delta-resident
        # vectors live in the DeltaBuffer until compaction appends them here
        self._X_host = np.ascontiguousarray(data, dtype=np.float32)
        self.X = jnp.asarray(self._X_host)
        self.delta = DeltaBuffer(d=self._X_host.shape[1],
                                 capacity=delta_capacity)
        self._next_id = len(self._X_host)
        self._rt = None  # set by shard_over; re-applied after compaction
        self._build_method = None  # compaction builder; None = auto by size
        # lazy verification-scan caches (DESIGN.md §10): the int8 band /
        # energy-permuted view cover the *frozen* rows only (the delta
        # tier stays f32 and is scanned exactly); compaction rebuilds
        # both over the grown corpus (deterministic, so recovery lands on
        # identical bytes)
        self._band = None
        self._scan_cache = None
        # durability hook (repro.index.persist.DurableIndex): called after a
        # compaction commits, when the delta is empty — the cheap moment to
        # rotate the snapshot + WAL pair. None = no durability layer.
        self.on_compact = None

    # -- construction -------------------------------------------------------

    @classmethod
    def build(
        cls,
        data: np.ndarray,
        num_segments: int = 4,
        m: int = 16,
        params: UHNSWParams | None = None,
        seed: int = 0,
        bulk: bool | None = None,
        delta_capacity: int = 1024,
        method: str | None = None,
        sharded_params: "ShardedParams | None" = None,
    ) -> "ShardedUHNSW":
        """Partition + build. `method` selects the per-segment builder
        ("incremental" / "bulk" / "bulk_host", DESIGN.md §7; None = auto by
        segment size) and is remembered: delta compaction builds its frozen
        segments with the same method."""
        segments = build_segments(data, num_segments=num_segments, m=m,
                                  seed=seed, bulk=bulk, method=method)
        idx = cls(segments, data, params=params,
                  delta_capacity=delta_capacity,
                  sharded_params=sharded_params)
        idx._build_method = method if method is not None else (
            None if bulk is None else ("bulk" if bulk else "incremental"))
        return idx

    @property
    def n(self) -> int:
        """Total searchable points (frozen segments + delta)."""
        return self._next_id

    @property
    def dim(self) -> int:
        """Vector dimensionality served by this index."""
        return int(self._X_host.shape[1])

    @property
    def X(self) -> jax.Array:
        """(n_frozen, d) f32 device copy of the frozen rows."""
        return self._X

    @X.setter
    def X(self, value: jax.Array) -> None:
        # every placement of the frozen rows also lays out the verification
        # kernels' row source: X itself unless the TPU needs lane padding
        self._X = value
        self._X_rows = kernel_rows(value)

    @property
    def num_segments(self) -> int:
        return self.segments.num_segments

    def index_size_bytes(self, p_range_max: float = 2.0) -> int:
        if p_range_max <= 1.0:
            return sum(g.index_size_bytes() for g in self.segments.graphs1)
        return self.segments.index_size_bytes()

    # -- placement ----------------------------------------------------------

    def shard_over(self, rt) -> "ShardedUHNSW":
        """Shard the stacked segment axis over the mesh's data axes.

        Picks the first dp axis whose size divides S; replicates (no-op)
        when none does — single-device tests and uneven meshes stay valid.
        The Runtime is retained so compaction (which restacks the arrays)
        re-applies the placement.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._rt = rt
        self._phase_cache.clear()  # sub-stacks must re-derive placement
        s = self.num_segments
        axis = next((a for a in rt.dp_axes
                     if s % int(rt.mesh.shape[a]) == 0), None)
        if axis is None:
            return self

        def place(x):
            spec = P(axis, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(rt.mesh, spec))

        seg = self.segments
        for name in ("arrays1", "arrays2"):
            arr = getattr(seg, name)
            children, aux = arr.tree_flatten()
            children = jax.tree.map(place, children)
            setattr(seg, name, GraphArrays.tree_unflatten(aux, children))
        seg.X = place(seg.X)
        seg.node_ids = place(seg.node_ids)
        return self

    # -- query --------------------------------------------------------------

    def base_arrays_for(self, p: float) -> tuple[GraphArrays, float]:
        """Scalar-p base-graph pick (G1 iff p <= cutoff); mixed-p batches
        use the two-way partition in `_search_mixed` instead."""
        base = base_metric_for(p, self.params.cutoff)
        seg = self.segments
        return (seg.arrays1, 1.0) if base == 1.0 else (seg.arrays2, 2.0)

    def compressed_band(self):
        """The lazily-built int8 CompressedBand over the frozen rows
        (DESIGN.md §10); rebuilt from scratch after each compaction."""
        if self._band is None:
            from repro.index.compressed import build_band

            self._band = build_band(self.X)
        return self._band

    def _scan_view(self):
        """(x_scan, perm) energy-ordered frozen-corpus view (energy_perm)."""
        if self._scan_cache is None:
            from repro.index.compressed import energy_order

            perm = jnp.asarray(energy_order(self.X))
            self._scan_cache = (
                kernel_rows(jnp.take(self.X, perm, axis=1)), perm)
        return self._scan_cache

    def _verify_extras(self) -> dict:
        """Band / scan-view kwargs for `verify_candidates` under the
        current params (empty when both §10 features are off)."""
        prm = self.params
        if not prm.abandon:
            return {}
        if prm.compressed_band:
            return {"band": self.compressed_band()}
        if prm.energy_perm:
            x_scan, perm = self._scan_view()
            return {"x_scan": x_scan, "scan_perm": perm}
        return {}

    def search(self, Q, p, k: int):
        """Batched ANNS-U-Lp over all segments + delta.

        Q: (B, d) f32; p: Python float or (B,) array (mixed-p batch — see
        the class docstring); returns (ids (B, k) int32, rooted dists
        (B, k) f32, SearchStats).
        """
        if metrics.is_static_p(p):
            p = float(p)
            _, base_p = self.base_arrays_for(p)
            cands = self.search_stage_candidates(Q, base_p, k=k)
            return self.search_stage_finish(Q, cands, p, k)
        return self._search_mixed(Q, p, k)

    def _alive_segments(self) -> list[int]:
        """Serving segment set from the health tracker (DESIGN.md §11)."""
        return self.health.alive()

    def coverage_frac(self, alive: list[int] | None = None) -> float:
        """Exact served fraction of the corpus for an alive set: alive
        frozen rows plus the (always-served) delta tier, over all rows."""
        sizes = [g.n for g in self.segments.graphs1]
        if alive is None:
            alive = self._alive_segments()
        total = sum(sizes) + len(self.delta)
        if total <= 0:
            return 1.0
        return (sum(sizes[i] for i in alive) + len(self.delta)) / total

    def search_stage_candidates(self, Q, base_p: float,
                                k: int | None = None,
                                alive: list[int] | None = None,
                                ) -> CandidateSet:
        """Stage 1 of 2: segmented base-metric candidate generation.

        Same contract as `UHNSW.search_stage_candidates` (DESIGN.md §6):
        dispatches the policy-selected cross-segment search (module
        docstring) on the base graph named by `base_p` and returns the
        device-resident CandidateSet without a host sync, so the serving
        engine can overlap wave N+1's search with wave N's verification.
        `k` (the caller's final top-k, when known) tightens the derived
        threshold rank; None falls back to the admissible minimum.

        `alive` restricts the search to a segment subset (DESIGN.md §11);
        None serves the health tracker's current alive set. The returned
        CandidateSet carries the exact `coverage_frac` for that set and
        the per-row `poisoned` flag from the NaN/inf guard.
        """
        Q = jnp.asarray(Q, dtype=jnp.float32)
        seg = self.segments
        arrays = seg.arrays1 if base_p == 1.0 else seg.arrays2
        alive_list = (self._alive_segments() if alive is None
                      else sorted(int(i) for i in alive))
        return self._segment_candidates(arrays, Q, base_p, k=k,
                                        alive=alive_list)

    def search_stage_finish(self, Q, cands: CandidateSet, p, k: int):
        """Stage 2 of 2: verification (or base-metric skip) + delta merge.

        Unlike the monolithic index, finishing here includes the exact
        delta-tier sort-merge — delta hits need no verification, so they
        belong to this stage, and `search` composes exactly these two
        stages (bitwise parity with staged execution by construction).
        """
        Q = jnp.asarray(Q, dtype=jnp.float32)
        ids, dists, stats = self._finish_graph(Q, cands, p, k)
        if metrics.is_static_p(p):
            p = float(p)
        else:  # the delta scan takes (B,) host p
            p = np.broadcast_to(np.asarray(p, np.float32).reshape(-1),
                                (int(Q.shape[0]),))
        return self._merge_delta(Q, p, k, ids, dists, stats)

    def _finish_graph(self, Q, cands: CandidateSet, p, k: int):
        """Verification (or the base-metric skip) of the merged candidates
        with the per-phase N_p split, before the delta merge."""
        ids, dists, stats = finish_candidates(self, Q, cands, p, k)
        return ids, dists, self._phase_split(cands, stats)

    def _phase_split(self, cands: CandidateSet, stats: SearchStats):
        """`stats` with the per-phase N_p attribution (DESIGN.md §3).

        N_b splits exactly (counted per phase in the beams, carried over
        from `cands`). N_p is one merged verification pass, so it splits
        by each phase's share of the merged candidate list — the verify
        work a phase's survivors brought in. The delta tier's exact scans
        (added later in `_merge_delta`) belong to neither phase.
        """
        n_valid = (cands.ids >= 0).sum(axis=1)
        spill_frac = (jnp.asarray(cands.n_cand_spill, jnp.float32)
                      / jnp.maximum(n_valid, 1).astype(jnp.float32))
        n_p = stats.n_p.astype(jnp.float32)
        n_p_spill = n_p * spill_frac
        return stats._replace(n_p_probe=n_p - n_p_spill, n_p_spill=n_p_spill)

    def _probe_order(self) -> list[int]:
        """Prior ordering for the probe phase: largest segments first
        (they cover the most data, so their running k-th best is the
        tightest available bound), oldest first among equals — freshly
        compacted slivers probe last."""
        sizes = [g.n for g in self.segments.graphs1]
        return sorted(range(len(sizes)), key=lambda i: (-sizes[i], i))

    def _phase_stacks(self, base_p: float, probe: int,
                      alive_key: tuple | None = None):
        """Cached (probe, spill) device sub-stacks of the segment axis.

        Slicing the stacked pytrees is a handful of gathers; caching them
        per (base graph, probe count, alive set) keeps the steady-state
        query path free of per-call restacking. `alive_key` (a sorted
        tuple of alive segment indices; None = all alive) filters the
        probe order for degraded serving — dead segments are physically
        absent from the sub-stacks, so the phase searches match an index
        built from only the alive segments (DESIGN.md §11). The cache
        clears on compaction and re-placement (`shard_over`).
        """
        key = ("split", base_p, probe, alive_key)
        hit = self._phase_cache.get(key)
        if hit is not None:
            return hit
        seg = self.segments
        arrays = seg.arrays1 if base_p == 1.0 else seg.arrays2
        order = self._probe_order()
        if alive_key is not None:
            keep = set(alive_key)
            order = [i for i in order if i in keep]
        sel_a = np.asarray(order[:probe])
        sel_b = np.asarray(order[probe:])

        def take(sel):
            return (jax.tree.map(lambda x: x[sel], arrays),
                    seg.X[sel], seg.node_ids[sel], sel)

        val = (take(sel_a), take(sel_b))
        self._phase_cache[key] = val
        return val

    def _segment_stack(self, base_p: float, i: int):
        """Cached singleton sub-stack of segment `i` (round_robin turns)."""
        key = ("one", base_p, i)
        hit = self._phase_cache.get(key)
        if hit is None:
            seg = self.segments
            arrays = seg.arrays1 if base_p == 1.0 else seg.arrays2
            sel = np.asarray([i])
            hit = (jax.tree.map(lambda x: x[sel], arrays),
                   seg.X[sel], seg.node_ids[sel], sel)
            self._phase_cache[key] = hit
        return hit

    def _fetch_rows(self, sel) -> tuple:
        """`fetch_rows` of a stack of the segments `sel`: the whole index's
        row source and each segment's first row in it (cached)."""
        seg = self.segments
        key = ("row0", tuple(int(i) for i in sel))
        row0 = self._phase_cache.get(key)
        if row0 is None:
            row0 = jnp.asarray(np.asarray(sel, np.int32) * seg.X.shape[1])
            self._phase_cache[key] = row0
        return seg.beam_src(), row0

    def _stack_search(self, stack, Q, width: int, **kw):
        """`segmented_knn_search` over a stack (arrays, X, node_ids, the
        segment indices it holds), with rows_read (B,) appended: the
        fetch kernel's count where the rows are whole DMA tiles
        (hnsw.beam_fetch_on), else hops x W*m0, every trip's gather."""
        arrays, x, ni, sel = stack
        if beam_fetch_on(x.shape[-1]):
            return segmented_knn_search(arrays, x, ni, Q, expand_width=width,
                                        fetch_rows=self._fetch_rows(sel),
                                        **kw)
        out = segmented_knn_search(arrays, x, ni, Q, expand_width=width, **kw)
        return out + (out[3] * (width * arrays.adj0.shape[-1]),)

    def _segment_candidates(self, arrays, Q, base_p: float,
                            k: int | None = None,
                            alive: list[int] | None = None) -> CandidateSet:
        """Policy-dispatched cross-segment candidate generation.

        Returns the CandidateSet: n_b_probe, n_b_spill and n_cand_spill
        feed the per-phase stats split (DESIGN.md §3); threshold-free work
        is "probe", work under an inherited bound is "spill". `poisoned` is
        the per-row NaN/inf-guard flag and `coverage_frac` the exact
        served fraction for `alive` (DESIGN.md §11). `hops_max` has one
        entry per searched segment: the trip count of the beam program
        that searched it.

        `alive` (sorted segment indices; None = all) restricts the search
        to a subset: every derived quantity — candidate width t, the
        threshold rank, the probe order and count — is computed over the
        subset exactly as an index built from only those segments would
        compute it, which is what makes degraded results bitwise equal to
        the healthy-subset index (the §11 parity invariant).
        """
        prm = self.params
        sp = self.sharded_params
        s_total = self.num_segments
        alive = list(range(s_total)) if alive is None else alive
        cands = functools.partial(CandidateSet, base_p=base_p,
                                  coverage_frac=self.coverage_frac(alive))
        if not alive:
            raise RuntimeError(
                "no alive segments to search — every frozen segment is "
                "quarantined; recover from a snapshot (DESIGN.md §11) or "
                "rebuild the index")
        all_alive = len(alive) == s_total
        sizes = [g.n for g in self.segments.graphs1]
        n_frozen = sum(sizes[i] for i in alive)
        t = min(prm.t, n_frozen)
        ef = max(prm.ef or 2 * prm.t, t)
        # degenerate tiny beams can't host the full W; clamp, don't fail
        width = min(prm.expand_width, ef)
        s = len(alive)
        probe = max(1, min(sp.probe, s))
        single = s == 1 or (sp.policy == "two_phase" and probe >= s)
        if sp.policy == "independent" or single:
            if all_alive:
                mask = None
            else:  # traced mask: one compiled program serves any subset
                m = np.zeros(s_total, dtype=bool)
                m[alive] = True
                mask = jnp.asarray(m)
            stack = (arrays, self.segments.X, self.segments.node_ids,
                     np.arange(s_total))
            gids, dists, n_b, hops, pois, h_max, rows = self._stack_search(
                stack, Q, width, ef=ef, t=t, max_hops=prm.max_hops,
                alive=mask,
            )
            zero = jnp.zeros_like(n_b)
            return cands(ids=gids, base_dists=dists, n_b=n_b, hops=hops,
                         n_b_probe=n_b, n_b_spill=zero, n_cand_spill=zero,
                         poisoned=pois, hops_max=jnp.full((s,), h_max),
                         rows_read=rows)
        rank = sp.resolve_thresh_rank(t, s, k)
        alive_key = None if all_alive else tuple(alive)
        if sp.policy == "two_phase":
            stack_a, stack_b = self._phase_stacks(base_p, probe, alive_key)
            g_a, d_a, nb_a, hops_a, pois_a, hmax_a, rows_a = (
                self._stack_search(stack_a, Q, width, ef=ef, t=t,
                                   max_hops=prm.max_hops))
            thresh = d_a[:, rank - 1]
            # spill beams only contribute candidates below the bound, so
            # their width floors at the caller's k (not the global t) —
            # phase A already guarantees t merged candidates exist. The
            # floor also includes `rank`: a rank-r bound can admit up to r
            # merged-list entrants per segment, and a narrower beam would
            # silently drop some — at thresh_rank=t this keeps the
            # conservative variant's ids==independent contract honest even
            # on ef=t builds (ef*ef_shrink < t there).
            ef_b = max(k or 1, rank, int(round(ef * sp.ef_shrink)))
            t_b = min(t, ef_b)
            g_b, d_b, nb_b, hops_b, pois_b, hmax_b, rows_b = (
                self._stack_search(stack_b, Q, min(width, ef_b), ef=ef_b,
                                   t=t_b, max_hops=prm.max_hops,
                                   thresh=thresh))
            gids, dists, flags = merge_phase_lists(g_a, d_a, g_b, d_b, t)
            n_cand_spill = ((flags == 1) & (gids >= 0)).sum(axis=1)
            hops_max = jnp.concatenate([jnp.full((probe,), hmax_a),
                                        jnp.full((s - probe,), hmax_b)])
            return cands(ids=gids, base_dists=dists, n_b=nb_a + nb_b,
                         hops=hops_a + hops_b, n_b_probe=nb_a, n_b_spill=nb_b,
                         n_cand_spill=n_cand_spill.astype(jnp.int32),
                         poisoned=pois_a | pois_b, hops_max=hops_max,
                         rows_read=rows_a + rows_b)
        # round_robin: single-phase cascade — every turn inherits the
        # running merged rank-r best of all earlier turns as its bound
        order = [i for i in self._probe_order() if i in set(alive)]
        gids = dists = flags = pois = None
        nb_probe = nb_spill = hops = rows = None
        hops_max = []
        for turn, i in enumerate(order):
            thresh = dists[:, rank - 1] if turn else None
            g_i, d_i, nb_i, hops_i, pois_i, hmax_i, rows_i = (
                self._stack_search(self._segment_stack(base_p, i), Q, width,
                                   ef=ef, t=t, max_hops=prm.max_hops,
                                   thresh=thresh))
            hops_max.append(hmax_i)
            if turn == 0:
                gids, dists, pois = g_i, d_i, pois_i
                flags = jnp.zeros_like(g_i)
                nb_probe, nb_spill, hops = nb_i, jnp.zeros_like(nb_i), hops_i
                rows = rows_i
            else:
                gids, dists, flags = merge_tagged_lists(
                    gids, dists, flags, g_i, d_i, t)
                nb_spill = nb_spill + nb_i
                hops = hops + hops_i
                rows = rows + rows_i
                pois = pois | pois_i
        n_cand_spill = ((flags == 1) & (gids >= 0)).sum(axis=1)
        return cands(ids=gids, base_dists=dists, n_b=nb_probe + nb_spill,
                     hops=hops, n_b_probe=nb_probe, n_b_spill=nb_spill,
                     n_cand_spill=n_cand_spill.astype(jnp.int32),
                     poisoned=pois, hops_max=jnp.stack(hops_max),
                     rows_read=rows)

    def _graph_search_base_vec(self, Q, p_vec, k: int, base_p: float):
        """One homogeneous-base sub-batch with per-row p (traced-p program),
        mirroring UHNSW._search_base_vec over the segmented candidates;
        the delta merge waits for the whole mixed batch."""
        Q = jnp.asarray(Q, dtype=jnp.float32)
        cands = self.search_stage_candidates(Q, base_p, k=k)
        return self._finish_graph(Q, cands, p_vec, k)

    def _search_mixed(self, Q, p, k: int):
        """Mixed-p batch: two-way G1/G2 partition, then one delta merge."""
        ids, dists, stats = two_way_mixed_search(
            Q, p, k, self.params.cutoff, self._graph_search_base_vec
        )
        p_arr = np.broadcast_to(np.asarray(p, np.float32).reshape(-1),
                                np.shape(stats.base_p))
        return self._merge_delta(Q, p_arr, k, ids, dists, stats)

    def _merge_delta(self, Q, p, k, ids, dists, stats: SearchStats):
        """Sort-merge exact delta-tier hits into the verified top-k.

        With abandonment on, the delta scan inherits the verified top-k's
        k-th-best as its abandon threshold (DESIGN.md §8): buffered
        vectors that provably cannot enter the top-k skip their remaining
        dimension blocks. Each n_p-weighted counter of `stats`
        (`n_dim_frac`, `n_scan_blocks`, `n_f32_rows_frac`, `n_band_frac`)
        becomes the N_p-weighted mean of the graph-verify value and the
        delta scan's: its dimensions scanned, its blocks entered, and its
        rows as full-f32 gathers with no band traffic (the delta tier has
        no compressed replica, DESIGN.md §10). Delta scans join the N_p
        total but neither phase (they are the mutable tier, not segment
        work). Returns (ids, dists, stats).
        """
        if not len(self.delta):
            return ids, dists, stats
        n_delta = len(self.delta)
        d = self.X.shape[1]
        # scalar basic-p scans have no transcendental work to skip and
        # the no-thresh path keeps the 1-D shared-ids pairwise form
        # (one gather for all queries, MXU matmul for p=2) — strictly
        # cheaper than a per-query blocked scan
        basic = metrics.is_static_p(p) and float(p) in (1.0, 2.0)
        thresh = dists[:, k - 1] if (self.params.abandon and not basic) \
            else None
        d_ids, d_dists, d_nd = self.delta.search(
            jnp.asarray(Q, dtype=jnp.float32), p,
            interpret=self.params.interpret, thresh=thresh,
            block_d=self.params.abandon_block_d,
        )
        all_ids = jnp.concatenate([ids, d_ids], axis=1)
        all_d = jnp.concatenate([dists, d_dists], axis=1)
        sd, si = jax.lax.sort((all_d, all_ids), num_keys=1)
        bd = self.params.abandon_block_d or pick_abandon_block_d(d)
        # each weighted counter's sum over the delta rows (None: nothing)
        delta_sums = {
            "n_dim_frac": (d_nd.sum(axis=1).astype(jnp.float32)
                           / (n_delta * d)) * n_delta,
            "n_scan_blocks": ((d_nd + bd - 1) // bd).sum(axis=1).astype(
                jnp.float32),
            "n_f32_rows_frac": 1.0 * n_delta,
            "n_band_frac": None,
        }
        n_p = stats.n_p
        denom = jnp.maximum(n_p + n_delta, 1)
        merged = {}
        for name, extra in delta_sums.items():
            weighted = getattr(stats, name) * n_p
            merged[name] = (weighted if extra is None
                            else weighted + extra) / denom
        # exact-Lp scans count toward N_p
        return si[:, :k], sd[:, :k], stats._replace(n_p=n_p + n_delta,
                                                    **merged)

    def modeled_query_cost(self, stats: SearchStats, p, d: int) -> dict:
        """Paper Eq. 1 cost split — the shared core/uhnsw helper."""
        return modeled_query_cost(stats, p, d)

    # -- segment health (DESIGN.md §11) --------------------------------------

    def canary_probe(self, seg: int, n_probes: int = 2,
                     seed: int = 0) -> bool:
        """One canary health check of segment `seg`: self-query a few of
        its own members against *only* that segment. A healthy segment
        must return each member as its own top-1 at a finite distance
        with the NaN/inf guard clean — restored-but-corrupt rows, a
        broken graph, or lingering poison all fail the probe. Records the
        outcome with the health tracker (re-admission requires
        `HealthPolicy.probe_successes` consecutive passes) and returns it.
        """
        ids = np.asarray(self.segments.global_ids[seg])
        rng = np.random.default_rng(seed * 1009 + seg)
        pick = rng.choice(len(ids), size=min(n_probes, len(ids)),
                          replace=False)
        gids = ids[np.sort(pick)]
        q = self._X_host[gids]
        cands = self.search_stage_candidates(q, 2.0, k=1, alive=[seg])
        top = np.asarray(cands.ids[:, 0])
        top_d = np.asarray(cands.base_dists[:, 0])
        pois = np.asarray(cands.poisoned)
        ok = bool(np.array_equal(top, gids) and np.all(np.isfinite(top_d))
                  and not pois.any())
        self.health.record_probe(seg, ok)
        return ok

    # -- streaming inserts --------------------------------------------------

    def add(self, vec: np.ndarray) -> int:
        """Insert one vector online. Returns its (stable) global id.

        O(1): the vector lands in the delta buffer only; the frozen data
        array grows once per compaction, not once per insert.
        """
        v = np.asarray(vec, dtype=np.float32).reshape(-1)
        # validate before touching any state: a failed add must not burn an
        # id (ids index data rows — a gap would desync every later insert)
        d = self._X_host.shape[1]
        if v.shape[0] != d:
            raise ValueError(f"vector has dim {v.shape[0]}, index has dim {d}")
        gid = self._next_id
        self._next_id += 1
        self.delta.add(v, gid)
        if self.delta.full:
            self.compact()
        return gid

    def get_vector(self, gid: int) -> np.ndarray:
        """Look up a vector by global id, whichever tier it lives in."""
        if 0 <= gid < len(self._X_host):
            return self._X_host[gid]
        pos = gid - len(self._X_host)
        if 0 <= pos < len(self.delta):
            return self.delta.vectors()[pos]
        raise IndexError(f"id {gid} not in index (n={self.n})")

    def compact(self):
        """Freeze the delta buffer into a new segment (graphs + restack)."""
        if not len(self.delta):
            return
        vecs, ids = self.delta.drain()
        assert int(ids[0]) == len(self._X_host)  # ids stay row-aligned
        self._X_host = np.concatenate([self._X_host, vecs], axis=0)
        m = self.segments.graphs1[0].m
        g1, g2 = build_segment_pair(vecs, m=m, seed=int(ids[0]) + 1,
                                    method=self._build_method)
        self.segments.append(g1, g2, ids)
        # the new segment starts HEALTHY; existing quarantines survive the
        # compaction (the rows they cover are still suspect)
        self.health.resize(self.num_segments)
        self._phase_cache.clear()  # restack invalidates cached sub-stacks
        self.X = jnp.asarray(self._X_host)
        # the frozen corpus grew: quantize the new rows into a fresh band
        # (full deterministic rebuild — scales/radii/perm may all shift)
        self._band = None
        self._scan_cache = None
        if self._rt is not None:  # restacking dropped the device placement
            self.shard_over(self._rt)
        if self.on_compact is not None:
            self.on_compact()
