"""U-HNSW (paper Algorithm 1): ANNS under universal Lp metrics.

Query processing for (q, p):
  1. Candidate generation — select G1 (L1) if p <= 1.4 else G2 (L2), run the
     batched JAX beam search (repro.core.hnsw) for the top-t candidates under
     the base metric. t = 300 by default (paper §3.2).
  2. Candidate verification — re-rank candidates under exact Lp, popping
     batches of kappa and early-terminating when the running top-K stabilizes:
     |R_new ∩ R| / K >= tau  (tau = target recall + 0.02 = 0.92 default).

Batched SPMD adaptation (DESIGN.md §2): the verification loop runs with a
vectorized convergence mask — queries that have already terminated stop
counting Lp evaluations (their N_p is frozen), and the `lax.while_loop`
exits when every query in the shard is done. This preserves the paper's
per-query N_p savings while staying jittable.

Special p values: for p == 1 or p == 2 the query *is* a base-metric search
(paper §3 preamble) and the verification step is skipped entirely.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.core.build import HNSWGraph, build_hnsw
from repro.core.hnsw import GraphArrays, beam_fetch_on, knn_search
from repro.core.metrics import base_metric_for


@dataclass(frozen=True)
class UHNSWParams:
    """Query-time parameters (paper Algorithm 1 + §3.2).

    Attributes:
      t: candidate set size fed to verification (paper §3.2; default 300).
      tau: early-termination threshold |R_new ∩ R| / K (target recall
        + 0.02; paper §3.1).
      kappa: verification batch size; None -> K // 2 (paper §3.1).
      cutoff: base-index selection crossover — G1 (L1) serves p <= cutoff,
        G2 (L2) the rest (paper Fig. 2). Applies per *query row* in a
        mixed-p batch (DESIGN.md §6).
      ef: beam width for candidate generation; None -> 2t.
      max_hops: hard cap on while_loop trips per layer (safety bound).
      expand_width: W-way multi-expansion in the level-0 beam
        (DESIGN.md §2.1); 1 = classic HNSW.
      interpret: exact-Lp scoring backend override, forwarded to
        `kernels.ops.lp_gather_distance` (DESIGN.md §2.1): None =
        backend-aware (fused Pallas kernel on TPU, jnp reference
        elsewhere), True = Pallas kernel in interpret mode (CPU parity
        testing), False = compiled Pallas kernel.
      abandon: early-abandoning blocked-dimension verification
        (DESIGN.md §8, default on). Each kappa batch carries the running
        k-th-best power sum as a per-query threshold; candidates whose
        partial sum over scanned dimension blocks — or whose provable
        base-distance lower bound — already exceeds it skip all remaining
        dimension work. Exact: abandoned candidates provably cannot enter
        the top-k, so returned ids/dists match the full-dimension path
        (`False` reproduces the pre-abandonment path bit-for-bit). The
        skip is real on the TPU kernel; the off-TPU jnp reference
        computes-then-masks, so CPU-bound deployments chasing wall-clock
        (not Eq. 1 dimension-work) may prefer `abandon=False`.
      abandon_block_d: dimension-block width for the abandoning scan;
        None = auto (`kernels.ops.pick_abandon_block_d`: 32 when it
        divides d, the TPU sublane-friendly default).
      compressed_band: two-band verification over the int8 compressed
        storage band (DESIGN.md §10, default off). Each kappa batch is
        first screened against the running k-th best using certified
        lower bounds from the quantized replica (index/compressed.py);
        only survivors issue f32 row gathers for the exact rerank.
        Returned ids *and* dists are bitwise-identical to the
        uncompressed path (a screened candidate's true distance provably
        exceeds the running k-th best, and survivors are rescored from
        the same f32 rows); `False` restores the pre-band program
        bit-for-bit. Requires abandon=True (the screen is the abandon
        path's storage-side sibling); `SearchStats.n_f32_rows_frac` /
        `n_band_frac` report the traffic split.
      energy_perm: scan coordinates in energy order (decreasing
        per-coordinate variance) inside the abandoning verification
        (DESIGN.md §10). Lp is coordinate-separable, so a fixed
        permutation leaves every distance mathematically unchanged;
        front-loading the mass makes the §8 suffix bounds go dead after
        fewer blocks at small p. Surviving candidates' sums reassociate
        across the permuted dimension order, so dists may wobble by
        float-accumulation ulps vs the unpermuted scan (ids ties
        included); default off to preserve the bit-exact legacy program.
    """

    t: int = 300          # candidate set size
    tau: float = 0.92     # early-termination threshold (target recall + 0.02)
    kappa: int | None = None  # verification batch size; None -> K // 2 (§3.1)
    cutoff: float = 1.4   # base-index selection crossover (Fig. 2)
    ef: int | None = None  # beam width for candidate generation; None -> 2t
    max_hops: int = 4096
    expand_width: int = 1  # W-way multi-expansion in the level-0 beam
                           # (DESIGN.md §2 hot path); 1 = classic HNSW
    interpret: bool | None = None  # exact-Lp kernel dispatch override
    abandon: bool = True  # early-abandoning verification (DESIGN.md §8)
    abandon_block_d: int | None = None  # dimension-block width; None = auto
    compressed_band: bool = False  # int8 screen + f32 rerank (DESIGN.md §10)
    energy_perm: bool = False  # energy-ordered abandon scan (DESIGN.md §10)


class CandidateSet(NamedTuple):
    """Device-resident output of the candidate-generation stage.

    The two-stage serving engine (repro.retrieval.engine, DESIGN.md §6)
    dispatches candidate generation and verification as separate device
    calls so the scheduler can pipeline wave N+1's base search against
    wave N's verification. Everything here stays on device between the
    stages; `base_p` names the base metric (1.0 = G1 / 2.0 = G2) the
    candidates were generated under.
    """

    ids: jax.Array         # (B, t) int32, ascending by base-metric distance
    base_dists: jax.Array  # (B, t) root-free base-metric power sums
    n_b: jax.Array         # (B,) base-metric evaluation counts (Eq. 1)
    hops: jax.Array        # (B,) level-0 while_loop trips
    base_p: float          # which base metric generated the candidates
    # cross-segment phase split (ShardedUHNSW two_phase / round_robin,
    # DESIGN.md §3): probe = threshold-free evaluations (phase A / the
    # first cascade turn), spill = evaluations under an inherited pruning
    # bound. n_b == n_b_probe + n_b_spill always; monolithic and
    # independent-policy candidate generation is all probe.
    n_b_probe: jax.Array | None = None   # (B,) defaults to n_b downstream
    n_b_spill: jax.Array | float = 0.0   # (B,) or scalar zero
    n_cand_spill: jax.Array | float = 0.0  # (B,) spill-phase survivors in
                                           # the merged candidate list
    # degraded-coverage serving (DESIGN.md §11). The sharded index's
    # query-time NaN/inf guard masks any candidate whose gathered base
    # distance is non-finite (it can never reach a top-k) and raises the
    # per-row flag so the engine can attribute the poison to a segment.
    poisoned: jax.Array | float = 0.0  # (B,) 1.0 where the guard tripped
    coverage_frac: float = 1.0  # exact served fraction of the corpus
                                # under the alive mask these candidates
                                # were generated with (host-side float)
    # beam-loop occupancy: one entry per segment lane of a row, the trip
    # count of the batched level-0 loop that searched it (its largest
    # lane `hops`). A batched while_loop runs every lane until the slowest
    # finishes, so a row's lanes occupy hops_max.sum() lane-trips of which
    # `hops` did work.
    hops_max: jax.Array | int = 0
    rows_read: jax.Array | int = 0  # (B,) corpus rows the level-0 loops
        # read, summed over segment lanes: hops x W*m0 where each trip
        # gathers its whole frontier, the level-0 share of n_b where the
        # fetch kernel reads only the new rows (hnsw.beam_fetch_on)


class SearchStats(NamedTuple):
    """The one carrier of a search's counters, from the verifier to the
    serving engine's stats.

    A new per-row counter is named here (with its default), in the code
    that computes it, and in `retrieval.engine.accumulate_stats`; a
    candidate-generation one also in `with_candidates`, a fraction
    weighted by n_p also in `ShardedUHNSW._merge_delta`. Per-row
    fields are (B,) device arrays, or a Python scalar that holds for every
    row; `ROW_FIELDS` lists them. `SKIPPED` holds the value each
    verification counter takes on a row whose p is its base metric (the
    skip, paper §3 preamble): the skip paths and `mask_base_rows` read it.
    """

    n_b: jax.Array | int = 0  # (B,) base-metric Q2D evaluation counts
    n_p: jax.Array | int = 0  # (B,) Lp Q2D evaluation counts
    iterations: jax.Array | int = 0  # () verification loop iterations
    base_p: float | np.ndarray = 1.0  # which base metric generated
                                # candidates: scalar for a single-p batch,
                                # (B,) array for a mixed-p batch (§6)
    hops: jax.Array | int = 0  # (B,) level-0 while_loop trips (one trip
                               # expands up to expand_width beam entries)
    n_dim_frac: jax.Array | float = 1.0  # (B,) fraction of verification
        # dimension-work actually scanned (DESIGN.md §8): the early-
        # abandoning path skips dimension blocks of candidates already
        # beaten by the running k-th best, so Eq. 1's effective T_p is
        # n_dim_frac * T_p. 1.0 on the full-dimension / base-metric-skip
        # paths. Counted over non-converged rows only, mirroring N_p.
    # cross-segment phase split (DESIGN.md §3). Invariants:
    # n_b == n_b_probe + n_b_spill; n_p_probe + n_p_spill == the graph-
    # verify share of n_p (delta-tier exact scans are neither phase). The
    # N_p split attributes verification work to each phase by its share of
    # merged candidates — probe-phase work is what a monolithic index
    # would also have paid; spill-phase work is the sharding overhead the
    # inherited threshold is squeezing out. Monolithic searches leave the
    # defaults (all probe, zero spill).
    n_b_probe: jax.Array | float | None = None  # None -> equals n_b
    n_b_spill: jax.Array | float = 0.0
    n_p_probe: jax.Array | float | None = None  # None -> equals n_p
    n_p_spill: jax.Array | float = 0.0
    n_f32_rows_frac: jax.Array | float = 1.0  # (B,) fraction of verified
        # candidates whose full f32 rows were actually gathered. The
        # two-band scan (DESIGN.md §10) screens candidates against the
        # compressed band first, so only (first-k + screen survivors)
        # rows hit f32 HBM: gathered f32 bytes = n_f32_rows_frac * n_p *
        # 4d. 1.0 everywhere else (every scored candidate cost a full-row
        # gather, even if the §8 scan then abandoned dimensions).
    n_band_frac: jax.Array | float = 0.0  # (B,) int8 band dimensions
        # scanned by the compressed screen, over n_p * d — the band-side
        # byte traffic (1 byte/dim vs 4 on the f32 side): bytes ratio
        # vs the uncompressed path = n_f32_rows_frac + n_band_frac / 4.
        # 0.0 when no compressed band is in play.
    # degraded-coverage serving (DESIGN.md §11): quarantined segments are
    # masked out of the search, and every result says exactly how much of
    # the corpus it covered. coverage_frac is exact — (alive frozen rows +
    # delta rows) / total rows, computed host-side from the health tracker
    # at candidate-generation time. Monolithic searches always report 1.0.
    coverage_frac: float = 1.0
    poisoned: jax.Array | float = 0.0  # (B,) 1.0 where the query-time
        # NaN/inf guard masked non-finite gathered distances (the engine
        # bisects this back to a segment and quarantines it)
    hops_max: jax.Array | int = 0  # CandidateSet.hops_max, carried
        # through stage B (0 where no single candidate set fed the stats)
    n_scan_blocks: jax.Array | float = 0.0  # (B,) mean dimension blocks
        # the abandoning scan entered per verified candidate (DESIGN.md
        # §8): 0 for a candidate abandoned at entry, ceil(d / block_d) for
        # one scored whole (full-dimension scoring enters every block).
        # Each block past the first is one mid-scan abandonment check.
        # Weighted by n_p like n_dim_frac; 0.0 where nothing was verified.
    rows_read: jax.Array | int = 0  # (B,) CandidateSet.rows_read
    n_verify_rows: jax.Array | int = 0  # (B,) candidate rows the
        # abandoning verification's kernels copied: the valid ids of the
        # first k, plus those of every kappa batch in which the row ran
        # its scan (some candidate alive at entry). Sentinel ids and the
        # kernels' lane padding copy nothing. 0 on the base-metric skip,
        # and on the paths that do not count it (abandon=False, the
        # two-band scan); the delta tier's scan is not in it.

    ROW_FIELDS = ("n_b", "n_p", "hops", "n_dim_frac", "n_b_probe",
                  "n_b_spill", "n_p_probe", "n_p_spill", "n_f32_rows_frac",
                  "n_band_frac", "poisoned", "n_scan_blocks", "rows_read",
                  "n_verify_rows")
    SKIPPED = {"n_p": 0, "n_dim_frac": 1.0, "n_f32_rows_frac": 1.0,
               "n_band_frac": 0.0, "n_scan_blocks": 0.0, "n_verify_rows": 0}

    @property
    def degraded(self) -> bool:
        """Served below full coverage (some segment was quarantined)."""
        return self.coverage_frac < 1.0

    def row(self, name: str):
        """Per-row field `name`, a probe split's None read as its total."""
        value = getattr(self, name)
        if value is None:  # n_b_probe / n_p_probe: all work was probe
            return getattr(self, name.removesuffix("_probe"))
        return value

    def phase_n_b(self):
        """(probe, spill) N_b split with the None default resolved."""
        return self.row("n_b_probe"), self.n_b_spill

    def phase_n_p(self):
        """(probe, spill) N_p split with the None default resolved."""
        return self.row("n_p_probe"), self.n_p_spill

    def with_candidates(self, cands: CandidateSet) -> "SearchStats":
        """This record with the candidate-generation fields of `cands`."""
        return self._replace(
            n_b=cands.n_b, hops=cands.hops, base_p=cands.base_p,
            n_b_probe=cands.n_b_probe, n_b_spill=cands.n_b_spill,
            poisoned=cands.poisoned, coverage_frac=cands.coverage_frac,
            hops_max=cands.hops_max, rows_read=cands.rows_read)

    def host_rows(self, n: int) -> "SearchStats":
        """The record on the host: every per-row field as n float64 rows
        (a scalar repeated; padding rows past n dropped), `hops_max` as a
        flat float64 array and `coverage_frac` as a float."""
        def rows(x):
            x = np.asarray(x, dtype=np.float64)
            return x[:n] if x.ndim else np.full(n, float(x))

        return self._replace(
            **{f: rows(self.row(f)) for f in self.ROW_FIELDS},
            hops_max=np.asarray(self.hops_max, np.float64).reshape(-1),
            coverage_frac=float(self.coverage_frac))


def skipped_stats(cands: CandidateSet) -> SearchStats:
    """Stats of a batch whose p is its base metric: no row verifies, and
    each verification counter takes its `SearchStats.SKIPPED` value."""
    skip = dict(SearchStats.SKIPPED)
    skip["n_p"] = jnp.full_like(cands.n_b, skip["n_p"])  # callers sum it
    return SearchStats(**skip).with_candidates(cands)


def _verify_impl(
    Q: jax.Array,         # (B, d)
    cand_ids: jax.Array,  # (B, t) sorted ascending by base-metric distance
    X: jax.Array,         # (n, d)
    p,                    # static float, or traced (B,) f32
    k: int,
    kappa: int,
    tau: float,
    interpret: bool | None,
):
    B, t = cand_ids.shape
    n_batches = max((t - k) // kappa, 0)
    # the root broadcast: scalar p applies as-is, per-row p gains a column
    p_col = p if metrics.is_static_p(p) else p[:, None]

    # Imported at trace time (not module scope): repro.core.__init__ pulls in
    # this module, so a top-level kernels import here would make the
    # repro.kernels <-> repro.core import order matter.
    from repro.kernels.ops import lp_gather_distance

    def lp_block(ids):
        """Exact Lp distances for a candidate id block; padding -> inf.

        Routed through the single dispatch entry point (kernels/ops.py):
        fused gather+distance Pallas kernel on TPU, jnp reference off-TPU.
        """
        return lp_gather_distance(Q, ids, X, p, root=False,
                                  interpret=interpret)

    def topk_merge(ids_a, d_a, ids_b, d_b):
        ids = jnp.concatenate([ids_a, ids_b], axis=1)
        d = jnp.concatenate([d_a, d_b], axis=1)
        sd, si = jax.lax.sort((d, ids), num_keys=1)
        return si[:, :k], sd[:, :k]

    # line 7: R <- first K points of C (their Lp distances count toward N_p)
    first = cand_ids[:, :k]
    r_dist = lp_block(first)
    r_dist, r_ids = jax.lax.sort((r_dist, first), num_keys=1)
    n_p0 = jnp.full((B,), k, dtype=jnp.int32)

    if n_batches == 0:
        return r_ids, metrics._root(r_dist, p_col), n_p0, jnp.int32(0)

    def cond(s):
        i, _, _, done, _ = s
        return (i < n_batches) & ~jnp.all(done)

    def body(s):
        i, r_ids, r_dist, done, n_p = s
        start = k + i * kappa
        batch = jax.lax.dynamic_slice(cand_ids, (0, start), (B, kappa))
        bd = lp_block(batch)  # (B, kappa) exact Lp, padding -> inf
        new_ids, new_dist = topk_merge(r_ids, r_dist, batch, bd)
        # |R_new ∩ R| via id-equality (ids are unique per query)
        inter = (new_ids[:, :, None] == r_ids[:, None, :]).any(-1).sum(-1)
        ratio = inter.astype(jnp.float32) / k
        newly_done = ratio >= tau
        keep = done[:, None]
        r_ids = jnp.where(keep, r_ids, new_ids)
        r_dist = jnp.where(keep, r_dist, new_dist)
        n_p = n_p + jnp.where(done, 0, kappa)
        return (i + 1, r_ids, r_dist, done | newly_done, n_p)

    state = (jnp.int32(0), r_ids, r_dist, jnp.zeros((B,), bool), n_p0)
    iters, r_ids, r_dist, done, n_p = jax.lax.while_loop(cond, body, state)
    return r_ids, metrics._root(r_dist, p_col), n_p, iters


def _verify_abandon_impl(
    Q: jax.Array,          # (B, d)
    cand_ids: jax.Array,   # (B, t) sorted ascending by base-metric distance
    cand_base: jax.Array,  # (B, t) base-metric power sums (beam distances)
    X: jax.Array,          # (n, d)
    p,                     # static float, or traced (B,) f32
    k: int,
    kappa: int,
    tau: float,
    base_p: float,
    interpret: bool | None,
    block_d: int | None,
    x_scan: jax.Array | None = None,  # (n, d) energy-permuted corpus view
    perm: jax.Array | None = None,    # (d,) the permutation (x_scan order)
):
    """Threshold-propagating early-abandoning verification (DESIGN.md §8).

    Same convergence protocol as `_verify_impl`, but each kappa batch
    passes the running k-th-best power sum into the abandoning kernel as
    a per-query threshold (frozen rows pass -inf, skipping their work
    entirely), and the full (k + kappa) `lax.sort` merge becomes a
    masked `lax.top_k` merge — abandoned candidates are +inf, so top_k's
    lowest-index tie rule selects exactly what the stable sort did.
    Returns the extra `n_dim_frac` (B,) — scanned dimension-work fraction
    — and the mean dimension blocks entered per verified candidate (B,),
    counted only where the last block is ragged: where block_d divides d
    a candidate's blocks are its scanned dimensions over block_d, so
    `verify_candidates` reads them off `n_dim_frac` and this program
    stays free of the count (None). Last, the candidate rows the kernels
    copied (B,) int32 (`SearchStats.n_verify_rows`): a batch's valid ids
    count where the row ran its scan, which it did iff some candidate
    scanned a dimension (nd > 0).

    When (x_scan, perm) are given, the blocked scan runs over the
    energy-ordered corpus view (UHNSWParams.energy_perm, DESIGN.md §10):
    Lp is coordinate-separable, so permuting q and x identically leaves
    every distance mathematically unchanged while the high-variance
    coordinates land in the earliest blocks and trip the abandon
    thresholds sooner. The first-k scoring stays on the original (Q, X)
    so the starting R is bitwise-identical either way; surviving
    candidates' sums reassociate across the permuted order (ulp wobble
    covered by the kernel contract's float tolerance).
    """
    B, t = cand_ids.shape
    d = Q.shape[1]
    n_batches = max((t - k) // kappa, 0)
    p_col = p if metrics.is_static_p(p) else p[:, None]

    from repro.kernels.ops import (
        lp_gather_abandon,
        lp_gather_distance,
        pick_abandon_block_d,
    )

    width = block_d or pick_abandon_block_d(d)
    ragged = d % width != 0
    Qs = Q if perm is None else jnp.take(Q, perm, axis=1)
    Xs = X if x_scan is None else x_scan

    # line 7: R <- first K points of C, scored full-dimension (no threshold
    # exists yet; these are also the rows the abandon path must match
    # bit-for-bit so both paths start from the identical R).
    first = cand_ids[:, :k]
    r_dist = lp_gather_distance(Q, first, X, p, root=False,
                                interpret=interpret)
    r_dist, r_ids = jax.lax.sort((r_dist, first), num_keys=1)
    n_p0 = jnp.full((B,), k, dtype=jnp.int32)
    ones = jnp.ones((B,), jnp.float32)
    n = X.shape[0]

    def n_valid(ids):
        return ((ids >= 0) & (ids < n)).sum(axis=1, dtype=jnp.int32)

    rows0 = n_valid(first)

    if n_batches == 0:
        return (r_ids, metrics._root(r_dist, p_col), n_p0, jnp.int32(0),
                ones, None, rows0)

    dim0 = ones * (k * d)
    # the first k rows were scored whole: every block, as in dim0
    blocks0 = (ones * (k * -(-d // width)),) if ragged else ()

    def cond(s):
        i, _, _, done, *_ = s
        return (i < n_batches) & ~jnp.all(done)

    def body(s):
        i, r_ids, r_dist, done, n_p, dim_scan, rows, *blocks = s
        start = k + i * kappa
        batch = jax.lax.dynamic_slice(cand_ids, (0, start), (B, kappa))
        bbase = jax.lax.dynamic_slice(cand_base, (0, start), (B, kappa))
        # threshold propagation: the current k-th best power sum bounds
        # what can still enter R; frozen rows abandon everything at entry
        thresh = jnp.where(done, -jnp.inf, r_dist[:, k - 1])
        bd, nd = lp_gather_abandon(
            Qs, batch, Xs, thresh, bbase, p, base_p=base_p,
            interpret=interpret, block_d=block_d,
        )
        # masked top-k merge (abandoned candidates are +inf): lax.top_k
        # prefers the lower index on ties, matching the stable sort's
        # concat-order preference, so selection is identical to the
        # legacy (k + kappa) lax.sort at a fraction of the work.
        all_d = jnp.concatenate([r_dist, bd], axis=1)
        all_i = jnp.concatenate([r_ids, batch], axis=1)
        neg, sel = jax.lax.top_k(-all_d, k)
        new_dist = -neg
        new_ids = jnp.take_along_axis(all_i, sel, axis=1)
        inter = (new_ids[:, :, None] == r_ids[:, None, :]).any(-1).sum(-1)
        ratio = inter.astype(jnp.float32) / k
        newly_done = ratio >= tau
        keep = done[:, None]
        r_ids = jnp.where(keep, r_ids, new_ids)
        r_dist = jnp.where(keep, r_dist, new_dist)
        n_p = n_p + jnp.where(done, 0, kappa)
        dim_scan = dim_scan + jnp.where(
            done, 0.0, nd.sum(axis=1).astype(jnp.float32))
        rows = rows + jnp.where((nd > 0).any(axis=1), n_valid(batch), 0)
        if ragged:
            # a candidate entered every block it scanned any dimension of
            entered = ((nd + width - 1) // width).sum(axis=1).astype(
                jnp.float32)
            blocks = [blocks[0] + jnp.where(done, 0.0, entered)]
        return (i + 1, r_ids, r_dist, done | newly_done, n_p, dim_scan,
                rows, *blocks)

    state = (jnp.int32(0), r_ids, r_dist, jnp.zeros((B,), bool), n_p0,
             dim0, rows0, *blocks0)
    iters, r_ids, r_dist, done, n_p, dim_scan, rows, *blocks = \
        jax.lax.while_loop(cond, body, state)
    # the denominator needs no separate carry: n_p accrues kappa under
    # exactly the mask dim_scan uses, so total offered work == n_p * d
    return (r_ids, metrics._root(r_dist, p_col), n_p, iters,
            dim_scan / (n_p.astype(jnp.float32) * d),
            blocks[0] / n_p.astype(jnp.float32) if ragged else None, rows)


def _verify_two_band_impl(
    Q: jax.Array,          # (B, d) original coordinate order
    Qp: jax.Array,         # (B, d) band (energy-permuted) coordinate order
    cand_ids: jax.Array,   # (B, t) sorted ascending by base-metric distance
    cand_base: jax.Array,  # (B, t) base-metric power sums (beam distances)
    X: jax.Array,          # (n, d) f32 exact rows
    codes: jax.Array,      # (n, d) int8 compressed band (band coord order)
    scale: jax.Array,      # (d,) f32 dequant scales (band order)
    radius: jax.Array,     # (d,) f32 max dequant error (band order)
    p,                     # static float, or traced (B,) f32
    k: int,
    kappa: int,
    tau: float,
    base_p: float,
    interpret: bool | None,
    block_d: int | None,
):
    """Two-band verification: int8 screen, then exact f32 rerank of the
    survivors (DESIGN.md §10).

    Same convergence protocol as `_verify_abandon_impl`, but each kappa
    batch first runs the compressed-band screen (`lp_gather_screen`):
    candidates whose certified lower bound already exceeds the running
    k-th best are dropped *before* any f32 row gather; only survivors hit
    f32 HBM, via `lp_gather_distance` on the keep-masked id block.

    Bitwise parity with the uncompressed paths, by construction: a
    screened candidate's true power sum provably exceeds the running
    k-th best (the bound is admissible and the kill strict), so it could
    never enter R; survivors are rescored full-dimension from the same
    f32 rows by the same elementwise-independent kernel, so ids AND
    dists match `abandon=False` exactly (the same masked top_k merge as
    the §8 path keeps selection identical to the stable sort).

    Returns (ids, rooted dists, n_p, iters, n_dim_frac, n_f32_rows_frac,
    n_band_frac) — the last two are the SearchStats traffic counters.
    """
    B, t = cand_ids.shape
    d = Q.shape[1]
    n_batches = max((t - k) // kappa, 0)
    p_col = p if metrics.is_static_p(p) else p[:, None]

    from repro.kernels.ops import lp_gather_distance, lp_gather_screen

    # line 7: R <- first K points of C, scored full-dimension from f32
    # rows (no threshold exists yet to screen against).
    first = cand_ids[:, :k]
    r_dist = lp_gather_distance(Q, first, X, p, root=False,
                                interpret=interpret)
    r_dist, r_ids = jax.lax.sort((r_dist, first), num_keys=1)
    n_p0 = jnp.full((B,), k, dtype=jnp.int32)
    ones = jnp.ones((B,), jnp.float32)
    zeros = jnp.zeros((B,), jnp.float32)

    if n_batches == 0:
        return (r_ids, metrics._root(r_dist, p_col), n_p0, jnp.int32(0),
                ones, ones, zeros)

    dim0 = ones * (k * d)   # the first-k full-dimension rows
    f32_0 = ones * k

    def cond(s):
        i, _, _, done, _, _, _, _ = s
        return (i < n_batches) & ~jnp.all(done)

    def body(s):
        i, r_ids, r_dist, done, n_p, dim_scan, f32_rows, band_scan = s
        start = k + i * kappa
        batch = jax.lax.dynamic_slice(cand_ids, (0, start), (B, kappa))
        bbase = jax.lax.dynamic_slice(cand_base, (0, start), (B, kappa))
        thresh = jnp.where(done, -jnp.inf, r_dist[:, k - 1])
        # band 1: int8 screen — certified-kill candidates that provably
        # cannot beat the running k-th best (frozen rows kill everything
        # at entry, so neither band touches their memory)
        keep, nd8 = lp_gather_screen(
            Qp, batch, codes, scale, radius, thresh, bbase, p,
            base_p=base_p, interpret=interpret, block_d=block_d,
        )
        # band 2: f32 rows for the survivors only; screened-out slots
        # become padding (-1) and score +inf without a gather
        rb = jnp.where(keep, batch, -1)
        bd = lp_gather_distance(Q, rb, X, p, root=False,
                                interpret=interpret)
        # identical masked top-k merge as the §8 abandon path (screened
        # candidates are +inf, lowest-index tie rule == stable sort)
        all_d = jnp.concatenate([r_dist, bd], axis=1)
        all_i = jnp.concatenate([r_ids, batch], axis=1)
        neg, sel = jax.lax.top_k(-all_d, k)
        new_dist = -neg
        new_ids = jnp.take_along_axis(all_i, sel, axis=1)
        inter = (new_ids[:, :, None] == r_ids[:, None, :]).any(-1).sum(-1)
        ratio = inter.astype(jnp.float32) / k
        newly_done = ratio >= tau
        keep_row = done[:, None]
        r_ids = jnp.where(keep_row, r_ids, new_ids)
        r_dist = jnp.where(keep_row, r_dist, new_dist)
        n_p = n_p + jnp.where(done, 0, kappa)
        n_kept = keep.sum(axis=1).astype(jnp.float32)
        live = ~done
        dim_scan = dim_scan + jnp.where(live, n_kept * d, 0.0)
        f32_rows = f32_rows + jnp.where(live, n_kept, 0.0)
        band_scan = band_scan + jnp.where(
            live, nd8.sum(axis=1).astype(jnp.float32), 0.0)
        return (i + 1, r_ids, r_dist, done | newly_done, n_p,
                dim_scan, f32_rows, band_scan)

    state = (jnp.int32(0), r_ids, r_dist, jnp.zeros((B,), bool), n_p0,
             dim0, f32_0, zeros)
    (iters, r_ids, r_dist, done, n_p,
     dim_scan, f32_rows, band_scan) = jax.lax.while_loop(cond, body, state)
    n_p_f = n_p.astype(jnp.float32)
    return (r_ids, metrics._root(r_dist, p_col), n_p, iters,
            dim_scan / (n_p_f * d), f32_rows / n_p_f,
            band_scan / (n_p_f * d))


_verify_jit_s = functools.partial(
    jax.jit, static_argnames=("p", "k", "kappa", "tau", "interpret")
)(_verify_impl)
_verify_jit_v = functools.partial(
    jax.jit, static_argnames=("k", "kappa", "tau", "interpret")
)(_verify_impl)
_verify_abandon_jit_s = functools.partial(
    jax.jit,
    static_argnames=("p", "k", "kappa", "tau", "base_p", "interpret",
                     "block_d"),
)(_verify_abandon_impl)
_verify_abandon_jit_v = functools.partial(
    jax.jit,
    static_argnames=("k", "kappa", "tau", "base_p", "interpret", "block_d"),
)(_verify_abandon_impl)
_verify_two_band_jit_s = functools.partial(
    jax.jit,
    static_argnames=("p", "k", "kappa", "tau", "base_p", "interpret",
                     "block_d"),
)(_verify_two_band_impl)
_verify_two_band_jit_v = functools.partial(
    jax.jit,
    static_argnames=("k", "kappa", "tau", "base_p", "interpret", "block_d"),
)(_verify_two_band_impl)


def verify_candidates(
    Q: jax.Array,         # (B, d) f32
    cand_ids: jax.Array,  # (B, t) int32, sorted ascending by base distance
    X: jax.Array,         # (n, d) f32
    p,
    k: int,
    kappa: int,
    tau: float,
    interpret: bool | None = None,
    *,
    cand_base: jax.Array | None = None,
    base_p: float = 1.0,
    abandon: bool = True,
    block_d: int | None = None,
    band=None,
    x_scan: jax.Array | None = None,
    scan_perm: jax.Array | None = None,
):
    """Early-terminated exact-Lp re-ranking (Algorithm 1 lines 7-11).

    Returns (ids (B, k) int32, dists (B, k) f32 with root applied,
    SearchStats) with the verification fields filled: n_p (B,) int32,
    iterations () int32, n_dim_frac (B,), the byte-traffic counters
    n_f32_rows_frac and n_band_frac ((B,) on the two-band path, the
    record's defaults 1.0 / 0.0 elsewhere) and n_scan_blocks (B,), the
    mean dimension blocks of width block_d (None: `pick_abandon_block_d`)
    entered per verified candidate, and on the default abandon path
    n_verify_rows (B,), the candidate rows its kernels copied. The
    candidate-generation fields are the caller's
    (`SearchStats.with_candidates`).

    p follows the scalar-vs-vector contract (DESIGN.md §6): a Python float
    re-ranks the whole batch under one metric (one compiled program per p);
    a (B,) array re-ranks row i under p[i] in ONE compiled program, each
    row bit-identical to the scalar call at its p. In a mixed batch the
    convergence `while_loop` runs until *every* row terminates, but rows
    freeze their (ids, dists, n_p) the moment they individually converge,
    so per-row results and Eq. 1 `N_p` accounting are independent of batch
    composition.

    abandon=True (default) runs the early-abandoning blocked-dimension
    scan (DESIGN.md §8): the running k-th-best power sum abandons
    candidates that provably cannot enter the top-k, making `T_p` itself
    adaptive — `n_dim_frac` reports the scanned fraction. The returned
    top-k is exact either way; abandon=False runs the pre-abandonment
    full-dimension path bit-for-bit (and reports n_dim_frac = 1).
    `cand_base` (the beam's base-metric power sums, metric named by the
    static `base_p`) enables the zero-scan entry/suffix lower bounds;
    None disables them (threshold-only abandonment).

    band (a CompressedBand, index/compressed.py) switches abandon=True to
    the two-band scan (DESIGN.md §10): kappa batches are screened against
    the running k-th best using certified int8 lower bounds and only
    survivors gather f32 rows — ids and dists stay bitwise-identical to
    band=None. (x_scan, scan_perm) instead keep the full-f32 abandon scan
    but run it in energy coordinate order (UHNSWParams.energy_perm) —
    x_scan is the pre-permuted corpus view, scan_perm its permutation;
    mutually exclusive with `band` (the band is already energy-ordered).

    Candidate ids outside [0, n) are padding (sentinels from underfilled
    beams / merges) and are scored as inf so they can never enter R.
    `interpret` forwards to the kernel dispatch (None = backend-aware).
    """
    d = Q.shape[1]
    from repro.kernels.ops import pick_abandon_block_d

    n_blocks = -(-d // (block_d or pick_abandon_block_d(d)))
    if abandon and band is not None:
        if cand_base is None:
            cand_base = jnp.zeros(cand_ids.shape, jnp.float32)
        Qp = jnp.take(Q, band.perm, axis=1)
        if metrics.is_static_p(p):
            out = _verify_two_band_jit_s(
                Q, Qp, cand_ids, cand_base, X, band.rows, band.scale,
                band.radius, float(p), k, kappa, tau, float(base_p),
                interpret, block_d)
        else:
            out = _verify_two_band_jit_v(
                Q, Qp, cand_ids, cand_base, X, band.rows, band.scale,
                band.radius, jnp.atleast_1d(jnp.asarray(p, jnp.float32)),
                k, kappa, tau, float(base_p), interpret, block_d)
        ids, dists, n_p, iters, frac, f32f, bandf = out
        # the f32 rows scored are whole rows: n_dim_frac is their share
        return ids, dists, SearchStats(
            n_p=n_p, iterations=iters, base_p=base_p, n_dim_frac=frac,
            n_f32_rows_frac=f32f, n_band_frac=bandf,
            n_scan_blocks=frac * n_blocks)
    if abandon:
        if cand_base is None:
            cand_base = jnp.zeros(cand_ids.shape, jnp.float32)
        if metrics.is_static_p(p):
            out = _verify_abandon_jit_s(
                Q, cand_ids, cand_base, X, float(p), k, kappa, tau,
                float(base_p), interpret, block_d, x_scan, scan_perm)
        else:
            out = _verify_abandon_jit_v(
                Q, cand_ids, cand_base, X,
                jnp.atleast_1d(jnp.asarray(p, jnp.float32)),
                k, kappa, tau, float(base_p), interpret, block_d,
                x_scan, scan_perm)
        ids, dists, n_p, iters, frac, blocks, rows = out
        if blocks is None:  # whole blocks: scanned dims over block_d
            blocks = frac * n_blocks
        return ids, dists, SearchStats(
            n_p=n_p, iterations=iters, base_p=base_p, n_dim_frac=frac,
            n_scan_blocks=blocks, n_verify_rows=rows)
    if metrics.is_static_p(p):
        out = _verify_jit_s(Q, cand_ids, X, float(p), k, kappa, tau,
                            interpret)
    else:
        out = _verify_jit_v(Q, cand_ids, X,
                            jnp.atleast_1d(jnp.asarray(p, jnp.float32)),
                            k, kappa, tau, interpret)
    ids, dists, n_p, iters = out
    # full-dimension scoring enters every block of every candidate
    return ids, dists, SearchStats(n_p=n_p, iterations=iters, base_p=base_p,
                                   n_scan_blocks=float(n_blocks))


def mask_base_rows(cands: CandidateSet, ids, dists, stats: SearchStats,
                   p_vec, k: int):
    """Per-row base-metric skip (paper §3 preamble) inside a mixed batch.

    Rows whose p equals the base metric of `cands` take the beam's own
    ordering — the exact values the scalar skip path produces — and each
    verification counter of `stats` takes its `SearchStats.SKIPPED` value
    on those rows. A counter already at that value on every row (a
    Python scalar) is left as it is. Returns (ids, dists, stats).
    """
    pj = jnp.asarray(p_vec, dtype=jnp.float32)
    is_base = pj == cands.base_p
    ids = jnp.where(is_base[:, None], cands.ids[:, :k], ids)
    dists = jnp.where(is_base[:, None],
                      metrics._root(cands.base_dists[:, :k], pj[:, None]),
                      dists)
    masked = {}
    for name, neutral in SearchStats.SKIPPED.items():
        value = getattr(stats, name)
        if isinstance(value, jax.Array) or value != neutral:
            masked[name] = jnp.where(is_base, neutral, value)
    return ids, dists, stats._replace(**masked)


def two_way_mixed_search(Q, p, k: int, cutoff: float, search_base_vec):
    """Shared mixed-p driver: two-way G1/G2 partition + scatter (DESIGN.md
    §6). Used by both UHNSW and ShardedUHNSW.

    search_base_vec(Q_sub (B', d), p_sub (B',) f32, k, base_p) must run one
    homogeneous-base sub-batch and return (ids, dists, SearchStats).
    Returns (ids (B, k), dists (B, k), SearchStats) with per-row stats
    scattered back into request order; stats.base_p is the (B,) host-side
    base-metric array (the partition itself is host logic). A homogeneous
    batch keeps its side's record whole; across two sides `iterations` is
    the larger, `coverage_frac` the smaller, and `hops_max` is left at 0
    (no single beam program ran every row).

    Sub-batch results stay *device-resident*: each per-row field is
    restored to request order by one concatenate + one gather on device
    at the end — no per-sub-batch `np.asarray` round trip, so a scheduled
    mixed bucket never forces an extra device->host synchronization per
    side. A field that is the same Python scalar on both sides stays one.
    """
    Q = jnp.asarray(Q, dtype=jnp.float32)
    b = Q.shape[0]
    p_arr = np.asarray(p, dtype=np.float32).reshape(-1)
    if p_arr.size == 1:
        p_arr = np.full(b, p_arr[0], dtype=np.float32)
    assert p_arr.shape[0] == b, (p_arr.shape, b)
    base = np.asarray(metrics.base_metric_for(p_arr, cutoff))
    if b == 0:  # a drained bucket: well-formed empties, no device calls
        z = jnp.zeros((0, k))
        zi = jnp.zeros((0,), jnp.int32)
        zf = jnp.zeros((0,), jnp.float32)
        default = SearchStats()
        return z.astype(jnp.int32), z, SearchStats(
            **{f: zi if isinstance(getattr(default, f), int) else zf
               for f in SearchStats.ROW_FIELDS},
            iterations=jnp.int32(0), base_p=base)
    sels, sides = [], []
    for base_p in (1.0, 2.0):
        sel = np.flatnonzero(base == base_p)
        if sel.size:
            sels.append(sel)
            sides.append(search_base_vec(Q[sel], p_arr[sel], k, base_p))
    if len(sides) == 1:  # homogeneous batch: already in request order
        ids, dists, stats = sides[0]
        return ids, dists, stats._replace(base_p=base)
    order = np.concatenate(sels)
    inv = np.empty(b, np.int64)
    inv[order] = np.arange(b)
    inv = jnp.asarray(inv)

    def restore(parts):
        if not any(isinstance(x, jax.Array) for x in parts) \
                and len(set(parts)) == 1:
            return parts[0]
        parts = [jnp.broadcast_to(x, (sel.size,) + jnp.shape(x)[1:])
                 for x, sel in zip(parts, sels)]
        return jnp.concatenate(parts, axis=0)[inv]

    recs = [st for _, _, st in sides]
    ids = restore([i for i, _, _ in sides])
    dists = restore([d for _, d, _ in sides])
    stats = SearchStats(
        **{f: restore([st.row(f) for st in recs])
           for f in SearchStats.ROW_FIELDS},
        iterations=jnp.maximum(*(jnp.asarray(st.iterations, jnp.int32)
                                 for st in recs)),
        base_p=base,
        coverage_frac=min(st.coverage_frac for st in recs))
    return ids, dists, stats


def finish_candidates(index, Q, cands: CandidateSet, p, k: int):
    """Stage 2 of a search over `index` (UHNSW or ShardedUHNSW): the
    base-metric skip when p is a float equal to `cands.base_p` (the
    beam's own ordering is exact), else verification over the index's
    row source — scalar-p, or the traced per-row-p program with the
    per-row skip mask. Returns (ids, dists, SearchStats) with the
    candidate-generation fields filled; all device-resident."""
    if metrics.is_static_p(p):
        p = float(p)
        if p == cands.base_p:
            return (cands.ids[:, :k],
                    metrics._root(cands.base_dists[:, :k], p),
                    skipped_stats(cands))
    prm = index.params
    # -1 padding passes through: verify_candidates scores it inf
    ids, dists, stats = verify_candidates(
        Q, cands.ids, index._X_rows, p, k, prm.kappa or max(k // 2, 1),
        prm.tau, interpret=prm.interpret, cand_base=cands.base_dists,
        base_p=cands.base_p, abandon=prm.abandon,
        block_d=prm.abandon_block_d, **index._verify_extras(),
    )
    if not metrics.is_static_p(p):
        ids, dists, stats = mask_base_rows(cands, ids, dists, stats, p, k)
    return ids, dists, stats.with_candidates(cands)


def modeled_query_cost(stats: SearchStats, p, d: int) -> dict:
    """T_query = N_b * T_b + N_p * (n_dim_frac * T_p) (paper Eq. 1, with
    the §8 adaptive-T_p correction) via the TPU op-cost model. p and
    stats.base_p may be scalars or (B,) arrays (mixed-p batch); array
    inputs report batch-mean per-distance costs. `n_dim_frac` (1.0 on
    full-dimension paths) scales the verification term down to the
    dimension-work the early-abandoning scan actually performed."""
    t_b = float(np.mean([metrics.lp_distance_cost_model(float(bp), d)
                         for bp in np.atleast_1d(stats.base_p)]))
    t_p = float(np.mean([metrics.lp_distance_cost_model(float(pp), d)
                         for pp in np.atleast_1d(np.asarray(p))]))
    n_b = float(jnp.mean(stats.n_b))
    n_p = float(jnp.mean(stats.n_p))
    # N_p-weighted per-row product, not mean(n_p)*mean(frac): rows that
    # skipped verification (n_p=0, frac=1) must not dilute the estimate —
    # the same weighting the serving stats use (dim_frac_w)
    n_p_row = np.asarray(stats.n_p, dtype=np.float64)
    frac_row = np.broadcast_to(np.asarray(stats.n_dim_frac,
                                          dtype=np.float64), n_p_row.shape)
    weighted = float(np.mean(n_p_row * frac_row))
    frac = weighted / n_p if n_p > 0 else 1.0
    return {"N_b": n_b, "N_p": n_p, "T_b": t_b, "T_p": t_p,
            "n_dim_frac": frac,
            "total": n_b * t_b + weighted * t_p}


class UHNSW:
    """The paper's index: two HNSW graphs (G1 under L1, G2 under L2).

    Public contract:
      * `search(Q, p, k)` — batched ANNS-U-Lp (Algorithm 1). Q: (B, d)
        f32; p: Python float (whole batch under one metric) or (B,) array
        (each row under its own metric — the mixed-p serving contract,
        DESIGN.md §6); k: result size. Returns (ids (B, k) int32, rooted
        dists (B, k) f32, SearchStats).
      * `base_graph_for(p)` — scalar-p base-graph pick; a mixed-p batch is
        instead *two-way partitioned* (G1 rows / G2 rows) inside `search`.
      * `build(...)` — construction: method="incremental" (sequential,
        paper-faithful) or method="bulk" (batched device-side shared-pass
        builder, DESIGN.md §7 — the benchmark-scale default elsewhere).

    Supported p range is the paper's universal family [0.5, 2].
    """

    def __init__(self, g1: HNSWGraph, g2: HNSWGraph, params: UHNSWParams | None = None):
        assert g1.metric_p == 1.0 and g2.metric_p == 2.0
        self.g1, self.g2 = g1, g2
        self.params = params or UHNSWParams()
        self.X = jnp.asarray(g1.data)
        from repro.kernels.ops import kernel_rows

        # the verification kernels' row source: X itself unless the TPU
        # needs the feature axis lane-padded (laid out once, here)
        self._X_rows = kernel_rows(self.X)
        self.arrays1 = GraphArrays.from_graph(g1)
        self.arrays2 = GraphArrays.from_graph(g2)
        # lazy verification-scan caches (DESIGN.md §10): the int8 band
        # for compressed_band, the energy-permuted corpus view for
        # energy_perm. Built on first verified query, deterministic from
        # X, so rebuilds (e.g. after snapshot recovery) are bit-stable.
        self._band = None
        self._scan_cache = None
        self._fetch_rows = None

    @property
    def dim(self) -> int:
        """Vector dimensionality served by this index."""
        return int(self.X.shape[1])

    def fetch_rows(self) -> tuple:
        """`knn_search`'s fetch_rows: X as the level-0 fetch kernel's row
        source (kernels.beam_fetch), made on first use, and X's first row
        in it."""
        if self._fetch_rows is None:
            from repro.kernels.beam_fetch import beam_rows

            self._fetch_rows = (beam_rows(self.X), jnp.int32(0))
        return self._fetch_rows

    def compressed_band(self):
        """The lazily-built int8 CompressedBand over self.X (§10)."""
        if self._band is None:
            from repro.index.compressed import build_band

            self._band = build_band(self.X)
        return self._band

    def _scan_view(self):
        """(x_scan, perm) energy-ordered corpus view for energy_perm."""
        if self._scan_cache is None:
            from repro.index.compressed import energy_order
            from repro.kernels.ops import kernel_rows

            perm = jnp.asarray(energy_order(self.X))
            self._scan_cache = (
                kernel_rows(jnp.take(self.X, perm, axis=1)), perm)
        return self._scan_cache

    def _verify_extras(self) -> dict:
        """The band / scan-view kwargs `verify_candidates` needs under
        the current params (empty when both §10 features are off)."""
        prm = self.params
        if not prm.abandon:
            return {}
        if prm.compressed_band:
            return {"band": self.compressed_band()}
        if prm.energy_perm:
            x_scan, perm = self._scan_view()
            return {"x_scan": x_scan, "scan_perm": perm}
        return {}

    # -- construction -------------------------------------------------------

    @classmethod
    def build(
        cls,
        data: np.ndarray,
        m: int = 32,
        ef_construction: int = 500,
        seed: int = 0,
        params: UHNSWParams | None = None,
        progress_every: int = 0,
        method: str = "incremental",
    ) -> "UHNSW":
        """Construct both base graphs and wrap them in a UHNSW.

        method (DESIGN.md §7):
          * "incremental" — paper-faithful sequential insertion (the
            default; ef_construction applies).
          * "bulk" — batched device-side shared-pass construction
            (repro.core.bulk_build): G1 and G2 from ONE candidate-
            generation pass, ~an order of magnitude faster at segment
            scale; ef_construction is ignored (the bulk path has no
            insertion beam).
          * "bulk_host" — the vectorized NumPy per-graph bulk builder
            (build_hnsw_bulk); ef_construction is ignored.
        """
        if method == "bulk":
            from repro.core.bulk_build import build_bulk_pair

            g1, g2 = build_bulk_pair(data, m=m, seed=seed,
                                     progress_every=progress_every)
            return cls(g1, g2, params)
        if method == "bulk_host":
            from repro.core.build import build_hnsw_bulk

            g1 = build_hnsw_bulk(data, 1.0, m=m, seed=seed,
                                 progress_every=progress_every)
            g2 = build_hnsw_bulk(data, 2.0, m=m, seed=seed + 1,
                                 progress_every=progress_every)
            return cls(g1, g2, params)
        if method != "incremental":
            raise ValueError(
                f"unknown build method {method!r} "
                "(options: 'incremental', 'bulk', 'bulk_host')")
        g1 = build_hnsw(data, 1.0, m, ef_construction, seed, progress_every=progress_every)
        g2 = build_hnsw(data, 2.0, m, ef_construction, seed + 1, progress_every=progress_every)
        return cls(g1, g2, params)

    def index_size_bytes(self, p_range_max: float = 2.0) -> int:
        """Index size (excluding data). For the MLSH comparison (p <= 1) only
        G1 is used, matching the paper's §4.2 accounting."""
        if p_range_max <= 1.0:
            return self.g1.index_size_bytes()
        return self.g1.index_size_bytes() + self.g2.index_size_bytes()

    # -- query --------------------------------------------------------------

    def base_graph_for(self, p: float) -> tuple[GraphArrays, float]:
        """Scalar-p base-graph pick (paper Alg. 1 line 3): G1 iff p <= cutoff.

        Mixed-p batches never call this per request — `_search_mixed` does
        the two-way G1/G2 partition with `metrics.base_metric_for` on the
        whole p vector instead (DESIGN.md §6).
        """
        base = base_metric_for(p, self.params.cutoff)
        return (self.arrays1, 1.0) if base == 1.0 else (self.arrays2, 2.0)

    def search(self, Q, p, k: int):
        """Batched ANNS-U-Lp query (Algorithm 1).

        Q: (B, d) f32. p: Python float (whole batch, one metric) or (B,)
        array — the mixed-p form partitions the batch *two ways* by base
        graph (G1/G2, never one group per distinct p) and runs one vector-p
        program per side; each row's result is bit-identical to the scalar
        call at its p (DESIGN.md §6). Returns (ids (B, k) int32, rooted
        dists (B, k) f32, SearchStats with per-row n_b/n_p/hops).

        The serving scheduler (repro.retrieval.service) pre-partitions its
        buckets by base graph, so each scheduled call hits exactly one side
        here — fixed shapes, two compiled entry points total.
        """
        if metrics.is_static_p(p):
            return self._search_scalar(Q, float(p), k)
        return self._search_mixed(Q, p, k)

    def search_stage_candidates(self, Q, base_p: float,
                                k: int | None = None) -> CandidateSet:
        """Stage 1 of 2: base-metric candidate generation (Alg. 1 lines 1-6).

        Dispatches the batched beam search on the base graph named by
        `base_p` (1.0 = G1, 2.0 = G2) and returns the device-resident
        CandidateSet without forcing a host sync — the serving engine
        (DESIGN.md §6) overlaps this call for wave N+1 with wave N's
        verification. `search` composes exactly this stage with
        `search_stage_finish`, so staged execution is bitwise-identical
        to the fused call by construction.

        `k` is accepted for signature parity with ShardedUHNSW (which
        uses it to size the cross-segment pruning threshold); the
        monolithic index has a single beam and ignores it.
        """
        del k
        prm = self.params
        Q = jnp.asarray(Q, dtype=jnp.float32)
        arrays = self.arrays1 if base_p == 1.0 else self.arrays2
        # bulk-built graphs want a beam wider than t (they trade the
        # sequential builder's deep exploration for vectorized construction)
        ef = max(prm.ef or 2 * prm.t, prm.t)
        # degenerate tiny beams can't host the full W; clamp, don't fail
        width = min(prm.expand_width, ef)
        kw = dict(ef=ef, t=prm.t, max_hops=prm.max_hops, expand_width=width)
        if beam_fetch_on(self.dim):  # rows that are whole DMA tiles
            cand_ids, cand_dists, n_b, hops, rows_read = knn_search(
                arrays, self.X, Q, fetch_rows=self.fetch_rows(), **kw)
        else:
            cand_ids, cand_dists, n_b, hops = knn_search(
                arrays, self.X, Q, **kw)
            # every trip gathers its whole frontier of W*m0 rows
            rows_read = hops * (width * arrays.adj0.shape[1])
        return CandidateSet(ids=cand_ids, base_dists=cand_dists, n_b=n_b,
                            hops=hops, base_p=base_p,
                            hops_max=jnp.max(hops, keepdims=True),
                            rows_read=rows_read)

    def search_stage_finish(self, Q, cands: CandidateSet, p, k: int):
        """Stage 2 of 2: verification (or the base-metric skip) over a
        CandidateSet from `search_stage_candidates`.

        p follows the scalar-vs-vector contract: a float equal to
        `cands.base_p` takes the exact skip path (the beam ordering is
        already exact); any other float runs scalar-p verification; a
        (B,) array runs the traced-p program with the per-row base-metric
        mask. Returns (ids, dists, SearchStats) — all device-resident.
        """
        return finish_candidates(self, jnp.asarray(Q, dtype=jnp.float32),
                                 cands, p, k)

    def _search_scalar(self, Q, p: float, k: int):
        _, base_p = self.base_graph_for(p)
        cands = self.search_stage_candidates(Q, base_p)
        return self.search_stage_finish(Q, cands, p, k)

    def _search_base_vec(self, Q, p_vec, k: int, base_p: float):
        """One homogeneous-base sub-batch with per-row p (traced-p program),
        as the two stages composed back-to-back."""
        cands = self.search_stage_candidates(Q, base_p)
        return self.search_stage_finish(Q, cands, p_vec, k)

    def _search_mixed(self, Q, p, k: int):
        """Mixed-p batch: two-way G1/G2 partition + per-row-p programs."""
        return two_way_mixed_search(Q, p, k, self.params.cutoff,
                                    self._search_base_vec)

    # -- paper Eq. 1 cost model ---------------------------------------------

    def modeled_query_cost(self, stats: SearchStats, p, d: int) -> dict:
        """Paper Eq. 1 cost split — see the module-level helper."""
        return modeled_query_cost(stats, p, d)


def recall(pred_ids, true_ids) -> float:
    """Top-K recall |S* ∩ S| / K averaged over the query batch (paper §4.1.2).

    Negative ids are padding (exact_topk emits -1 when the corpus has fewer
    than k points; searches emit -1 past the end of real data) and are
    excluded from both sets; the denominator counts only real ground-truth
    entries, so recall stays in [0, 1] on degenerate corpora.

    Vectorized as one NumPy broadcast intersection (every benchmark and the
    CI bench-guard sit on this path; the old per-row Python set loop was
    O(B*k) host work). Counts each ground-truth id at most once per row —
    set semantics, relying on search/oracle rows holding distinct real ids
    (every search path emits unique ids per row by construction).
    """
    pred = np.asarray(pred_ids)
    true = np.asarray(true_ids)
    valid_t = true >= 0
    # (B, k_true, k_pred) membership; a true id counts as hit if it appears
    # anywhere in the row's predictions (padding masked on both sides)
    eq = (true[:, :, None] == pred[:, None, :]) & valid_t[:, :, None] \
        & (pred >= 0)[:, None, :]
    hits = int(eq.any(-1).sum())
    denom = int(valid_t.sum())
    return hits / max(denom, 1)
