"""One counter record from the verifier to the engine (`SearchStats`).

Every per-row counter of a search must read the same whichever way the
batch is served:

  * `index.search` on a mixed-p batch (the two-way partition of
    `two_way_mixed_search`);
  * the two index stages composed by hand, one sub-batch per base graph;
  * the two schedulers that fill `service.stats` — the serving engine
    (`serve`) and the v1 submit/drain path (`serve_v1`) — whose keys must
    equal the sums the rows imply (the N_p-weighted sum for the
    verification fractions).

And a row whose p is its base metric, inside a mixed batch, must carry
the same value of every counter as the scalar-p skip call on that base:
the single `SearchStats.SKIPPED` table. That check runs on indexes with
no delta tier: the delta scan of a vector-p batch abandons against the
verified k-th best, while a scalar base-p scan reads every dimension, so
with a delta the two differ by design in their dimension counters.
"""

import numpy as np
import pytest

from repro.core.metrics import base_metric_for
from repro.core.uhnsw import UHNSW, SearchStats, UHNSWParams
from repro.index import SegmentedGraphs, ShardedUHNSW
from repro.retrieval.service import QueryRequest, UniversalVectorService

FIELDS = SearchStats.ROW_FIELDS
K = 10
B = 16
# no base metric among them, and both base graphs serve some rows
P_ROUTE = np.array([0.5, 0.8, 1.25, 1.7] * (B // 4), np.float32)
P_SKIP = np.array([1.0, 0.8, 2.0, 1.7, 1.0, 0.5, 2.0, 1.25] * (B // 8),
                  np.float32)
# the service.stats key each per-row field feeds, and whether it is
# summed as is or weighted by the row's n_p
ENGINE_KEY = {
    "n_b": ("n_b", False),
    "n_p": ("n_p", False),
    "hops": ("beam_lane_trips", False),
    "n_dim_frac": ("dim_frac_w", True),
    "n_b_probe": ("n_b_probe", False),
    "n_b_spill": ("n_b_spill", False),
    "n_p_probe": ("n_p_probe", False),
    "n_p_spill": ("n_p_spill", False),
    "n_f32_rows_frac": ("f32_rows_w", True),
    "poisoned": ("poison_detected", False),
    "n_scan_blocks": ("scan_blocks_w", True),
    "rows_read": ("beam_rows_read", False),
    "n_verify_rows": ("verify_rows", False),
}


def _sharded(segments4, data, n_delta: int = 0):
    """A fresh 4-segment index over the session's graphs; `n_delta`
    vectors are then inserted into its delta tier."""
    segs = SegmentedGraphs(graphs1=list(segments4.graphs1),
                           graphs2=list(segments4.graphs2),
                           global_ids=[i.copy() for i in segments4.global_ids])
    idx = ShardedUHNSW(segs, data, params=UHNSWParams(t=150),
                       delta_capacity=64)
    rng = np.random.default_rng(3)
    for row in rng.choice(len(data), size=n_delta, replace=False):
        idx.add(data[row] + rng.normal(0, 1, data.shape[1]).astype(np.float32))
    return idx


def _rows(stats: SearchStats, name: str, n: int) -> np.ndarray:
    value = np.asarray(stats.row(name))
    assert value.shape in ((), (n,)), (name, value.shape)
    return np.broadcast_to(value, (n,))


@pytest.fixture(scope="module")
def route_indexes(small_ds, graphs_bulk, segments4):
    return {
        "monolithic": UHNSW(*graphs_bulk, UHNSWParams(t=150)),
        "sharded_delta": _sharded(segments4, small_ds.data, n_delta=6),
    }


# keys only one scheduler fills: the NaN guard's bisection is the engine's
ENGINE_ONLY = {"poison_detected"}
BATCH_KEYS = ("queries", "coverage_w", "beam_lane_trips", "beam_lane_slots")
SCHEDULERS = ("serve", "serve_v1")


@pytest.fixture(scope="module")
def routes(route_indexes, small_ds):
    """Per index: the rows of the two row routes and, per scheduler, the
    `service.stats` deltas of serving the same batch, each computed once."""
    q = np.asarray(small_ds.queries[:B], np.float32)
    out = {}
    for name, idx in route_indexes.items():
        assert name == "monolithic" or len(idx.delta) > 0
        _, _, mixed = idx.search(q, P_ROUTE, K)
        staged = {f: np.zeros(B) for f in FIELDS}
        base = np.asarray(base_metric_for(P_ROUTE, idx.params.cutoff))
        for base_p in (1.0, 2.0):
            sel = np.flatnonzero(base == base_p)
            cands = idx.search_stage_candidates(q[sel], base_p, k=K)
            _, _, st = idx.search_stage_finish(q[sel], cands, P_ROUTE[sel], K)
            for f in FIELDS:
                staged[f][sel] = _rows(st, f, sel.size)
        keys = {key for key, _ in ENGINE_KEY.values()} | set(BATCH_KEYS)
        served = {}
        for scheduler in SCHEDULERS:
            svc = UniversalVectorService(index=idx)
            before = {key: svc.stats[key] for key in keys}
            getattr(svc, scheduler)([
                QueryRequest(vector=q[i], p=float(P_ROUTE[i]), k=K,
                             request_id=i) for i in range(B)])
            served[scheduler] = {key: svc.stats[key] - before[key]
                                 for key in keys}
        out[name] = ({f: _rows(mixed, f, B) for f in FIELDS}, staged, served)
    return out


@pytest.mark.parametrize("index", ["monolithic", "sharded_delta"])
@pytest.mark.parametrize("field", FIELDS)
def test_counter_same_by_every_route(routes, field, index):
    mixed, staged, served = routes[index]
    np.testing.assert_array_equal(mixed[field], staged[field])
    if field not in ENGINE_KEY:  # feeds no service.stats key
        return
    key, weighted = ENGINE_KEY[field]
    rows = mixed[field].astype(np.float64)
    want = (rows * mixed["n_p"]).sum() if weighted else rows.sum()
    for scheduler in SCHEDULERS:
        if scheduler == "serve_v1" and key in ENGINE_ONLY:
            continue
        assert served[scheduler][key] == pytest.approx(
            want, rel=1e-12, abs=0), (scheduler, key)


@pytest.mark.parametrize("index", ["monolithic", "sharded_delta"])
def test_batch_keys_by_every_scheduler(routes, index):
    """The per-batch keys: every request counted at full coverage, and
    the beam-lane slots (trips of the longest lane, per lane) at least
    the trips the lanes ran, by either scheduler."""
    served = routes[index][2]
    for scheduler in SCHEDULERS:
        st = served[scheduler]
        assert st["queries"] == B, scheduler
        assert st["coverage_w"] == pytest.approx(float(B)), scheduler
        assert st["beam_lane_slots"] >= st["beam_lane_trips"] > 0, scheduler


@pytest.fixture(scope="module")
def skip_indexes(small_ds, graphs_bulk, segments4):
    return (UHNSW(*graphs_bulk, UHNSWParams(t=150)),
            _sharded(segments4, small_ds.data))


@pytest.mark.parametrize("base_p", [1.0, 2.0])
@pytest.mark.parametrize("field", FIELDS)
def test_base_row_skip_neutral(skip_indexes, small_ds, field, base_p):
    q = np.asarray(small_ds.queries[:B], np.float32)
    rows = np.flatnonzero(P_SKIP == base_p)
    for idx in skip_indexes:
        _, _, mixed = idx.search(q, P_SKIP, K)
        _, _, skip = idx.search(q[rows], base_p, K)
        np.testing.assert_array_equal(
            _rows(mixed, field, B)[rows], _rows(skip, field, rows.size),
            err_msg=f"{type(idx).__name__}.{field}")
