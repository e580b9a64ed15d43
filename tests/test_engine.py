"""Continuous-batching serving engine (repro.retrieval.engine).

Scheduler semantics run against a ManualClock — every deadline test is
deterministic and nothing here ever sleeps. Device-facing tests pin the
engine's correctness contract: staged execution (candidates -> finish)
is bitwise-identical to the fused index call, and engine serving is
bitwise-identical to `serve_grouped` / `serve_v1` for the same request
set, delta tier included.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.uhnsw import UHNSW, UHNSWParams
from repro.index.sharded import ShardedUHNSW
from repro.retrieval.engine import (
    DEADLINE,
    DRAIN,
    FULL,
    BucketScheduler,
    EnginePolicy,
    EngineRequest,
    ManualClock,
    bucket_ladder,
    chunk_plan,
)
from repro.retrieval.service import QueryRequest, UniversalVectorService

P_ACCEPT = [0.5, 0.8, 1.25, 2.0]


# ---------------------------------------------------------------------------
# pure scheduler semantics (no device, no sleeps)
# ---------------------------------------------------------------------------


def _ereq(rid, p=0.8, k=10, now=0.0, max_wait_s=0.005, d=4):
    base = 1.0 if p <= 1.4 else 2.0
    return EngineRequest(
        vector=np.zeros(d, np.float32), p=p, k=k, request_id=rid,
        base=base, exact=p == base, arrival_t=now,
        deadline_t=now + max_wait_s,
    )


def test_bucket_ladder_half_octave():
    assert bucket_ladder(8, 128) == [8, 12, 16, 24, 32, 48, 64, 96, 128]
    assert bucket_ladder(8, 32) == [8, 12, 16, 24, 32]
    # max_batch always present even off-ladder
    assert 20 in bucket_ladder(8, 20)


def test_chunk_plan_minimizes_padding_then_calls():
    lad = bucket_ladder(8, 128)
    assert chunk_plan(96, lad) == [96]        # exact fit, one call
    assert chunk_plan(60, lad) == [48, 12]    # exact fit beats 64 (4 pad)
    assert chunk_plan(30, lad) == [32]        # same 2-pad as 24+8, 1 call
    assert chunk_plan(11, lad) == [12]
    assert chunk_plan(5, lad) == [8]          # sub-min tail pads
    for n in range(1, 129):                   # plans always cover n
        assert sum(chunk_plan(n, lad)) >= n


def test_deadline_flush_under_manual_clock():
    clk = ManualClock()
    sched = BucketScheduler(EnginePolicy(max_batch=32, min_bucket=8), clk)
    for i in range(3):
        sched.admit(_ereq(i, now=clk(), max_wait_s=0.005))
    assert sched.poll() == []         # partial bucket, deadline unexpired
    clk.advance(0.004)
    assert sched.poll() == []         # still inside max_wait
    clk.advance(0.002)                # 6ms > 5ms deadline
    flushes = sched.poll()
    assert len(flushes) == 1
    assert flushes[0].reason == DEADLINE
    assert [r.request_id for r in flushes[0].requests] == [0, 1, 2]
    assert sched.depth == 0
    for r in flushes[0].requests:     # flush time recorded off the clock
        assert r.flush_t == pytest.approx(0.006)


def test_full_bucket_flush_keeps_fifo_and_remainder():
    sched = BucketScheduler(EnginePolicy(max_batch=4, min_bucket=2),
                            ManualClock())
    for i in range(9):
        sched.admit(_ereq(i, max_wait_s=1.0))
    flushes = sched.poll()            # two full flushes, 1 request left
    assert [f.reason for f in flushes] == [FULL, FULL]
    assert [r.request_id for f in flushes for r in f.requests] == \
        list(range(8))
    assert sched.depth == 1
    rest = sched.flush_all()
    assert rest[0].reason == DRAIN
    assert [r.request_id for r in rest[0].requests] == [8]


def test_requeue_goes_to_bucket_front():
    sched = BucketScheduler(EnginePolicy(max_batch=32, min_bucket=8),
                            ManualClock())
    old = [_ereq(i) for i in range(3)]
    for r in old:
        sched.admit(r)
    flushed = sched.flush_all()[0].requests
    sched.admit(_ereq(99))            # arrived after the failure
    sched.requeue(flushed)            # failure recovery: old go first
    out = sched.flush_all()[0].requests
    assert [r.request_id for r in out] == [0, 1, 2, 99]


def test_buckets_key_on_base_k_exact():
    sched = BucketScheduler(EnginePolicy(max_batch=32, min_bucket=8),
                            ManualClock())
    for i, p in enumerate([0.5, 0.8, 1.25]):   # all G1 verify lane
        sched.admit(_ereq(i, p=p))
    sched.admit(_ereq(3, p=1.0))               # G1 exact lane
    sched.admit(_ereq(4, p=2.0))               # G2 exact lane
    sched.admit(_ereq(5, p=0.5, k=5))          # distinct k
    flushes = sched.flush_all()
    keys = {(f.base, f.k, f.exact): len(f.requests) for f in flushes}
    assert keys == {(1.0, 10, False): 3, (1.0, 10, True): 1,
                    (2.0, 10, True): 1, (1.0, 5, False): 1}


# ---------------------------------------------------------------------------
# staged index API: composition identity
# ---------------------------------------------------------------------------


def test_stage_composition_matches_fused_search(small_ds, graphs_bulk):
    idx = UHNSW(*graphs_bulk, UHNSWParams(t=80))
    Q = jnp.asarray(small_ds.queries[:8])
    # scalar verify path (p != base), scalar exact path (p == base)
    for p, base in ((0.8, 1.0), (2.0, 2.0), (1.25, 1.0)):
        fused_ids, fused_d, fused_st = idx.search(Q, p, 10)
        cands = idx.search_stage_candidates(Q, base)
        sids, sd, sst = idx.search_stage_finish(Q, cands, p, 10)
        np.testing.assert_array_equal(np.asarray(fused_ids),
                                      np.asarray(sids), err_msg=f"p={p}")
        np.testing.assert_array_equal(np.asarray(fused_d), np.asarray(sd))
        np.testing.assert_array_equal(np.asarray(fused_st.n_b),
                                      np.asarray(sst.n_b))
    # vector-p over one base: stage composition == the homogeneous slice
    # of the fused mixed call
    ps = np.array([0.5, 0.8, 1.0, 1.25] * 2, np.float32)  # all G1
    fused_ids, fused_d, _ = idx.search(Q, ps, 10)
    cands = idx.search_stage_candidates(Q, 1.0)
    sids, sd, _ = idx.search_stage_finish(Q, cands, ps, 10)
    np.testing.assert_array_equal(np.asarray(fused_ids), np.asarray(sids))
    np.testing.assert_array_equal(np.asarray(fused_d), np.asarray(sd))


def test_sharded_stage_composition_with_delta(small_ds):
    sh = ShardedUHNSW.build(small_ds.data, num_segments=3, m=12,
                            params=UHNSWParams(t=60), seed=0,
                            delta_capacity=64)
    for i in range(6):   # delta-resident rows must merge inside stage B
        sh.add(small_ds.data[i] + 0.01)
    Q = jnp.asarray(small_ds.queries[:6])
    for p, base in ((0.8, 1.0), (2.0, 2.0)):
        fused_ids, fused_d, _ = sh.search(Q, p, 10)
        cands = sh.search_stage_candidates(Q, base)
        sids, sd, _ = sh.search_stage_finish(Q, cands, p, 10)
        np.testing.assert_array_equal(np.asarray(fused_ids),
                                      np.asarray(sids), err_msg=f"p={p}")
        np.testing.assert_array_equal(np.asarray(fused_d), np.asarray(sd))
    ps = np.array([1.5, 2.0, 1.75, 2.0, 1.5, 1.9], np.float32)  # all G2
    fused_ids, fused_d, _ = sh.search(Q, ps, 10)
    cands = sh.search_stage_candidates(Q, 2.0)
    sids, sd, _ = sh.search_stage_finish(Q, cands, ps, 10)
    np.testing.assert_array_equal(np.asarray(fused_ids), np.asarray(sids))
    np.testing.assert_array_equal(np.asarray(fused_d), np.asarray(sd))


# ---------------------------------------------------------------------------
# engine end-to-end (service-level)
# ---------------------------------------------------------------------------


def _requests(small_ds, n, seed=0, k=10):
    rng = np.random.default_rng(seed)
    return [
        QueryRequest(vector=small_ds.queries[i % len(small_ds.queries)],
                     p=float(rng.choice(P_ACCEPT)), k=k, request_id=i)
        for i in range(n)
    ]


@pytest.fixture()
def svc(small_ds, graphs_bulk):
    return UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), max_batch=32,
        min_bucket=8)


def test_engine_deadline_flush_end_to_end(small_ds, graphs_bulk):
    clk = ManualClock()
    svc = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), max_batch=32,
        min_bucket=8, max_wait_ms=5.0, clock=clk)
    eng = svc.engine
    reqs = [eng.make_request(QueryRequest(vector=small_ds.queries[i],
                                          p=0.8, k=10, request_id=i))
            for i in range(3)]                   # one (G1, 10, verify) bucket
    eng.admit(reqs)
    eng.pump()
    assert svc.stats["flushes"][DEADLINE] == 0   # nothing due yet
    clk.advance(0.006)                           # past the 5ms deadline
    eng.pump()                                   # deadline flush dispatches
    assert svc.stats["flushes"][DEADLINE] == 1
    out = eng.drain()
    assert len(out) == 3
    assert svc.stats["flushes"][DRAIN] == 0      # nothing left to drain
    # queue-wait in the records is the simulated deadline wait
    rec = list(svc.stats["latency_records"])[-3:]
    for total, queue, compute, _cold in rec:
        assert queue == pytest.approx(6.0)


def test_engine_partial_bucket_dispatch(svc, small_ds):
    before = svc.stats["batches"]
    out = svc.serve(_requests(small_ds, 5, seed=2))
    assert len(out) == 5
    assert svc.stats["flushes"][DRAIN] >= 1      # partial buckets drained
    assert svc.stats["batches"] > before
    assert svc.stats["queries"] == 5             # padding not counted


def test_engine_full_flush_reason(svc, small_ds):
    reqs = [QueryRequest(vector=small_ds.queries[i % 8], p=0.8, k=10,
                         request_id=i) for i in range(32)]
    svc.serve(reqs)
    assert svc.stats["flushes"][FULL] == 1       # 32 == max_batch
    assert svc.stats["batches"] == 1             # one exact-fit wave


def test_engine_admission_shed(small_ds, graphs_bulk):
    svc = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), max_batch=32,
        watermark=4, overload="shed")
    reqs = _requests(small_ds, 10, seed=3)
    out = svc.serve(reqs)
    assert svc.stats["shed"] == 6                # watermark 4: 6 rejected
    assert len(out) == 4
    served = set(out)
    assert served == {r.request_id for r in reqs[:4]}


def test_engine_admission_degrade_exact_base_lane(small_ds, graphs_bulk):
    svc = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), max_batch=32,
        watermark=2, overload="degrade")
    reqs = [QueryRequest(vector=small_ds.queries[i], p=0.8, k=10,
                         request_id=i) for i in range(6)]
    out = svc.serve(reqs)
    assert len(out) == 6                         # nobody dropped
    assert svc.stats["degraded"] == 4            # but 4 short-circuited
    # degraded rows carry the base-metric (G1) answer: the exact fast lane
    q = np.stack([r.vector for r in reqs[2:]]).astype(np.float32)
    bids, bdists, _ = svc.index.search(q, 1.0, 10)
    for i, r in enumerate(reqs[2:]):
        np.testing.assert_array_equal(out[r.request_id][0],
                                      np.asarray(bids)[i])


def test_engine_transient_failure_retried_transparently(svc, small_ds,
                                                        monkeypatch):
    """A device call that fails once is retried in place (DESIGN.md §9):
    the caller sees every request served, bitwise-identical to a clean
    run, with the fault visible only in the stats counters."""
    # 40 one-bucket requests -> a full 32-wave + an 8-row drain wave
    reqs = [QueryRequest(vector=small_ds.queries[i % 8], p=0.8, k=10,
                         request_id=i) for i in range(40)]
    clean = svc.serve(reqs)
    real = svc.index.search_stage_candidates
    calls = {"n": 0}

    def flaky(Q, base_p, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom")
        return real(Q, base_p, **kw)

    monkeypatch.setattr(svc.index, "search_stage_candidates", flaky)
    svc2 = UniversalVectorService(index=svc.index, max_batch=32,
                                  min_bucket=8)
    out = svc2.serve(reqs)
    # nothing lost, nothing double-served, nobody sees the fault
    assert set(out) == set(range(40))
    assert svc2.engine.take_failures() == {}
    assert svc2.stats["faults"] == 1
    assert svc2.stats["retries"] == 1
    assert svc2.stats["failed"] == 0
    # the retried wave's results are bitwise-identical to the clean run
    for rid, (ids, dists) in out.items():
        np.testing.assert_array_equal(ids, clean[rid][0])
        np.testing.assert_array_equal(dists, clean[rid][1])


def test_engine_bitwise_vs_grouped_and_v1_sharded_delta(small_ds):
    sh = ShardedUHNSW.build(small_ds.data, num_segments=3, m=12,
                            params=UHNSWParams(t=60), seed=0,
                            delta_capacity=64)
    for i in range(6):
        sh.add(small_ds.data[i] + 0.01)
    svc = UniversalVectorService(index=sh, max_batch=16, min_bucket=8)
    reqs = _requests(small_ds, 20, seed=4)
    engine_out = svc.serve(reqs)
    grouped = svc.serve_grouped(reqs)
    v1 = svc.serve_v1(reqs)
    for r in reqs:
        np.testing.assert_array_equal(engine_out[r.request_id][0],
                                      grouped[r.request_id][0],
                                      err_msg=f"ids p={r.p}")
        np.testing.assert_array_equal(engine_out[r.request_id][1],
                                      grouped[r.request_id][1])
        np.testing.assert_array_equal(engine_out[r.request_id][0],
                                      v1[r.request_id][0])
        np.testing.assert_array_equal(engine_out[r.request_id][1],
                                      v1[r.request_id][1])


def test_engine_bitwise_vs_grouped_interpret(small_ds, graphs_bulk):
    svc = UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=60, interpret=True)),
        max_batch=16, min_bucket=8)
    reqs = _requests(small_ds, 8, seed=5)
    engine_out = svc.serve(reqs)
    grouped = svc.serve_grouped(reqs)
    for r in reqs:
        np.testing.assert_array_equal(engine_out[r.request_id][0],
                                      grouped[r.request_id][0],
                                      err_msg=f"ids p={r.p}")
        np.testing.assert_array_equal(engine_out[r.request_id][1],
                                      grouped[r.request_id][1])


# ---------------------------------------------------------------------------
# service hardening + latency attribution satellites
# ---------------------------------------------------------------------------


def test_submit_validation_hardening(svc, small_ds):
    good = small_ds.queries[0]
    with pytest.raises(ValueError, match="k must be >= 1"):
        svc.submit([QueryRequest(vector=good, p=0.8, k=0, request_id=1)])
    with pytest.raises(ValueError, match="non-finite"):
        bad = good.copy()
        bad[0] = np.nan
        svc.submit([QueryRequest(vector=bad, p=0.8, k=5, request_id=2)])
    with pytest.raises(ValueError, match=r"expected d=\d+, got d=3"):
        svc.submit([QueryRequest(vector=np.zeros(3, np.float32), p=0.8,
                                 k=5, request_id=3)])
    assert svc.queue_depth == 0                  # nothing partially queued
    # engine serve validates identically (same _validate)
    with pytest.raises(ValueError, match="k must be >= 1"):
        svc.serve([QueryRequest(vector=good, p=0.8, k=0, request_id=4)])


def test_engine_warmup_precompiles_every_ladder_shape(svc, small_ds):
    eng = svc.engine
    # one verify p per base + one exact-base p: 3 lanes x 5 ladder sizes
    batches = eng.warmup(k=10, ps=(0.8, 1.8, 2.0))
    assert batches == 3 * len(eng.policy.ladder)
    # warmup must not leak into the served counters...
    assert svc.stats["queries"] == 0 and len(svc.stats["latency_records"]) == 0
    assert eng.take_results() == {}
    # ...but after it, no traffic at these lanes ever rides a compile
    svc.serve(_requests(small_ds, 13, seed=9))     # 13 -> an odd wave mix
    lat = svc.latency_summary()
    assert lat["count"] == 13
    assert lat["cold_count"] == 0


def test_latency_summary_attribution(svc, small_ds):
    svc.serve(_requests(small_ds, 12, seed=6))
    lat = svc.latency_summary()
    assert lat["count"] == 12
    assert lat["p95"] >= lat["p50"] > 0
    # the attribution fix: queue-wait + device-compute == total, per the
    # engine's clock, and first-compile requests are flagged cold
    assert lat["queue_ms"]["p50"] >= 0
    assert lat["compute_ms"]["p50"] > 0
    assert lat["cold_count"] >= 1
    recs = list(svc.stats["latency_records"])
    for total, queue, compute, _cold in recs:
        assert total == pytest.approx(queue + compute, rel=1e-6, abs=1e-6)
    warm = [r for r in recs if not r[3]]
    if warm:
        assert lat["warm"]["count"] == len(warm)
