"""The engine's host-time ledger (repro.retrieval.engine.telemetry).

Small engines run on a clock that ticks a fixed, exactly representable
step at every read, so every stamp is a distinct time and every sum is
exact. The stage counters must add up to the time of the engine calls
that hold them, the four state counters to the time from the first
admission to the last stamp, each dispatch stage to one tick a wave,
and the counters must not change a served id or distance.
"""

import numpy as np
import pytest

from repro.core.uhnsw import UHNSW, UHNSWParams
from repro.retrieval.engine import ManualClock
from repro.retrieval.engine.telemetry import (
    OTHER,
    STAGE_KEYS,
    STATE_KEYS,
    stage_key,
)
from repro.retrieval.service import QueryRequest, UniversalVectorService

DT = 2.0 ** -10
P_MIX = [0.5, 0.8, 1.25, 1.7, 2.0]


class TickClock:
    """Adds DT at every read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += DT
        return self.t


def _requests(small_ds, n, seed):
    rng = np.random.default_rng(seed)
    return [QueryRequest(vector=small_ds.queries[i % len(small_ds.queries)],
                         p=float(rng.choice(P_MIX)), k=10, request_id=i)
            for i in range(n)]


def _service(graphs_bulk, clock, **kw):
    return UniversalVectorService(
        index=UHNSW(*graphs_bulk, UHNSWParams(t=80)), max_batch=16,
        min_bucket=8, clock=clock, **kw)


class _Timed:
    """Times each engine call with two reads of the engine's own clock:
    the call's first and last stamps lie one tick inside them."""

    def __init__(self, eng, clock):
        self.eng, self.clock = eng, clock
        self.call_s = 0.0
        self.first_admit_end = None
        self.last_end = None

    def __call__(self, method, *args):
        t0 = self.clock()
        out = getattr(self.eng, method)(*args)
        t1 = self.clock()
        self.call_s += t1 - t0 - 2 * DT
        self.last_end = t1 - DT
        if method == "admit" and self.first_admit_end is None:
            self.first_admit_end = t1 - DT
        return out


def _drive(eng, clock, reqs, per_admit=5):
    """Admit the stream a few requests at a time, pumping between
    admissions, then drain; returns the timings and the answers."""
    run = _Timed(eng, clock)
    out = {}
    for i in range(0, len(reqs), per_admit):
        ereqs = [eng.make_request(r) for r in reqs[i:i + per_admit]]
        run("admit", ereqs)
        run("pump")
        out.update(eng.take_results())
    out.update(run("drain"))
    return run, out


@pytest.fixture(scope="module")
def ticked(small_ds, graphs_bulk):
    clock = TickClock()
    svc = _service(graphs_bulk, clock)
    before = dict(svc.stats)
    svc.engine.warmup(k=10, ps=(0.8, 1.7))
    assert {k: svc.stats[k] for k in STAGE_KEYS + STATE_KEYS} == \
        {k: before[k] for k in STAGE_KEYS + STATE_KEYS}  # warm-up: none
    reqs = _requests(small_ds, 37, seed=3)
    run, out = _drive(svc.engine, clock, reqs)
    return svc, run, reqs, out


def test_stage_key_follows_the_span_name():
    assert stage_key("engine.collect.wait") == "eng_s_collect_wait"
    assert stage_key("engine.dispatch_finish") == "eng_s_dispatch_finish"
    assert OTHER in STAGE_KEYS and len(set(STAGE_KEYS)) == 8


def test_stages_sum_to_the_engine_call_time(ticked):
    svc, run, _, _ = ticked
    st = svc.stats
    assert run.call_s > 0
    assert sum(st[k] for k in STAGE_KEYS) == run.call_s
    for k in STAGE_KEYS:
        assert st[k] > 0, k  # every stage ran in this stream


def test_states_sum_to_the_time_since_the_first_admission(ticked):
    svc, run, _, _ = ticked
    st = svc.stats
    assert sum(st[k] for k in STATE_KEYS) == \
        run.last_end - run.first_admit_end
    for k in STATE_KEYS:
        assert st[k] > 0, k


def test_each_wave_charges_one_tick_to_each_dispatch(ticked):
    svc, _, reqs, _ = ticked
    st = svc.stats
    waves = st["batches"]
    assert waves > 2 and st["faults"] == 0 and st["queries"] == len(reqs)
    assert st["eng_s_dispatch_search"] == waves * DT
    assert st["eng_s_dispatch_finish"] == waves * DT
    assert st["eng_s_collect_wait"] == waves * DT
    assert st["eng_s_make_waves"] == sum(st["flushes"].values()) * DT


def test_counters_leave_answers_bitwise_unchanged(ticked):
    svc, _, reqs, out = ticked
    grouped = svc.serve_grouped(reqs)
    assert set(out) == {r.request_id for r in reqs}
    for r in reqs:
        np.testing.assert_array_equal(out[r.request_id][0],
                                      grouped[r.request_id][0])
        np.testing.assert_array_equal(out[r.request_id][1],
                                      grouped[r.request_id][1])


def test_states_follow_the_pipeline(small_ds, graphs_bulk):
    """On a clock that stands still inside each call, the held wait is
    the deadline wait and the caller's turn is the time with nothing
    queued; the dispatch and the wave in flight take no time."""
    clk = ManualClock()
    svc = _service(graphs_bulk, clk, max_wait_ms=5.0)
    eng = svc.engine
    st = svc.stats
    clk.advance(0.5)                 # before the first admission: no state
    reqs = [eng.make_request(QueryRequest(vector=small_ds.queries[i],
                                          p=0.8, k=10, request_id=i))
            for i in range(3)]
    eng.admit(reqs)
    clk.advance(0.004)
    eng.pump()                       # nothing due: still held
    assert st["eng_s_idle_held"] == pytest.approx(0.004)
    clk.advance(0.002)
    eng.pump()                       # the deadline flush, served whole
    assert st["batches"] == 1
    clk.advance(0.25)                # the caller's turn
    eng.admit([eng.make_request(QueryRequest(
        vector=small_ds.queries[3], p=0.8, k=10, request_id=3))])
    assert st["eng_s_idle_held"] == pytest.approx(0.006)
    assert st["eng_s_idle_no_work"] == pytest.approx(0.25)
    assert st["eng_s_idle_dispatch"] == 0.0
    assert st["eng_s_inflight"] == 0.0
    eng.drain()
    assert sum(st[k] for k in STATE_KEYS) == pytest.approx(0.256)
