"""Serving-engine benchmark: throughput, latency, and flush behavior.

Three comparisons per distinct-p count (every request carries its own
p — the paper's ANNS-U-Lp deployment premise, DESIGN.md §6), between
the continuous-batching engine (`serve`, the default path), the
per-(p, k) grouped baseline (`serve_grouped`), and the v1 synchronous
power-of-two micro-batcher (`serve_v1`). All three run the same traced
per-query-p kernel programs, so every comparison is pure *scheduling*
with bit-identical results (`bitwise_equal` checks engine == grouped ==
v1 on every request of every stream served).

1. **Throughput.** Cold = the first stream ever served (compiles
   included). Warm/steady = serving *fresh* request streams (new
   random p mixes and stream lengths) after a warm-up — the production
   traffic shape. This is the measure that exposes the grouped
   baseline's structural cost: its batch shapes are data-dependent, so
   every fresh stream retraces, while the engine's exact-fit ladder
   shapes are all hot after warm-up. `speedup_warm_repeat`
   (informational, ungated) re-serves one identical stream best-of-3 —
   the one scenario with no shape churn, where grouped's zero-padding
   exact shapes are hard to beat.

2. **Paced latency** (open loop: requests arrive in bursts on a
   simulated arrival clock, device time is measured wall time) — the
   engine's admit/pump/deadline loop against the v1 submit/drain cycle
   at identical arrival schedules, paced to ~70% of the engine's warm
   capacity. Per-request latency = simulated finish - simulated
   arrival; the engine's deadline-triggered flushes and exact-fit
   ladder waves vs v1's drain-the-backlog padding show up as the
   p50/p95 gap (`p50_vs_v1` < 1 means the engine is faster). No
   wall-clock sleeps: arrivals advance the simulated clock directly.

3. **Flush accounting** — why engine batches dispatched during the
   paced scenario (full / deadline / drain), reported per row.

Rows land in results/BENCH_serving.json via benchmarks/run.py; the CI
bench-guard gates recall, warm/cold speedup, bitwise equality, and the
p50/p95 latency ratios (tools/check_bench.py).
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from benchmarks.common import emit, get_dataset, get_uhnsw, ground_truth
from repro.retrieval.service import QueryRequest, UniversalVectorService

K = 10
BURST = 12          # paced-scenario burst size (requests per arrival event)
UTILIZATION = 0.9   # fraction of engine warm capacity the pacing targets


def _p_grid(d: int) -> list[float]:
    """d distinct metrics spread over the universal range [0.5, 2]."""
    if d == 1:
        return [0.8]
    return [round(float(p), 4) for p in np.linspace(0.5, 2.0, d)]


def _make_stream(ds, ps: list[float], n_requests: int, seed: int):
    """Returns (requests, per-request query index into ds.queries)."""
    rng = np.random.default_rng(seed)
    reqs, qidx = [], []
    for i in range(n_requests):
        qi = int(rng.integers(len(ds.queries)))
        qidx.append(qi)
        reqs.append(QueryRequest(vector=ds.queries[qi],
                                 p=float(rng.choice(ps)), k=K,
                                 request_id=i))
    return reqs, qidx


def _timed(fn, reqs):
    t0 = time.perf_counter()
    out = fn(reqs)
    return out, time.perf_counter() - t0


def _best_of(fn, reqs, n: int = 3) -> float:
    """Min wall time over n identical passes (warm-path timing)."""
    return min(_timed(fn, reqs)[1] for _ in range(n))

def _mean_recall(name: str, reqs, qidx, out) -> float:
    """Recall@K over the stream, using cached per-p exact ground truth."""
    gt = {}
    hits, denom = 0, 0
    for r, qi in zip(reqs, qidx):
        p = float(r.p)
        if p not in gt:
            gt[p] = ground_truth(name, p, k=K)[0]
        true = {int(v) for v in gt[p][qi] if v >= 0}
        got = {int(v) for v in out[r.request_id][0] if v >= 0}
        hits += len(got & true)
        denom += len(true)
    return hits / max(denom, 1)


def _bitwise(a: dict, b: dict, n: int) -> bool:
    return all(
        np.array_equal(a[i][0], b[i][0]) and np.array_equal(a[i][1], b[i][1])
        for i in range(n)
    )


# -- the paced open-loop latency scenario ---------------------------------
#
# Arrivals happen on a *simulated* clock (bursts of BURST requests every
# `gap` seconds); device work advances that clock by its measured wall
# time. Per-request latency is simulated finish - simulated arrival, so
# the comparison captures each scheduler's *batch-forming* behavior
# (engine: deadline flush + exact-fit ladder waves; v1: drain whatever
# queued into padded power-of-two buckets) under identical load, without
# a single wall-clock sleep.

def _paced_schedule(n: int, gap: float) -> list[float]:
    return [gap * (i // BURST) for i in range(n)]


def _sim_engine(service: UniversalVectorService, reqs, schedule):
    """Drive the engine's admit/pump loop on the simulated clock."""
    eng = service.engine
    arrival = {r.request_id: ts for r, ts in zip(reqs, schedule)}
    pend = deque(zip(reqs, schedule))
    t = 0.0
    lat, out = {}, {}

    def harvest(got):
        for rid, res in got.items():
            lat[rid] = (t - arrival[rid]) * 1e3
            out[rid] = res

    while pend or eng.pending:
        while pend and pend[0][1] <= t:
            r, ts = pend.popleft()
            eng.admit([eng.make_request(r, now=ts)])
        w0 = time.perf_counter()
        eng.pump(now=t)
        t += time.perf_counter() - w0
        got = eng.take_results()
        harvest(got)
        if got:
            continue
        # nothing completed: jump the simulated clock to the next event
        # (an arrival or the oldest queued deadline)
        nxt = [pend[0][1]] if pend else []
        nd = eng.sched.next_deadline()
        if nd is not None:
            nxt.append(nd)
        if nxt:
            t = max(t, min(nxt))
        elif eng.pending:
            # only the in-flight wave remains
            w0 = time.perf_counter()
            got = eng.drain(now=t)
            t += time.perf_counter() - w0
            harvest(got)
    return lat, out


def _sim_v1(service: UniversalVectorService, reqs, schedule):
    """The v1 synchronous cycle on the same simulated clock: drain
    everything queued, and whatever arrived during the (simulated) drain
    waits for the next cycle — the convoy the engine's deadline flush
    replaces."""
    arrival = {r.request_id: ts for r, ts in zip(reqs, schedule)}
    pend = deque(zip(reqs, schedule))
    t = 0.0
    lat, out = {}, {}
    while pend or service.queue_depth:
        if not service.queue_depth and pend and pend[0][1] > t:
            t = pend[0][1]
        while pend and pend[0][1] <= t:
            service.submit([pend.popleft()[0]])
        w0 = time.perf_counter()
        got = service.drain()
        t += time.perf_counter() - w0
        for rid, res in got.items():
            lat[rid] = (t - arrival[rid]) * 1e3
            out[rid] = res
    return lat, out


def _pcts(lat: dict) -> tuple[float, float]:
    arr = np.asarray(list(lat.values()), dtype=np.float64)
    return float(np.percentile(arr, 50)), float(np.percentile(arr, 95))


# Steady-state stream lengths. Warm-up lengths are chosen so their
# engine chunk plans (280 -> [128][128][24], 104 -> [96, 8]) cover every
# ladder shape the measured streams need (152 -> [128][24],
# 136 -> [128][8]) — after warm-up the engine serves fresh streams with
# zero compiles, which is the point of a bounded shape set. The grouped
# baseline's shapes are data-dependent, so no warm-up can cover a
# stream length/mix it hasn't literally seen; it retraces on the
# measured streams exactly as it would on live traffic.
WARMUP_LENS = (280, 104)
STEADY_LENS = (152, 136)


def run(quick: bool = False):
    name = "sun" if quick else "deep"
    n_requests = 96 if quick else 384
    # quick (the CI-gated lane) covers the structurally differentiated
    # mixed-stream cases. d=1 and d=2 are near-ties by construction —
    # grouped's max_batch chunks coincide with the engine's ladder at
    # d=1, and at d=2 both schedulers emit near-identical per-burst
    # shapes — so gating CI on them would gate on noise; they stay in
    # the full grid for the record.
    d_grid = [4, 8] if quick else [1, 2, 4, 8, 16]
    t = 100 if quick else 150
    ds = get_dataset(name)

    index = get_uhnsw(name, m=16, t=t)
    service = UniversalVectorService(index=index, max_batch=128)

    rows = []
    for d in d_grid:
        ps = _p_grid(d)
        reqs, qidx = _make_stream(ds, ps, n_requests, seed=d)

        # -- cold: the first stream ever served at this distinct-p count -
        g_out, g_cold = _timed(service.serve_grouped, reqs)
        e_out, e_cold = _timed(service.serve, reqs)
        v_out, v_cold = _timed(service.serve_v1, reqs)
        bitwise = (_bitwise(g_out, e_out, n_requests)
                   and _bitwise(g_out, v_out, n_requests))

        # -- same-stream repeat (informational): zero shape churn --------
        g_rep = _best_of(service.serve_grouped, reqs)
        e_rep = _best_of(service.serve, reqs)

        # one-time boot warmup (after the first cold row, so the engine's
        # own organic compile cost is on the record): pre-compiles every
        # ladder shape for the verify lanes and the exact-base p values
        # the _p_grid streams contain, so no steady/paced measurement
        # rides a compiling program
        if not getattr(service.engine, "_bench_warmed", False):
            service.engine.warmup(k=K, ps=(0.8, 1.8, 1.0, 2.0))
            service.engine._bench_warmed = True

        # -- steady state: fresh streams after warm-up -------------------
        paths = [("grouped", service.serve_grouped),
                 ("engine", service.serve),
                 ("v1", service.serve_v1)]
        for n_w, off in zip(WARMUP_LENS, (51, 52)):
            w_reqs, _ = _make_stream(ds, ps, n_w, seed=d + off)
            for _, fn in paths:
                fn(w_reqs)
        steady = {pname: 0.0 for pname, _ in paths}
        for n_s, off in zip(STEADY_LENS, (101, 102)):
            s_reqs, _ = _make_stream(ds, ps, n_s, seed=d + off)
            outs = {}
            for pname, fn in paths:
                outs[pname], dt = _timed(fn, s_reqs)
                steady[pname] += dt
            bitwise = (bitwise
                       and _bitwise(outs["grouped"], outs["engine"], n_s)
                       and _bitwise(outs["grouped"], outs["v1"], n_s))
        g_st, e_st, v_st = steady["grouped"], steady["engine"], steady["v1"]
        n_steady = sum(STEADY_LENS)

        # -- paced open-loop latency -------------------------------------
        gap = BURST * (e_rep / n_requests) / UTILIZATION
        schedule = _paced_schedule(n_requests, gap)
        _sim_v1(service, reqs, schedule)        # warm-up (odd shapes)
        v1_lat, _ = _sim_v1(service, reqs, schedule)
        _sim_engine(service, reqs, schedule)    # warm-up (odd shapes)
        fl0 = dict(service.stats["flushes"])
        eng_lat, _ = _sim_engine(service, reqs, schedule)
        fl = {k: service.stats["flushes"][k] - fl0[k]
              for k in service.stats["flushes"]}
        e_p50, e_p95 = _pcts(eng_lat)
        v_p50, v_p95 = _pcts(v1_lat)

        row = {
            "bench": "serving", "dataset": name, "distinct_p": d,
            "requests": n_requests, "k": K,
            "grouped_qps_cold": round(n_requests / g_cold, 1),
            "mixed_qps_cold": round(n_requests / e_cold, 1),
            "speedup_cold": round(g_cold / e_cold, 2),
            # steady state: fresh streams (lengths 152 + 136), hot caches
            "grouped_qps_warm": round(n_steady / g_st, 1),
            "mixed_qps_warm": round(n_steady / e_st, 1),
            "v1_qps_warm": round(n_steady / v_st, 1),
            "speedup_warm": round(g_st / e_st, 2),
            "speedup_warm_vs_v1": round(v_st / e_st, 2),
            # informational: re-serving one identical stream (no churn)
            "speedup_warm_repeat": round(g_rep / e_rep, 2),
            "recall_grouped": round(_mean_recall(name, reqs, qidx, g_out), 4),
            "recall_mixed": round(_mean_recall(name, reqs, qidx, e_out), 4),
            "bitwise_equal": bitwise,
            # paced open-loop latency (simulated arrivals, measured compute)
            "engine_p50_ms": round(e_p50, 1),
            "engine_p95_ms": round(e_p95, 1),
            "v1_p50_ms": round(v_p50, 1),
            "v1_p95_ms": round(v_p95, 1),
            "p50_vs_v1": round(e_p50 / v_p50, 3),
            "p95_vs_v1": round(e_p95 / v_p95, 3),
            "flush_full": fl.get("full", 0),
            "flush_deadline": fl.get("deadline", 0),
            "flush_drain": fl.get("drain", 0),
        }
        rows.append(row)
        print(f"  D={d}: steady {row['speedup_warm']}x vs grouped / "
              f"{row['speedup_warm_vs_v1']}x vs v1 "
              f"(repeat {row['speedup_warm_repeat']}x), cold "
              f"{row['speedup_cold']}x; paced p50 {row['engine_p50_ms']}ms "
              f"vs v1 {row['v1_p50_ms']}ms (ratio {row['p50_vs_v1']}); "
              f"flushes full={row['flush_full']} "
              f"deadline={row['flush_deadline']} drain={row['flush_drain']}; "
              f"recall {row['recall_mixed']} (bitwise_equal={bitwise})",
              flush=True)

    emit(rows, "serving")
    # acceptance is evaluated over the structurally differentiated rows
    # (the quick-lane grid, d >= 4); bitwise equality must hold on every
    # row including the d<=2 near-tie ones
    ok = (all(r["bitwise_equal"] for r in rows)
          and all(r["speedup_warm"] >= 1.0 and r["p50_vs_v1"] < 1.0
                  for r in rows if r["distinct_p"] >= 4))
    print(f"acceptance (engine >= grouped on steady fresh streams and p50 "
          f"below v1 at every gated distinct-p count, bitwise everywhere): "
          f"{'PASS' if ok else 'FAIL'}")
    return rows


# -- faulted-stream degraded serving (DESIGN.md §11) -----------------------
#
# The robustness counterpart of the scheduling rows above: the same engine
# over a durable 8-segment index, serving one clean pass and one chaos
# pass — one flaky segment's fault site injected at rate FAULT_RATE plus
# one mid-stream NaN-poisoned segment (detected by the query-time guard,
# bisected to the segment, quarantined, restored from the snapshot and
# canary-readmitted by background maintenance). Reported: achieved
# coverage, faulted/clean throughput and p50 ratios, and the hard zero:
# no poisoned id in any faulted-stream result.

FAULT_RATE = 0.05
N_SEGMENTS = 8
POISON_SEG = 3
FLAKY_SEG = 1


def run_faulted(quick: bool = False):
    import os
    import tempfile

    from repro.core.uhnsw import UHNSWParams
    from repro.index import DurableIndex, ShardedUHNSW
    from repro.retrieval.engine import FaultInjector
    from repro.retrieval.engine.faults import poison_segment, segment_site

    name = "sun" if quick else "deep"
    t = 100 if quick else 150
    # streams long enough that the one-off poison event (wasted wave +
    # bisection probes + snapshot restore) amortizes: the gated ratio
    # measures sustained degraded throughput, not the event spike
    n_requests = 128 if quick else 192
    n_streams = 4 if quick else 6
    seed = int(os.environ.get("REPRO_SEGFAULT_SEED", "0"))
    ds = get_dataset(name)
    ps = _p_grid(4)

    t0 = time.perf_counter()
    index = ShardedUHNSW.build(ds.data, num_segments=N_SEGMENTS, m=12,
                               params=UHNSWParams(t=t), seed=0)
    print(f"  built {N_SEGMENTS}-segment {name} in "
          f"{time.perf_counter() - t0:.0f}s", flush=True)

    def streams(offset):
        return [_make_stream(ds, ps, n_requests, seed=offset + i)[0]
                for i in range(n_streams)]

    def serve_all(service, reqs_list):
        dt = 0.0
        outs = []
        for reqs in reqs_list:
            out, d = _timed(service.serve, reqs)
            outs.append(out)
            dt += d
        return outs, dt

    with tempfile.TemporaryDirectory() as td:
        dur = DurableIndex.create(index, td)
        # one persistently flaky segment at rate 0.05 per wave (the
        # "segment" wildcard would compound to 1-0.95^8 = 34% of waves
        # faulting — a different scenario than the advertised 5%), plus a
        # mid-stream NaN poisoning of a *different* segment so both the
        # EWMA-retry path and the quarantine/recovery path are measured
        injector = FaultInjector(rate=FAULT_RATE, seed=seed,
                                 sites=(segment_site(FLAKY_SEG),))
        service = UniversalVectorService(index=dur, max_batch=64,
                                         fault_injector=injector,
                                         min_coverage=0.5)
        eng = service.engine

        # warm every ladder shape, then pre-warm the degraded-mask and
        # bisection-probe programs (poison -> detect -> restore) so the
        # measured chaos pass pays chaos, not compiles
        eng.warmup(k=K, ps=tuple(ps))
        keep, eng.fault_injector = eng.fault_injector, None
        serve_all(service, streams(900))
        gids = poison_segment(dur, POISON_SEG)
        serve_all(service, streams(910))     # detect + quarantine (compile)
        eng.pump()                           # restore + readmit (compile)
        assert dur.health.alive() == list(range(N_SEGMENTS))
        eng.fault_injector = keep
        injector.reset()

        # -- clean pass (injector detached, index fully healthy) ---------
        eng.fault_injector = None
        base = dict(service.stats)
        service.stats["latency_records"].clear()  # per-pass p50 windows
        clean_outs, clean_dt = serve_all(service, streams(1000))
        clean_lat = service.latency_summary()
        n_served_clean = sum(len(o) for o in clean_outs)

        # -- faulted pass: segment-site chaos + one mid-stream poison ----
        # (counters are cumulative over the service lifetime — the warmup
        # pass above deliberately poisons/recovers once to compile those
        # paths, so the row must report measured-pass deltas)
        eng.fault_injector = injector
        q0 = service.stats["queries"]
        cov0 = service.stats["coverage_w"]
        ctr0 = {key: int(service.stats[key])
                for key in ("poison_detected", "seg_quarantined",
                            "seg_recovered")}
        service.stats["latency_records"].clear()
        fault_dt = 0.0
        outs = []
        for i, reqs in enumerate(streams(2000)):
            if i == n_streams // 2:          # mid-stream corruption
                poison_segment(dur, POISON_SEG)
            out, d = _timed(service.serve, reqs)
            outs.append(out)
            fault_dt += d
        fault_lat = service.latency_summary()
        n_served = sum(len(o) for o in outs)
        st = service.stats
        coverage_mean = ((st["coverage_w"] - cov0)
                         / max(st["queries"] - q0, 1))
        # the hard zero applies to the stream served WHILE the segment
        # held poisoned rows (quarantine keeps them out of every result);
        # once background maintenance restores + readmits the segment, its
        # ids are clean again and legitimately servable
        poisoned = set(map(int, gids))
        leaked = {int(i)
                  for ids, _ in outs[n_streams // 2].values()
                  for i in np.asarray(ids) if int(i) >= 0} & poisoned
        recovered_all = dur.health.alive() == list(range(N_SEGMENTS))

    qps_clean = n_served_clean / clean_dt
    qps_fault = n_served / fault_dt
    row = {
        "bench": "health", "dataset": name, "segments": N_SEGMENTS,
        "fault_rate": FAULT_RATE, "requests": n_streams * n_requests,
        "seed": seed,
        "served": n_served,
        "failed": int(st["failed"] - base.get("failed", 0)),
        "coverage_mean": round(float(coverage_mean), 4),
        "clean_qps": round(qps_clean, 1),
        "faulted_qps": round(qps_fault, 1),
        "throughput_ratio": round(qps_fault / qps_clean, 3),
        "p50_ratio": round(fault_lat["p50"] / max(clean_lat["p50"], 1e-9), 3),
        "no_poisoned_ids": not leaked,
        "poison_detected": int(st["poison_detected"]) - ctr0["poison_detected"],
        "seg_quarantined": int(st["seg_quarantined"]) - ctr0["seg_quarantined"],
        "seg_recovered": int(st["seg_recovered"]) - ctr0["seg_recovered"],
        "injected_faults": int(injector.injected),
        "recovered_all_segments": bool(recovered_all),
    }
    print(f"  chaos rate={FAULT_RATE}: coverage {row['coverage_mean']}, "
          f"throughput {row['throughput_ratio']}x clean "
          f"({row['faulted_qps']} vs {row['clean_qps']} qps), "
          f"p50 ratio {row['p50_ratio']}; "
          f"quarantined={row['seg_quarantined']} "
          f"recovered={row['seg_recovered']} "
          f"poison rows caught={row['poison_detected']} "
          f"(leaked ids: {len(leaked)})", flush=True)
    emit([row], "health")
    ok = (row["coverage_mean"] >= 0.95 and row["throughput_ratio"] >= 0.8
          and row["no_poisoned_ids"] and row["recovered_all_segments"])
    print(f"acceptance (>=0.95 coverage, >=0.8x clean throughput, zero "
          f"poisoned ids, all segments re-admitted): "
          f"{'PASS' if ok else 'FAIL'}")
    return [row]


if __name__ == "__main__":
    run()
