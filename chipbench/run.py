"""Run one cell of the chip benchmark and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up draws the cell's corpus on the device from the seed, builds the
index with `UniversalVectorService.build`, and warms the engine's lanes
that the traffic uses. The window then drives the service's
`ServingEngine` with the mix's loop for `--seconds`, and drains. After
the window the program's state is freed and every answer is compared with
the plain reference (`compare.py`). `--trace 0` reports the end-to-end
metrics; `--trace 1` traces the window with the JAX profiler and reports
the per-layer metrics. The last stdout line is one JSON object; the
numbers compared, each beside its limit, are the last stderr lines.

It runs only on a TPU: off the chip it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HARNESS_DIR = Path(__file__).resolve().parent
ROOT = HARNESS_DIR.parent
CACHE_DIR = HARNESS_DIR / ".jax_cache"
DRAIN_LIMIT_S = 60.0  # an answer may come this long after the close


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Env:
    """What a loop sees: the engine, the stream, the clock and the spans."""

    def __init__(self, service, stream, traffic, queries, inserts, seconds,
                 seed, span, recorder):
        from repro.retrieval.service import QueryRequest

        self.service, self.engine = service, service.engine
        self.stream, self.traffic, self.seconds, self.seed = \
            stream, traffic, seconds, seed
        self.queries, self.inserts = queries, inserts
        self.span, self.recorder = span, recorder
        self.clock = time.perf_counter
        self._next = 0
        self._query_request = QueryRequest

    def new_id(self) -> int:
        self._next += 1
        return self._next - 1

    def visible(self) -> int:
        return int(self.service.index.n)

    def request(self, rid: int, op):
        return self._query_request(vector=self.queries[op.index], p=op.p,
                                   k=op.k, request_id=rid)

    def insert(self, rid: int, op, due: float) -> None:
        """A synchronous insert; acknowledged when `insert` returns."""
        from repro.retrieval.service import InsertRequest

        from chipbench.stream import Record

        rec = Record(op, due=due, admitted=self.clock())
        self.recorder.add(rid, rec)
        with self.span("bench.insert"):
            self.service.insert([InsertRequest(vector=self.inserts[op.index],
                                               request_id=rid)])
        rec.finish = self.clock()
        self.recorder.inserted.append(op.index)

    def drain(self) -> None:
        """Close the window: admit nothing more, answer what is queued."""
        deadline = self.clock() + DRAIN_LIMIT_S
        eng, rec = self.engine, self.recorder
        with self.span("bench.drain"):
            while eng.pending and self.clock() < deadline:
                out = eng.drain()
                out.update(eng.take_results())
                rec.finish(out, eng.take_failures(), self.clock())
            rec.finish(eng.take_results(), eng.take_failures(), self.clock())
        rec.closed = self.clock()


def _numeric(stats: dict) -> dict:
    return {k: v for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


class Measurements:
    """What a per-layer reader can read: engine-stat deltas over the traced
    window, the build's seconds, the trace reduction and the peaks."""

    def __init__(self, *, stats, trace, cfg, device_kind, build_s):
        self.stats, self.trace, self.cfg = stats, trace, cfg
        self.device_kind, self.build_s = device_kind, build_s

    @property
    def queries(self) -> int:
        return int(self.stats.get("queries", 0))

    def peak(self, key: str) -> float:
        """A published peak of this device (`peaks.json`); a device kind
        the table lacks is an error, never a default."""
        return float(load_peaks(self.device_kind)[key])


def _percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def _device_info(devices) -> dict:
    dev = devices[0]
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def load_peaks(kind: str) -> dict:
    with open(HARNESS_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, interpret: bool | None = None,
             t_start: float | None = None) -> tuple[dict, list[str]]:
    """Set up, run and check one cell; returns (result, check lines).

    `root` holds the BENCHMARK.json to read; `interpret` is forwarded to
    `UHNSWParams.interpret` (None: compiled kernels on the chip).
    """
    import jax
    import numpy as np

    from repro.index.sharded import ShardedParams
    from repro.core.uhnsw import UHNSWParams
    from repro.retrieval.service import UniversalVectorService

    from chipbench import compare, data, stream
    from chipbench.spec import Spec

    t_start = T_START if t_start is None else t_start
    spec = Spec(root)
    cell = spec.workload(workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    loop = spec.loop(traffic["loop"])
    devices = jax.devices()[:int(cell["chips"])]
    pool, n_ins = int(traffic["query_pool"]), int(traffic.get(
        "insert_pool", 0))

    # -- set-up: corpus, build, warm-up --------------------------------
    corpus, extra = data.draw(cfg, seed, pool + n_ins)
    corpus_host = np.asarray(corpus)
    extra_host = np.asarray(extra)
    del corpus, extra
    queries, inserts = extra_host[:pool], extra_host[pool:]
    _log(f"set-up: corpus {corpus_host.shape} drawn "
         f"{time.perf_counter() - t_start:.1f} s after start")
    idx_cfg, eng_cfg = cfg["index"], cfg["engine"]
    t_b = time.perf_counter()
    service = UniversalVectorService.build(
        corpus_host,
        UHNSWParams(t=int(idx_cfg["t"]), tau=float(idx_cfg["tau"]),
                    abandon=bool(idx_cfg["abandon"]), interpret=interpret),
        m=int(idx_cfg["m"]),
        num_segments=int(idx_cfg["num_segments"]),
        seed=int(idx_cfg["build_seed"]),
        delta_capacity=int(idx_cfg["delta_capacity"]),
        sharded_params=ShardedParams(policy=idx_cfg["policy"]),
        max_batch=int(eng_cfg["max_batch"]),
        min_bucket=int(eng_cfg["min_bucket"]),
        max_wait_ms=float(eng_cfg["max_wait_ms"]))
    idx = service.index
    jax.block_until_ready([idx.X, idx.segments.X, idx.segments.arrays1,
                           idx.segments.arrays2])
    build_s = time.perf_counter() - t_b
    _log(f"set-up: build {build_s:.3f} s")
    lanes = stream.lp_lanes(traffic["p"], idx.params.cutoff)
    eng = service.engine
    warm = eng.warmup(k=int(traffic["k"]), ps=tuple(lanes))
    _log(f"set-up: warm-up {warm} batches over lanes p={lanes}")

    # -- the window -------------------------------------------------------
    recorder = stream.Recorder()
    st = service.stats
    st["latency_records"] = type(st["latency_records"])()  # unbounded
    before = _numeric(st)
    compiles = _CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    env = Env(service, stream.Stream(traffic, seed, pool, n_ins), traffic,
              queries, inserts, seconds, seed, span, recorder)
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness's own spans suffice
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with compiles, span("bench.window"):
        loop.run(env)
    if trace:
        jax.profiler.stop_trace()
    after = _numeric(st)
    delta = {k: after[k] - before.get(k, 0) for k in after}
    latency_records = list(st["latency_records"])
    device = _device_info(devices)

    # -- what the window gave ------------------------------------------
    t0, t_end = recorder.window
    recs = recorder.records
    q_recs = {r: v for r, v in recs.items() if v.op.kind == stream.QUERY}
    answered = {r: v for r, v in q_recs.items() if v.ids is not None}
    # the rate covers all the work due in the window and all the time it
    # took: from the window's start to the last of those answers
    t_last = max((v.finish for v in answered.values()), default=t_end)
    # an unanswered request waited at least until the drain gave up on it
    lat = [((v.finish if v.ids is not None else recorder.closed) - v.due)
           * 1e3 for v in q_recs.values()]
    late = [v.admitted - v.due for v in q_recs.values()]
    attempted = len(recs)
    failed = attempted - len(answered) - sum(
        1 for v in recs.values() if v.op.kind == stream.INSERT
        and v.finish is not None)
    _log(f"window: {attempted} attempted, {len(answered)} answered "
         f"(last {t_last - t0:.3f} s after the start of a "
         f"{t_end - t0:.3f} s window), {failed} failed, "
         f"{len(recorder.inserted)} inserts, compiles in window "
         f"{compiles.count}")

    reduction = None
    if trace:
        from chipbench import trace as trace_mod

        reduction = trace_mod.Reduction(trace_mod.load_xplane(
            trace_mod.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- free the program's state, then the reference ------------------
    del env, eng, idx, service, st
    gc.collect()
    x_ref, extra_ref = data.draw(cfg, seed, pool + n_ins)
    if recorder.inserted:
        import jax.numpy as jnp

        x_ref = jnp.concatenate(
            [x_ref, extra_ref[pool + np.asarray(recorder.inserted)]])
    del extra_ref
    rids = sorted(answered)
    rng = stream.rng_for(seed, 5)
    n_sample = min(int(traffic["recall_sample"]), len(rids))
    sample = sorted(int(r) for r in rng.choice(rids, n_sample,
                                               replace=False)) \
        if n_sample else []
    t_c = time.perf_counter()
    checks, extras = compare.compare(
        x_ref, queries,
        {r: (v.op.index, v.op.p, v.visible, v.ids, v.dists)
         for r, v in answered.items()},
        attempted - len(answered) - len(recorder.inserted), sample,
        cfg["limits"])
    for ex in extras.get("bad_examples", []):
        _log(f"bad answer: {ex}")
    _log(f"reference: {extras.get('compared', 0)} answers, "
         f"{extras.get('sampled', 0)} sampled, "
         f"{time.perf_counter() - t_c:.1f} s")
    correct = attempted > 0 and compare.verdict(checks)

    # -- metrics -----------------------------------------------------------
    metrics = {}
    if not trace:
        e2e = {
            "setup_s": setup_s,
            "qps": len(answered) / (t_last - t0) if answered else 0.0,
            "p99_ms": _percentile(lat, 99) if lat else 0.0,
            "recall_at_10": extras["recall"],
        }
        for m in spec.metrics(workload, trace=False):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        meas = Measurements(
            stats={**delta, "latency_records": latency_records},
            trace=reduction, cfg=cfg, device_kind=device["kind"],
            build_s=build_s)
        for m in spec.metrics(workload, trace=True):
            v = spec.reader(m["name"])(meas)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": reduction.idle_gaps(10)}
    result["loadgen"] = {
        "late_p99_ms": _percentile(late, 99) * 1e3 if late else 0.0,
        "late_max_ms": max(late) * 1e3 if late else 0.0,
        "compiles_in_window": compiles.count,
        "inserts": len(recorder.inserted)}
    result["checks"] = checks
    lines = [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
             for name, c in checks.items()]
    return result, lines


class _CompileCounter:
    """Counts XLA compilations while active (none should land in a window).
    """

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event: str, *args, **kwargs) -> None:
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def __enter__(self):
        import jax

        if not getattr(_CompileCounter, "_registered", False):
            jax.monitoring.register_event_duration_secs_listener(
                _CompileCounter._dispatch)
            _CompileCounter._registered = True
        _CompileCounter._active = self
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        _CompileCounter._active = None
        return False

    @staticmethod
    def _dispatch(event: str, *args, **kwargs) -> None:
        active = getattr(_CompileCounter, "_active", None)
        if active is not None:
            active._listen(event)


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout, or
    where JAX_COMPILATION_CACHE_DIR says. Every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _log(f"chipbench: no program under {ROOT / 'src'}; run from a "
             "checkout of the repository")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"chipbench: JAX found no TPU (first device: "
             f"{devices[0].platform}); refusing to run off the chip")
        return 3
    from chipbench.spec import Spec

    chips = int(Spec(ROOT).workload(args.workload)["chips"])
    if len(devices) < chips:
        _log(f"chipbench: {args.workload} needs {chips} chips, JAX found "
             f"{len(devices)}")
        return 3
    cache = enable_compile_cache()
    _log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
         f"{cache}")
    result, lines = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    for line in lines:
        _log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
