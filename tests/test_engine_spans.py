"""The engine's per-wave profiler spans (repro.retrieval.engine).

A small engine serves a mixed-p stream under `jax.profiler` on the CPU.
The host plane of the profile must hold one span per engine stage, the
spans of one wave must share its `wave=` id and padded size, and the
blocking read must lie inside the collect that owns it. Profiling must
not change a single served id or distance.
"""

from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.retrieval.service import QueryRequest, UniversalVectorService

STAGES = ("engine.make_waves", "engine.dispatch_search",
          "engine.dispatch_finish", "engine.collect", "engine.collect.wait")
PER_WAVE = STAGES[1:]  # every wave passes these once; make_waves per flush


def _requests(small_ds, n, seed):
    rng = np.random.default_rng(seed)
    return [QueryRequest(vector=small_ds.queries[i % len(small_ds.queries)],
                         p=float(rng.choice([0.5, 0.8, 1.25, 1.7, 2.0])),
                         k=10, request_id=i) for i in range(n)]


def _service(index):
    return UniversalVectorService(index=index, max_batch=16, min_bucket=8)


def _engine_spans(trace_dir: Path) -> list[tuple[str, int, int, dict]]:
    """(name, start_ns, end_ns, args) of the host plane's engine spans."""
    path, = trace_dir.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#")[0]
                if name.startswith("engine."):
                    out.append((name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                {k: v for k, v in e.stats}))
    return out


@pytest.fixture(scope="module")
def profiled(small_ds, sharded_index, tmp_path_factory):
    """One stream served twice: under the profiler and without it."""
    reqs = _requests(small_ds, 40, seed=13)
    plain = _service(sharded_index).serve(reqs)
    svc = _service(sharded_index)
    svc.engine.warmup(k=10, ps=(0.8, 1.7, 2.0))  # no compiles in the trace
    trace_dir = tmp_path_factory.mktemp("engine-trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        traced = svc.serve(reqs)
    finally:
        jax.profiler.stop_trace()
    return reqs, plain, traced, svc.stats, _engine_spans(trace_dir)


def test_host_plane_holds_every_stage_span(profiled):
    _, _, _, stats, spans = profiled
    assert {name for name, *_ in spans} == set(STAGES)
    # one dispatch_search per wave executed (no retries in a clean run)
    n_waves = sum(1 for name, *_ in spans
                  if name == "engine.dispatch_search")
    assert n_waves == stats["batches"] > 1


def test_each_wave_spans_share_one_wave_id(profiled):
    _, _, _, _, spans = profiled
    by_wave: dict[int, list] = {}
    for name, s, e, args in spans:
        assert set(args) == {"wave", "rows"}, (name, args)
        if name in PER_WAVE:
            by_wave.setdefault(int(args["wave"]), []).append(
                (name, int(args["rows"])))
    first = min(by_wave)  # the warm-up's waves were numbered before these
    assert sorted(by_wave) == list(range(first, first + len(by_wave)))
    for wave, stages in by_wave.items():
        assert sorted(n for n, _ in stages) == sorted(PER_WAVE), wave
        assert len({rows for _, rows in stages}) == 1, (wave, stages)
    # a flush's cut is tagged with the first wave it yields
    cuts = {int(a["wave"]) for n, _, _, a in spans
            if n == "engine.make_waves"}
    assert cuts <= set(by_wave)


def test_collect_wait_nests_inside_its_collect(profiled):
    _, _, _, _, spans = profiled
    collect = {int(a["wave"]): (s, e) for n, s, e, a in spans
               if n == "engine.collect"}
    waits = [(int(a["wave"]), s, e) for n, s, e, a in spans
             if n == "engine.collect.wait"]
    assert len(waits) == len(collect)
    for wave, s, e in waits:
        cs, ce = collect[wave]
        assert cs <= s <= e <= ce, wave


def test_profiling_leaves_answers_bitwise_unchanged(profiled):
    reqs, plain, traced, _, _ = profiled
    assert set(traced) == set(plain) == {r.request_id for r in reqs}
    for r in reqs:
        np.testing.assert_array_equal(traced[r.request_id][0],
                                      plain[r.request_id][0])
        np.testing.assert_array_equal(traced[r.request_id][1],
                                      plain[r.request_id][1])
