"""`engine.idle_share`, `engine.host_ms_per_wave` and
`engine.finish_hold_ms_per_wave`: the engine's host-time counters over
the traced window, read without a profiler."""

import json
import time

import pytest

from chipbench.spec import Spec
from chipbench.tests.helpers import REPO, tiny_root

NAMES = ("engine.idle_share", "engine.host_ms_per_wave",
         "engine.finish_hold_ms_per_wave")

# the window deltas of a program with the counters: 4 waves, 2 s
STATS = {"batches": 4, "queries": 40,
         "eng_s_inflight": 1.5, "eng_s_idle_no_work": 0.25,
         "eng_s_idle_held": 0.125, "eng_s_idle_dispatch": 0.125,
         "eng_s_admit": 0.01, "eng_s_poll": 0.002, "eng_s_make_waves": 0.004,
         "eng_s_dispatch_search": 0.02, "eng_s_dispatch_finish": 1.2,
         "eng_s_collect": 0.008, "eng_s_collect_wait": 0.3,
         "eng_s_other": 0.006}
PARENT = {"batches": 4, "queries": 40, "n_b": 10.0}  # no counters


class _M:
    def __init__(self, stats):
        self.stats = stats


@pytest.mark.parametrize("stats,expected", [
    (STATS, 25.0),
    ({**STATS, "eng_s_idle_held": 0.0, "eng_s_idle_dispatch": 0.0,
      "eng_s_idle_no_work": 0.0}, 0.0),
    ({**STATS, "batches": 0}, None),
    ({**STATS, "eng_s_inflight": 0.0, "eng_s_idle_no_work": 0.0,
      "eng_s_idle_held": 0.0, "eng_s_idle_dispatch": 0.0}, None),
    (PARENT, None),
])
def test_idle_share_reader(stats, expected):
    got = Spec(REPO).reader("engine.idle_share")(_M(stats))
    assert got == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("stats,expected", [
    (STATS, (0.002 + 0.004 + 0.02 + 0.008 + 0.006) * 1e3 / 4),
    ({**STATS, "batches": 1}, 40.0),
    ({**STATS, "batches": 0}, None),
    (PARENT, None),
])
def test_host_ms_per_wave_reader(stats, expected):
    got = Spec(REPO).reader("engine.host_ms_per_wave")(_M(stats))
    assert got == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("stats,expected", [
    (STATS, 300.0),
    ({**STATS, "batches": 8}, 150.0),
    ({**STATS, "batches": 0}, None),
    (PARENT, None),
])
def test_finish_hold_ms_per_wave_reader(stats, expected):
    got = Spec(REPO).reader("engine.finish_hold_ms_per_wave")(_M(stats))
    assert got == (None if expected is None else pytest.approx(expected))


def test_engine_metrics_apply_to_every_cell():
    spec = Spec(REPO)
    for cell in (w["name"] for w in spec.data["workloads"]):
        names = {m["name"] for m in spec.metrics(cell, trace=True)}
        assert set(NAMES) <= names, cell


def test_traced_run_reports_the_engine_counters(tmp_path):
    """A whole traced run on the CPU: the counters reach the readers as
    window deltas, and each reads a value in range."""
    from chipbench import run

    root = tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "engine.idle_share", "unit": "%"},
                          {"name": "engine.host_ms_per_wave", "unit": "ms"},
                          {"name": "engine.finish_hold_ms_per_wave",
                           "unit": "ms"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, lines = run.run_cell("tiny.mix", 2**31 + 41, 1.5, True,
                                 root=root, interpret=True,
                                 t_start=time.perf_counter())
    assert result["correct"], lines
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(got) == set(NAMES)
    assert 0 <= got["engine.idle_share"] < 100
    assert got["engine.host_ms_per_wave"] > 0
    assert got["engine.finish_hold_ms_per_wave"] > 0
