"""`cand.trip_fill`: the beam loop's lane-trips that did work, over those
it ran, from the engine's counters over the traced window."""

import json
import time

import pytest

from chipbench.spec import Spec
from chipbench.tests.helpers import REPO, tiny_root


class _M:
    def __init__(self, stats):
        self.stats = stats


@pytest.mark.parametrize("stats,expected", [
    ({"beam_lane_trips": 600, "beam_lane_slots": 600}, 100.0),
    ({"beam_lane_trips": 3, "beam_lane_slots": 4}, 75.0),
    ({"beam_lane_trips": 0, "beam_lane_slots": 0}, None),
    ({"queries": 8, "n_b": 10.0}, None),  # a program without the counters
])
def test_reader_is_the_counters_ratio(stats, expected):
    assert Spec(REPO).reader("cand.trip_fill")(_M(stats)) == expected


def test_traced_run_reports_trip_fill(tmp_path):
    """A whole traced run on the CPU: the counters reach the reader as
    window deltas; with no TPU plane the device's idle share is absent."""
    from chipbench import run

    root = tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": n, "unit": "%"}
                          for n in ("cand.trip_fill", "device.idle_share")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, lines = run.run_cell("tiny.mix", 2**31 + 29, 1.5, True,
                                 root=root, interpret=True,
                                 t_start=time.perf_counter())
    assert result["correct"], lines
    assert set(result["metrics"]) == {"cand.trip_fill"}
    assert 0 < result["metrics"]["cand.trip_fill"]["value"] <= 100
