"""The trace reduction: busy time, idle gaps and time by name."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e_deep256.json"


def _planes(device_ops, device_modules, host):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": device_modules},
            {"name": "XLA Ops", "events": device_ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]


def test_hand_computed_window():
    ops = [["fusion.1", 100, 50], ["fusion.2", 120, 60],   # union 100..180
           ["%_gather_impl.1 = f32", 300, 100],            # 300..400
           ["fusion.1", 950, 100]]                         # clipped at 1000
    mods = [["jit_segmented_knn_search(1)", 100, 80],
            ["jit__verify_abandon_impl(2)", 300, 100]]
    host = [["bench.window", 0, 1000], ["bench.pump", 0, 250],
            ["bench.harvest", 400, 500], ["bench.admit", 900, 100]]
    red = trace.Reduction(_planes(ops, mods, host))
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(230e-9)   # 80 + 100 + 50
    assert red.program_s("segmented_knn_search") == pytest.approx(80e-9)
    assert red.program_s("_verify_") == pytest.approx(100e-9)
    assert red.op_s(r"^%_(gather|abandon)_impl\.") == pytest.approx(100e-9)
    assert red.op_s("^fusion") == pytest.approx(160e-9)  # 50 + 60 + 50
    assert red.top_ops(3) == [
        ["jit__verify_abandon_impl/%_gather_impl.1", pytest.approx(100e-9)],
        ["jit_segmented_knn_search/fusion.2", pytest.approx(60e-9)],
        ["jit_segmented_knn_search/fusion.1", pytest.approx(50e-9)]]
    gaps = red.idle_gaps(10)
    # gaps: 0..100 (pump), 180..300 (pump 70, harvest 0), 400..950 (harvest)
    assert gaps == [["bench.harvest", pytest.approx(550e-9)],
                    ["bench.pump", pytest.approx(120e-9)],
                    ["bench.pump", pytest.approx(100e-9)]]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.Reduction(_planes([], [], [["bench.pump", 0, 10]]))


def _busy_by_timeline(planes, lo, hi):
    """Busy ns of the device by marking a boolean timeline, op by op."""
    line = np.zeros(hi - lo, bool)
    for pl in planes:
        if pl["name"] != "/device:TPU:0":
            continue
        for ln in pl["lines"]:
            if ln["name"] == "XLA Ops":
                for _, s, d in ln["events"]:
                    line[max(s, lo) - lo:max(min(s + d, hi), lo) - lo] = True
    return int(line.sum())


def test_chip_fixture_reduces_to_its_recorded_sums():
    """A 30 ms slice of a traced deep256 window on a TPU v5e, cut round
    the end of a candidate-generation program (op names cut to 96
    characters; `bench.window` set to the slice)."""
    fx = json.loads(FIXTURE.read_text())
    lo, hi = fx["slice"]
    red = trace.Reduction(fx["planes"])
    assert (red.lo, red.hi) == (lo, hi)
    assert red.busy_s == pytest.approx(
        _busy_by_timeline(fx["planes"], lo, hi) / 1e9, abs=1e-9)
    for name, value in fx["expected"].items():
        kind, pattern = name.split(":", 1)
        got = {"program": red.program_s, "op": red.op_s}[kind](pattern) \
            if kind in ("program", "op") else getattr(red, pattern)
        assert got == pytest.approx(value, rel=1e-9), name
    gaps = red.idle_gaps(10)
    assert [g[0] for g in gaps] == fx["expected_gap_spans"]
    assert 0 < red.busy_s < red.window_s
