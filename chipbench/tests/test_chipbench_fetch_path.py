"""The harness end to end at a width where the level-0 loop fetches rows
with the beam-fetch kernel: `trevi64k`'s configuration at d = 1024."""

import json
import time

from chipbench.tests.helpers import HARNESS_DIR, tiny_root


def test_harness_runs_correct_on_the_fetch_kernels_path(tmp_path):
    """trevi64k's configuration at d = 1024, the narrowest rows the
    beam-fetch kernel takes, through `run_cell` with interpret-mode
    kernels: every answer agrees with the reference, and the loop read
    fewer rows than hops x W*m0, the gather path's count."""
    from chipbench import run

    root = tiny_root(tmp_path)
    cfg = json.loads((HARNESS_DIR / "configs" / "trevi64k.json").read_text())
    cfg.update(name="tiny", n=2048, d=1024)
    cfg["index"].update(t=64, delta_capacity=256)
    cfg["engine"].update(max_batch=16)
    (root / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    # level-0 trips per query, summed over segment lanes
    (root / "chipbench" / "metrics").mkdir()
    (root / "chipbench" / "metrics" / "test.hops_per_q.py").write_text(
        "def read(m):\n    return m.stats['beam_lane_trips'] / m.queries\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "cand.rows_read_per_q", "unit": "rows/q"},
                          {"name": "test.hops_per_q", "unit": "trips/q"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result, lines = run.run_cell("tiny.mix", 2**31 + 67, 1.5, True,
                                 root=root, interpret=True,
                                 t_start=time.perf_counter())
    assert result["correct"], lines
    rows = result["metrics"]["cand.rows_read_per_q"]["value"]
    hops = result["metrics"]["test.hops_per_q"]["value"]
    w_m0 = 1 * 2 * cfg["index"]["m"]  # expand_width 1, level-0 degree 2m
    assert 0 < rows < hops * w_m0
