"""`cand.rows_read_per_q`: the corpus rows the level-0 loop read per
query, from the engine's counters over the traced window."""

import pytest

from chipbench.spec import Spec
from chipbench.tests.helpers import REPO


class _M:
    def __init__(self, stats):
        self.stats = stats

    @property
    def queries(self):
        return int(self.stats.get("queries", 0))


@pytest.mark.parametrize("stats,expected", [
    ({"queries": 4, "beam_rows_read": 307200}, 76800.0),
    ({"queries": 8, "beam_rows_read": 136000}, 17000.0),
    ({"queries": 0, "beam_rows_read": 0}, None),
    ({"queries": 8, "n_b": 10.0}, None),  # a program without the counter
])
def test_reader_is_rows_per_query(stats, expected):
    assert Spec(REPO).reader("cand.rows_read_per_q")(_M(stats)) == expected


def test_traced_run_reports_rows_read(tmp_path):
    """A whole traced run on the CPU: the counter reaches the reader as a
    window delta. The tiny cell's rows (d = 64) are not whole DMA tiles,
    so every trip reads its whole frontier: a positive multiple of m0
    rows in all."""
    import json
    import time

    from chipbench import run
    from chipbench.tests.helpers import tiny_root

    root = tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "cand.rows_read_per_q", "unit": "rows/q"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, lines = run.run_cell("tiny.mix", 2**31 + 31, 1.5, True,
                                 root=root, interpret=True,
                                 t_start=time.perf_counter())
    assert result["correct"], lines
    assert result["metrics"]["cand.rows_read_per_q"]["value"] > 0
