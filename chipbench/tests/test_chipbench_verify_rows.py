"""`verify.rows_fetched_per_q`: the candidate rows the verification
kernels copied per query, from the engine's counters over the traced
window."""

import json
import time

import pytest

from chipbench.spec import Spec
from chipbench.tests.helpers import REPO, tiny_root

MIXEDP = ["deep256.mixedp", "trevi4096.mixedp", "glove100.mixedp",
          "trevi64k.mixedp"]


class _M:
    def __init__(self, stats):
        self.stats = stats

    @property
    def queries(self):
        return int(self.stats.get("queries", 0))


@pytest.mark.parametrize("stats,expected", [
    ({"queries": 4, "verify_rows": 100}, 25.0),
    ({"queries": 8, "verify_rows": 80}, 10.0),
    ({"queries": 0, "verify_rows": 0}, None),
    ({"queries": 8, "n_p": 160.0}, None),  # a program without the counter
])
def test_reader_is_rows_per_query(stats, expected):
    assert Spec(REPO).reader("verify.rows_fetched_per_q")(_M(stats)) == \
        expected


def test_rows_fetched_applies_to_the_mixedp_cells_only():
    spec = Spec(REPO)
    for cell in (w["name"] for w in spec.data["workloads"]):
        names = {m["name"] for m in spec.metrics(cell, trace=True)}
        assert ("verify.rows_fetched_per_q" in names) == (cell in MIXEDP), \
            cell


def test_traced_run_reports_rows_fetched(tmp_path):
    """A whole traced run on the CPU: every query of the mix verifies, so
    it copies at least its first k rows and at most the candidates it
    verified (N_p)."""
    from chipbench import run

    root = tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "verify.rows_fetched_per_q",
                           "unit": "rows/q"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, lines = run.run_cell("tiny.mix", 2**31 + 37, 1.5, True,
                                 root=root, interpret=True,
                                 t_start=time.perf_counter())
    assert result["correct"], lines
    rows = result["metrics"]["verify.rows_fetched_per_q"]["value"]
    k = Spec(root).traffic("mix")["k"]
    assert k <= rows <= Spec(root).config("tiny")["index"]["t"], rows
