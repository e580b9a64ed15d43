"""The GloVe-100 configuration, the exact-base mix, and `verify.scan_blocks`:
the mean dimension blocks the abandoning scan entered per verified
candidate, from the engine's counters over the traced window."""

import json
import time

import pytest

from chipbench.spec import Spec
from chipbench.tests.helpers import REPO, tiny_root

MIXEDP = ["deep256.mixedp", "trevi4096.mixedp", "glove100.mixedp"]


def test_glove100_and_basep_load():
    spec = Spec(REPO)
    glove = spec.config(spec.workload("glove100.mixedp")["config"])
    assert (glove["name"], glove["d"], glove["paper_n"]) == \
        ("glove100", 100, 1_191_714)
    assert glove["d"] % 8 != 0          # the ragged width is the point
    deep = spec.config("deep256")
    for key in ("index", "engine", "limits"):
        assert glove[key] == deep[key], key   # differs in d and data only
    assert glove["generator"] == dict(deep["generator"], df=5)
    assert set(glove["reduced"]) == {"n"}
    basep = spec.traffic(spec.workload("deep256.basep")["traffic"])
    mixedp = spec.traffic("mixedp")
    assert sorted(basep["p"]) == [1.0, 2.0]
    assert basep["weights"] == [1, 1]
    for key in ("loop", "clients", "k", "query_pool", "recall_sample",
                "insert_share"):
        assert basep[key] == mixedp[key], key
    assert spec.workload("deep256.basep")["config"] == "deep256"


def test_scan_blocks_applies_to_the_mixedp_cells_only():
    spec = Spec(REPO)
    cells = [w["name"] for w in spec.data["workloads"]]
    assert "glove100.mixedp" in cells and "deep256.basep" in cells
    for cell in cells:
        names = {m["name"] for m in spec.metrics(cell, trace=True)}
        assert ("verify.scan_blocks" in names) == (cell in MIXEDP), cell


class _M:
    def __init__(self, stats):
        self.stats = stats


@pytest.mark.parametrize("stats,expected", [
    ({"n_p": 40.0, "scan_blocks_w": 100.0}, 2.5),
    ({"n_p": 3.0, "scan_blocks_w": 0.0}, 0.0),
    ({"n_p": 0.0, "scan_blocks_w": 0.0}, None),   # nothing verified
    ({"queries": 8, "n_p": 40.0}, None),          # a program without it
])
def test_reader_is_blocks_over_verified_candidates(stats, expected):
    assert Spec(REPO).reader("verify.scan_blocks")(_M(stats)) == expected


def test_traced_run_at_a_ragged_width_reports_scan_blocks(tmp_path):
    """A whole traced run on the CPU at d = 100, as GloVe has it: every
    answer correct, and between 0 and ceil(100 / 32) = 4 blocks entered
    per verified candidate."""
    from chipbench import run

    root = tiny_root(tmp_path)
    cfg_path = root / "chipbench" / "configs" / "tiny.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(d=100, generator=dict(cfg["generator"], df=5))
    cfg_path.write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "verify.scan_blocks",
                           "unit": "blocks/row"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result, lines = run.run_cell("tiny.mix", 2**31 + 53, 1.5, True,
                                 root=root, interpret=True,
                                 t_start=time.perf_counter())
    assert result["correct"], lines
    assert 0 < result["metrics"]["verify.scan_blocks"]["value"] <= 4
