"""Found by name: a new configuration, mix or metric is a new file and a new
entry; and the harness refuses to run off the chip."""

import json
import os
import shutil
import subprocess
import sys

from chipbench.spec import Spec
from chipbench.tests.helpers import HARNESS_DIR, REPO, tiny_root

ROOT_BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def test_new_files_are_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    metric = tmp_path / "chipbench" / "metrics"
    metric.mkdir()
    (metric / "engine.rows_per_q.py").write_text(
        "def read(m):\n    return m.stats['n_b'] / m.queries\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "engine.rows_per_q", "unit": "dist/q"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = Spec(root)
    cell = spec.workload("tiny.mix")
    assert spec.config(cell["config"])["n"] == 2048
    assert spec.traffic(cell["traffic"])["clients"] == 32
    assert spec.loop("closed").run and spec.loop("open").run
    assert [m["name"] for m in spec.metrics("tiny.mix", trace=True)] == \
        ["engine.rows_per_q"]

    class M:
        stats, queries = {"n_b": 30.0}, 10

    assert spec.reader("engine.rows_per_q")(M) == 3.0
    # the harness's own readers stay reachable from any root
    assert spec.reader("cand.n_b_per_q")(M) == 3.0


def test_every_named_file_of_the_benchmark_exists():
    spec = Spec(REPO)
    for w in ROOT_BENCH["workloads"]:
        cfg = spec.config(w["config"])
        mix = spec.traffic(w["traffic"])
        assert spec.loop(mix["loop"]).run
        assert cfg["name"] == w["config"]
    for m in ROOT_BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for c in ROOT_BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")


def test_metrics_of_a_cell_follow_their_workloads_key():
    spec = Spec(REPO)
    for w in ROOT_BENCH["workloads"]:
        names = {m["name"] for m in spec.metrics(w["name"], trace=True)}
        for m in ROOT_BENCH["per_layer"]:
            assert (m["name"] in names) == (w["name"] in m.get(
                "workloads", [w["name"]]))


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         ROOT_BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_off_the_chip():
    out = _run_py(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HARNESS_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    out = _run_py(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
