"""BENCHMARK.json and the files it names, found by name.

A cell names a configuration and a traffic mix; the mix names its loop;
each per-layer metric has a reader. Each is looked up under the
benchmark's `paths` first (so a checkout, or a test's directory, can add
one by adding a file) and then beside this harness.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HARNESS_DIR = Path(__file__).resolve().parent


class Spec:
    """One BENCHMARK.json and the directory it lives in."""

    def __init__(self, root: Path):
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as f:
            self.data = json.load(f)

    def _find(self, kind: str, name: str, suffix: str) -> Path:
        dirs = [self.root / p for p in self.data.get("paths", [])]
        for d in dirs + [HARNESS_DIR]:
            path = d / kind / f"{name}{suffix}"
            if path.is_file():
                return path
        raise FileNotFoundError(f"no {kind}/{name}{suffix} under "
                                f"{[str(d) for d in dirs + [HARNESS_DIR]]}")

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(self._find("traffic", name, ".json")) as f:
            return json.load(f)

    def loop(self, kind: str):
        return _load_module(self._find("loops", kind, ".py"))

    def metrics(self, workload: str, trace: bool) -> list[dict]:
        """The metrics a run of `workload` reports: end-to-end ones with
        the trace off, per-layer ones with it on."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.data[key]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        """The `read(measurements)` function of a per-layer metric."""
        return _load_module(self._find("metrics", metric, ".py")).read


def _load_module(path: Path):
    # file names follow metric names, which may hold dots: load by path
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
