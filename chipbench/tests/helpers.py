"""A tiny benchmark root on the CPU: a BENCHMARK.json, one configuration
and one traffic mix in a temporary directory, found by name like any
other. Sizes are small so that a whole run fits a unit test."""

from __future__ import annotations

import json
import time
from pathlib import Path

HARNESS_DIR = Path(__file__).resolve().parents[1]
REPO = HARNESS_DIR.parent

E2E = [("setup_s", "s"), ("qps", "queries/s"), ("p99_ms", "ms"),
       ("recall_at_10", "ratio")]


def tiny_root(tmp: Path, *, loop: str = "closed", insert_share: float = 0,
              extra_traffic: dict | None = None) -> Path:
    """Write a tiny cell `tiny.mix` under `tmp`; returns the root."""
    cfg = json.loads((HARNESS_DIR / "configs" / "deep256.json").read_text())
    cfg.update(name="tiny", n=2048, d=64)
    cfg["index"].update(t=64, delta_capacity=256)
    cfg["engine"].update(max_batch=16)
    mix = json.loads((HARNESS_DIR / "traffic" / "mixedp.json").read_text())
    mix.update(loop=loop, clients=32, query_pool=64, recall_sample=32,
               insert_share=insert_share,
               insert_pool=64 if insert_share else 0)
    if loop == "open":
        mix.update(rate_qps=40.0, burst=4, arrivals="poisson")
    mix.update(extra_traffic or {})
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    (tmp / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp / "chipbench" / "traffic" / "mix.json").write_text(json.dumps(mix))
    bench = {
        "paths": ["chipbench"],
        "configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny",
                       "traffic": "mix", "chips": 1}],
        "end_to_end": [{"name": n, "unit": u} for n, u in E2E],
        "per_layer": [],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(root: Path, seed: int = 2**31 + 11, seconds: float = 1.5):
    """One run of `tiny.mix` with interpret-mode kernels."""
    from chipbench import run

    return run.run_cell("tiny.mix", seed, seconds, False, root=root,
                        interpret=True, t_start=time.perf_counter())


def assert_contract_shape(result: dict, metric_names) -> None:
    """The result line's keys and types, as the benchmark contract has
    them; the numbers compared come last, each beside its limit."""
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result, key
    assert isinstance(result["correct"], bool)
    assert isinstance(result["attempted"], int) and result["attempted"] > 0
    assert isinstance(result["failed"], int)
    assert set(result["metrics"]) == set(metric_names)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in result["device"], key
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(result, allow_nan=False)
