"""Exit status of the retrieval launcher (`repro.launch.serve`).

A run with no fault injector must not report success when the engine
marked a request FAILED; a chaos run (`--fault-rate > 0`) expects
injected failures and still exits 0. The service is stubbed so the test
checks only the launcher's accounting.
"""

from argparse import Namespace

import pytest

from repro.launch import serve
from repro.retrieval import service as service_mod
from repro.retrieval.engine import LIVE, default_stats


class _StubEngine:
    state = LIVE

    def __init__(self, failures):
        self._failures = failures

    def take_failures(self):
        out, self._failures = self._failures, {}
        return out


class _StubService:
    def __init__(self, failed: int):
        self.stats = default_stats()
        self.stats["failed"] = failed
        self.engine = _StubEngine({i: "device error" for i in range(failed)})

    def serve(self, reqs):
        return {r.request_id: None for r in reqs[self.stats["failed"]:]}

    def latency_summary(self):
        return {"p50": 0.0, "p95": 0.0}


def _args(fault_rate: float) -> Namespace:
    return Namespace(fault_rate=fault_rate, fault_seed=0, fault_sites=None,
                     n=64, compressed=False, state_dir=None, segments=4,
                     min_coverage=0.0, requests=4, seed=0)


@pytest.mark.parametrize("fault_rate,failed,want", [
    (0.0, 0, 0),    # clean run
    (0.0, 2, 1),    # FAILED without an injector: a real fault
    (0.3, 2, 0),    # chaos rehearsal: injected failures are expected
])
def test_serve_retrieval_exit_status(monkeypatch, fault_rate, failed, want):
    monkeypatch.setattr(service_mod.UniversalVectorService, "build",
                        classmethod(lambda cls, *a, **k: _StubService(failed)))
    assert serve.serve_retrieval(_args(fault_rate)) == want
