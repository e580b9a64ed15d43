"""`correct` comes out false when the timed path is broken underneath.

Each test drives a whole run (the harness's look for a chip aside) with
one fault planted in the program where its answers are produced, and
the control (the reference in bfloat16, put in the program's place) must
fail the same comparison. The index is built once for the module.
"""

import numpy as np
import pytest

from chipbench.tests.helpers import run_tiny, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.fixture(scope="module", autouse=True)
def built_once():
    """Serve every run of this module from one build of the tiny index."""
    from repro.retrieval.service import UniversalVectorService

    orig = UniversalVectorService.__dict__["build"]
    cache = {}

    def build(cls, data, *args, **kw):
        key = (data.shape, kw.get("seed"))
        if key not in cache:
            cache[key] = orig.__func__(cls, data, *args, **kw)
        return cache[key]

    UniversalVectorService.build = classmethod(build)
    yield
    UniversalVectorService.build = orig


def test_sound_run_is_correct(root):
    result, lines = run_tiny(root)
    assert result["correct"], lines


def test_altered_answer_is_caught(root, monkeypatch):
    """An id of every answer altered where the engine collects it."""
    from repro.retrieval.engine.pipeline import TwoStagePipeline

    orig = TwoStagePipeline.collect

    def collect(self, wave):
        ids, *rest = orig(self, wave)
        ids = np.array(ids)
        ids[:, -1] = (ids[:, -1] + 1) % self.index.n
        return (ids, *rest)

    monkeypatch.setattr(TwoStagePipeline, "collect", collect)
    result, lines = run_tiny(root)
    assert not result["correct"], lines
    checks = result["checks"]
    assert (checks["dist_gap"]["value"] > checks["dist_gap"]["limit"]
            or checks["bad_answers"]["value"] > 0)


def test_half_of_each_wave_left_out_is_caught(root, monkeypatch):
    """The engine answers only half of each wave's requests."""
    from repro.retrieval.engine import ServingEngine

    orig = ServingEngine._collect

    def collect(self, wave):
        orig(self, wave)
        for r in wave.requests[::2]:
            self._results.pop(r.request_id, None)

    monkeypatch.setattr(ServingEngine, "_collect", collect)
    result, lines = run_tiny(root)
    assert not result["correct"], lines
    assert result["checks"]["unanswered"]["value"] > 0
    assert result["failed"] > 0


def test_control_in_bfloat16_is_not_correct(root):
    from chipbench.control import control_run

    for seed in (1, 2, 2**33 + 5):
        out = control_run("tiny.mix", seed, root=root)
        assert not out["correct"], out
        assert out["checks"]["dist_gap"]["value"] > \
            out["checks"]["dist_gap"]["limit"]
