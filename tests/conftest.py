"""Shared fixtures: small synthetic datasets + prebuilt indexes.

NOTE: no XLA_FLAGS device-count forcing here — smoke tests and benches must
see the real single-device CPU backend; a multi-device mesh is modelled
with `repro.dist.sharding.abstract_mesh` instead.
"""

import jax
import numpy as np
import pytest

from repro.core.build import build_hnsw, build_hnsw_bulk
from repro.core.datasets import make_dataset
from repro.core.uhnsw import UHNSW, UHNSWParams
from repro.index import SegmentedGraphs, ShardedUHNSW, build_segments


@pytest.fixture(autouse=True, scope="module")
def _bounded_compile_cache():
    """Drop compiled executables after each test module.

    The CPU XLA JIT keeps every compiled program alive for the whole
    process; once the suite grew past ~500 tests, the accumulated state
    reliably segfaulted LLVM inside a later large Pallas compile (the
    vector-p abandoning-verify program) in single-process `pytest -x -q`
    runs. Clearing per module bounds the live set to one module's worth.
    Device arrays are unaffected, so session fixtures (datasets, built
    graphs) survive; the cost is cross-module recompiles of the shared
    search programs.
    """
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def small_ds():
    """~2k-point SIFT-like dataset: big enough for meaningful recall."""
    return make_dataset("sift", n=2000, n_queries=24, seed=7)


@pytest.fixture(scope="session")
def graphs_bulk(small_ds):
    g1 = build_hnsw_bulk(small_ds.data, 1.0, m=12, seed=0)
    g2 = build_hnsw_bulk(small_ds.data, 2.0, m=12, seed=1)
    return g1, g2


@pytest.fixture(scope="session")
def graph_incremental(small_ds):
    # smaller subset: the sequential builder is Python-bound
    data = small_ds.data[:600]
    return build_hnsw(data, 2.0, m=8, ef_construction=60, seed=0)


@pytest.fixture(scope="session")
def segments4(small_ds):
    """Frozen 4-segment build of small_ds (both base graphs per segment).

    The per-segment graph builds are the expensive part of every sharded
    test; they happen once per session here. Tests never search this object
    directly — they wrap it via `sharded_index` (read-only) or
    `make_sharded` (fresh mutable wrapper per call)."""
    return build_segments(small_ds.data, num_segments=4, m=12, seed=0)


def _wrap_segments(segs4, data, **kwargs):
    """Fresh ShardedUHNSW over the frozen per-segment graphs: the wrapper's
    mutable state (segment lists, delta buffer, params, phase caches) is
    new, while the graphs themselves are shared and never rebuilt
    (compaction appends, it does not modify existing segments)."""
    clone = SegmentedGraphs(
        graphs1=list(segs4.graphs1),
        graphs2=list(segs4.graphs2),
        global_ids=[ids.copy() for ids in segs4.global_ids],
    )
    return ShardedUHNSW(clone, data, **kwargs)


@pytest.fixture(scope="session")
def sharded_index(small_ds, segments4):
    """Session-shared 4-segment index (t=150). READ-ONLY: tests that add(),
    compact(), or mutate params/sharded_params must use make_sharded."""
    return _wrap_segments(segments4, small_ds.data,
                          params=UHNSWParams(t=150), delta_capacity=16)


@pytest.fixture
def make_sharded(small_ds, segments4):
    """Factory for throwaway ShardedUHNSW instances over the session's
    frozen 4-segment build. kwargs forward to ShardedUHNSW.__init__
    (params, delta_capacity, sharded_params)."""
    def _make(**kwargs):
        kwargs.setdefault("params", UHNSWParams(t=150))
        return _wrap_segments(segments4, small_ds.data, **kwargs)

    return _make


@pytest.fixture(scope="session")
def monolithic_index(small_ds, graphs_bulk):
    """Session-shared monolithic UHNSW at the same t as sharded_index —
    the recall-parity reference. READ-ONLY."""
    return UHNSW(*graphs_bulk, UHNSWParams(t=150))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
