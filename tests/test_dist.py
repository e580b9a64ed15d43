"""Sharding rules, divisibility fallbacks, runtime axes."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import Runtime, abstract_mesh, logical_to_spec


@pytest.fixture(scope="module")
def rt():
    return Runtime(mesh=jax.make_mesh((1, 1), ("data", "model")))


def test_runtime_axes(rt):
    assert rt.dp_axes == ("data",)
    assert rt.tp_axis == "model"
    assert rt.dp_size == 1 and rt.tp_size == 1


def test_logical_mapping_divisible(rt):
    spec = logical_to_spec(("embed", "ff"), (64, 128), rt)
    assert spec == P("data", "model")


def test_divisibility_fallback():
    # AbstractMesh lets us model a multi-device mesh on the 1-CPU container
    rt = Runtime(mesh=abstract_mesh((1, 2), ("data", "model")))
    fallbacks = []
    spec = logical_to_spec(("heads", "head"), (41, 8), rt, fallbacks)
    assert spec == P(None, None)  # 41 not divisible by 2 -> replicated
    assert fallbacks and fallbacks[0][0] == "heads"


def test_missing_axis_fallback():
    rt = Runtime(mesh=abstract_mesh((2,), ("data",)))  # no 'model'
    spec = logical_to_spec(("ff",), (64,), rt)
    assert spec == P(None)


def test_production_mesh_rules_16x16():
    """The real production-mesh rules at 16x16 sizes (abstract devices)."""
    rt = Runtime(mesh=abstract_mesh((2, 16, 16), ("pod", "data", "model")))
    assert rt.dp_axes == ("pod", "data")
    assert rt.dp_size == 32 and rt.tp_size == 16
    # qwen: 40 heads not divisible by 16 -> replicated; ff 27648 shards
    assert logical_to_spec(("heads",), (40,), rt) == P(None)
    assert logical_to_spec(("ff",), (27648,), rt) == P("model")
    assert logical_to_spec(("embed",), (5120,), rt) == P(("pod", "data"))
    # full-DP mode spans all axes
    rt2 = Runtime(mesh=rt.mesh, full_dp=True)
    assert rt2.dp_size == 512
    assert logical_to_spec(("ff",), (27648,), rt2) == P(None)


def test_pod_axis_detection():
    # a single-pod mesh has no 'pod' data axis
    rt = Runtime(mesh=jax.make_mesh((1, 1), ("data", "model")))
    assert "pod" not in rt.dp_axes
