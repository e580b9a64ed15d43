"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode runs the kernel bodies as plain JAX and never meets
Mosaic's lowering rules (no value indexing with a traced index, DMA
slices aligned to the HBM tiling, i1 vectors across branches, VMEM
limits). These tests compile each gather-family kernel — scalar p and
vector p — plus the vector-p pairwise kernel for a *described* v5e chip,
through the same `kernels.ops` entry points the index calls with
`interpret=False`, and require the compiled program to hold the kernel
(`tpu_custom_call`). No chip is needed: the installed TPU compiler
compiles for a topology it is told about.

The row sources (corpus, band codes) enter in `ops.kernel_rows` layout,
the feature axis lane-padded, as the index places them on the chip; the
verification program must then hold no copy of the corpus.

The topology is described inside a module-scoped fixture (never at
import), so every pytest worker collects the same tests and only the one
that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

D_GRID = [96, 128, 256, 960]   # Deep-96, SIFT, Deep-256, GIST widths
P_GRID = [0.8, 2.0, "vector"]  # slow family, MXU family, per-row p
B, C, N = 16, 300, 4099        # ragged batch / candidate / corpus sizes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rows(d: int) -> int:
    """Width of a d-wide row source in `kernel_rows` layout on the chip."""
    return -(-d // 128) * 128


def _p_args(p, sharding):
    """(extra operand specs, p getter): vector p rides as a (B,) operand."""
    if p == "vector":
        return (_spec((B,), sharding),), lambda extra: extra[0]
    return (), lambda extra: p


def _assert_kernel_compiles(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d", D_GRID)
def test_gather_kernel_compiles(one_chip, d, p):
    extra, p_of = _p_args(p, one_chip)
    _assert_kernel_compiles(
        lambda q, ids, x, *e: ops.lp_gather_distance(
            q, ids, x, p_of(e), interpret=False),
        _spec((B, d), one_chip), _spec((B, C), one_chip, jnp.int32),
        _spec((N, _rows(d)), one_chip), *extra)


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d", D_GRID)
def test_abandon_kernel_compiles(one_chip, d, p):
    extra, p_of = _p_args(p, one_chip)
    _assert_kernel_compiles(
        lambda q, ids, x, thr, sb, *e: ops.lp_gather_abandon(
            q, ids, x, thr, sb, p_of(e), interpret=False),
        _spec((B, d), one_chip), _spec((B, C), one_chip, jnp.int32),
        _spec((N, _rows(d)), one_chip), _spec((B,), one_chip),
        _spec((B, C), one_chip), *extra)


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d", D_GRID)
def test_screen_kernel_compiles(one_chip, d, p):
    extra, p_of = _p_args(p, one_chip)
    _assert_kernel_compiles(
        lambda q, ids, codes, sc, rad, thr, sb, *e: ops.lp_gather_screen(
            q, ids, codes, sc, rad, thr, sb, p_of(e), interpret=False),
        _spec((B, d), one_chip), _spec((B, C), one_chip, jnp.int32),
        _spec((N, _rows(d)), one_chip, jnp.int8), _spec((d,), one_chip),
        _spec((d,), one_chip), _spec((B,), one_chip),
        _spec((B, C), one_chip), *extra)


@pytest.mark.parametrize("d", D_GRID)
def test_vector_p_pairwise_kernel_compiles(one_chip, d):
    _assert_kernel_compiles(
        lambda q, x, p: ops.pallas_pairwise_lp(q, x, p, root=False,
                                               interpret=False),
        _spec((B, d), one_chip), _spec((N, d), one_chip),
        _spec((B,), one_chip))


# result shape and op name of one HLO instruction: `%x = f32[4099,128]{1,0} pad(`
_HLO_RESULT = re.compile(r"=\s*(\w+\[[\d,]*\])(?:\{[^}]*\})?\s+([\w\-]+)\(")


@pytest.mark.parametrize("d", [96, 960])
def test_verification_program_holds_no_corpus_copy(one_chip, d):
    """The mixed-p verification program reads the corpus where the index
    laid it out: an (N, dx) array comes only from the parameter and the
    loop carry's tuple reads of it — no per-call pad or copy of the
    corpus."""
    from repro.core.uhnsw import verify_candidates

    t, k = 64, 10
    dx = _rows(d)

    def verify(X, Q, cand, base, p_vec):
        return verify_candidates(Q, cand, X, p_vec, k, k // 2, 0.92,
                                 False, cand_base=base, base_p=1.0)

    text = jax.jit(verify).lower(
        _spec((N, dx), one_chip), _spec((B, d), one_chip),
        _spec((B, t), one_chip, jnp.int32), _spec((B, t), one_chip),
        _spec((B,), one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
    made = {op for shape, op in _HLO_RESULT.findall(text)
            if shape == f"f32[{N},{dx}]"}
    assert made <= {"parameter", "get-tuple-element"}, made


def test_compiled_kernels_refuse_an_unaligned_row_source():
    q = jnp.zeros((8, 96), jnp.float32)
    ids = jnp.zeros((8, 128), jnp.int32)
    with pytest.raises(ValueError, match="kernel_rows"):
        ops.lp_gather_distance(q, ids, jnp.zeros((64, 96), jnp.float32),
                               0.8, interpret=False)


def test_kernel_rows_pads_only_on_the_chip(monkeypatch):
    x = jnp.asarray(np.arange(5 * 96, dtype=np.float32).reshape(5, 96))
    assert ops.kernel_rows(x) is x  # off the chip: no copy
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    rows = ops.kernel_rows(x)
    assert rows.shape == (5, 128)
    np.testing.assert_array_equal(np.asarray(rows[:, :96]), np.asarray(x))
    assert not np.asarray(rows[:, 96:]).any()
    aligned = jnp.zeros((5, 256), jnp.int8)
    assert ops.kernel_rows(aligned) is aligned
