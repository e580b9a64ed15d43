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

D_GRID = [96, 100, 128, 256, 960]  # Deep-96, GloVe, SIFT, Deep-256, GIST
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


def _compile_gather(sharding, d, c, p):
    extra, p_of = _p_args(p, sharding)
    _assert_kernel_compiles(
        lambda q, ids, x, *e: ops.lp_gather_distance(
            q, ids, x, p_of(e), interpret=False),
        _spec((B, d), sharding), _spec((B, c), sharding, jnp.int32),
        _spec((N, _rows(d)), sharding), *extra)


def _compile_abandon(sharding, d, c, p):
    extra, p_of = _p_args(p, sharding)
    _assert_kernel_compiles(
        lambda q, ids, x, thr, sb, *e: ops.lp_gather_abandon(
            q, ids, x, thr, sb, p_of(e), interpret=False),
        _spec((B, d), sharding), _spec((B, c), sharding, jnp.int32),
        _spec((N, _rows(d)), sharding), _spec((B,), sharding),
        _spec((B, c), sharding), *extra)


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d", D_GRID)
def test_gather_kernel_compiles(one_chip, d, p):
    _compile_gather(one_chip, d, C, p)


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d", D_GRID)
def test_abandon_kernel_compiles(one_chip, d, p):
    _compile_abandon(one_chip, d, C, p)


# the shapes verification calls at k = 10: its first k and its kappa = 5
# batches, each one grid step of 128 candidate lanes with a static slot
# count; and trevi's width, which D_GRID lacks, at C too
ENGINE_SHAPES = [(d, c) for d in (100, 256, 4096) for c in (5, 10)] + \
    [(4096, C)]


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d,c", ENGINE_SHAPES)
def test_gather_kernel_compiles_at_engine_shapes(one_chip, d, c, p):
    _compile_gather(one_chip, d, c, p)


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("d,c", ENGINE_SHAPES)
def test_abandon_kernel_compiles_at_engine_shapes(one_chip, d, c, p):
    _compile_abandon(one_chip, d, c, p)


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


@pytest.mark.parametrize("d", [96, 100, 960])
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


def test_block_choice_is_unchanged_where_8_divides_d():
    """Widths that are a multiple of 8 keep the widest of 32, 16 and 8
    that divides them, so their compiled scans stay as they were; any
    other width takes 32-dimension blocks with a ragged last one."""
    for d in range(8, 4097, 8):
        want = 32 if d % 32 == 0 else 16 if d % 16 == 0 else 8
        assert ops.pick_abandon_block_d(d) == want, d
    for d in (1, 4, 36, 100, 300, 4095):
        assert ops.pick_abandon_block_d(d) == 32, d


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


# the beam fetch kernel's shapes: S segments of N_SEG rows, B queries,
# frontiers of W*m0 = 32 ids (kernels/beam_fetch.py)
S, N_SEG, M0 = 4, 2048, 32


@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("d", [1024, 4096])
def test_beam_fetch_kernel_compiles(one_chip, d, p):
    from repro.kernels import beam_fetch

    lanes = S * B
    _assert_kernel_compiles(
        lambda q, ids, new, row0, src: beam_fetch.fetch_score_lanes(
            q, ids, new, row0, src, p=p, interpret=False),
        _spec((B, d // 128, 128), one_chip),
        _spec((lanes, M0), one_chip, jnp.int32),
        _spec((lanes, M0), one_chip, jnp.bool_),
        _spec((lanes,), one_chip, jnp.int32),
        _spec((S * N_SEG, d // 128, 128), one_chip))


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_beam_fetch_kernel_compiles_at_w2(one_chip, p):
    """W = 2: frontiers of 64 ids a lane, eight score groups of 8 rows."""
    from repro.kernels import beam_fetch

    lanes, d, f = S * B, 4096, 2 * M0
    _assert_kernel_compiles(
        lambda q, ids, new, row0, src: beam_fetch.fetch_score_lanes(
            q, ids, new, row0, src, p=p, interpret=False),
        _spec((B, d // 128, 128), one_chip),
        _spec((lanes, f), one_chip, jnp.int32),
        _spec((lanes, f), one_chip, jnp.bool_),
        _spec((lanes,), one_chip, jnp.int32),
        _spec((S * N_SEG, d // 128, 128), one_chip))


def _candidate_program_text(one_chip, monkeypatch, d: int, fetch: bool):
    """The compiled segmented candidate program, W = 1, with the level-0
    loop on the fetch kernel (compiled, as on the chip) or on the gather."""
    from repro.core.hnsw import GraphArrays, knn_search
    from repro.index.sharded import segmented_knn_search
    from repro.kernels import beam_fetch

    monkeypatch.setattr(beam_fetch, "_interpret", lambda: False)
    arrays = GraphArrays(
        adj0=_spec((S, N_SEG, M0), one_chip, jnp.int32),
        upper_adj=(_spec((S, 64, 16), one_chip, jnp.int32),),
        upper_g2l=(_spec((S, N_SEG), one_chip, jnp.int32),),
        entry=_spec((S,), one_chip, jnp.int32), n=N_SEG, metric_p=1.0)
    specs = [arrays, _spec((S, N_SEG, d), one_chip),
             _spec((S, N_SEG), one_chip, jnp.int32), _spec((B, d), one_chip)]
    kw = dict(ef=64, t=32)
    if fetch:
        specs += [_spec((S * N_SEG, d // 128, 128), one_chip),
                  _spec((S,), one_chip, jnp.int32)]
        fn = lambda a, x, ni, q, src, row0: segmented_knn_search(  # noqa: E731
            a, x, ni, q, fetch_rows=(src, row0), **kw)
    else:
        fn = lambda a, x, ni, q: segmented_knn_search(  # noqa: E731
            a, x, ni, q, **kw)
    # traces made under the other `_interpret` must not be reused
    segmented_knn_search.clear_cache()
    knn_search.clear_cache()
    try:
        return jax.jit(fn).lower(*specs).compile().as_text()
    finally:
        segmented_knn_search.clear_cache()
        knn_search.clear_cache()


@pytest.mark.parametrize("d", [1024, 4096])
def test_candidate_program_fetches_only_by_kernel(one_chip, monkeypatch, d):
    """The segmented candidate program on the fetch path holds the
    kernel and no gather of a (lanes * W*m0, d) block of rows, which the
    gather path's program does hold."""
    gathered = S * B * M0 * d

    def row_blocks(text):
        """Ops whose f32 result holds lanes * W*m0 rows of d."""
        return {op for shape, op in _HLO_RESULT.findall(text)
                if shape.startswith("f32[") and np.prod(
                    [int(v) for v in shape[4:-1].split(",") if v]) == gathered}

    text = _candidate_program_text(one_chip, monkeypatch, d, fetch=True)
    assert "tpu_custom_call" in text
    assert not row_blocks(text)
    text = _candidate_program_text(one_chip, monkeypatch, d, fetch=False)
    assert "tpu_custom_call" not in text
    assert row_blocks(text)
