"""W-sweep of the multi-expansion beam search (DESIGN.md §2 hot path).

For W ∈ {1, 2, 4, 8} runs the same ANNS-U-Lp workload (fractional p, so the
full generate+verify pipeline executes) and records recall, mean level-0
`while_loop` trip count (stats.hops), mean N_b / N_p (paper Eq. 1), and
wall-clock per query. The tentpole claim this tracks: W=4 cuts the level-0
trip count >= 2x vs W=1 at equal recall — the serialized pointer-chase
becomes a quarter as many hops, each doing 4x wider (hardware-friendly)
tensor work.

  PYTHONPATH=src python -m benchmarks.run --only beam [--quick]
"""

from __future__ import annotations

import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import K_DEFAULT, get_dataset, get_uhnsw, ground_truth
from repro.core.uhnsw import recall

P_QUERY = 0.8  # fractional p: G1 candidates + exact-Lp verification
WIDTHS = (1, 2, 4, 8)
TIMING_REPS = 3


MERGE_LANES = 512
MERGE_FRONTIERS = (32, 64, 128, 256, 512)


def _merge_microbench(quick: bool, lanes: int = MERGE_LANES, ef: int = 600,
                      frontiers=MERGE_FRONTIERS, m0: int = 32,
                      trips: int = 64, seed: int = 0) -> list[dict]:
    """The level-0 loop's merge alone, one sort against the dense merge.

    Each frontier width F (= W*m0 neighbours per hop) runs `trips` hops of
    the merge and nothing else: every lane (one query of one segment)
    carries a sorted (ef,) beam of (dist, id, expanded) and merges a fresh
    F-entry frontier into it per hop, most of it inf (already visited),
    vmapped over the lanes inside one `fori_loop` as the beam loop runs it.
    A loop that only reads the frontier is timed too, and subtracted, so
    `us_per_trip_*` is the merge's own time per hop for all lanes. The
    unstable sort the loop ran before the stable one (`sort_unstable`) is
    timed beside it, for the frontiers above the crossover that still sort.
    DENSE_MERGE_MAX_FRONTIER (core/hnsw.py) is read from this table: the
    largest F at which the dense merge is still the faster.

      PYTHONPATH=src python -m benchmarks.beam_width   # prints the tables
    """
    from repro.core.hnsw import _merge_dense, _merge_sort

    def merge_sort_unstable(beam, front):
        cat = tuple(jnp.concatenate([b, f]) for b, f in zip(beam, front))
        out = jax.lax.sort(cat, num_keys=1)
        return tuple(x[:ef] for x in out)

    if quick:
        lanes, trips = min(lanes, 64), min(trips, 8)
        frontiers = tuple(f for f in frontiers if f <= 128)
    reps = 3 if quick else 10
    rng = np.random.default_rng(seed)
    bd = np.sort(rng.exponential(size=(lanes, ef)).astype(np.float32), axis=1)
    bd[:, ef // 2:] = np.inf
    beam = (jnp.asarray(bd),
            jnp.asarray(rng.integers(0, 1 << 20, size=(lanes, ef),
                                     dtype=np.int32)),
            jnp.asarray(np.isinf(bd).astype(np.int32)))
    rows = []
    for f in frontiers:
        fd = rng.exponential(size=(trips, lanes, f)).astype(np.float32)
        fd[rng.random(fd.shape) < 0.7] = np.inf
        fd = jnp.asarray(fd)
        fi = jnp.asarray(rng.integers(0, 1 << 20, size=(trips, lanes, f),
                                      dtype=np.int32))

        def make(form):
            @jax.jit
            def loop(beam, fd, fi):
                def body(t, beam):
                    front = (fd[t], fi[t], jnp.isinf(fd[t]).astype(jnp.int32))
                    if form is None:
                        return (beam[0], beam[1] + front[1][:, :1], beam[2])
                    return jax.vmap(form)(beam, front)

                return jax.lax.fori_loop(0, trips, body, beam)
            return loop

        def timed(fn):
            out = jax.block_until_ready(fn(beam, fd, fi))
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn(beam, fd, fi))
            return out, (time.perf_counter() - t0) / reps / trips * 1e6

        _, us_null = timed(make(None))
        out_s, us_sort = timed(make(_merge_sort))
        out_d, us_dense = timed(make(_merge_dense))
        _, us_unstable = timed(make(merge_sort_unstable))
        for x, y in zip(out_s, out_d):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        row = {
            "dataset": "merge-microbench", "p": None, "k": None,
            "expand_width": f // m0,
            "device": jax.devices()[0].device_kind,
            "lanes": lanes, "ef": ef, "frontier": f, "trips": trips,
            "us_per_trip_null": round(us_null, 2),
            "us_per_trip_sort": round(us_sort - us_null, 2),
            "us_per_trip_dense": round(us_dense - us_null, 2),
            "us_per_trip_sort_unstable": round(us_unstable - us_null, 2),
        }
        rows.append(row)
        print(f"  merge micro-bench lanes={lanes} ef={ef} F={f}: dense "
              f"{row['us_per_trip_dense']}us vs sort "
              f"{row['us_per_trip_sort']}us (unstable "
              f"{row['us_per_trip_sort_unstable']}us) per trip "
              f"(loop alone {row['us_per_trip_null']}us)", flush=True)
    return rows


VISITED_LANES = (128, 512)
VISITED_WORDS = (256, 512, 1024, 2048, 4096, 8192)


def _visited_microbench(quick: bool, lanes_grid=VISITED_LANES,
                        words_grid=VISITED_WORDS, trips: int = 64,
                        j: int = 32, seed: int = 0) -> list[dict]:
    """Visited-bitmask test-and-set alone, dense against indexed form.

    Each (lanes, words) point runs `trips` hops of the level-0 loop's
    bookkeeping and nothing else: every lane (one query of one segment)
    tests and sets j = W*m0 distinct neighbour ids per hop, vmapped over
    the lanes inside one `fori_loop`, as the beam loop runs it. A loop
    that only reads the ids is timed too, and subtracted, so `us_per_trip_*`
    is the bookkeeping's own time per hop for all lanes.
    DENSE_VISITED_MAX_WORDS (core/hnsw.py) is read from this table: the
    largest `words` at which the dense form is still the faster.

      PYTHONPATH=src python -m benchmarks.beam_width   # prints the table
    """
    from repro.core.hnsw import _visited_dense, _visited_scatter

    reps = 3 if quick else 10
    rng = np.random.default_rng(seed)
    rows = []
    for lanes in lanes_grid:
        for words in words_grid:
            n = words * 32
            # distinct ids per (trip, lane): a random base plus j strides
            base = rng.integers(0, n, size=(trips, lanes, 1))
            ids = (base + np.arange(j) * (n // j)) % n
            ids = jnp.asarray(ids.astype(np.int32))
            eligible = jnp.ones((lanes, j), bool)

            def make(form):
                @jax.jit
                def loop(ids):
                    def body(t, carry):
                        visited, count = carry
                        nb = ids[t]
                        word = nb >> 5
                        bit = jnp.uint32(1) << (nb.astype(jnp.uint32) & 31)
                        if form is None:
                            return visited, count + word.sum()
                        new, visited = jax.vmap(form)(visited, word, bit,
                                                      eligible)
                        return visited, count + new.sum()

                    visited = jnp.zeros((lanes, words), jnp.uint32)
                    return jax.lax.fori_loop(0, trips, body,
                                             (visited, jnp.int32(0)))
                return loop

            def timed(fn):
                out = jax.block_until_ready(fn(ids))
                t0 = time.perf_counter()
                for _ in range(reps):
                    jax.block_until_ready(fn(ids))
                return out, (time.perf_counter() - t0) / reps / trips * 1e6

            _, us_null = timed(make(None))
            (vis_d, cnt_d), us_dense = timed(make(_visited_dense))
            (vis_s, cnt_s), us_scatter = timed(make(_visited_scatter))
            assert np.array_equal(np.asarray(vis_d), np.asarray(vis_s))
            assert int(cnt_d) == int(cnt_s)
            row = {
                "dataset": "visited-microbench",
                "device": jax.devices()[0].device_kind,
                "lanes": lanes, "words": words, "ids_per_lane": j,
                "trips": trips,
                "us_per_trip_null": round(us_null, 2),
                "us_per_trip_dense": round(us_dense - us_null, 2),
                "us_per_trip_scatter": round(us_scatter - us_null, 2),
            }
            rows.append(row)
            print(f"  visited micro-bench lanes={lanes} words={words}: "
                  f"dense {row['us_per_trip_dense']}us vs scatter "
                  f"{row['us_per_trip_scatter']}us per trip "
                  f"(loop alone {row['us_per_trip_null']}us)", flush=True)
    return rows


def run(quick: bool = False):
    name = "trevi" if quick else "sun"
    widths = (1, 4) if quick else WIDTHS
    ds = get_dataset(name)
    idx = get_uhnsw(name)
    Q = jnp.asarray(ds.queries)
    true_ids, _ = ground_truth(name, P_QUERY, K_DEFAULT)

    rows = []
    for w in widths:
        idx.params = replace(idx.params, expand_width=w)
        # warm the per-W jit cache, then time steady-state
        ids, _, stats = idx.search(Q, P_QUERY, K_DEFAULT)
        jax.block_until_ready(ids)
        t0 = time.time()
        for _ in range(TIMING_REPS):
            ids, _, stats = idx.search(Q, P_QUERY, K_DEFAULT)
            jax.block_until_ready(ids)
        ms_per_query = (time.time() - t0) / TIMING_REPS / Q.shape[0] * 1e3
        rows.append({
            "dataset": name,
            "p": P_QUERY,
            "k": K_DEFAULT,
            "expand_width": w,
            "recall": round(recall(np.asarray(ids), true_ids), 4),
            "mean_hops": round(float(jnp.mean(stats.hops)), 1),
            "mean_n_b": round(float(jnp.mean(stats.n_b)), 1),
            "mean_n_p": round(float(jnp.mean(stats.n_p)), 1),
            "ms_per_query": round(ms_per_query, 3),
        })
        print(f"  W={w}: recall={rows[-1]['recall']:.4f} "
              f"hops={rows[-1]['mean_hops']} N_b={rows[-1]['mean_n_b']} "
              f"N_p={rows[-1]['mean_n_p']} {ms_per_query:.2f} ms/q",
              flush=True)

    base = rows[0]
    for r in rows[1:]:
        r["hops_speedup_vs_w1"] = round(base["mean_hops"] / r["mean_hops"], 2)
    rows.extend(_merge_microbench(quick))
    rows.extend(_visited_microbench(quick))
    return rows


if __name__ == "__main__":
    import json
    import sys

    quick = "--quick" in sys.argv
    print(json.dumps(_merge_microbench(quick)))
    print(json.dumps(_visited_microbench(quick)))
