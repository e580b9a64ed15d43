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


def _merge_microbench(quick: bool) -> dict:
    """Cost of the level-0 (ef + W*m0) merge's expanded-mask construction
    (DESIGN.md §2.1): the historical code rebuilt `jnp.isinf` over the
    full concatenated array every hop; the hoisted form masks only the
    (W*m0) frontier half, relying on the invariant that beam entries with
    inf distance always carry exp=1 (sentinel init + every earlier
    merge's forcing). Both variants are measured here so the note in
    DESIGN.md §2.1 stays pinned to data; the merge sort itself dominates,
    which is why the win is a few percent of the hop, not a multiple.
    """
    ef, w, m0 = 600, 4, 32
    reps = 200 if quick else 1000
    rng = np.random.default_rng(0)
    dist = jnp.asarray(rng.exponential(size=ef).astype(np.float32))
    dv = jnp.asarray(
        np.where(rng.random(w * m0) < 0.3, np.inf,
                 rng.exponential(size=w * m0)).astype(np.float32))
    ids = jnp.asarray(rng.permutation(ef * 4)[:ef].astype(np.int32))
    nbrs = jnp.asarray(rng.permutation(ef * 4)[:w * m0].astype(np.int32))
    exp = jnp.asarray((rng.random(ef) < 0.5).astype(np.int32))

    @jax.jit
    def merge_full_mask(ids, dist, exp, nbrs, dv):
        all_ids = jnp.concatenate([ids, nbrs])
        all_dist = jnp.concatenate([dist, dv])
        all_exp = jnp.concatenate([exp, jnp.zeros((w * m0,), jnp.int32)])
        all_exp = jnp.where(jnp.isinf(all_dist), 1, all_exp)
        sd, si, se = jax.lax.sort((all_dist, all_ids, all_exp), num_keys=1)
        return si[:ef], sd[:ef], se[:ef]

    @jax.jit
    def merge_hoisted(ids, dist, exp, nbrs, dv):
        all_ids = jnp.concatenate([ids, nbrs])
        all_dist = jnp.concatenate([dist, dv])
        all_exp = jnp.concatenate([exp, jnp.isinf(dv).astype(jnp.int32)])
        sd, si, se = jax.lax.sort((all_dist, all_ids, all_exp), num_keys=1)
        return si[:ef], sd[:ef], se[:ef]

    def timed(fn):
        jax.block_until_ready(fn(ids, dist, exp, nbrs, dv))
        t0 = time.time()
        for _ in range(reps):
            out = fn(ids, dist, exp, nbrs, dv)
        jax.block_until_ready(out)
        return (time.time() - t0) / reps * 1e6

    us_full = timed(merge_full_mask)
    us_hoist = timed(merge_hoisted)
    row = {
        "dataset": "merge-microbench", "p": None, "k": None,
        "expand_width": w, "ef": ef, "m0": m0,
        "us_per_merge_full_mask": round(us_full, 2),
        "us_per_merge_hoisted": round(us_hoist, 2),
        "mask_hoist_speedup": round(us_full / us_hoist, 3),
    }
    print(f"  merge micro-bench (ef={ef}, W*m0={w * m0}): full-mask "
          f"{us_full:.1f}us vs hoisted {us_hoist:.1f}us "
          f"({row['mask_hoist_speedup']}x)", flush=True)
    return row


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
    rows.append(_merge_microbench(quick))
    rows.extend(_visited_microbench(quick))
    return rows


if __name__ == "__main__":
    import json
    import sys

    table = _visited_microbench(quick="--quick" in sys.argv)
    print(json.dumps(table))
