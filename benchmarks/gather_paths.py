"""Per-row cost of the two candidate-scoring paths on the current device.

    PYTHONPATH=src python -m benchmarks.gather_paths [--n N] [--d D]
        [--rows B] [--cands C] [--reps R]
    PYTHONPATH=src python -m benchmarks.gather_paths --beam-fetch
        [--segments S] [--rows B] [--trips T] [--stack-mb M] [--reps R]

The bulk graph builder scores its candidate blocks with an XLA gather
plus `rowwise_lp` (kernels.ops.gather_rowwise_lp); verification in the
query path scores them with the fused Pallas gather kernel
(kernels.ops.lp_gather_distance), which copies one row tile per
candidate. This times both on a (B, C) block of random ids into an
(n, d) corpus and prints the best warm time per scored row, then one
JSON line with every number. Only a run on a TPU compares the two: off
the chip `lp_gather_distance` takes the same XLA path.

`--beam-fetch` times one level-0 beam-loop trip's frontier scoring
(core/hnsw.py::_score_frontier) over an M MB stack of S segments, with
S x B query lanes of 32
frontier ids each: the XLA gather of every frontier row plus the masked
reduction, against the kernel that fetches and scores only the new rows
(kernels/beam_fetch.py), at new shares 0.2, 0.35 and 1.0, d 1024 and
4096, p 1 and 2, and the kernel's pipeline depths. T trips run in one
jitted loop, each on ids shifted by the trip number; it prints µs per
trip, ns per new row, the share of the HBM roofline that the new rows'
bytes (new rows x d x 4 B over the device's peak bytes/s,
`chipbench/peaks.json`) take of the trip, and, for each form, a line
fitted over the three shares: ns per lane plus ns per new row. Then one
JSON line. The kernel path engages at d % 1024 == 0
(`hnsw.BEAM_FETCH_ROW_ELEMS`), which these numbers are the basis of.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import time

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "peaks.json"


def _best(fn, reps: int) -> tuple[float, float]:
    """(first call s, best warm call s) of a device computation."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    warm = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        warm.append(time.perf_counter() - t0)
    return first, min(warm)


def _hbm_bytes_per_s(kind: str) -> float | None:
    """The device's published HBM peak, None for a device not in the
    table (a CPU run has no roofline)."""
    with open(PEAKS) as f:
        dev = json.load(f)["devices"].get(kind)
    return dev["hbm_bytes_per_s"] if dev else None


def _fit(points) -> tuple[float, float]:
    """Least-squares t = c + b * rows over (new rows, µs per trip) points
    -> (c µs per trip, b ns per new row)."""
    rows = [r for r, _ in points]
    us = [t for _, t in points]
    mr, mt = sum(rows) / len(rows), sum(us) / len(us)
    b = (sum((r - mr) * (t - mt) for r, t in points)
         / sum((r - mr) ** 2 for r in rows))
    return mt - b * mr, b * 1e3


def beam_fetch(args) -> dict:
    """Per-trip µs of the two frontier-scoring forms (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.hnsw import _base_dist
    from repro.kernels import beam_fetch as bf

    s, b, f, trips = args.segments, args.rows, 32, args.trips
    interpret = jax.default_backend() != "tpu"  # a CPU run checks the code
    kind = jax.devices()[0].device_kind
    peak = _hbm_bytes_per_s(kind)
    out = {"device": kind, "segments": s, "rows": b, "frontier": f,
           "trips": trips, "us_per_trip": {}, "ns_per_new_row": {},
           "hbm_share": {}, "fit": {}}
    for d in (4096, 1024):
        points = {}
        n = (args.stack_mb << 20) // (4 * s * d)  # rows per segment
        rng = np.random.default_rng(d)
        x = jnp.asarray(rng.standard_normal((s, n, d), np.float32))
        src = bf.beam_rows(x)
        q = jnp.asarray(rng.standard_normal((b, d), np.float32))
        q_t = q.reshape(b, d // 128, 128)
        ids0 = jnp.asarray(rng.integers(0, n, (s, b, f), dtype=np.int32))
        row0 = jnp.repeat(jnp.arange(s, dtype=jnp.int32) * n, b)
        for share in (0.2, 0.35, 1.0):
            new = jnp.asarray(rng.random((s, b, f)) < share)
            n_new = int(new.sum())
            for p in (1.0, 2.0):
                # the corpus and the masks are arguments, never constants
                # folded into the program
                def xla(ids, new, x, src, p=p):
                    def lane(q1, i1, m1, xs):
                        dv = _base_dist(q1, xs[i1], p)
                        return jnp.where(m1, dv, jnp.inf)
                    return jax.vmap(lambda xs, i2, m2: jax.vmap(
                        lambda q1, i1, m1: lane(q1, i1, m1, xs))(q, i2, m2)
                    )(x, ids, new)

                def kernel(ids, new, x, src, depth=bf.DEPTH, p=p):
                    lanes = s * b
                    return bf.fetch_score_lanes(
                        q_t, ids.reshape(lanes, f), new.reshape(lanes, f),
                        row0, src, p=p, interpret=interpret,
                        depth=depth).reshape(s, b, f)

                args_ = (ids0, new, x, src)
                ref, got = jax.jit(xla)(*args_), jax.jit(kernel)(*args_)
                live = jnp.isfinite(ref)
                rel = jnp.where(live, jnp.abs(got - ref) / ref, 0.0).max()
                same = bool((live == jnp.isfinite(got)).all())
                key = f"d={d} new={share} p={p}"
                out.setdefault("max_rel_err", {})[key] = (
                    float(rel) if same else None)
                print(f"{key} max_rel_err {out['max_rel_err'][key]}",
                      flush=True)
                forms = [("xla", xla)] + [
                    (f"kernel_depth{k}", functools.partial(kernel, depth=k))
                    for k in (2, 4, 8)]
                for name, form in forms:
                    @jax.jit
                    def loop(ids, new, x, src, form=form):
                        def trip(t, acc):
                            shifted = (ids + t * 7919) % n
                            dv = form(shifted, new, x, src)
                            return acc + jnp.where(jnp.isinf(dv), 0.0,
                                                   dv).sum()
                        return jax.lax.fori_loop(0, trips, trip,
                                                 jnp.float32(0))
                    _, warm = _best(lambda: loop(*args_), args.reps)
                    us = warm / trips * 1e6
                    ns_row = us * 1e3 / n_new
                    hbm = n_new * d * 4 / peak / (us * 1e-6) if peak else None
                    out["us_per_trip"][f"{key} {name}"] = us
                    out["ns_per_new_row"][f"{key} {name}"] = ns_row
                    out["hbm_share"][f"{key} {name}"] = hbm
                    points.setdefault(f"d={d} p={p} {name}", []).append(
                        (n_new, us))
                    hbm_s = "not measured" if hbm is None else f"{hbm:.1%}"
                    print(f"{key} {name}: {us:.1f} us/trip, {ns_row:.1f} "
                          f"ns/new row, HBM roofline {hbm_s}", flush=True)
        for name, pts in points.items():
            c, per_row = _fit(pts)
            per_lane = c * 1e3 / (s * b)
            out["fit"][name] = {"ns_per_lane": per_lane,
                                "ns_per_new_row": per_row}
            print(f"{name} fit: {per_lane:.1f} ns/lane + {per_row:.1f} "
                  f"ns/new row", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--cands", type=int, default=448)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--beam-fetch", action="store_true")
    ap.add_argument("--segments", type=int, default=4)
    ap.add_argument("--trips", type=int, default=50)
    ap.add_argument("--stack-mb", type=int, default=512)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.beam_fetch:
        print(json.dumps(beam_fetch(args)))
        return 0
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import (
        gather_rowwise_lp,
        kernel_rows,
        lp_gather_distance,
    )

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    b, c = args.rows, args.cands
    x = jnp.asarray(rng.standard_normal((args.n, args.d), np.float32))
    rows = kernel_rows(x)
    q = jnp.asarray(rng.standard_normal((b, args.d), np.float32))
    ids = jnp.asarray(rng.integers(0, args.n, (b, c), dtype=np.int32))
    xla = jax.jit(gather_rowwise_lp, static_argnames=("p",))
    out = {"device": dev.device_kind, "n": args.n, "d": args.d, "rows": b,
           "cands": c, "ns_per_row": {}}
    for p in (1.0, 2.0, 0.8):
        for name, fn in (
            ("pallas_gather", lambda: lp_gather_distance(q, ids, rows, p)),
            ("xla_gather", lambda: xla(q, ids, x, p=p)),
        ):
            first, warm = _best(fn, args.reps)
            ns = warm / (b * c) * 1e9
            out["ns_per_row"][f"{name} p={p}"] = ns
            print(f"{name} p={p}: first {first:.3f} s, warm {warm * 1e3:.3f} "
                  f"ms = {ns:.2f} ns/row", flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
