"""Per-row cost of the two candidate-scoring paths on the current device.

    PYTHONPATH=src python -m benchmarks.gather_paths [--n N] [--d D]
        [--rows B] [--cands C] [--reps R]

The bulk graph builder scores its candidate blocks with an XLA gather
plus `rowwise_lp` (kernels.ops.gather_rowwise_lp); verification in the
query path scores them with the fused Pallas gather kernel
(kernels.ops.lp_gather_distance), which copies one row tile per
candidate. This times both on a (B, C) block of random ids into an
(n, d) corpus and prints the best warm time per scored row, then one
JSON line with every number. Only a run on a TPU compares the two: off
the chip `lp_gather_distance` takes the same XLA path.
"""

from __future__ import annotations

import argparse
import json
import time


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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--cands", type=int, default=448)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
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
