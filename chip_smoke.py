#!/usr/bin/env python3
"""One-chip end-to-end smoke of the universal-Lp index.

    python chip_smoke.py [--n N] [--seed S] [--requests R]

Drives the served path once, through the entry points a user calls, at a
real size on one TPU chip:

  1. refuses to run unless JAX's first device is a TPU;
  2. builds a Deep-shaped corpus (`make_dataset("deep")`, d=256) with
     `UniversalVectorService.build(..., m=16, num_segments=4)` — the bulk
     builder, as `repro.launch.serve` does;
  3. serves a few hundred mixed-p requests (p in {0.5, 0.8, 1, 1.25, 1.5,
     2}, k=10) through the engine: no request may fail or be shed, no
     device fault may be caught, the engine must stay live;
  4. checks the engine's ids bitwise against per-p scalar `index.search`;
  5. checks recall@10 >= 0.8 against brute-force `exact_topk` at
     p in {0.5, 1.25, 2.0};
  6. checks the compiled gather and abandon kernels against the jnp
     reference (rtol 1e-5) for one candidate block per p family;
  7. checks the lowered verification program contains the Pallas kernel
     (`tpu_custom_call`);
  8. serves one stream with the int8 compressed band (screen kernel) and
     requires the plain stream's ids; streams one far-out insert and
     requires it to be its own nearest neighbour at p=0.5 and p=2 (delta
     scan).

Timings go to earlier lines; the last stdout line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Any failed check exits non-zero before that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

P_MIX = (0.5, 0.8, 1.0, 1.25, 1.5, 2.0)
P_RECALL = (0.5, 1.25, 2.0)
P_KERNEL = (0.5, 0.8, 1.0, 2.0)  # one p per op-sequence family
RECALL_FLOOR = 0.8
RECALL_QUERIES = 32
KERNEL_RTOL = 1e-5
K = 10
PAPER_N = 1_000_000  # Deep, paper Table 1
# The default is cut to 2^18 rows so the whole smoke, build included, stays
# inside 1200 s on one v5e; PERF.md's Findings say how the cut was chosen.
DEFAULT_N = 262_144


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _check_clean(service, reqs, out, what: str) -> None:
    """Every request answered; nothing shed, FAILED or faulted."""
    from repro.retrieval.engine import LIVE

    st = service.stats
    failures = service.engine.take_failures()
    _check(not failures, f"{what}: FAILED requests {sorted(failures)[:5]}: "
           f"{next(iter(failures.values()), '')}")
    _check(st["shed"] == 0, f"{what}: {st['shed']} requests shed")
    _check(st["failed"] == 0, f"{what}: {st['failed']} requests FAILED")
    _check(st["faults"] == 0, f"{what}: {st['faults']} device faults caught")
    _check(service.engine.state == LIVE,
           f"{what}: engine state is {service.engine.state!r}")
    missing = [r.request_id for r in reqs if r.request_id not in out]
    _check(not missing, f"{what}: no answer for requests {missing[:5]}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _check_kernels(X, Q, rng, interpret) -> dict:
    """Compiled gather + abandon kernels vs the jnp reference, one
    candidate block per p family. Returns the worst relative errors."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.metrics import base_metric_for, rowwise_lp
    from repro.kernels.ops import (
        kernel_rows,
        lp_gather_abandon,
        lp_gather_distance,
    )

    n = X.shape[0]
    rows = kernel_rows(X)  # the kernels' layout of the corpus
    ids = rng.integers(0, n, size=(Q.shape[0], 256)).astype(np.int32)
    ids[0, 0], ids[-1, -1] = -1, n  # padding sentinels score +inf
    valid = (ids >= 0) & (ids < n)
    ids_j = jnp.asarray(ids)
    cand = X[jnp.clip(ids_j, 0, n - 1)]
    errs = {}
    for p in P_KERNEL:
        want = np.where(valid, np.asarray(rowwise_lp(Q, cand, p, root=False)),
                        np.inf)
        got = np.asarray(lp_gather_distance(Q, ids_j, rows, p,
                                            interpret=interpret))
        _check(np.array_equal(np.isinf(got), ~valid),
               f"gather p={p}: padding mask differs from the reference")
        err_g = float(np.max(np.abs(got[valid] - want[valid])
                             / np.abs(want[valid])))
        _check(err_g <= KERNEL_RTOL, f"gather p={p}: rel err {err_g:.3g}")

        base_p = float(base_metric_for(p))
        sb = rowwise_lp(Q, cand, base_p, root=False)
        # +inf threshold: every dimension block is scanned
        full, nd = lp_gather_abandon(
            Q, ids_j, rows, jnp.full((Q.shape[0],), jnp.inf), sb, p,
            base_p=base_p, interpret=interpret)
        full, nd = np.asarray(full), np.asarray(nd)
        _check(np.array_equal(np.isinf(full), ~valid),
               f"abandon p={p}: padding mask differs from the reference")
        _check(bool(np.all(nd[valid] == X.shape[1])),
               f"abandon p={p}: +inf threshold skipped dimensions")
        err_a = float(np.max(np.abs(full[valid] - want[valid])
                             / np.abs(want[valid])))
        _check(err_a <= KERNEL_RTOL, f"abandon p={p}: rel err {err_a:.3g}")
        # a per-row median threshold: survivors keep their exact value,
        # clear keepers survive, the abandoned provably exceed the bound
        thr = np.nanmedian(np.where(valid, want, np.nan), axis=1)
        cut, _ = lp_gather_abandon(Q, ids_j, rows, jnp.asarray(thr), sb, p,
                                   base_p=base_p, interpret=interpret)
        cut = np.asarray(cut)
        kept = np.isfinite(cut)
        _check(bool(np.all(np.abs(cut[kept] - want[kept])
                           <= KERNEL_RTOL * np.abs(want[kept]))),
               f"abandon p={p}: a surviving value differs from the reference")
        margin = thr[:, None] * (1 - 1e-4)
        _check(bool(np.all(kept[valid & (want < margin)])),
               f"abandon p={p}: abandoned a candidate under the threshold")
        _check(bool(np.all((want > margin)[valid & ~kept])),
               f"abandon p={p}: abandoned a candidate that was competitive")
        errs[p] = max(err_g, err_a)
    return errs


def _verification_has_kernel(X, Q, compiled: bool) -> bool:
    """Is the Pallas kernel inside the mixed-p verification program?

    Compiled for the chip it lowers to a `tpu_custom_call`; in interpret
    mode the kernel is traced into plain HLO, so the check reads the
    program's jaxpr for the `pallas_call` instead."""
    import jax
    import jax.numpy as jnp

    from repro.core.uhnsw import verify_candidates
    from repro.kernels.ops import kernel_rows

    b, t = Q.shape[0], 64
    cand = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    base = jnp.zeros((b, t), jnp.float32)
    p_vec = jnp.full((b,), 0.8, jnp.float32)

    # X is an argument, not a closed-over constant: the program text must
    # not carry the corpus
    def verify(X, Q, cand, base, p_vec):
        return verify_candidates(Q, cand, X, p_vec, K, K // 2, 0.92,
                                 None if compiled else True,
                                 cand_base=base, base_p=1.0)

    args = (kernel_rows(X), Q, cand, base, p_vec)
    if compiled:
        return "tpu_custom_call" in jax.jit(verify).lower(*args).as_text()
    return "pallas_call" in str(jax.make_jaxpr(verify)(*args))


def run_smoke(n: int = DEFAULT_N, *, seed: int = 0, requests: int = 256,
              interpret: bool | None = None, log=print) -> dict:
    """The smoke's phases on whatever backend JAX runs on.

    `interpret=None` is the production dispatch (compiled kernels on the
    chip); `interpret=True` runs the Pallas kernels in interpret mode so
    every check can be rehearsed on the CPU at a tiny size. Raises
    SmokeFailure on the first failed check; returns the measurements.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.datasets import make_dataset
    from repro.core.hnsw import exact_topk
    from repro.core.uhnsw import UHNSWParams, recall
    from repro.retrieval.service import (
        InsertRequest,
        QueryRequest,
        UniversalVectorService,
    )

    summary: dict = {"n": n}
    ds = make_dataset("deep", n=n, n_queries=128, seed=seed)
    log(f"corpus: deep-shaped n={n} d={ds.d} seed={seed}"
        + ("" if n >= PAPER_N else f" (cut from the paper's {PAPER_N})"))
    params = UHNSWParams(interpret=interpret)
    service, summary["build_s"] = _timed(lambda: UniversalVectorService.build(
        ds.data, params, m=16, num_segments=4))
    index = service.index
    log(f"build: {summary['build_s']:.1f} s (bulk builder, m=16, 4 segments)")

    rng = np.random.default_rng(seed)
    qi = rng.integers(0, len(ds.queries), size=requests)
    ps = rng.choice(P_MIX, size=requests)
    reqs = [QueryRequest(vector=ds.queries[q], p=float(p), k=K, request_id=i)
            for i, (q, p) in enumerate(zip(qi, ps))]
    out, summary["first_serve_s"] = _timed(lambda: service.serve(reqs))
    _check_clean(service, reqs, out, "mixed-p stream")
    warm_reqs = [dataclasses.replace(r, request_id=r.request_id + requests)
                 for r in reqs]
    warm, summary["warm_serve_s"] = _timed(lambda: service.serve(warm_reqs))
    _check_clean(service, warm_reqs, warm, "warm mixed-p stream")
    for r in reqs:
        _check(np.array_equal(warm[r.request_id + requests][0],
                              out[r.request_id][0]),
               f"request {r.request_id}: a repeated stream changed its ids")
    log(f"serve: {requests} mixed-p requests, first call (compiles) "
        f"{summary['first_serve_s']:.1f} s, warm "
        f"{summary['warm_serve_s']:.3f} s "
        f"({requests / summary['warm_serve_s']:.1f} qps)")

    # engine ids == per-p scalar index.search, bitwise
    for p in P_MIX:
        sel = [r for r in reqs if r.p == p]
        if not sel:
            continue
        ids, _, _ = index.search(np.stack([r.vector for r in sel]), p, K)
        ids = np.asarray(ids)
        for row, r in zip(ids, sel):
            _check(np.array_equal(row, out[r.request_id][0]),
                   f"p={p} request {r.request_id}: engine ids {out[r.request_id][0]} "
                   f"!= scalar index.search ids {row}")
    log("parity: engine ids == per-p scalar index.search (bitwise)")

    # recall@10 against brute force on the device
    Qr = ds.queries[:RECALL_QUERIES]
    summary["recall"] = {}
    for j, p in enumerate(P_RECALL):
        rq = [QueryRequest(vector=q, p=p, k=K, request_id=10**6 * (j + 1) + i)
              for i, q in enumerate(Qr)]
        got = service.serve(rq)
        _check_clean(service, rq, got, f"recall stream p={p}")
        true_ids, _ = exact_topk(index.X, jnp.asarray(Qr), p, K)
        r = recall(np.stack([got[x.request_id][0] for x in rq]), true_ids)
        summary["recall"][p] = r
        log(f"recall@{K} p={p}: {r:.4f}")
        _check(r >= RECALL_FLOOR, f"recall@{K} at p={p} is {r:.4f} "
               f"< {RECALL_FLOOR}")

    summary["kernel_rel_err"] = _check_kernels(
        index.X, jnp.asarray(ds.queries[:8]), rng, interpret)
    log("kernels vs jnp reference (worst rel err): " + ", ".join(
        f"p={p}: {e:.2e}" for p, e in summary["kernel_rel_err"].items()))
    _check(_verification_has_kernel(index.X, jnp.asarray(ds.queries[:8]),
                                    compiled=interpret is None),
           "the verification program holds no Pallas kernel")
    log("verification program holds the Pallas kernel")

    # the int8 compressed band (screen kernel) returns the plain ids
    index.params = dataclasses.replace(params, compressed_band=True)
    try:
        banded = UniversalVectorService(index=index)
        outc = banded.serve(reqs)
        _check_clean(banded, reqs, outc, "compressed-band stream")
    finally:
        index.params = params
    for r in reqs:
        _check(np.array_equal(outc[r.request_id][0], out[r.request_id][0]),
               f"request {r.request_id}: compressed-band ids differ")
    log("compressed band: ids == plain stream")

    # a streamed far-out insert is its own nearest neighbour (delta scan)
    far = (np.abs(ds.data).max(axis=0) * 4.0).astype(np.float32)
    gid = service.insert([InsertRequest(vector=far, request_id=0)])[0]
    for j, p in enumerate((0.5, 2.0)):
        rq = [QueryRequest(vector=far, p=p, k=K, request_id=2 * 10**7 + j)]
        got = service.serve(rq)
        _check_clean(service, rq, got, f"insert probe p={p}")
        ids, dists = got[rq[0].request_id]
        _check(int(ids[0]) == gid and float(dists[0]) == 0.0,
               f"p={p}: inserted id {gid} is not its own nearest "
               f"neighbour (got {ids[:3]}, {dists[:3]})")
    log(f"streaming insert: id {gid} is its own nearest neighbour at "
        f"p=0.5 and p=2")
    jax.block_until_ready(index.X)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=DEFAULT_N,
                    help=f"corpus rows (paper Table 1: {PAPER_N})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=256)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's package is missing ({e}); "
              "run this script from a checkout", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
              "refusing to run the smoke off the chip", file=sys.stderr)
        return 3
    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}",
          flush=True)
    t0 = time.perf_counter()
    try:
        run_smoke(args.n, seed=args.seed, requests=args.requests,
                  log=lambda s: print(s, flush=True))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
