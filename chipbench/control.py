"""The control of `correct`: the reference itself, in bfloat16, put in the
program's place. It must come out not correct.

    python chipbench/control.py --workload <cell> --seeds 1 2 3

For each seed it draws the cell's corpus and as many queries of its
traffic as a run's recall sample holds, answers them with the
exact search computed in bfloat16 (the precision below the configuration's
float32), and runs the same comparison a benchmark run makes. It prints
one JSON line per seed with the numbers compared and `correct`; the
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HARNESS_DIR = Path(__file__).resolve().parent
ROOT = HARNESS_DIR.parent


def control_run(workload: str, seed: int, *, root: Path = ROOT) -> dict:
    """One seed of the control; returns its checks and verdict."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import compare, data, reference, stream
    from chipbench.spec import Spec

    spec = Spec(root)
    cell = spec.workload(workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    pool = int(traffic["query_pool"])
    r = int(traffic["recall_sample"])
    x, extra = data.draw(cfg, seed, pool + int(traffic.get("insert_pool",
                                                            0)))
    queries = np.asarray(extra)[:pool]
    src = stream.Stream(traffic, seed, pool)
    ops = []
    while len(ops) < r:
        op = src.next()
        if op.kind == stream.QUERY:
            ops.append(op)
    qrow = np.array([op.index for op in ops])
    ps = np.array([op.p for op in ops], np.float32)
    t0 = time.perf_counter()
    ids, dists = reference.exact_topk(x, queries[qrow], ps, int(traffic["k"]),
                                      dtype=jnp.bfloat16)
    n = int(x.shape[0])
    answered = {i: (int(qrow[i]), float(ps[i]), n, ids[i], dists[i])
                for i in range(r)}
    checks, extras = compare.compare(x, queries, answered, 0,
                                     list(range(r)), cfg["limits"])
    return {"workload": workload, "seed": seed, "requests": r,
            "correct": compare.verdict(checks), "recall": extras["recall"],
            "seconds": time.perf_counter() - t0, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    for seed in args.seeds:
        print(json.dumps(control_run(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
