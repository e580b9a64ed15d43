"""Benchmark aggregator: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,sharded]

Each benchmark's rows also land in results/BENCH_<name>.json together with
wall time and the quick flag, so the perf trajectory (query time, recall,
N_b/N_p, ...) is machine-readable across PRs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

RESULTS = Path(__file__).parent.parent / "results"


def _write_bench_result(name: str, rows, seconds: float, quick: bool,
                        error: str | None = None):
    RESULTS.mkdir(parents=True, exist_ok=True)
    payload = {
        "bench": name,
        "status": "error" if error else "ok",
        "quick": quick,
        "seconds": round(seconds, 1),
        "rows": rows if isinstance(rows, list) else [],
    }
    if error:
        payload["error"] = error
    (RESULTS / f"BENCH_{name}.json").write_text(json.dumps(payload, indent=2))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small dataset subset (CI mode)")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        beam_width,
        build,
        compressed,
        fig1_lp_distance_cost,
        fig2_recall_vs_p,
        fig3_param_tuning,
        fig4_uhnsw_vs_hnsw,
        serving,
        sharded_index,
        table2_uhnsw_vs_mlsh,
        verify,
    )

    benches = {
        "build": build.run,
        "fig1": fig1_lp_distance_cost.run,
        "fig2": fig2_recall_vs_p.run,
        "fig3": fig3_param_tuning.run,
        "table2": table2_uhnsw_vs_mlsh.run,
        "fig4": fig4_uhnsw_vs_hnsw.run,
        "sharded": sharded_index.run,
        "beam": beam_width.run,
        "serving": serving.run,
        "health": serving.run_faulted,
        "verify": verify.run,
        "compressed": compressed.run,
    }
    only = set(args.only.split(",")) if args.only else set(benches)
    unknown = only - set(benches)
    if unknown:
        # a typo must not silently run nothing and exit 0 (the bench-guard
        # gate would then compare stale committed JSONs)
        print(f"unknown benchmark name(s) {sorted(unknown)}; "
              f"options: {sorted(benches)}")
        return 2
    failures = []
    for name, fn in benches.items():
        if name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
            _write_bench_result(name, rows, time.time() - t0, args.quick)
        except Exception as e:  # keep going; report at the end
            import traceback
            traceback.print_exc()
            _write_bench_result(name, None, time.time() - t0, args.quick,
                                error=repr(e))
            failures.append((name, repr(e)))
        print(f"===== {name} done in {time.time() - t0:.0f}s =====", flush=True)
    if failures:
        print("\nFAILED:", failures)
        return 1
    print("\nall benchmarks complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
