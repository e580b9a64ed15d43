"""Chip benchmark of the universal-Lp index: one harness, driven by data.

`python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of `BENCHMARK.json`. A cell names a
configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`); its loop kind is `loops/<kind>.py` and each
per-layer metric is read by `metrics/<metric>.py`. Everything is found by
name, so a new cell, mix or metric is a new file plus a new entry.
"""
