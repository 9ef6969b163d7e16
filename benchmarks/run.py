"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  table1   — paper Table 1/2 (single-job power/energy, model vs paper)
  fig1     — paper Fig. 1 / Tables 3-4 (co-location energy & JCT)
  fig3     — paper Fig. 3 (cluster energy/runtime, 3 regimes x 5 schedulers)
  fig4     — paper Fig. 4 (active-node timelines)
  elastic  — EaCO-Elastic vs EaCO + baselines (energy/JCT/resize counts)
  scale    — 10k-job Philly-style replay on a heterogeneous V100/A100 fleet
  serve    — mixed day: 10k-job trace + 1M-request serving, co-located vs split
  dvfs     — EaCO vs EaCO-PowerCap at 3 cluster power-cap levels (10k jobs)
  synergy  — host-aware vs host-blind EaCO on the 10k hetero trace (Synergy)
  kernels  — Pallas kernel micro-benches + interpret-mode correctness

Flags:
  ``--check`` — snapshot the committed repo-root ``BENCH_*.json`` files
  before the sweep, re-compare after it, and exit non-zero if any shared
  energy/JCT metric regressed by more than 10% against its committed
  baseline (see ``common.check_regression``).

The driver exports one wall-clock timestamp (``REPRO_BENCH_TIMESTAMP``)
so every BENCH file of a sweep carries the same stamp; direct module
invocation leaves the artifacts timestamp-free and deterministic.
"""

from __future__ import annotations

import datetime
import glob
import json
import os
import sys

from benchmarks.common import (
    REPO_ROOT, TIMESTAMP_ENV, bench_context, check_regression,
)

REGRESSION_TOLERANCE = 0.10


def _snapshot_benches() -> dict:
    out = {}
    for path in sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json"))):
        try:
            with open(path) as f:
                out[os.path.basename(path)] = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
    return out


def main() -> None:
    check = "--check" in sys.argv[1:]
    baselines = _snapshot_benches() if check else {}
    os.environ.setdefault(
        TIMESTAMP_ENV,
        datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
    )
    print("name,us_per_call,derived")
    from benchmarks import (
        dvfs_bench, elastic_bench, fig1, fig3, fig4, kernels_bench,
        scale_bench, serve_bench, synergy_bench, table1, tpu_cluster,
    )

    modules = [
        ("table1", table1),
        ("fig1", fig1),
        ("fig3", fig3),
        ("fig4", fig4),
        ("tpu_cluster", tpu_cluster),
        ("elastic", elastic_bench),
        ("scale", scale_bench),
        ("serve", serve_bench),
        ("dvfs", dvfs_bench),
        ("synergy", synergy_bench),
        ("kernels", kernels_bench),
    ]
    failures = 0
    for name, mod in modules:
        try:
            for row in mod.run():
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0.00,ERROR: {type(e).__name__}: {e}", flush=True)
    if check:
        for fn, base in sorted(baselines.items()):
            fresh = _snapshot_benches().get(fn)
            if fresh is None:
                continue  # the sweep did not regenerate this file
            # context lives in meta (schema v2) or at the top level (v1):
            # only compare runs of the same workload shape
            mismatched = [
                key
                for key in ("n_jobs", "fleet", "queue_window")
                if bench_context(base, key) is not None
                and bench_context(fresh, key) is not None
                and bench_context(base, key) != bench_context(fresh, key)
            ]
            if mismatched:
                print(
                    f"check,0.00,SKIP {fn}: context changed "
                    f"({', '.join(mismatched)}) — baselines not comparable",
                    flush=True,
                )
                continue
            for problem in check_regression(
                base, fresh, tolerance=REGRESSION_TOLERANCE
            ):
                failures += 1
                print(f"check,{0:.2f},REGRESSION {fn}: {problem}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
