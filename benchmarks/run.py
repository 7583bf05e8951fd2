"""Benchmark harness entry: one module per paper table/figure.

``python -m benchmarks.run [--quick]`` prints a CSV block per figure
followed by the paper-claim check lines, and writes runs/bench_results.json.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.compile_cache import place_compile_cache

from . import (bench_engine, bench_ingest_device, bench_kernels, fig4_fanout,
               fig5_dtree_size, fig67_insertion, fig89_query, fig_failover,
               fig_mixed, fig_range, fig_recovery, fig_saturation,
               fig_scaling, fig_stability, fig_tenancy, table2_theory)

SUITES = [
    ("fig4_fanout (Fig 4a/4b)", fig4_fanout),
    ("fig5_dtree_size (Fig 5a/5b)", fig5_dtree_size),
    ("fig67_insertion (Figs 6,7)", fig67_insertion),
    ("fig89_query (Figs 8,9)", fig89_query),
    ("fig_range (range scans)", fig_range),
    ("fig_mixed (mixed workloads)", fig_mixed),
    ("fig_scaling (sharded scale-out)", fig_scaling),
    ("fig_saturation (open-loop tail latency)", fig_saturation),
    ("fig_recovery (durability / crash recovery)", fig_recovery),
    ("fig_failover (replicated kill-primary)", fig_failover),
    ("fig_stability (long-horizon windowed stability)", fig_stability),
    ("fig_tenancy (multi-tenant isolation)", fig_tenancy),
    ("table2_theory (Table 2)", table2_theory),
    ("bench_kernels (Pallas)", bench_kernels),
    ("bench_engine (serving)", bench_engine),
    ("bench_ingest_device (fused cascade)", bench_ingest_device),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workloads (CI mode)")
    args = ap.parse_args()
    place_compile_cache()

    all_rows = {}
    verdicts = []
    for title, mod in SUITES:
        t0 = time.time()
        kwargs = {}
        if args.quick and mod in (fig4_fanout, fig5_dtree_size):
            kwargs = {"n": 40_000}
        elif args.quick and mod is fig67_insertion:
            kwargs = {"sizes": (20_000, 60_000)}
        elif args.quick and mod is fig89_query:
            kwargs = {"sizes": (20_000, 60_000)}
        elif args.quick and mod is fig_range:
            kwargs = {"sizes": (20_000,), "n_q": 8}
        elif args.quick and mod is fig_mixed:
            kwargs = {"mixes": ("ycsb-a",), "n_ops": 1024, "preload": 1024}
        elif args.quick and mod is fig_scaling:
            kwargs = fig_scaling.QUICK_KWARGS
        elif args.quick and mod is fig_saturation:
            kwargs = fig_saturation.QUICK_KWARGS
        elif args.quick and mod is fig_recovery:
            kwargs = fig_recovery.QUICK_KWARGS
        elif args.quick and mod is fig_failover:
            kwargs = fig_failover.QUICK_KWARGS
        elif args.quick and mod is fig_stability:
            kwargs = fig_stability.QUICK_KWARGS
        elif args.quick and mod is fig_tenancy:
            kwargs = fig_tenancy.QUICK_KWARGS
        elif args.quick and mod is table2_theory:
            kwargs = {"sizes": (10_000, 30_000, 90_000)}
        elif args.quick and mod is bench_ingest_device:
            kwargs = bench_ingest_device.QUICK_KWARGS
        rows = mod.run(**kwargs)
        dt = time.time() - t0
        all_rows[title] = rows
        print(f"\n== {title}  ({dt:.1f}s) ==")
        if rows:
            cols = list(rows[0].keys())
            print(",".join(cols))
            for r in rows:
                print(",".join(f"{r[c]:.4g}" if isinstance(r[c], float)
                               else str(r[c]) for c in cols))
        checks = mod.check(rows)
        verdicts.extend(checks)
        for c in checks:
            print("  ->", c)

    print("\n== PAPER-CLAIM SUMMARY ==")
    n_match = sum("matches paper" in v for v in verdicts)
    n_mismatch = sum("MISMATCH" in v for v in verdicts)
    for v in verdicts:
        print(" ", v)
    print(f"\n{n_match} claims reproduced, {n_mismatch} mismatches")

    os.makedirs("runs", exist_ok=True)
    with open("runs/bench_results.json", "w") as f:
        json.dump({"rows": all_rows, "verdicts": verdicts}, f, indent=1)


if __name__ == "__main__":
    main()
