"""Render dry-run JSONL records as the EXPERIMENTS.md roofline tables.

  PYTHONPATH=src python -m benchmarks.roofline_report runs/dryrun_baseline.jsonl [--mesh single]

Measured kernel time and roofline share on the chip come from the
profiler trace that ``bench/`` reduces (``python3 -m bench.run ... --trace
1``), not from here.
"""
from __future__ import annotations

import argparse
import json


def load(path, mesh=None):
    recs = [json.loads(l) for l in open(path)]
    if mesh:
        recs = [r for r in recs if r.get("mesh_kind") == mesh]
    return recs


MOVE_HINT = {
    "compute": "raise arithmetic intensity (fuse, larger tiles/microbatch)",
    "memory": "cut HBM traffic (blockwise attn, bf16 streams, in-place cache)",
    "collective": "cut wire bytes (local dispatch, sharded weights, int8 DCN)",
}


def table(recs):
    lines = [
        "| mesh | arch | shape | peak GiB | t_comp s | t_mem s | t_coll s "
        "| bottleneck | MODEL_FLOPs/HLO | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["status"] != "ok":
            continue
        ro = r["roofline"]
        tmax = max(ro["t_compute"], ro["t_memory"], ro["t_collective"], 1e-12)
        frac = ro["t_compute"] / tmax
        lines.append(
            f"| {r['mesh_kind']} | {r['arch']} | {r['shape']} "
            f"| {r['memory_analysis']['peak_gib']:.2f} "
            f"| {ro['t_compute']:.4f} | {ro['t_memory']:.4f} "
            f"| {ro['t_collective']:.4f} | {ro['bottleneck']} "
            f"| {min(ro['useful_flops_ratio'], 9.99):.3f} | {frac*100:.1f}% |")
    skips = [r for r in recs if r["status"].startswith("skip")]
    if skips:
        lines.append("")
        lines.append("Skipped cells (per assignment rules):")
        for r in sorted({(r["arch"], r["shape"], r["status"]) for r in skips}):
            lines.append(f"* {r[0]} x {r[1]} — {r[2]}")
    return "\n".join(lines)


def bottleneck_summary(recs):
    out = []
    for r in recs:
        if r["status"] != "ok":
            continue
        ro = r["roofline"]
        out.append(f"* {r['arch']} x {r['shape']} [{r['mesh_kind']}]: "
                   f"{ro['bottleneck']}-bound -> {MOVE_HINT[ro['bottleneck']]}")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--hints", action="store_true")
    args = ap.parse_args()
    recs = load(args.path, args.mesh)
    print(table(recs))
    if args.hints:
        print()
        print(bottleneck_summary(recs))


if __name__ == "__main__":
    main()
