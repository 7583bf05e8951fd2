"""Render dry-run JSONL records as the EXPERIMENTS.md roofline tables.

  PYTHONPATH=src python -m benchmarks.roofline_report runs/dryrun_baseline.jsonl [--mesh single]

``--measure`` switches from analytic (dry-run artifact) mode to the
*empirical* side of the roofline: it drives the device NB-tree with a
tracer attached (DESIGN.md §11), collects per-kernel dispatch wall
timings + argument/result byte footprints, and prints measured achieved
bandwidth per kernel against the peak-HBM line::

  PYTHONPATH=src python -m benchmarks.roofline_report --measure --ops 4096

With a positional path, ``--measure`` instead reads ``dispatch_stats``
from that JSON report (any file carrying a ``dispatch_stats`` block and a
top-level ``device_kind``).  Peaks come from ``repro.roofline.hardware``
by device kind; a kind missing from that table is an error.
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


def _find_dispatch_stats(obj):
    """Depth-first search for a ``dispatch_stats`` block in a report."""
    if isinstance(obj, dict):
        ds = obj.get("dispatch_stats")
        if isinstance(ds, dict) and ds:
            return ds
        for v in obj.values():
            found = _find_dispatch_stats(v)
            if found:
                return found
    elif isinstance(obj, list):
        for v in obj:
            found = _find_dispatch_stats(v)
            if found:
                return found
    return None


def measure(path=None, *, ops=4096, batch=256, trace_out=None):
    """Measured per-kernel table: live device run, or a saved report."""
    import jax

    from repro.obs.trace import Tracer
    from repro.roofline import hardware as hw
    from repro.roofline.analysis import measured_kernel_table

    if path is not None:
        with open(path) as f:
            report = json.load(f)
        stats = _find_dispatch_stats(report)
        kind = report.get("device_kind")
        if not stats or kind is None:
            raise SystemExit(f"{path}: needs a dispatch_stats block (run "
                             "with a tracer attached) and a top-level "
                             "device_kind it was measured on")
    else:
        kind = jax.devices()[0].device_kind
        import numpy as np
        from repro.core.engine_api import make_engine

        eng = make_engine("jax-nbtree", f=4, sigma=512, max_nodes=4096)
        tracer = Tracer()
        eng.attach_tracer(tracer)
        rng = np.random.default_rng(0)
        from repro.core.engine_api import OpBatch
        for i in range(0, ops, batch):
            keys = rng.integers(1, 1 << 40, size=batch, dtype=np.uint64)
            eng.apply(OpBatch.inserts(keys, keys))
            eng.maintain(4)
        eng.drain()
        stats = eng.idx.dispatch_stats
        if trace_out:
            tracer.save(trace_out)
            print(f"wrote {trace_out}")

    rows = measured_kernel_table(stats, device_kind=kind)
    print(f"Measured kernel bandwidth on {kind} "
          f"(peak HBM {hw.peaks(kind).hbm_bw/1e9:.0f} GB/s):")
    print("| kernel | dispatches | wall s | MiB moved | achieved GB/s "
          "| % of peak |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['kernel']} | {r['count']} | {r['wall_s']:.4f} "
              f"| {r['bytes']/2**20:.2f} | {r['achieved_gb_s']:.3f} "
              f"| {r['peak_frac']*100:.2f}% |")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default=None)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--hints", action="store_true")
    ap.add_argument("--measure", action="store_true",
                    help="measured per-kernel bandwidth from tracer "
                         "dispatch stats (live device run, or a report "
                         "file carrying dispatch_stats)")
    ap.add_argument("--ops", type=int, default=4096,
                    help="--measure live mode: inserts to drive")
    ap.add_argument("--trace-out", default=None,
                    help="--measure live mode: also save the dispatch "
                         "span trace here (Chrome trace_event JSON)")
    args = ap.parse_args()
    if args.measure:
        measure(args.path, ops=args.ops, trace_out=args.trace_out)
        return
    if args.path is None:
        ap.error("path required unless --measure")
    recs = load(args.path, args.mesh)
    print(table(recs))
    if args.hints:
        print()
        print(bottleneck_summary(recs))


if __name__ == "__main__":
    main()
