"""Find the knee of an open-loop cell: serve it at several fixed rates.

    python3 -m bench.sweep --workload ycsb-b.open --seed 7 --seconds 15 \\
        --rates 2000 4000 6000 8000

One process loads the cell's data set once, warms it, then serves the
cell's traffic at each rate in turn, for ``--seconds`` each.  A line per
rate goes to standard output: offered and completed ops/s, p50/p99 latency
and queue wait, and the queue wait in the window's first and last quarter
(a wait that keeps growing means the rate is past the knee).  The cell's
traffic file fixes its rate at about 0.8x the highest rate that holds.
The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from .run import ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from . import harness
    from .generator import Traffic

    if jax.devices()[0].platform != "tpu":
        print("bench.sweep: needs a TPU", file=sys.stderr)
        return 1
    from repro.compile_cache import place_compile_cache

    place_compile_cache()
    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        ap.error("a sweep is for open-loop cells")
    t = Traffic(cell.config, cell.traffic, args.seed)
    server = harness.Server(harness.make_engine(cell), cell.traffic)
    harness.preload(server, t, cell.config)
    harness.warm_open(server, t)
    for rate in args.rates:
        t.traffic = dict(cell.traffic, rate=rate)
        req = t.open_loop(args.seconds)
        t0 = time.perf_counter()
        out = harness.open_loop(server, req, t0)
        c = out["commits"]
        q = out["queue"]
        quarter = max(1, len(q) // 4)
        print(json.dumps({
            "rate": rate, "offered": len(req),
            "served_per_s": float(c[:, 3].sum() / (c[-1, 2] - t0)),
            "failed": out["failed"],
            "p50_ms": float(np.percentile(out["latency"], 50) * 1e3),
            "p99_ms": float(np.percentile(out["latency"], 99) * 1e3),
            "queue_p99_ms": float(np.percentile(q, 99) * 1e3),
            "queue_first_quarter_ms": float(q[:quarter].mean() * 1e3),
            "queue_last_quarter_ms": float(q[-quarter:].mean() * 1e3),
            "ops_per_commit": float(c[:, 3].mean())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
