"""Several runs of one cell in one process, with or without a planted fault.

    python3 -m bench.control --workload <cell> --seconds <s> \\
        --fault <none|write_behind|unchanged|half_batch|altered> --seeds 1 2 3

Each seed loads, warms, serves and checks as ``bench.run`` does, at the
cell's own size and load; the compiled programs are shared between the
seeds, so only the first pays for them.  ``--fault write_behind`` is the
control that the comparison must fail.  One JSON line per seed goes to
standard output: the seed, ``correct`` and the numbers compared.  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from .run import ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="write_behind")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from . import faults, harness

    if jax.devices()[0].platform != "tpu":
        print("bench.control: needs a TPU", file=sys.stderr)
        return 1
    from repro.compile_cache import place_compile_cache

    place_compile_cache()
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, t_process=t0,
                               engine_factory=faults.factory(args.fault))
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "metrics": out["metrics"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
