"""Run one benchmark cell once on the chip this process finds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of ``BENCHMARK.json``'s
``workloads``.  The run places JAX's compile cache, refuses any device that
is not a TPU (or fewer chips than the cell asks for), loads the cell's data
set from ``--seed``, warms every shape its traffic uses, serves the traffic
for ``--seconds`` on the wall clock and compares every answer with a plain
numpy reference.  Progress, the compiles inside the window and each
number compared beside its limit go to standard error; the last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench.run: the system under test is not in {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench.run: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 1
    from repro.compile_cache import place_compile_cache

    cache = place_compile_cache()
    harness.log(f"device: {devices[0].device_kind} x{len(devices)}; "
                f"jax {jax.__version__}; compile cache {cache}")
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_process=T_PROCESS)
    for name, c in result["checks"].items():
        harness.log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
