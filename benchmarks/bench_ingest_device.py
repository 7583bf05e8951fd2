"""Device-tier ingest microbenchmark: fused vs eager emptying cascade.

The paper's headline claim is a *consistently high insertion rate*; on the
device tier that rate is decided by the maintenance path — every flush of
the emptying cascade used to issue ~25 eager dispatches with blocking host
syncs in the middle, and every insert batch rebuilt the root Bloom filter
over the full run.  This benchmark measures the write path both ways
(``NBTreeIndex(fused=...)``) on the *same* key stream and records the first
wall-clock entries in the perf trajectory:

* **insert ops/s** — end-to-end ingest wall-clock (insert + interleaved
  ``maintain`` + final drain) over the measured window,
* **dispatches per flush unit** — counted through the per-instance
  ``NBTreeIndex.dispatch_count`` (every dispatch flows through the
  ``jax_nbtree._device_call`` funnel), split into insert-path and
  maintenance-path budgets,
* **maintain-unit latency** — p50/p99/p100 wall-clock of individual
  ``maintain(1)`` work units (the deamortized stall quantum).

Absolute numbers on CPU are interpret-mode Pallas (the kernel target is
TPU) and are NOT byte-reproducible — the fused/eager *ratios* are the
signal, and the dispatch counts are exact.  ``check`` enforces the PR's
acceptance floor: >= 5x fewer dispatches per flush unit and a higher
insert rate on the fused path.

Standalone CLI (CI bench-smoke; ``BENCH_device_ingest.json`` at the repo
root is the full-run trajectory seed)::

    PYTHONPATH=src python -m benchmarks.bench_ingest_device --quick \
        --out runs/bench_ingest_device.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

import repro.core.jax_nbtree as jnb
from repro.core.jax_nbtree import NBTreeIndex
from repro.kernels import ops
from repro.workloads.driver import SCHEMA_VERSION

#: one source of truth for the smoke-sized run (--quick here and in
#: benchmarks/run.py must produce comparable artifacts).
QUICK_KWARGS = dict(n_batches=48, warmup=24, batch=256, sigma=512)


def _precompile_fused(idx: NBTreeIndex) -> None:
    """Compile every fused maintenance variant against the live table shapes.

    The fused impls are shape-specialized jits (the split keyed on its
    mode); a variant first met mid-measurement would charge its
    multi-second first compile to one unlucky unit.  Warming them on dummy
    tables of identical shape keeps the measured window compile-free.  The
    eager path needs no equivalent: its helpers are per-table-shape only
    and all appear within the first few warmup flushes.
    """
    import jax.numpy as jnp

    dummy = lambda: (jnp.zeros_like(idx.run_keys),
                     jnp.zeros_like(idx.run_vals),
                     jnp.zeros_like(idx.run_count),
                     jnp.zeros_like(idx.bloom))
    jax.block_until_ready(jnb._flush_impl(
        *dummy(), jnp.zeros_like(idx.pivots), jnp.zeros_like(idx.children),
        jnp.zeros_like(idx.nchild), jnp.int32(-1),
        jnp.zeros_like(idx._chain_log), sigma=idx.sigma,
        sigma_pad=idx.sigma_pad, run_cap=idx.run_cap, nbits=idx.nbits,
        h=idx.h, interpret=ops._interpret()))
    for has_key in (False, True):
        jax.block_until_ready(jnb._split_impl(
            *dummy(), jnp.int32(0), jnp.int32(1), jnp.int32(2),
            jnp.int32(idx.sigma + 1), jnp.uint32(0), has_key=has_key,
            run_cap=idx.run_cap, nbits=idx.nbits, h=idx.h))
    jax.block_until_ready(jnb._clear_impl(*dummy(), jnp.int32(0)))
    jax.block_until_ready(jnb._sync_impl(
        jnp.zeros_like(idx.pivots), jnp.zeros_like(idx.children),
        jnp.zeros_like(idx.nchild), jnp.int32(0),
        jnp.zeros(idx.f - 1, jnp.uint32), jnp.zeros(idx.f, jnp.int32),
        jnp.int32(0)))


def _ingest(fused: bool, *, n_batches: int, warmup: int, batch: int,
            sigma: int, f: int, max_nodes: int, budget: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    keys = rng.choice(np.arange(1, 2**31, dtype=np.uint32),
                      (n_batches + warmup) * batch, replace=False)
    idx = NBTreeIndex(f=f, sigma=sigma, max_nodes=max_nodes, fused=fused)

    units = {"flush": 0, "split": 0}
    orig_handle = idx._handle_full

    def counted(node, budget=1):
        units["split" if node.is_leaf else "flush"] += 1
        return orig_handle(node, budget)

    idx._handle_full = counted

    def one_batch(b, unit_times, disp):
        """Insert one batch then pay maintenance one timed unit at a time."""
        ks = keys[b * batch:(b + 1) * batch]
        d0 = idx.dispatch_count
        t0 = time.perf_counter()
        idx.insert_batch(ks, np.arange(batch, dtype=np.int32))
        jax.block_until_ready(idx.run_keys)
        disp["insert"] += idx.dispatch_count - d0
        disp["insert_batches"] += 1
        for _ in range(budget):
            if not idx._pending:
                break
            u0 = units["flush"] + units["split"]
            d1 = idx.dispatch_count
            t1 = time.perf_counter()
            idx.maintain(1)
            jax.block_until_ready(idx.run_keys)
            dt = time.perf_counter() - t1
            if units["flush"] + units["split"] > u0:
                unit_times.append(dt)
                disp["maintain"] += idx.dispatch_count - d1
        return time.perf_counter() - t0

    # ---- warmup: compile every maintenance variant + steady the tree -------
    if fused:
        _precompile_fused(idx)
    sink_times: list = []
    sink_disp = {"insert": 0, "insert_batches": 0, "maintain": 0}
    for b in range(warmup):
        one_batch(b, sink_times, sink_disp)

    # ---- measured window ---------------------------------------------------
    units["flush"] = units["split"] = 0
    unit_times: list = []
    disp = {"insert": 0, "insert_batches": 0, "maintain": 0}
    wall = 0.0
    for b in range(warmup, warmup + n_batches):
        wall += one_batch(b, unit_times, disp)
    t0 = time.perf_counter()
    n_drain_units0 = units["flush"] + units["split"]
    d0 = idx.dispatch_count
    idx.drain()
    jax.block_until_ready(idx.run_keys)
    drain_s = time.perf_counter() - t0
    disp["maintain"] += idx.dispatch_count - d0
    wall += drain_s

    n_units = units["flush"] + units["split"]
    ut = np.asarray(unit_times) * 1e3
    return dict(
        name=f"device_ingest_{'fused' if fused else 'eager'}",
        insert_ops_s=float(n_batches * batch / wall),
        wall_s=float(wall),
        dispatches_per_flush_unit=float(disp["maintain"] / max(n_units, 1)),
        dispatches_per_insert_batch=float(disp["insert"]
                                          / max(disp["insert_batches"], 1)),
        maintain_units=int(n_units),
        flush_units=int(units["flush"]),
        split_units=int(units["split"]),
        drain_units=int(n_units - n_drain_units0),
        maintain_p50_ms=float(np.percentile(ut, 50)) if ut.size else 0.0,
        maintain_p99_ms=float(np.percentile(ut, 99)) if ut.size else 0.0,
        maintain_p100_ms=float(ut.max()) if ut.size else 0.0,
        drain_ms=float(drain_s * 1e3),
    )


def run(n_batches: int = 160, warmup: int = 40, batch: int = 512,
        sigma: int = 1024, f: int = 4, max_nodes: int = 512,
        budget: int = 2, seed: int = 0):
    rows = []
    for fused in (True, False):
        rows.append(_ingest(fused, n_batches=n_batches, warmup=warmup,
                            batch=batch, sigma=sigma, f=f,
                            max_nodes=max_nodes, budget=budget, seed=seed))
    return rows


def check(rows) -> list[str]:
    fu = next(r for r in rows if r["name"].endswith("fused"))
    ea = next(r for r in rows if r["name"].endswith("eager"))
    out = []
    dr = ea["dispatches_per_flush_unit"] / max(fu["dispatches_per_flush_unit"],
                                              1e-9)
    tag = "matches paper" if dr >= 5.0 else "MISMATCH"
    out.append(f"device_ingest: {ea['dispatches_per_flush_unit']:.1f} -> "
               f"{fu['dispatches_per_flush_unit']:.1f} dispatches per flush "
               f"unit ({dr:.1f}x fewer, fused cascade)  [{tag}]")
    ir = fu["insert_ops_s"] / max(ea["insert_ops_s"], 1e-9)
    tag = "matches paper" if ir > 1.0 else "MISMATCH"
    out.append(f"device_ingest: insert rate {ea['insert_ops_s']:.0f} -> "
               f"{fu['insert_ops_s']:.0f} ops/s ({ir:.2f}x, one-dispatch "
               f"flush + incremental Blooms)  [{tag}]")
    br = ea["dispatches_per_insert_batch"] / max(
        fu["dispatches_per_insert_batch"], 1e-9)
    out.append(f"device_ingest: {ea['dispatches_per_insert_batch']:.1f} -> "
               f"{fu['dispatches_per_insert_batch']:.1f} dispatches per "
               f"insert batch ({br:.1f}x fewer)")
    out.append(f"device_ingest: fused maintain-unit p100 "
               f"{fu['maintain_p100_ms']:.1f}ms (p50 "
               f"{fu['maintain_p50_ms']:.1f}ms) over {fu['maintain_units']} "
               f"units")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller run (CI smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="runs/bench_ingest_device.json")
    args = ap.parse_args(argv)
    kwargs = dict(QUICK_KWARGS) if args.quick else {}
    rows = run(seed=args.seed, **kwargs)
    checks = check(rows)
    for r in rows:
        print(r)
    for c in checks:
        print(" ->", c)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"schema_version": SCHEMA_VERSION, "seed": args.seed,
                   "quick": bool(args.quick),
                   "backend": jax.default_backend(),
                   "clock": "wall", "rows": rows, "checks": checks}, f,
                  indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
