"""Run the ``jax-nbtree`` served path once on one TPU chip; check its answers.

    python chip_smoke.py [--seed 0] [--log2-keys 23] [--serve-ops 8192]

Everything runs in this one process, through the entry points a user calls:

1. **kernels** — both merge kernels against ``merge_sorted_ref`` at insert
   and flush widths; the compiled insert and flush impls must hold a Pallas
   kernel (``tpu_custom_call``).
2. **load** — ``make_engine("jax-nbtree")`` at its registry defaults (f=4,
   sigma=2048) takes ``2**log2_keys`` distinct keys drawn from ``--seed``
   over a key space 64 times larger, in equal power-of-two insert batches,
   then drains its maintenance debt.  The default is 2^23 keys (2^29 of
   key space, 1.3 GB of node tables): a 2^24-key load passes too but takes
   7.5 minutes of the run's 20 on a v5e.  The node
   tables are sized up front, so no impl recompiles for table growth.
3. **serve** — ``--serve-ops`` ops of the ``delete-churn`` mix (INSERT,
   DELETE, QUERY, RANGE) arrive as a Poisson process and are served by the
   open-loop ingest frontend (``run_open_workload``); none may be shed.
4. **verify** — point lookups of loaded, overwritten, new, deleted and
   absent keys and range scans, issued through ``engine.apply``, and the
   engine's whole live table, must equal a plain numpy reference: the load
   arrays with the trace's writes applied in order.

Earlier lines report the device, compiles (cold: compiled by XLA; warm:
loaded from the persistent cache), the load rate, maintenance-unit wall
clock from ``EngineStats`` and the HBM in use.  The frontend's end-to-end
tails come from its virtual service model, not from the device, so they
are not printed.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Where JAX finds no TPU the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: key space per loaded key: 2^30 keys of space over a 2^24-key load.
KEY_SPACE_PER_KEY = 64
#: default load: 2^23 distinct keys.
LOG2_KEYS = 23
#: keys of space per range scan: about 64 live keys a scan, YCSB-E's short
#: scans (1-100 records).
SCAN_SPAN = 4096
LOAD_BATCH = 1 << 16
#: offered rate of the serve phase; the frontend's bounded queue never
#: fills at this rate, so no op is shed.
SERVE_RATE = 20_000.0


def _log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """JAX monitoring listener: programs compiled by XLA (cold) and loaded
    from the persistent cache (warm), with the seconds each took.

    JAX times every compile request, cache hit or not, under one event; a
    hit also records its cache retrieval just before that event ends.
    """

    def __init__(self):
        self.cold = self.warm = 0
        self.cold_s = self.warm_s = 0.0
        self._hit = False

    def __call__(self, event: str, duration_s: float, **_):
        if event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self._hit = True
        elif event == "/jax/core/compile/backend_compile_duration":
            if self._hit:
                self.warm += 1
                self.warm_s += duration_s
            else:
                self.cold += 1
                self.cold_s += duration_s
            self._hit = False

    def line(self) -> str:
        return (f"compiles: cold={self.cold} ({self.cold_s:.3f} s) "
                f"warm={self.warm} ({self.warm_s:.3f} s)")


def distinct_keys(rng: np.random.Generator, n: int, key_space: int):
    """``n`` distinct keys from ``[1, key_space]``, in random order."""
    keys = np.zeros(0, np.uint64)
    while len(keys) < n:
        more = rng.integers(1, key_space + 1, n + n // 8 + 16,
                            dtype=np.uint64)
        keys = np.unique(np.concatenate([keys, more]))
    return rng.permutation(keys)[:n]


def table_rows(n_keys: int, sigma: int) -> int:
    """Node-table rows for a load of ``n_keys`` distinct keys.

    Every split retires one node id and takes two; loads of 2^14 to 2^19
    keys at f=4, sigma=2048 end with 2.2 ids per sigma keys.  Three per
    sigma keys, rounded up to 1024 rows, leaves headroom for the serve
    phase.
    """
    return max(256, -(-3 * n_keys // sigma // 1024) * 1024)


def final_state(load_keys, load_vals, ops):
    """The reference: sorted keys and each key's last written value.

    ``load_*`` are applied first, then ``ops``' INSERT/DELETE rows in
    order; a deleted key's value is -1 (written values are >= 0).
    """
    from repro.core.engine_api import OpKind

    w = np.isin(ops.kinds, (int(OpKind.INSERT), int(OpKind.DELETE)))
    keys = np.concatenate([load_keys, ops.keys[w]])
    vals = np.concatenate([
        load_vals,
        np.where(ops.kinds[w] == int(OpKind.DELETE), -1, ops.vals[w])])
    last, first = np.unique(keys[::-1], return_index=True)
    return last, vals[::-1][first]


def _lookup(ref_keys, ref_vals, q):
    """Reference point lookups: (found, value or -1)."""
    i = np.minimum(np.searchsorted(ref_keys, q), len(ref_keys) - 1)
    found = (ref_keys[i] == q) & (ref_vals[i] >= 0)
    return found, np.where(found, ref_vals[i], -1)


def point_probes(rng, ref_keys, ref_vals, load_keys, ops, n_points: int,
                 key_space: int) -> dict:
    """Lookup keys by class: every key the trace wrote, plus loaded keys it
    left alone and absent keys, at least ``n_points`` in all."""
    from repro.core.engine_api import OpKind

    w = np.isin(ops.kinds, (int(OpKind.INSERT), int(OpKind.DELETE)))
    written = np.unique(ops.keys[w])
    was_loaded = np.isin(written, load_keys)
    live, _ = _lookup(ref_keys, ref_vals, written)
    untouched = np.setdiff1d(load_keys, written)
    n_absent = n_points // 4
    n_loaded = max(n_points // 2, n_points - len(written) - n_absent)
    absent = rng.integers(1, key_space + 1, 2 * n_absent + 64,
                          dtype=np.uint64)
    absent = np.setdiff1d(absent, ref_keys)[:n_absent]
    return {
        "loaded": rng.choice(untouched, min(n_loaded, len(untouched)),
                             replace=False),
        "overwritten": written[live & was_loaded],
        "new": written[live & ~was_loaded],
        "deleted": written[~live],
        "absent": absent,
    }


def range_probes(rng, load_keys, ops, n_ranges: int, key_space: int):
    """``n_ranges`` inclusive scans of ``SCAN_SPAN`` keys: half start just
    below a loaded or written key, half anywhere."""
    near = rng.choice(np.concatenate([load_keys, ops.keys]), n_ranges // 2)
    near = near - np.minimum(near - 1, rng.integers(
        0, SCAN_SPAN, len(near), dtype=np.uint64))
    anywhere = rng.integers(1, key_space + 1, n_ranges - len(near),
                            dtype=np.uint64)
    los = np.concatenate([near, anywhere])
    return los, los + np.uint64(SCAN_SPAN - 1)


def verify(engine, rng, ref_keys, ref_vals, load_keys, ops, *,
           n_points: int, n_ranges: int, key_space: int) -> dict:
    """Mismatches of ``engine`` against the reference, by probe class."""
    from repro.core.engine_api import OpBatch

    probes = point_probes(rng, ref_keys, ref_vals, load_keys, ops, n_points,
                          key_space)
    q = np.concatenate(list(probes.values()))
    res = engine.apply(OpBatch.queries(q))
    found, vals = _lookup(ref_keys, ref_vals, q)
    bad = (res.found != found) | (res.values != vals)
    ends = np.cumsum([len(p) for p in probes.values()])
    out = {name: (len(p), int(b.sum())) for (name, p), b in
           zip(probes.items(), np.split(bad, ends[:-1]))}

    los, his = range_probes(rng, load_keys, ops, n_ranges, key_space)
    res = engine.apply(OpBatch.ranges(los, his))
    live = ref_vals >= 0
    lk, lv = ref_keys[live], ref_vals[live]
    s = np.searchsorted(lk, los, side="left")
    e = np.searchsorted(lk, his, side="right")
    bad = 0
    for i in range(len(los)):
        got_k, got_v = res.range_hits[i]
        bad += int(res.range_truncated[i]
                   or not np.array_equal(got_k, lk[s[i]:e[i]])
                   or not np.array_equal(got_v, lv[s[i]:e[i]]))
    out["ranges"] = (len(los), bad)

    dk, dv = engine.dump_live()
    out["live_table"] = (len(lk), int(not (np.array_equal(dk, lk)
                                           and np.array_equal(dv, lv))))
    return out


def check_kernels(rng, *, sigma: int = 2048, run_cap: int = 11264,
                  fanout: int = 4) -> None:
    """Both merge kernels equal ``merge_sorted_ref`` bit for bit at insert
    (sigma x run_cap) and flush (fanout x sigma, fanout x run_cap) widths,
    on keys dense enough that equal keys meet across and within runs."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.ref import merge_sorted_ref

    def run(*lead):
        a = np.sort(rng.integers(0, 4 * run_cap, (*lead, sigma)), -1)
        b = np.sort(rng.integers(0, 4 * run_cap, (*lead, run_cap)), -1)
        av = rng.integers(0, 2**31 - 1, a.shape)
        bv = rng.integers(0, 2**31 - 1, b.shape)
        return [jnp.asarray(x, d) for x, d in ((a, jnp.uint32),
                                               (av, jnp.int32),
                                               (b, jnp.uint32),
                                               (bv, jnp.int32))]

    args = run()
    got = ops.merge_sorted(*args)
    want = merge_sorted_ref(*args)
    batch = run(fanout)
    got_b = ops.merge_sorted_batch(*batch)
    want_b = [merge_sorted_ref(*(x[r] for x in batch)) for r in range(fanout)]
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), "merge_sorted"
    for r in range(fanout):
        for g, w in zip(got_b, want_b[r]):
            assert np.array_equal(np.asarray(g[r]), np.asarray(w)), \
                "merge_sorted_batch"


def impls_hold_kernels(idx) -> dict:
    """Compile the insert and flush impls for this index's tables and say
    whether each compiled program holds a Pallas kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core import jax_nbtree as J

    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    tables = [spec(t) for t in (idx.run_keys, idx.run_vals, idx.run_count,
                                idx.bloom)]
    common = dict(run_cap=idx.run_cap, nbits=idx.nbits, h=idx.h,
                  interpret=False)
    insert = J._insert_impl.lower(
        *tables, jax.ShapeDtypeStruct((idx.sigma,), jnp.uint32),
        jax.ShapeDtypeStruct((idx.sigma,), jnp.int32), **common)
    flush = J._flush_impl.lower(
        *tables, *(spec(t) for t in (idx.pivots, idx.children, idx.nchild)),
        jax.ShapeDtypeStruct((), jnp.int32), spec(idx._chain_log),
        sigma=idx.sigma, sigma_pad=idx.sigma_pad, **common)
    return {name: "tpu_custom_call" in low.compile().as_text()
            for name, low in (("_insert_impl", insert), ("_flush_impl", flush))}


def run_smoke(*, seed: int, log2_keys: int, serve_ops: int,
              n_points: int = 4096, n_ranges: int = 256, log=_log,
              compiles: CompileCounter | None = None, **engine_kw) -> dict:
    """Load, serve and verify one ``jax-nbtree`` engine; returns a summary.

    ``engine_kw`` override the registry defaults (tests use small ones);
    ``compiles``, if given, splits compile time out of the load time.
    Raises ``AssertionError`` if any op is shed.
    """
    import jax

    from repro.core.engine_api import OpBatch, make_engine
    from repro.workloads import make_workload
    from repro.workloads.driver import run_open_workload

    n = 1 << log2_keys
    key_space = KEY_SPACE_PER_KEY * n
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x10AD]))
    keys = distinct_keys(rng, n, key_space)
    vals = rng.integers(0, 2**31 - 1, n)

    probe = make_engine("jax-nbtree", **engine_kw)
    sigma = probe.idx.sigma
    rows = table_rows(n, sigma)
    eng = make_engine("jax-nbtree", max_nodes=rows, **engine_kw)
    del probe
    dev = jax.devices()[0]

    # ---- load: equal power-of-two batches, then drain -------------------
    batch = min(n, LOAD_BATCH)
    compile_s = lambda: compiles.cold_s + compiles.warm_s if compiles else 0.0
    c0, t0 = compile_s(), time.perf_counter()
    for i in range(0, n, batch):
        eng.apply(OpBatch.inserts(keys[i:i + batch], vals[i:i + batch]))
    eng.drain()
    jax.block_until_ready(eng.idx.run_keys)
    load_s = time.perf_counter() - t0
    grew = eng.idx.max_nodes != rows
    log(f"load: {n} keys in {load_s:.3f} s = {n / load_s:.1f} ops/s, "
        f"{compile_s() - c0:.3f} s of it compiling "
        f"(batches of {batch}, node ids used {eng.idx._next_id} of {rows} "
        f"rows{', tables GREW' if grew else ''})")
    mem = dev.memory_stats() or {}
    if mem:
        log(f"hbm after load: bytes_in_use={mem.get('bytes_in_use')} "
            f"peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
            f"bytes_limit={mem.get('bytes_limit')}")

    # ---- serve: delete-churn through the open-loop frontend --------------
    wl = make_workload("delete-churn", key_space=key_space, preload=0,
                       n_ops=serve_ops, seed=seed,
                       range_selectivity=SCAN_SPAN / key_space)
    t0 = time.perf_counter()
    report = run_open_workload(eng, wl, arrival="poisson", rate=SERVE_RATE)
    serve_s = time.perf_counter() - t0
    ol = report["open_loop"]
    log(f"serve: delete-churn {ol['n_done']} ops poisson@{SERVE_RATE:g}/s "
        f"in {serve_s:.3f} s wall, commits={ol['server']['n_commits']} "
        f"shed={ol['n_shed']} offered={ol['offered_per_kind']}")
    assert ol["n_shed"] == 0, f"serve phase shed {ol['n_shed']} ops"

    st = eng.stats()
    log(f"maintenance units (EngineStats, wall clock, synced): "
        f"n={st.maintain_units} p50={st.maintain_unit_p50_s * 1e3:.3f} ms "
        f"p99={st.maintain_unit_p99_s * 1e3:.3f} ms "
        f"p100={st.maintain_unit_p100_s * 1e3:.3f} ms "
        f"height={st.height} live_pairs={st.total_pairs} "
        f"dispatches={st.device_dispatches}")

    # ---- verify against the numpy reference -----------------------------
    ops = OpBatch.concat(list(wl.batches()))
    ref_keys, ref_vals = final_state(keys, vals, ops)
    result = verify(eng, rng, ref_keys, ref_vals, keys, ops,
                    n_points=n_points, n_ranges=n_ranges,
                    key_space=key_space)
    log("verify: " + " ".join(f"{k}={c}/{bad}bad"
                              for k, (c, bad) in result.items()))
    mem = dev.memory_stats() or {}
    if mem:
        log(f"hbm at end: bytes_in_use={mem.get('bytes_in_use')} "
            f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
    return {"load_s": load_s, "keys": n, "verify": result,
            "mismatches": sum(bad for _, bad in result.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the loaded keys and of the served trace")
    ap.add_argument("--log2-keys", type=int, default=LOG2_KEYS,
                    help="load 2**N distinct keys (key space 64x that)")
    ap.add_argument("--serve-ops", type=int, default=8192)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import place_compile_cache
    from repro.core.engine_api import make_engine

    cache = place_compile_cache()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    _log(f"device: {dev.device_kind} (platform={dev.platform}, "
         f"count={len(devices)}); jax {jax.__version__}; compile cache {cache}")

    t0 = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    check_kernels(rng)
    held = impls_hold_kernels(make_engine("jax-nbtree").idx)
    _log(f"kernels: merge_sorted and merge_sorted_batch equal "
         f"merge_sorted_ref; tpu_custom_call in {held} "
         f"({time.perf_counter() - t0:.3f} s)")
    assert all(held.values()), f"Pallas merge missing from {held}"

    out = run_smoke(seed=args.seed, log2_keys=args.log2_keys,
                    serve_ops=args.serve_ops, compiles=counter)
    _log(counter.line())
    if out["mismatches"]:
        print(f"chip_smoke: {out['mismatches']} mismatches against the "
              "reference", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
