"""Multi-tenant serving front door tests (DESIGN.md §10).

Covers the namespace packing (collision-free round trip, contiguous
intervals, batch encoding), the weighted-fair queue (DRR service ratios,
work conservation, per-tenant shed, the mid-visit resume that prevents a
deep queue from monopolizing commits), commit-watermark snapshots
(differential against a sorted-dict oracle frozen at the pin while
inserts and cascades proceed — on a sim tier AND the device tier),
multi-tenant conformance (each namespace's final state equals its own
single-tenant oracle), the durable multi-tenant crash path (every
namespace recovers with zero lost acked writes; single-namespace
key-range recovery), trace multiplexing, and the driver's multi-stream
modes.
"""
import numpy as np
import pytest

from repro.core.engine_api import OpBatch, OpKind, make_engine
from repro.ingest import (DurabilityConfig, FrontendConfig, PoissonArrivals,
                          make_trace, multiplex)
from repro.tenancy import (MultiTenantFrontend, NamespaceMap, SnapshotManager,
                           TenantConfig, WeightedFairQueue, recover_namespace,
                           run_multi_tenant)
from repro.wal import CrashPoint, FaultInjector, SimulatedCrash, recover
from repro.workloads import make_workload
from repro.workloads.tenants import TenantStream, build_scenario, build_streams

KEYS = np.uint64
VALS = np.int64


# ---------------------------------------------------------------- namespaces
def test_namespace_round_trip_and_intervals():
    ns = NamespaceMap()
    assert ns.key_bits == 27 and ns.max_tenants == 16
    rng = np.random.default_rng(0)
    for tid in (0, 3, 15):
        local = rng.integers(1, ns.max_local_key + 1, 256).astype(KEYS)
        enc = ns.encode(tid, local)
        assert enc.dtype == KEYS
        assert int(enc.max()) < (1 << 31), "uint32 device envelope"
        tids, dec = ns.decode(enc)
        assert (tids == tid).all()
        assert np.array_equal(dec, local)
        lo, hi = ns.tenant_interval(tid)
        assert lo <= int(enc.min()) and int(enc.max()) <= hi

    # intervals are disjoint and ordered -> collision-free across tenants
    ivals = [ns.tenant_interval(t) for t in range(ns.max_tenants)]
    for (lo1, hi1), (lo2, hi2) in zip(ivals, ivals[1:]):
        assert hi1 < lo2

    # order within a namespace is preserved (contiguous RANGE scans work)
    local = np.sort(rng.choice(np.arange(1, 10_000, dtype=KEYS), 64, False))
    enc = ns.encode(5, local)
    assert (np.diff(enc.astype(np.int64)) > 0).all()


def test_namespace_rejects_out_of_range():
    ns = NamespaceMap(tenant_bits=2)
    with pytest.raises(AssertionError):
        ns.encode(4, [1])                       # tenant id out of range
    with pytest.raises(AssertionError):
        ns.encode(1, [0])                       # local keys start at 1
    with pytest.raises(AssertionError):
        ns.encode(1, [ns.max_local_key + 1])    # overflows into tenant bits


def test_namespace_encode_batch_ranges():
    ns = NamespaceMap()
    b = OpBatch.ranges(np.array([10, 20], KEYS), np.array([15, 25], KEYS))
    ins = OpBatch.inserts(np.array([7], KEYS), np.array([70], VALS))
    enc = ns.encode_batch(2, OpBatch.concat([ins, b]))
    lo, _ = ns.tenant_interval(2)
    base = lo - 1
    assert enc.keys.tolist() == [base + 7, base + 10, base + 20]
    assert enc.his.tolist() == [0, base + 15, base + 25], \
        "RANGE his encodes; non-RANGE placeholder stays 0"
    assert enc.vals.tolist() == [70, 0, 0]


# ----------------------------------------------------------------- fair queue
def test_drr_service_follows_weights():
    q = WeightedFairQueue(quantum=10)
    q.add_tenant(0, weight=3.0, max_queue=1000)
    q.add_tenant(1, weight=1.0, max_queue=1000)
    for i in range(900):
        q.offer(0, i)
    for i in range(300, 600):
        q.offer(1, i)
    served = {0: 0, 1: 0}
    while q.backlog(0) and q.backlog(1):
        for tid, _ in q.take(16):
            served[tid] += 1
        if q.backlog(0) and q.backlog(1):
            # DRR bound: error vs the 3:1 weight ratio stays within a few
            # quanta over any backlogged interval.
            assert abs(served[0] - 3 * served[1]) <= 5 * q.quantum
    assert served[0] > served[1] > 0


def test_drr_work_conserving_and_shed_accounting():
    q = WeightedFairQueue(quantum=4)
    q.add_tenant(0, weight=1.0, max_queue=4)
    q.add_tenant(1, weight=1.0, max_queue=100)
    for i in range(10):
        q.offer(0, i)                 # 4 admitted, 6 shed
    assert q.backlog(0) == 4
    for i in range(3):
        q.offer(1, i)
    got = q.take(7)                   # never idles while ops are queued
    assert len(got) == 7 and q.backlog() == 0
    st = q.stats()
    assert st["0"]["shed"] == 6 and st["0"]["offered"] == 10
    assert st["1"]["shed"] == 0 and st["1"]["served"] == 3
    assert st["0"]["depth_max"] == 4


def test_drr_deep_queue_cannot_monopolize():
    """Regression: a batch-filling visit must not re-credit the same
    tenant a fresh quantum next call (cursor advances when the deficit is
    spent), so a co-tenant's op is served within the next batch."""
    q = WeightedFairQueue(quantum=16)
    q.add_tenant(0, weight=1.0, max_queue=2000)
    q.add_tenant(1, weight=1.0, max_queue=100)
    for i in range(1000):
        q.offer(0, i)
    q.offer(1, 0)
    first = q.take(16)
    second = q.take(16)
    assert (1, 0) in first + second
    # and per-tenant FIFO order is preserved for the deep queue
    t0 = [item for tid, item in first + second if tid == 0]
    assert t0 == sorted(t0)


# ------------------------------------------------------------------ snapshots
def test_snapshot_reads_are_frozen_at_pin():
    eng = make_engine("nbtree", f=3, sigma=64)
    keys = np.arange(10, 200, 2, dtype=KEYS)
    eng.apply(OpBatch.inserts(keys, keys.astype(VALS)))
    sm = SnapshotManager(eng)
    snap = sm.pin(watermark_lsn=1)
    # mutate + cascade after the pin: the view must not move
    eng.apply(OpBatch.deletes(keys[:50]))
    eng.apply(OpBatch.inserts(np.array([11, 13], KEYS),
                              np.array([1, 2], VALS)))
    eng.drain()
    found, vals = snap.query(np.array([10, 11, 12, 13], KEYS))
    assert found.tolist() == [True, False, True, False]
    assert vals.tolist() == [10, 0, 12, 0]
    rk, rv = snap.range(10, 20)
    assert rk.tolist() == [10, 12, 14, 16, 18, 20]
    assert rv.tolist() == [10, 12, 14, 16, 18, 20]
    sm.release(snap)
    assert sm.stats() == {"pins": 1, "releases": 1, "active": 0,
                          "active_pairs": 0, "pinned_pairs_max": 95}


def _oracle_from_acked(preloads, acked):
    """Sorted-dict ground truth over ENCODED keys: preload + acked ops."""
    d = {}
    for b in preloads:
        for k, v in zip(b.keys.tolist(), b.vals.tolist()):
            d[int(k)] = int(v)
    for _lsn, kinds, keys, vals in acked:
        for kk, k, v in zip(kinds.tolist(), keys.tolist(), vals.tolist()):
            if kk == int(OpKind.INSERT):
                d[int(k)] = int(v)
            else:
                d.pop(int(k), None)
    return d


def _scoped(d, interval):
    lo, hi = interval
    return sorted((k, v) for k, v in d.items() if lo <= k <= hi)


@pytest.mark.parametrize("name,kw", [
    ("nbtree", dict(f=3, sigma=64)),
    ("sharded:nbtree", dict(shards=3, f=3, sigma=64)),
    ("jax-nbtree", dict(f=4, sigma=64, max_nodes=256)),
])
def test_snapshot_differential_vs_oracle_mid_cascade(tmp_path, name, kw):
    """Pin snapshots at commit boundaries mid-run (whole keyspace and one
    tenant's interval), let ingest + emptying cascades proceed, then check
    every pinned view against a sorted-dict oracle frozen at its own
    watermark — on sim, sharded, and device tiers."""
    ns = NamespaceMap()
    streams = [
        TenantStream(tenant=TenantConfig(0, weight=2.0), mix="delete-churn",
                     n_ops=600, preload=128, key_space=1 << 14,
                     arrival={"process": "poisson", "rate": 50_000.0}),
        TenantStream(tenant=TenantConfig(1), mix="insert-heavy",
                     n_ops=600, preload=128, key_space=1 << 14,
                     arrival={"process": "poisson", "rate": 50_000.0}),
    ]
    tenants, traces = build_streams(streams, seed=3)
    eng = make_engine(name, **kw)
    fe = MultiTenantFrontend(
        eng, tenants, FrontendConfig(max_queue=4096, commit_ops=32),
        durability=DurabilityConfig(str(tmp_path / name.replace(":", "_"))),
        namespace=ns)

    pinned = []          # (snapshot, oracle-dict frozen at the pin)

    def on_commit(front, _t):
        if front._n_commits % 7 == 3 and len(pinned) < 8:
            pre = [ns.encode_batch(t, traces[t].preload) for t in traces]
            oracle = _oracle_from_acked(pre, front.acked)
            pinned.append((front.pin_snapshot(), oracle, None))
            pinned.append((front.pin_snapshot(tenant_id=0), oracle,
                           ns.tenant_interval(0)))

    rep = fe.run(traces, on_commit=on_commit)
    assert len(pinned) >= 4, "pins must actually happen mid-run"
    assert rep["snapshots"]["pins"] == len(pinned)
    # cascades really proceeded while snapshots were held
    assert rep["server"]["maintain_s"] >= 0.0
    for snap, oracle, interval in pinned:
        want = _scoped(oracle, interval) if interval else \
            sorted(oracle.items())
        assert snap.keys.tolist() == [k for k, _ in want], \
            "pinned view drifted from its watermark oracle"
        assert snap.vals.tolist() == [v for _, v in want]
        # point reads against the frozen view
        probe = snap.keys[:8]
        if len(probe):
            found, vals = snap.query(probe)
            assert found.all()
            assert vals.tolist() == [oracle[int(k)] for k in probe]


# ---------------------------------------------------------------- conformance
def test_multi_tenant_namespaces_match_solo_oracles():
    """With no shedding, each tenant's final namespace equals the oracle
    of its OWN trace alone — co-tenants are invisible (isolation)."""
    tenants, traces = build_scenario("mixed-oltp", seed=2, n_ops=600,
                                     base_rate=20_000.0)
    eng = make_engine("nbtree", f=3, sigma=128)
    ns = NamespaceMap()
    rep = run_multi_tenant(eng, tenants, traces, namespace=ns)
    ol = rep["open_loop"]
    assert ol["n_shed"] == 0 and ol["n_done"] == ol["n_offered"]
    for t in tenants:
        tid = t.tenant_id
        d = {}
        for k, v in zip(traces[tid].preload.keys.tolist(),
                        traces[tid].preload.vals.tolist()):
            d[int(k)] = int(v)
        kinds = traces[tid].ops.kinds.tolist()
        for kk, k, v in zip(kinds, traces[tid].ops.keys.tolist(),
                            traces[tid].ops.vals.tolist()):
            if kk == int(OpKind.INSERT):
                d[int(k)] = int(v)
            elif kk == int(OpKind.DELETE):
                d.pop(int(k), None)
        lo, hi = ns.tenant_interval(tid)
        gk, gv = eng.dump_live_range(lo, hi)
        _, local = ns.decode(gk)
        assert sorted(d.items()) == list(zip(local.tolist(), gv.tolist()))
        assert ol["tenants"][str(tid)]["live_pairs"] == len(d)


def test_multi_tenant_report_deterministic():
    import json

    def one():
        tenants, traces = build_scenario("noisy-neighbor", seed=4, n_ops=300,
                                         victim_rate=1000.0,
                                         aggressor_rate=20_000.0)
        eng = make_engine("nbtree", f=3, sigma=128)
        return json.dumps(run_multi_tenant(eng, tenants, traces),
                          sort_keys=True, default=float)

    assert one() == one()


def test_unfair_mode_sheds_victims_too():
    tenants, traces = build_scenario("noisy-neighbor", seed=0, n_ops=400,
                                     victim_rate=500.0,
                                     aggressor_rate=50_000.0)
    eng = make_engine("btree")
    rep = run_multi_tenant(
        eng, tenants, traces, fair=False,
        config=FrontendConfig(max_queue=512, commit_ops=16))
    adm = rep["open_loop"]["admission"]
    assert rep["open_loop"]["fair"] is False
    assert adm["2"]["shed"] > 0, "aggressor bursts overflow the shared FIFO"
    assert adm["0"]["shed"] + adm["1"]["shed"] > 0, \
        "shared FIFO lets the aggressor shed victims (the unfair baseline)"


def test_slo_targets_in_report():
    streams = [
        TenantStream(tenant=TenantConfig(0, slo_p999_s=10.0),    # generous
                     n_ops=200, preload=64),
        TenantStream(tenant=TenantConfig(1, slo_p999_s=1e-9),    # impossible
                     n_ops=200, preload=64),
    ]
    tenants, traces = build_streams(streams, seed=1)
    rep = run_multi_tenant(make_engine("nbtree", f=3, sigma=128),
                           tenants, traces)
    slo0 = rep["open_loop"]["tenants"]["0"]["slo"]
    slo1 = rep["open_loop"]["tenants"]["1"]["slo"]
    assert slo0["met"] is True and slo0["p999_target_s"] == 10.0
    assert slo1["met"] is False


# ------------------------------------------------------------------ multiplex
def test_multiplex_merges_in_time_order():
    wl = make_workload("insert-heavy", n_ops=200, preload=0, seed=0)
    traces = {0: make_trace(wl, PoissonArrivals(1000.0), arrival_seed=1),
              2: make_trace(wl, PoissonArrivals(3000.0), arrival_seed=2)}
    t, sid, loc = multiplex(traces)
    assert len(t) == 400
    assert (np.diff(t) >= 0).all(), "merged stream is time-sorted"
    for s in (0, 2):
        mine = loc[sid == s]
        assert np.array_equal(mine, np.arange(len(mine))), \
            "per-stream op order preserved"
    t2, sid2, loc2 = multiplex(traces)
    assert np.array_equal(t, t2) and np.array_equal(sid, sid2) \
        and np.array_equal(loc, loc2)
    e = multiplex({})
    assert len(e[0]) == 0


# --------------------------------------------------------- durability + crash
def _durable_multi(tmp_path, injector=None):
    streams = [
        TenantStream(tenant=TenantConfig(0, weight=2.0), mix="delete-churn",
                     n_ops=700, preload=128, key_space=1 << 14,
                     arrival={"process": "poisson", "rate": 50_000.0}),
        TenantStream(tenant=TenantConfig(1), mix="insert-heavy",
                     n_ops=700, preload=128, key_space=1 << 14,
                     arrival={"process": "poisson", "rate": 50_000.0}),
        TenantStream(tenant=TenantConfig(5), mix="delete-churn",
                     n_ops=400, preload=64, key_space=1 << 14,
                     arrival={"process": "poisson", "rate": 30_000.0}),
    ]
    tenants, traces = build_streams(streams, seed=7)
    eng = make_engine("nbtree", f=3, sigma=64)
    fe = MultiTenantFrontend(
        eng, tenants, FrontendConfig(max_queue=4096, commit_ops=32),
        durability=DurabilityConfig(str(tmp_path), segment_bytes=4096,
                                    checkpoint_every_commits=6),
        injector=injector)
    return fe, traces


def _factory():
    return make_engine("nbtree", f=3, sigma=64)


def test_multi_tenant_crash_recovers_every_namespace(tmp_path):
    """Kill a durable 3-tenant run mid-flight: global recovery restores
    every namespace to exactly its acked prefix (zero lost acked writes,
    zero resurrected unacked ones), and key-range recovery restores each
    single namespace from the shared log."""
    ns = NamespaceMap()
    inj = FaultInjector(CrashPoint.AFTER_WAL_FSYNC, at_occurrence=11)
    fe, traces = _durable_multi(tmp_path, injector=inj)
    with pytest.raises(SimulatedCrash):
        fe.run(traces)
    assert inj.fired and len(fe.acked) >= 10

    pre = [ns.encode_batch(t, traces[t].preload) for t in sorted(traces)]
    oracle = _oracle_from_acked(pre, fe.acked)

    rr = recover(str(tmp_path), _factory)
    rk, rv = rr.engine.dump_live()
    assert list(zip(rk.tolist(), rv.tolist())) == sorted(oracle.items())
    assert rr.last_lsn == fe.last_acked_lsn

    # every tenant id present in the oracle survived recovery
    tids = {int(k) >> ns.key_bits for k in oracle}
    assert tids == {0, 1, 5}

    for tid in (0, 1, 5):
        one = recover_namespace(str(tmp_path), _factory, tid, namespace=ns)
        assert one.key_range == ns.tenant_interval(tid)
        ok, ov = one.engine.dump_live()
        want = _scoped(oracle, ns.tenant_interval(tid))
        assert list(zip(ok.tolist(), ov.tolist())) == want, \
            f"namespace {tid} lost acked writes under scoped recovery"
        assert one.last_lsn == rr.last_lsn, "shared LSN watermark"


def test_wal_replay_key_range_filters_rows(tmp_path):
    from repro.wal import WriteAheadLog

    wal = WriteAheadLog(str(tmp_path))
    wal.append_commit(np.full(3, int(OpKind.INSERT), np.int8),
                      np.array([10, 20, 30], KEYS),
                      np.array([1, 2, 3], VALS))
    wal.append_commit(np.full(2, int(OpKind.INSERT), np.int8),
                      np.array([100, 200], KEYS), np.array([4, 5], VALS))
    recs = list(wal.replay(key_lo=15, key_hi=35))
    assert len(recs) == 1, "records left empty by the filter are skipped"
    assert recs[0].keys.tolist() == [20, 30]
    assert recs[0].vals.tolist() == [2, 3]
    assert [r.lsn for r in wal.replay()] == [1, 2], "unfiltered unchanged"
    wal.close()


# --------------------------------------------------------------- driver modes
def test_driver_multi_stream_closed_loop():
    from repro.workloads.driver import run_multi_workload

    wls = [make_workload("insert-heavy", n_ops=300, preload=64,
                         key_space=1 << 14, seed=0),
           make_workload("delete-churn", n_ops=300, preload=64,
                         key_space=1 << 14, seed=1)]
    eng = make_engine("nbtree", f=3, sigma=128)
    rep = run_multi_workload(eng, wls)
    assert len(rep["streams"]) == 2
    for s in rep["streams"]:
        assert s["per_kind"], "per-stream histograms present"
        assert sum(h["count"] for h in s["per_kind"].values()) == 300
    # namespaces are disjoint, so per-stream live pairs sum to the total
    assert sum(s["live_pairs"] for s in rep["streams"]) \
        == len(eng.dump_live()[0])


def test_driver_multi_stream_open_loop():
    from repro.workloads.driver import SCHEMA_VERSION, run_open_multi_workload

    wls = [make_workload("insert-heavy", n_ops=200, preload=64,
                         key_space=1 << 14, seed=0),
           make_workload("insert-heavy", n_ops=200, preload=64,
                         key_space=1 << 14, seed=1)]
    eng = make_engine("nbtree", f=3, sigma=128)
    rep = run_open_multi_workload(eng, wls, arrival="poisson", rate=20_000.0,
                                  weights=[2.0, 1.0])
    assert rep["schema_version"] == SCHEMA_VERSION
    ol = rep["open_loop"]
    assert ol["fair"] is True
    assert set(ol["tenants"]) == {"0", "1"}
    assert ol["tenants"]["0"]["weight"] == 2.0
    assert ol["n_done"] == 400


def test_driver_cli_multi_mix(tmp_path, capsys, monkeypatch):
    from repro.workloads import driver

    # the CLI places JAX's compile cache; keep it out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    out = tmp_path / "multi.json"
    driver.main(["--engines", "nbtree", "--mix", "insert-heavy",
                 "--mix", "point-read-heavy", "--ops", "200", "--batch",
                 "64", "--preload", "64", "--out", str(out)])
    import json
    data = json.loads(out.read_text())
    assert data["mix"] == ["insert-heavy", "point-read-heavy"]
    assert len(data["reports"][0]["streams"]) == 2
    assert "stream 1" in capsys.readouterr().out
