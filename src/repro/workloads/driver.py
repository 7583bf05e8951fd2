"""Workload driver: stream any mix through any registered engine.

``run_workload(engine, workload)`` applies the preload then the mixed
stream batch by batch, calling ``engine.maintain(budget)`` between batches
(the serving-loop deamortization knob), and records per-op latencies into
per-kind :class:`LatencyHistogram`s.  The report carries p50/p99/p100/mean
per kind, the histogram buckets, and the engine's final ``stats()``
snapshot — everything ``benchmarks/fig_mixed.py`` and the CI smoke job
need, in JSON-ready form.

CLI (used by the CI benchmark-smoke job)::

    PYTHONPATH=src python -m repro.workloads.driver \
        --engines all --mix ycsb-a --ops 512 --batch 64 --out runs/mixed.json

``--shards N`` (N > 1) wraps every requested engine in the sharded layer
(``sharded:<name>``, DESIGN.md §6) with ``--partition`` choosing range or
hash placement.  ``--arrival poisson --rate R [--duration T]`` switches
from closed-loop (service time only) to *open-loop* serving through the
ingest frontend (``repro.ingest``, DESIGN.md §7): timestamped arrivals,
bounded queue + admission control, group commit, end-to-end latency =
queueing + service.  ``--list-engines`` / ``--list-mixes`` enumerate the
registries.  Emitted JSON carries ``schema_version`` (top level and per
report) so bench trajectory files are comparable across PRs.

**Multiple streams** (DESIGN.md §10): repeat ``--mix`` to drive one
stream *per tenant*, each namespace-encoded into its own key interval
(``repro.tenancy``) and reported with its own per-stream latency
histograms.  Closed-loop, the streams interleave round-robin batch by
batch; with ``--arrival`` they serve open-loop through the multi-tenant
frontend (weighted-fair admission; ``--weights`` sets DRR shares,
``--unfair`` swaps back the shared FIFO baseline)::

    PYTHONPATH=src python -m repro.workloads.driver --engines nbtree \
        --mix insert-heavy --mix point-read-heavy --weights 2 1 \
        --arrival poisson --rate 4000 --out runs/two_tenants.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np

from repro.compile_cache import place_compile_cache
from repro.core.engine_api import (FIVE_TIERS, OpKind, StorageEngine,
                                   available_engines, make_engine)
from repro.obs.metrics import BUCKET_EDGES_S, LogBucketHistogram, ObsConfig

from .generator import MIXES, Workload, make_workload

#: bump when the emitted JSON layout changes (stamped into every report so
#: trajectory files from different PRs are comparable — or visibly not).
#: v3: EngineStats bloom_* counters; open-loop (``--arrival``) reports.
#: v4: EngineStats maintain-unit wall-clock fields (units, total,
#: p50/p99/p100 per unit) — real device-tier maintenance service cost.
#: v5: EngineStats.applied_lsn; open-loop reports gain a ``durability``
#: section (WAL/checkpoint counters + charged fsync service) when the
#: frontend runs with a DurabilityConfig (DESIGN.md §9).
#: v6: multi-stream reports (repeated ``--mix``): closed-loop ``streams``
#: sections with per-stream per-kind histograms + namespace intervals;
#: open-loop multi-tenant reports (``tenants``/``admission``/``fair``
#: sections from the tenancy frontend, DESIGN.md §10).
#: v7: closed-loop per-kind histograms switch to the shared bounded
#: log-bucket implementation (``repro.obs.metrics``): same bucket edges
#: and JSON shape, count/mean/p100 still exact, but p50/p99 are now
#: bucket-interpolated (within one bucket of the exact sample quantile)
#: instead of exact-sample percentiles; open-loop reports gain an ``obs``
#: section (windowed timeline + stall attribution + trace block) when
#: driven with ``--trace``/``--metrics-window`` (DESIGN.md §11).
#: v8: replicated open-loop reports (``--replicas``): top-level SLO report
#: plus a ``replication`` section — ReplicationConfig, acked commit/row
#: counts, failover event list (detection/promotion/RTO timestamps),
#: per-group availability timelines, and the chaos schedule when
#: ``--chaos`` is set (DESIGN.md §12).
SCHEMA_VERSION = 8


class LatencyHistogram:
    """Bounded log-bucket latency histogram (per-kind driver reports).

    A thin façade over the shared :class:`repro.obs.metrics.
    LogBucketHistogram`: 4 buckets/decade across 1 ns .. ~1000 s,
    out-of-range samples clamped into the edge buckets (zero-cost ops —
    e.g. buffered sim-tier inserts — land in the first bucket) so
    ``sum(bucket_counts) == count`` always holds.  Memory is O(buckets),
    not O(samples): count, mean, and p100 stay exact, while p50/p99 are
    interpolated within the owning bucket (within one bucket width of the
    exact sample quantile — property-tested in ``tests/test_obs.py``).
    """

    EDGES = BUCKET_EDGES_S                  # seconds

    def __init__(self):
        self._h = LogBucketHistogram()

    @property
    def count(self) -> int:
        return self._h.count

    def add(self, latencies_s) -> None:
        self._h.add_many(np.asarray(latencies_s, np.float64))

    def percentile(self, q: float) -> float:
        """Quantile at ``q`` in [0, 100]; exact at q=0 and q=100."""
        return self._h.quantile(q / 100.0)

    def to_dict(self) -> dict:
        s = self._h.summary()
        del s["p999_s"]         # per-kind blocks predate the p99.9 field
        return s


def run_workload(engine: StorageEngine, workload: Workload, *,
                 maintain_budget: int = 1) -> dict:
    """Drive ``workload`` through ``engine``; returns the JSON-ready report."""
    spec = workload.spec
    hists = {k: LatencyHistogram() for k in OpKind}

    pre = workload.preload_batch()
    engine.apply(pre)
    engine.drain()
    io_after_preload = engine.io_time_s()

    max_debt = 0
    for batch in workload.batches():
        res = engine.apply(batch)
        for k in OpKind:
            hists[k].add(res.latencies(k))
        max_debt = max(max_debt, engine.maintain(maintain_budget))
    debt_before_drain = engine.maintain(0)
    engine.drain()

    stats = engine.stats()
    return {
        "schema_version": SCHEMA_VERSION,
        "engine": engine.name,
        "workload": dataclasses.asdict(spec) | {
            "mix": {OpKind(k).name.lower(): p for k, p in spec.mix.items()}},
        "maintain_budget": maintain_budget,
        "preload_pairs": len(pre),
        "io_time_preload_s": io_after_preload,
        "max_pending_debt": int(max_debt),
        "pending_debt_before_drain": int(debt_before_drain),
        "per_kind": {OpKind(k).name.lower(): h.to_dict()
                     for k, h in hists.items() if h.count},
        "stats": dataclasses.asdict(stats),
    }


def run_open_workload(engine: StorageEngine, workload: Workload, *,
                      arrival: str, rate: float,
                      duration_s: float | None = None,
                      maintain_budget: int = 1,
                      frontend_config=None,
                      obs: ObsConfig | None = None,
                      chaos_spec: str | None = None) -> dict:
    """Open-loop counterpart of :func:`run_workload` (DESIGN.md §7).

    Timestamps ``workload``'s op stream with the named arrival process and
    serves it through the ingest frontend; the report mirrors the
    closed-loop shape with the SLO section under ``"open_loop"``.
    ``maintain_budget`` (the per-commit deamortization knob) shapes the
    default frontend config; an explicit ``frontend_config`` wins
    wholesale.  ``obs`` (DESIGN.md §11) adds a windowed-metrics timeline,
    stall attribution, and a structured span trace under ``report["obs"]``.
    ``chaos_spec`` (DESIGN.md §12) schedules faults against the frontend
    itself — the DSL's default target ``"wal"``.
    """
    from repro.ingest import (FrontendConfig, make_arrivals, make_trace,
                              run_open_loop)
    from repro.wal import FaultSchedule

    if frontend_config is None:
        frontend_config = FrontendConfig(maintain_budget=maintain_budget)
    process = make_arrivals(arrival, rate)
    trace = make_trace(workload, process, duration_s=duration_s)
    chaos = FaultSchedule.parse(chaos_spec) if chaos_spec else None
    report = run_open_loop(engine, trace, config=frontend_config, obs=obs,
                           chaos=chaos)
    report["schema_version"] = SCHEMA_VERSION
    report["workload"] = dataclasses.asdict(workload.spec) | {
        "mix": {OpKind(k).name.lower(): p
                for k, p in workload.spec.mix.items()}}
    return report


def run_multi_workload(engine: StorageEngine, workloads: list, *,
                       maintain_budget: int = 1, namespace=None) -> dict:
    """Closed-loop multi-stream drive: one namespace per workload.

    Stream *i*'s keys are encoded into tenant *i*'s interval
    (``repro.tenancy.NamespaceMap``) and the streams interleave
    round-robin batch by batch — deterministic contention on one shared
    engine — with latencies recorded into per-stream per-kind histograms.
    """
    from repro.core.engine_api import OpBatch
    from repro.tenancy import NamespaceMap

    ns = namespace or NamespaceMap()
    pre = [ns.encode_batch(i, wl.preload_batch())
           for i, wl in enumerate(workloads)]
    pre = [b for b in pre if len(b)]
    n_pre = sum(len(b) for b in pre)
    if pre:
        engine.apply(OpBatch.concat(pre))
        engine.drain()

    hists = [{k: LatencyHistogram() for k in OpKind} for _ in workloads]
    iters = [wl.batches() for wl in workloads]
    alive = list(range(len(workloads)))
    max_debt = 0
    while alive:
        for i in list(alive):
            batch = next(iters[i], None)
            if batch is None:
                alive.remove(i)
                continue
            res = engine.apply(ns.encode_batch(i, batch))
            for k in OpKind:
                hists[i][k].add(res.latencies(k))
            max_debt = max(max_debt, engine.maintain(maintain_budget))
    debt_before_drain = engine.maintain(0)
    engine.drain()

    stats = engine.stats()
    streams = []
    for i, wl in enumerate(workloads):
        lo, hi = ns.tenant_interval(i)
        streams.append({
            "stream": i,
            "workload": dataclasses.asdict(wl.spec) | {
                "mix": {OpKind(k).name.lower(): p
                        for k, p in wl.spec.mix.items()}},
            "interval": [int(lo), int(hi)],
            "live_pairs": int(engine.count_live_range(lo, hi)),
            "per_kind": {OpKind(k).name.lower(): h.to_dict()
                         for k, h in hists[i].items() if h.count},
        })
    return {
        "schema_version": SCHEMA_VERSION,
        "engine": engine.name,
        "namespace": ns.describe(),
        "maintain_budget": maintain_budget,
        "preload_pairs": n_pre,
        "max_pending_debt": int(max_debt),
        "pending_debt_before_drain": int(debt_before_drain),
        "streams": streams,
        "stats": dataclasses.asdict(stats),
    }


def run_open_multi_workload(engine: StorageEngine, workloads: list, *,
                            arrival: str, rate: float,
                            duration_s: float | None = None,
                            maintain_budget: int = 1, weights=None,
                            fair: bool = True,
                            obs: ObsConfig | None = None) -> dict:
    """Open-loop multi-stream drive through the multi-tenant frontend.

    One tenant per workload; every tenant gets its own instance of the
    named arrival process at ``rate`` (its trace seeded by its workload
    seed, so streams stay independent).  ``weights`` sets the DRR shares
    (default: equal); ``fair=False`` is the shared-FIFO baseline.
    """
    from repro.ingest import FrontendConfig, make_arrivals, make_trace
    from repro.tenancy import TenantConfig, run_multi_tenant

    tenants = [TenantConfig(i, name=wl.spec.name,
                            weight=(float(weights[i]) if weights else 1.0))
               for i, wl in enumerate(workloads)]
    traces = {i: make_trace(wl, make_arrivals(arrival, rate),
                            duration_s=duration_s)
              for i, wl in enumerate(workloads)}
    cfg = FrontendConfig(maintain_budget=maintain_budget)
    report = run_multi_tenant(engine, tenants, traces, config=cfg, fair=fair,
                              obs=obs)
    report["schema_version"] = SCHEMA_VERSION
    report["workloads"] = [
        dataclasses.asdict(wl.spec) | {
            "mix": {OpKind(k).name.lower(): p
                    for k, p in wl.spec.mix.items()}}
        for wl in workloads]
    return report


def run_replicated_workload(engine_name: str, workload: Workload, *,
                            arrival: str, rate: float,
                            duration_s: float | None = None,
                            groups: int = 4, replicas: int = 2,
                            ack_mode: str = "quorum",
                            chaos_spec: str | None = None,
                            maintain_budget: int = 1,
                            obs: ObsConfig | None = None,
                            directory: str | None = None,
                            base_kw: dict | None = None) -> dict:
    """Replicated open loop (DESIGN.md §12): R WAL-shipped copies per range.

    Serves the open-loop trace through :class:`repro.replication.
    ReplicatedFrontend` — ``groups`` range partitions, each a primary plus
    ``replicas - 1`` replicas acking at ``ack_mode`` ("quorum" or
    "primary").  ``chaos_spec`` is the ``--chaos`` DSL
    (``kind@t[:target[:arg[:dur]]]`` joined with ``;``, see
    :meth:`repro.wal.FaultSchedule.parse`); the report gains a
    ``"replication"`` section with failover events and per-group
    availability timelines.  WAL segment directories live under
    ``directory`` (a temp dir when None).
    """
    import tempfile

    from repro.ingest import FrontendConfig, make_arrivals, make_trace
    from repro.replication import ReplicationConfig, run_replicated
    from repro.wal import FaultSchedule

    def factory():
        return make_engine(engine_name, **(base_kw or {}))

    process = make_arrivals(arrival, rate)
    trace = make_trace(workload, process, duration_s=duration_s)
    chaos = FaultSchedule.parse(chaos_spec) if chaos_spec else None
    rep = ReplicationConfig(replicas=replicas, ack_mode=ack_mode)
    cfg = FrontendConfig(maintain_budget=maintain_budget)
    if directory is None:
        with tempfile.TemporaryDirectory(prefix="repro_repl_") as d:
            report = run_replicated(factory, trace, d, groups=groups,
                                    replication=rep, config=cfg,
                                    chaos=chaos, obs=obs)
    else:
        report = run_replicated(factory, trace, directory, groups=groups,
                                replication=rep, config=cfg,
                                chaos=chaos, obs=obs)
    report["schema_version"] = SCHEMA_VERSION
    report["workload"] = dataclasses.asdict(workload.spec) | {
        "mix": {OpKind(k).name.lower(): p
                for k, p in workload.spec.mix.items()}}
    return report


# ---------------------------------------------------------------- CLI harness
_SMALL_CONFIGS = {
    # tiny-footprint constructor kwargs for smoke runs (CI, demos).
    "nbtree": dict(f=3, sigma=1024),
    "nbtree-basic": dict(f=3, sigma=1024),
    "nbtree-nobloom": dict(f=3, sigma=1024),
    "lsm": dict(mem_pairs=1024),
    "blsm": dict(mem_pairs=1024),
    "btree": {},
    "bepsilon": dict(node_bytes=1 << 16, cached_levels=1),
    "jax-nbtree": dict(f=4, sigma=512, max_nodes=256),
}


def _resolve_engine_names(engines, parser: argparse.ArgumentParser) -> tuple:
    """'all' -> the five paper tiers; anything unknown is a clean CLI error."""
    if engines == ["all"]:
        return FIVE_TIERS
    known = set(available_engines())
    bad = [n for n in engines if n not in known]
    if bad:
        parser.error(f"unknown engine(s): {', '.join(sorted(bad))}; "
                     f"registered: {', '.join(available_engines())} "
                     "(--list-engines to enumerate)")
    return tuple(engines)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engines", nargs="+", default=["all"],
                    help="engine names, or 'all' for the five paper tiers "
                         f"({', '.join(FIVE_TIERS)}); see --list-engines")
    ap.add_argument("--list-engines", action="store_true",
                    help="print the registered engine names and exit")
    ap.add_argument("--list-mixes", action="store_true",
                    help="print the named workload mixes and exit")
    ap.add_argument("--mix", action="append", choices=sorted(MIXES),
                    help="workload mix; repeat for one stream per tenant "
                         "(multi-stream mode, DESIGN.md §10). Default: ycsb-a")
    ap.add_argument("--weights", nargs="+", type=float, default=None,
                    help="multi-stream fair-share weights, one per --mix")
    ap.add_argument("--unfair", action="store_true",
                    help="multi-stream open loop: shared-FIFO baseline "
                         "instead of weighted-fair admission")
    ap.add_argument("--ops", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--preload", type=int, default=2048)
    ap.add_argument("--key-space", type=int, default=1 << 20)
    ap.add_argument("--dist", choices=("uniform", "zipfian", "hotspot"),
                    default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="workload stream seed (same seed -> same op stream)")
    ap.add_argument("--maintain-budget", type=int, default=1)
    ap.add_argument("--shards", type=int, default=1,
                    help="N > 1 wraps each engine as sharded:<name> with N "
                         "range-partitioned shards (DESIGN.md §6)")
    ap.add_argument("--partition", choices=("range", "hash"), default="range")
    ap.add_argument("--arrival", choices=("poisson", "mmpp", "diurnal"),
                    default=None,
                    help="open-loop mode: serve through the ingest frontend "
                         "with this arrival process (DESIGN.md §7)")
    ap.add_argument("--replicas", type=int, default=0, metavar="R",
                    help="replicated open loop (DESIGN.md §12): R WAL-"
                         "shipped copies per range partition (--shards sets "
                         "the group count); needs --arrival")
    ap.add_argument("--ack", choices=("quorum", "primary"), default="quorum",
                    help="replicated ack mode: wait for a majority of "
                         "copies (quorum, default) or the primary's fsync "
                         "only (faster, loses acked tail on failover)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="fault-injection schedule for the open loop: "
                         "';'-joined kind@t[:target[:arg[:dur]]] events, "
                         "kinds crash|fsync_stall|latency_spike|"
                         "torn_segment|bit_flip; with --replicas, targets "
                         "like g0/primary, g1/r0, g2 (group-wide); without, "
                         "the default target 'wal' hits the single-engine "
                         "frontend; e.g. 'crash@0.05:"
                         "g0/primary;latency_spike@0.1:g1:8:0.05'")
    ap.add_argument("--rate", type=float, default=10_000.0,
                    help="open-loop offered rate, ops/second (poisson/"
                         "diurnal mean; mmpp burst rate)")
    ap.add_argument("--duration", type=float, default=None,
                    help="open-loop trace window in seconds (default: the "
                         "full --ops stream)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="open-loop mode: save a Chrome trace_event JSON "
                         "of frontend spans here (load in Perfetto / "
                         "chrome://tracing; DESIGN.md §11)")
    ap.add_argument("--metrics-window", type=float, default=None,
                    metavar="SECONDS",
                    help="open-loop mode: windowed-metrics timeline width "
                         "in sim seconds (enables the report's 'obs' "
                         "section; implied 1.0 when --trace is set)")
    ap.add_argument("--out", default="runs/driver_report.json",
                    help="write the JSON report here")
    args = ap.parse_args(argv)

    if args.list_engines:
        for name in available_engines():
            print(name)
        print("sharded:<base>  (any of the above via --shards N)")
        return
    if args.list_mixes:
        for name in sorted(MIXES):
            kinds = {OpKind(k).name.lower(): p for k, p in MIXES[name].items()}
            print(f"{name}: {kinds}")
        return

    names = _resolve_engine_names(args.engines, ap)
    mixes = args.mix or ["ycsb-a"]
    if args.weights is not None and len(args.weights) != len(mixes):
        ap.error("--weights needs exactly one value per --mix")
    if args.chaos and not args.arrival:
        ap.error("--chaos needs open-loop mode (--arrival; replicated "
                 "targets additionally need --replicas R)")
    if args.replicas:
        if not args.arrival:
            ap.error("--replicas needs open-loop mode (--arrival)")
        if len(mixes) > 1:
            ap.error("--replicas runs a single stream (one --mix)")
    obs = None
    if args.trace or args.metrics_window is not None:
        if not args.arrival:
            ap.error("--trace/--metrics-window need open-loop mode "
                     "(--arrival)")
        if len(names) > 1 and args.trace:
            ap.error("--trace needs a single --engines value (one trace "
                     "file per run)")
        obs = ObsConfig(window_s=args.metrics_window or 1.0,
                        trace_path=args.trace)
    overrides = dict(n_ops=args.ops, batch_size=args.batch,
                     preload=args.preload, key_space=args.key_space,
                     seed=args.seed)
    if args.dist:
        overrides["dist"] = args.dist

    place_compile_cache()
    reports = []
    for name in names:
        base_kw = _SMALL_CONFIGS.get(name, {})
        if args.shards > 1:
            engine = make_engine(f"sharded:{name}", shards=args.shards,
                                 partition=args.partition, **base_kw)
        else:
            engine = make_engine(name, **base_kw)
        if len(mixes) > 1:
            # one stream per mix, each in its own namespace; decorrelate
            # stream seeds the same way the scenario catalog does.
            workloads = [make_workload(m, **overrides
                                       | {"seed": args.seed * 1000 + i})
                         for i, m in enumerate(mixes)]
            if args.arrival:
                report = run_open_multi_workload(
                    engine, workloads, arrival=args.arrival, rate=args.rate,
                    duration_s=args.duration,
                    maintain_budget=args.maintain_budget,
                    weights=args.weights, fair=not args.unfair, obs=obs)
                reports.append(report)
                ol = report["open_loop"]
                print(f"{engine.name:>14} ({report['stats']['clock']}) "
                      f"{len(mixes)} streams +{args.arrival}@{args.rate:g}/s "
                      f"fair={ol['fair']}: shed={ol['n_shed']} "
                      f"util={ol['server']['utilization']:.2f}")
                for tid, t in sorted(ol["tenants"].items()):
                    sub = t["open_loop"]
                    ins = sub["per_kind_e2e"].get("insert", {})
                    print(f"    stream {tid} ({t['name']}, w={t['weight']:g})"
                          f": done={sub['n_done']} shed={sub['n_shed']} "
                          f"insert p99.9={ins.get('p999_s', 0)*1e3:.3f}ms "
                          f"live={t['live_pairs']}")
            else:
                report = run_multi_workload(
                    engine, workloads, maintain_budget=args.maintain_budget)
                reports.append(report)
                print(f"{engine.name:>14} ({report['stats']['clock']}) "
                      f"{len(mixes)} streams closed-loop: "
                      f"pairs={report['stats']['total_pairs']}")
                for s in report["streams"]:
                    line = " ".join(
                        f"{kind}[p50={h['p50_s']*1e3:.3f}ms "
                        f"p99={h['p99_s']*1e3:.3f}ms]"
                        for kind, h in s["per_kind"].items())
                    print(f"    stream {s['stream']} "
                          f"({s['workload']['name']}): {line} "
                          f"live={s['live_pairs']}")
            continue
        workload = make_workload(mixes[0], **overrides)
        if args.replicas:
            report = run_replicated_workload(
                name, workload, arrival=args.arrival, rate=args.rate,
                duration_s=args.duration, groups=max(1, args.shards),
                replicas=args.replicas, ack_mode=args.ack,
                chaos_spec=args.chaos,
                maintain_budget=args.maintain_budget, obs=obs,
                base_kw=base_kw)
            reports.append(report)
            rep = report["replication"]
            ins = report["per_kind_e2e"].get("insert", {})
            down = sum(a["downtime_s"] for a in rep["availability"])
            print(f"{name:>14} R={args.replicas}/{args.ack} "
                  f"x{rep['n_groups']} groups {mixes[0]}+{args.arrival}"
                  f"@{args.rate:g}/s: done={report['n_done']} "
                  f"shed={report['n_shed']} acked={rep['acked_commits']} "
                  f"failovers={len(rep['failovers'])} "
                  f"downtime={down*1e3:.1f}ms "
                  f"insert p99.9={ins.get('p999_s', 0)*1e3:.3f}ms")
            continue
        if args.arrival:
            report = run_open_workload(engine, workload,
                                       arrival=args.arrival, rate=args.rate,
                                       duration_s=args.duration,
                                       maintain_budget=args.maintain_budget,
                                       obs=obs, chaos_spec=args.chaos)
            reports.append(report)
            ol = report["open_loop"]
            ins = ol["per_kind_e2e"].get("insert", {})
            print(f"{engine.name:>14} ({report['stats']['clock']}) "
                  f"{mixes[0]}+{args.arrival}@{args.rate:g}/s: "
                  f"util={ol['server']['utilization']:.2f} "
                  f"shed={ol['n_shed']} "
                  f"e2e insert p50={ins.get('p50_s', 0)*1e3:.3f}ms "
                  f"p99.9={ins.get('p999_s', 0)*1e3:.3f}ms "
                  f"debt_max={ol['stalls']['debt_max']}")
            if obs is not None and "obs" in ol:
                ob = ol["obs"]
                print(f"    obs: {ob['n_windows']} windows "
                      f"stall_free={ob['stall_free_pct']:.1f}% "
                      f"fluctuation={ob['fluctuation_score']:.3f} "
                      f"trace_events={ob['trace']['events']}"
                      + (f" -> {args.trace}" if args.trace else ""))
            continue
        report = run_workload(engine, workload,
                              maintain_budget=args.maintain_budget)
        reports.append(report)
        pk = report["per_kind"]
        line = " ".join(
            f"{kind}[p50={h['p50_s']*1e3:.3f}ms p99={h['p99_s']*1e3:.3f}ms "
            f"p100={h['p100_s']*1e3:.3f}ms]" for kind, h in pk.items())
        print(f"{engine.name:>14} ({report['stats']['clock']}) {mixes[0]}: "
              f"{line} pairs={report['stats']['total_pairs']} "
              f"shards={report['stats']['shards']}")

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION,
                       "mix": mixes[0] if len(mixes) == 1 else list(mixes),
                       "seed": args.seed, "shards": args.shards,
                       "arrival": args.arrival,
                       "reports": reports}, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
