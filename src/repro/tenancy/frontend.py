"""Multi-tenant serving front door (DESIGN.md §10).

:class:`MultiTenantFrontend` serves several tenants' open-loop arrival
traces against ONE shared storage engine through the serving loop of
:mod:`repro.ingest.frontend` — the single-engine commit seam, with the
tenants' own admission, keyspaces and reports:

* each tenant's keys are rewritten into its :class:`NamespaceMap`
  interval, so one engine (any tier, sharded included) holds every
  namespace with zero cross-tenant key collisions and per-tenant RANGE
  stays a contiguous scan;
* admission runs through a :class:`WeightedFairQueue` — per-tenant
  bounded queues, per-tenant shed accounting, deficit-round-robin pick —
  so an aggressor overflows *its own* queue instead of starving
  co-tenants (``fair=False`` swaps back the single shared FIFO, the
  noisy-neighbor baseline the tenancy benchmark measures against);
* one :class:`~repro.ingest.slo.SLOTracker` runs per tenant beside the
  aggregate, all at the run's ``stall_factor``, and each tenant's report
  carries its own p99.9 and an SLO verdict against its target;
* encoded keys carry tenant identity into the shared WAL, so
  ``repro.wal.recovery.recover`` restores every namespace at once and
  ``key_range=namespace.tenant_interval(tid)`` restores exactly one;
* :meth:`pin_snapshot` freezes a cross-shard-consistent read view at the
  current commit watermark (``repro.tenancy.snapshots``) that stays
  valid while ingest and emptying cascades proceed underneath.

On sim tiers the whole multi-tenant run is a pure function of (traces,
tenant configs, engine config) — byte-identical reports across runs.
"""
from __future__ import annotations

import dataclasses

from repro.core.engine_api import OpBatch, StorageEngine
from repro.ingest.frontend import (DurabilityConfig, FrontendConfig,
                                   IngestFrontend, SharedFifo,
                                   offered_by_kind, serve)
from repro.ingest.slo import SLOTracker
from repro.wal.faults import FaultInjector

from .fair_queue import WeightedFairQueue
from .namespace import NamespaceMap
from .snapshots import SnapshotManager


@dataclasses.dataclass(frozen=True)
class TenantConfig:
    """One tenant's identity, fair-share weight, bound and SLO target."""

    tenant_id: int
    name: str = ""
    weight: float = 1.0            # DRR share relative to peers
    max_queue: int = 4096          # this tenant's own admission bound (ops)
    #: insert end-to-end p99.9 target in seconds (None = no target); the
    #: per-tenant report carries ``slo.met`` against it.
    slo_p999_s: float | None = None

    def __post_init__(self):
        assert self.tenant_id >= 0 and self.weight > 0 and self.max_queue >= 1
        assert self.slo_p999_s is None or self.slo_p999_s > 0

    @property
    def label(self) -> str:
        return self.name or f"tenant{self.tenant_id}"


class MultiTenantFrontend(IngestFrontend):
    """Serve several tenants' traces on one engine; see module docstring.

    The durability plumbing (WAL group commit, periodic checkpoints,
    crash points, ``acked`` oracle) is inherited verbatim — a multi-tenant
    commit is just a group commit whose keys happen to span namespaces.
    """

    def __init__(self, engine: StorageEngine, tenants: list,
                 config: FrontendConfig | None = None,
                 durability: DurabilityConfig | None = None,
                 injector: FaultInjector | None = None, *,
                 namespace: NamespaceMap | None = None, fair: bool = True,
                 obs=None):
        super().__init__(engine, config, durability, injector, obs=obs)
        assert tenants, "at least one tenant required"
        self.tenants = {int(t.tenant_id): t for t in tenants}
        assert len(self.tenants) == len(tenants), "duplicate tenant ids"
        self.namespace = namespace or NamespaceMap()
        for t in tenants:
            self.namespace._check_tenant(t.tenant_id)
        self.fair = bool(fair)
        if self.fair:
            # quantum = commit size: one round's credit for a weight-1
            # tenant is one full commit — the finest granularity at which
            # the server can reorder service anyway.
            self.queue = WeightedFairQueue(quantum=self.config.commit_ops)
        else:
            self.queue = SharedFifo(self.config.max_queue)
        for t in tenants:
            self.queue.add_tenant(t.tenant_id, weight=t.weight,
                                  max_queue=t.max_queue)
        self.snapshots = SnapshotManager(engine)

    # ------------------------------------------------------------- snapshots
    def pin_snapshot(self, tenant_id: int | None = None,
                     now_s: float = 0.0):
        """Freeze a consistent read view at the current commit watermark.

        Call on a group-commit boundary (e.g. from ``run``'s ``on_commit``
        callback, or before/after ``run``).  ``tenant_id`` scopes the view
        to that namespace's interval; None pins the whole keyspace.  The
        watermark is the durable commit LSN when a WAL is attached, else
        the commit ordinal — either way the applied prefix the view equals.
        """
        wm = self.last_acked_lsn if self._wal is not None else self._n_commits
        kr = None if tenant_id is None \
            else self.namespace.tenant_interval(tenant_id)
        return self.snapshots.pin(wm, now_s, key_range=kr)

    # ---------------------------------------------------------------- serving
    def take(self, queue, max_ops: int, t_commit: float) -> list:
        """Take a commit's ops; under DRR a tenant with backlog that got
        zero slots was deferred by the scheduler — the throttle event."""
        take = queue.take(max_ops)
        if self.tracer is not None and self.fair:
            served = {tid for tid, _ in take}
            for tid in self.tenants:
                if tid not in served and queue.backlog(tid) > 0:
                    self.tracer.instant("tenant_throttle", "drr_defer",
                                        t_commit, tenant=int(tid),
                                        backlog=int(queue.backlog(tid)))
        return take

    def run(self, traces: dict, *, drain: bool = True,
            on_commit=None) -> dict:
        """Serve every tenant's :class:`ArrivalTrace`; JSON-ready report.

        ``traces`` maps tenant id -> trace in that tenant's *local*
        keyspace (encoding is this frontend's job).  ``on_commit``, if
        given, is called as ``on_commit(frontend, t_commit)`` after every
        group commit fully lands — a commit boundary, i.e. a legal instant
        to :meth:`pin_snapshot` (how the differential snapshot tests drive
        pins mid-run, cascades still pending).
        """
        cfg, eng, ns = self.config, self.engine, self.namespace
        assert set(traces) == set(self.tenants), \
            "traces and tenant configs must cover the same tenant ids"
        tids = sorted(self.tenants)
        # encode every tenant's preload and ops into its namespace up front
        # — one vectorized pass per tenant, so the per-commit gather stays
        # index arithmetic.
        enc = {tid: dataclasses.replace(
            tr, preload=ns.encode_batch(tid, tr.preload),
            ops=ns.encode_batch(tid, tr.ops)) for tid, tr in traces.items()}
        self._load(OpBatch.concat([enc[tid].preload for tid in tids]))
        trackers = {tid: SLOTracker(stall_factor=cfg.stall_factor)
                    for tid in self.tenants}
        report, t_end = serve(enc, self.queue, self, trackers=trackers,
                              on_commit=on_commit, drain=drain)
        report["fair"] = self.fair
        report["namespace"] = ns.describe()
        report["admission"] = self.queue.stats()
        report["snapshots"] = self.snapshots.stats()
        tenants_out = {}
        for tid in tids:
            tc = self.tenants[tid]
            sub = trackers[tid].report(
                offered=offered_by_kind(traces[tid].ops.kinds), t_end=t_end)
            lo, hi = ns.tenant_interval(tid)
            p999 = float(sub["per_kind_e2e"].get("insert", {})
                         .get("p999_s", 0.0))
            tenants_out[str(tid)] = {
                "name": tc.label, "weight": tc.weight,
                "interval": [int(lo), int(hi)],
                "live_pairs": int(eng.count_live_range(lo, hi)),
                "slo": {"p999_target_s": tc.slo_p999_s,
                        "observed_insert_p999_s": p999,
                        "met": (None if tc.slo_p999_s is None
                                else bool(p999 <= tc.slo_p999_s))},
                "open_loop": sub,
            }
        report["tenants"] = tenants_out
        if self._wal is not None:
            report["durability"] = self._durability_report()
        return report


def run_multi_tenant(engine: StorageEngine, tenants: list, traces: dict, *,
                     config: FrontendConfig | None = None,
                     durability: DurabilityConfig | None = None,
                     namespace: NamespaceMap | None = None,
                     fair: bool = True, obs=None) -> dict:
    """One-call harness: serve every tenant's trace, full JSON report."""
    fe = MultiTenantFrontend(engine, tenants, config, durability,
                             namespace=namespace, fair=fair, obs=obs)
    ol = fe.run(traces)
    stats = engine.stats()
    return {
        "engine": engine.name,
        "tenants": {str(t.tenant_id):
                    {"name": t.label, "weight": t.weight,
                     "arrival": dict(traces[t.tenant_id].arrival),
                     "n_ops": len(traces[t.tenant_id])}
                    for t in tenants},
        "open_loop": ol,
        "stats": dataclasses.asdict(stats),
    }
