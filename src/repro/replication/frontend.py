"""Replicated open-loop frontend: partitioned serving with failover.

DESIGN.md §12: the key space is range-partitioned into replica groups
(:class:`~repro.replication.replica.ReplicaGroup`), each a primary +
R−1 replicas kept in sync by WAL shipping.  :class:`ReplicatedFrontend`
is the commit seam of the shared serving loop
(:func:`repro.ingest.frontend.serve`) for such an ensemble — one leg per
group an op touches — plus the failure machinery:

* **Heartbeats** — every live node beats the shared
  :class:`~repro.distributed.fault_tolerance.HeartbeatMonitor` at each
  loop tick (sim time, float).  A node silent past the timeout is
  declared dead exactly once; a dead primary triggers promotion, a dead
  replica a rebuild.
* **Graceful degradation** — ops routed to a group that cannot currently
  commit (dead primary awaiting detection, quorum short a replica,
  promotion replay in flight) are *parked*: retried with exponential
  backoff and shed at a deadline, while every other group keeps serving
  untouched — an unavailable range never head-of-line-blocks the rest.
* **Chaos** — a :class:`~repro.wal.faults.FaultSchedule` fires between
  commits against stable slot addresses (``g0/primary``, ``g1/r0``,
  ``g2`` for group-wide latency spikes), so runs under chaos stay a pure
  function of (trace, config, schedule seed).

Per-group :class:`~repro.obs.metrics.WindowedMetrics` timelines are
always on (they are the availability measurement: the failover benchmark
reads windowed p99.9 through a kill), and the report carries every
failover's RTO decomposition: crash → detected (heartbeat timeout) →
promoted (tail replay) → writes restored (quorum whole again).
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from repro.core.engine_api import OpBatch, OpKind
from repro.distributed.fault_tolerance import HeartbeatMonitor
from repro.ingest.arrivals import ArrivalTrace
from repro.ingest.frontend import (KIND_NAMES, WRITE_KINDS, CommitSeam,
                                   FrontendConfig, SharedFifo, rows_at,
                                   serve, write_rows)
from repro.obs.metrics import ObsConfig, WindowedMetrics
from repro.shard.partition import RangePartitioner
from repro.wal.faults import FaultSchedule

from .replica import ReplicaGroup, ReplicationConfig

_RANGE = int(OpKind.RANGE)


class ReplicatedFrontend(CommitSeam):
    """Open-loop serving over replicated range partitions; see module doc.

    It is the commit seam for replica groups: each commit runs one leg
    per group it touches, and ops whose range cannot commit are parked.
    """

    def __init__(self, engine_factory, directory: str, *, groups: int = 4,
                 replication: ReplicationConfig | None = None,
                 config: FrontendConfig | None = None,
                 chaos: FaultSchedule | None = None,
                 obs: ObsConfig | None = None,
                 window_s: float = 0.05, key_hi: int = 1 << 20):
        super().__init__(config, obs)
        self._factory = engine_factory
        self.dir = directory
        self.n_groups_requested = int(groups)
        self.rep = replication or ReplicationConfig()
        self.chaos = chaos
        self.window_s = float(window_s)
        self.key_hi = int(key_hi)
        self.monitor = HeartbeatMonitor(
            timeout=self.rep.heartbeat_timeout_s)
        self.partitioner: RangePartitioner | None = None
        self.groups: list[ReplicaGroup] = []
        self._node_of: dict = {}       # node_id -> (group, node)
        #: every acked group commit as ``(gid, lsn, kinds, keys, vals)`` in
        #: ack order — the chaos soak test's oracle feed (a write row is in
        #: here iff its quorum fsync returned, i.e. iff it was acked).
        self.acked: list = []
        self.shed_unavailable = 0
        self._parked: list[list] = []   # [i, next_t, backoff, deadline]

    # -------------------------------------------------------------- topology
    def _bootstrap(self, trace: ArrivalTrace) -> None:
        """Fix the routing table and spawn every group's initial nodes."""
        n = self.n_groups_requested
        ins = trace.ops.keys[np.asarray(trace.ops.kinds) == int(OpKind.INSERT)]
        if len(trace.preload):
            self.partitioner = RangePartitioner.from_sample(
                trace.preload.keys, n)
        elif len(ins) >= 2 * n:
            self.partitioner = RangePartitioner.from_sample(ins[:4096], n)
        else:
            self.partitioner = RangePartitioner.even(n, self.key_hi)
        for gid in range(self.partitioner.n_shards):
            lo, hi = self.partitioner.interval(gid)
            g = ReplicaGroup(gid, os.path.join(self.dir, f"g{gid}"),
                             self._factory, self.rep, key_lo=lo, key_hi=hi)
            self.groups.append(g)
            for node in g.nodes:
                self._register_node(g, node)
            if self.chaos is not None:
                for slot in ([f"g{gid}", f"g{gid}/primary"]
                             + [f"g{gid}/r{k}"
                                for k in range(self.rep.replicas - 1)]):
                    self.chaos.register(
                        slot, lambda ev, g=g, s=slot: g.handle_event(ev, s))
        if len(trace.preload):
            gids = self.partitioner.shard_of(trace.preload.keys)
            for gid, g in enumerate(self.groups):
                m = gids == gid
                if not m.any():
                    continue
                sub = OpBatch.inserts(trace.preload.keys[m],
                                      trace.preload.vals[m])
                for node in g.nodes:
                    node.engine.apply(sub)
                    node.engine.drain()

    def _register_node(self, group: ReplicaGroup, node) -> None:
        self._node_of[node.node_id] = (group, node)
        self.monitor.add_host(node.node_id)

    # ------------------------------------------------------------ event pump
    def _tick(self, now: float) -> None:
        """Advance all failure machinery to ``now`` (between commits)."""
        if self.chaos is not None:
            for ev in self.chaos.fire_due(now):
                if self.tracer is not None:
                    self.tracer.instant("chaos", ev.kind.value, ev.t,
                                        target=ev.target, arg=ev.arg)
        for g in self.groups:
            for node in g.nodes:
                if node.alive:
                    self.monitor.beat(node.node_id, now)
        for host in self.monitor.advance(now):
            g, node = self._node_of.get(host, (None, None))
            if g is None or g.failed or node not in g.nodes:
                continue
            if node is g.primary:
                g.promote(now)
            else:
                g.replace_replica(node, now)
        for g in self.groups:
            # corruption-diverged replicas (alive, out of sync): replace.
            for r in list(g.replicas()):
                if r.alive and not r.synced:
                    g.replace_replica(r, now)
            for rb in g.poll_rebuilds(now):
                self._register_node(g, rb["node"])
                self.monitor.revive(rb["node"].node_id, now)
                if self.tracer is not None:
                    self.tracer.complete(
                        "catchup", "rebuild", rb["t_start"],
                        now - rb["t_start"], gid=g.gid,
                        node=rb["node"].node_id,
                        snapshot_pairs=rb["snapshot_pairs"])
        # write-availability transitions close out failover RTOs.
        for g in self.groups:
            wa = g.write_available(now)
            if not wa and g.pending_down_t is None and not g.failed \
                    and (g.primary is None or not g.primary.alive):
                g.pending_down_t = now
            if wa and g.pending_down_t is not None:
                g.downtime_s += now - g.pending_down_t
                g.pending_down_t = None
                ev = g.failovers[-1] if g.failovers else None
                if ev is not None and ev["t_write_restored"] is None:
                    ev["t_write_restored"] = float(now)
                    ev["rto_s"] = float(now - ev["t_crash"])
                    if self.tracer is not None:
                        self.tracer.complete(
                            "failover", "primary_failover", ev["t_crash"],
                            ev["rto_s"], gid=g.gid,
                            new_primary=ev["new_primary"],
                            replayed_ops=ev["replayed_ops"])

    # --------------------------------------------------------------- routing
    def _route(self, kind, key, hi, point_gid) -> list[int]:
        if int(kind) == _RANGE:
            return list(self.partitioner.shards_for_range(int(key), int(hi)))
        return [int(point_gid)]

    def _gids_of(self, i: int) -> list[int]:
        ops = self._ops
        return self._route(ops.kinds[i], ops.keys[i], ops.his[i],
                           self._point_gid[i])

    def _admissible(self, i: int, now: float) -> bool:
        groups = [self.groups[gid] for gid in self._gids_of(i)]
        if int(self._ops.kinds[i]) in WRITE_KINDS:
            return all(g.write_available(now) for g in groups)
        return all(g.read_available(now) for g in groups)

    def _doomed(self, i: int) -> bool:
        """True when the op targets a permanently failed group."""
        return any(self.groups[gid].failed for gid in self._gids_of(i))

    def _shed_parked(self, i: int, now: float) -> None:
        kname = KIND_NAMES[int(self._ops.kinds[i])]
        self.tracker.record_shed(kname)
        self.shed_unavailable += 1
        for gid in self._gids_of(i):
            self._gwm[gid].record_shed(now)
        if self.tracer is not None:
            self.tracer.instant("shed", f"unavailable_{kname}", now)

    # ------------------------------------------------------------ commit seam
    @property
    def parked(self) -> int:
        return len(self._parked)

    def advance(self, now: float, queue) -> None:
        """Failure machinery up to ``now``, then retry every parked op due:
        shed it past its deadline, requeue it if its range is back, else
        back off exponentially."""
        self._tick(now)
        for p in list(self._parked):
            i, next_t, backoff, deadline = p
            if self._doomed(i) or (next_t <= now and now >= deadline):
                self._parked.remove(p)
                self._shed_parked(i, now)
            elif next_t <= now:
                if self._admissible(i, now):
                    self._parked.remove(p)
                    queue.requeue(0, i)
                else:
                    p[2] = min(backoff * 2, self.rep.retry_backoff_max_s)
                    p[1] = min(now + p[2], deadline)

    def wakeups(self, now: float) -> list:
        """Parked retries, chaos events, rebuild completions, promotion
        gates and heartbeat timeouts still pending."""
        cands = [p[1] for p in self._parked]
        if self.chaos is not None and self.chaos.next_time is not None:
            cands.append(self.chaos.next_time)
        for g in self.groups:
            cands.extend(rb["ready_at"] for rb in g.rebuilds)
            if g.write_blocked_until > now:
                cands.append(g.write_blocked_until)
            for node in g.nodes:
                if not node.alive and node.node_id not in self.monitor.dead:
                    beat = self.monitor.last_beat.get(node.node_id, 0.0)
                    cands.append(beat + self.monitor.timeout)
        return cands

    def take(self, queue, max_ops: int, t_commit: float) -> list:
        """Take admissible ops in order; park the rest (their range is
        down — the queue must not head-of-line-block other ranges)."""
        self._tick(t_commit)
        rep, take = self.rep, []
        while queue.backlog() and len(take) < max_ops:
            (tid, i), = queue.take(1)
            if self._doomed(i):
                self._shed_parked(i, t_commit)
            elif self._admissible(i, t_commit):
                take.append((tid, i))
            else:
                self._parked.append([i, t_commit + rep.retry_backoff_s,
                                     rep.retry_backoff_s,
                                     t_commit + rep.retry_deadline_s])
        return take

    def commit(self, batch: OpBatch, arrive: np.ndarray, t_commit: float,
               qdepth: int) -> tuple:
        """One leg per group touched: quorum commit and primary apply
        (scaled by the group's latency spike), then the leg's maintenance.
        The commit is done when its slowest leg is; maintenance is folded
        into each leg's service, so the loop is charged none separately."""
        cfg = self.config
        point = self.partitioner.shard_of(batch.keys)
        legs: dict[int, list[int]] = {}
        for pos in range(len(batch)):
            for gid in self._route(batch.kinds[pos], batch.keys[pos],
                                   batch.his[pos], point[pos]):
                legs.setdefault(gid, []).append(pos)
        done = np.full(len(batch), t_commit)
        leg_totals, debt_max = [], 0
        for gid, members in legs.items():
            g = self.groups[gid]
            sub = rows_at(batch, members)
            rows = write_rows(sub)
            wal_s = 0.0
            if len(rows[0]):
                lsn, wal_s = g.commit(*rows)
                self.acked.append((gid, lsn, *rows))
            res = g.apply_primary(sub)
            spike = g.spike(t_commit)
            op_service = np.asarray(res.latency_s, np.float64)
            leg_done = t_commit + spike * (wal_s + np.cumsum(op_service))
            done[members] = np.maximum(done[members], leg_done)
            io0 = g.primary.engine.io_time_s()
            debt = g.primary.engine.maintain(cfg.maintain_budget)
            maintain_s = g.primary.engine.io_time_s() - io0
            leg_totals.append(spike * (wal_s + float(op_service.sum()))
                              + maintain_s)
            debt_max = max(debt_max, int(debt))
            self._gwm[gid].record(t_commit, done[members] - arrive[members],
                                  ops=len(members), queue_depth=qdepth,
                                  debt=int(debt))
        service_s = max(leg_totals)
        if self.tracer is not None:
            self.tracer.complete("commit", "group_commit", t_commit,
                                 service_s, ops=len(batch), legs=len(legs))
        return done, service_s, 0.0, debt_max

    def finish(self, t_end: float, drain: bool) -> dict:
        """Bring the failure machinery to ``t_end``, then drain every live
        primary."""
        self._tick(t_end)
        if drain:
            for g in self.groups:
                if g.primary is not None and g.primary.alive:
                    g.primary.engine.drain()
        return {}

    # ----------------------------------------------------------------- serve
    def run(self, trace: ArrivalTrace, *, drain: bool = True) -> dict:
        self._bootstrap(trace)
        self._gwm = [WindowedMetrics(self.window_s) for _ in self.groups]
        self._ops = trace.ops       # one trace: queue items are its indices
        self._point_gid = self.partitioner.shard_of(trace.ops.keys)
        queue = SharedFifo(self.config.max_queue)
        queue.add_tenant(0)
        report, t_end = serve({0: trace}, queue, self, drain=drain)
        failovers = [ev for g in self.groups for ev in g.failovers]
        report["replication"] = {
            "config": dataclasses.asdict(self.rep),
            "n_groups": len(self.groups),
            "acked_commits": len(self.acked),
            "acked_rows": int(sum(g.acked_rows for g in self.groups)),
            "shed_unavailable": int(self.shed_unavailable),
            "failovers": failovers,
            "failed_groups": [g.gid for g in self.groups if g.failed],
            "lost_acked_rows_failed_groups": int(sum(
                g.acked_rows for g in self.groups if g.failed)),
            "groups": [g.describe() for g in self.groups],
            "availability": [
                {"gid": g.gid, "downtime_s": float(g.downtime_s),
                 "timeline": self._gwm[g.gid].finish(t_end)}
                for g in self.groups],
        }
        if self.chaos is not None:
            report["replication"]["chaos"] = self.chaos.describe()
        return report


def run_replicated(engine_factory, trace: ArrivalTrace, directory: str, *,
                   groups: int = 4,
                   replication: ReplicationConfig | None = None,
                   config: FrontendConfig | None = None,
                   chaos: FaultSchedule | None = None,
                   obs: ObsConfig | None = None,
                   window_s: float = 0.05) -> dict:
    """One-call harness: serve ``trace`` on a replicated ensemble."""
    fe = ReplicatedFrontend(engine_factory, directory, groups=groups,
                            replication=replication, config=config,
                            chaos=chaos, obs=obs, window_s=window_s)
    return fe.run(trace)
