"""Open-loop serving: one group-commit loop on a simulated clock.

This is the serving layer between workload generation and the storage
engines (DESIGN.md §7).  A closed-loop driver asks "how long does an op
take once the engine starts it?"; an open-loop frontend asks the question
the paper's worst-case-delay claim is actually about: *what latency does a
request experience when it arrives on its own schedule* — queueing behind
a compaction stall included.

:func:`serve` is the one loop behind every frontend (one engine here,
tenants in ``repro.tenancy``, replica groups in ``repro.replication``):

* **Admission**: each op of the arrival trace (tenants' traces merged in
  time order) is offered at its arrival instant to the admission seam —
  :class:`SharedFifo` or a weighted-fair queue — and is **shed** when it
  is refused (admission control: availability traded for bounded memory
  and bounded tail).
* **Group commit**: the server coalesces queued ops into an
  :class:`~repro.core.engine_api.OpBatch` of up to ``commit_ops``,
  lingering at most ``linger_s`` past the moment it could first serve
  (size *or* deadline, whichever first), and hands it to the commit seam
  (:class:`CommitSeam`): one engine, or one leg per replica group.
* **Service** is charged from the engine's own accounting: on cost-model
  tiers (``clock == "sim"``) a batch's service time is the sum of its
  per-op simulated latencies and maintenance time is the engine's charged
  I/O delta — so the whole run is a pure function of (trace, engine
  config) and two runs produce byte-identical reports.  On the wall-clock
  device tier, real measurements are nondeterministic by nature, so the
  clock instead uses a fixed *virtual* per-op service time
  (:data:`VIRTUAL_OP_SERVICE_S`); device rows exercise the full protocol
  and queueing math deterministically, while their absolute latencies are
  the surrogate model's, flagged ``service_model: "virtual"`` in reports.
* **Maintenance** is interleaved once per commit — ``maintain(budget)``
  on the simulated clock, exactly like the closed-loop driver — and the
  engine's pending-debt snapshot is recorded at every commit, which is
  what lets :mod:`repro.ingest.slo` attribute tail latency to stalls and
  verify the deamortized debt bound under load.

End-to-end latency of op *i* = (commit time + its share of batch service)
- arrival time = queueing + service; the SLO tracker reports exact
p50/p99/p99.9/p100 per kind plus queue/shed/stall accounting.

* **Durability** (optional; DESIGN.md §9): with a :class:`DurabilityConfig`
  the frontend write-ahead-logs every group commit's INSERT/DELETE rows
  (``repro.wal``) and **acks only after the record's fsync returns** — the
  ack instant *is* durability.  The fsync-per-commit cost is charged on the
  same clock as everything else: simulated seek + sequential-write seconds
  on sim tiers (through a :class:`~repro.core.cost_model.CostModel` on the
  engine's own device constants), measured wall seconds on the device tier.
  Every ``checkpoint_every_commits`` commits the engine's live table is
  snapshotted (``EngineCheckpointer``) at the current commit LSN and the
  WAL is truncated past it, bounding recovery replay.  A crash at any
  point (``repro.wal.faults``) recovers via ``repro.wal.recovery.recover``
  to exactly the acked prefix.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time

import numpy as np

from repro.core.cost_model import PAIR_BYTES, SSD
from repro.core.engine_api import OpBatch, OpKind, StorageEngine
from repro.obs.metrics import ObsConfig, WindowedMetrics
from repro.obs.stall import attribute_stalls, detect_stalls
from repro.obs.trace import Tracer
from repro.wal.faults import (ChaosEvent, ChaosKind, CrashPoint,
                              FaultInjector, FaultSchedule, SimulatedCrash,
                              flip_wal_byte, reach as _reach, tear_wal_tail)

from .arrivals import ArrivalTrace, multiplex
from .slo import STALL_FACTOR, SLOTracker

KIND_NAMES = {int(k): k.name.lower() for k in OpKind}
WRITE_KINDS = (int(OpKind.INSERT), int(OpKind.DELETE))

#: deterministic surrogate service time per op (and per maintenance unit)
#: on wall-clock engines; see the module docstring.
VIRTUAL_OP_SERVICE_S = 5e-6


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Serving-node knobs (defaults sized for benchmark-scale traces)."""

    max_queue: int = 4096          # admission-control bound (ops)
    commit_ops: int = 64           # group-commit size cap
    linger_s: float = 1e-3         # group-commit deadline past first-servable
    maintain_budget: int = 1       # maintenance units interleaved per commit
    #: stall-attribution threshold (see ``repro.ingest.slo``): a commit is
    #: a stall when its service time exceeds this multiple of the run's
    #: typical commit service.  Recorded in ``report["stalls"]`` so sweeps
    #: with different thresholds are self-describing.
    stall_factor: float = STALL_FACTOR

    def __post_init__(self):
        assert self.max_queue >= 1 and self.commit_ops >= 1
        assert self.commit_ops <= self.max_queue, \
            "a commit cannot exceed the queue bound"
        assert self.linger_s >= 0.0 and self.maintain_budget >= 0
        assert self.stall_factor > 1.0


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """Knobs of the WAL + checkpoint layer (DESIGN.md §9).

    ``directory`` holds ``wal/`` (redo segments) and ``checkpoints/``
    (LSN-keyed engine snapshots).  ``checkpoint_every_commits = 0``
    disables periodic snapshots (the WAL alone still makes every acked
    write recoverable; a nonempty preload is always snapshotted once
    before the clock starts, since preload is never logged).
    """

    directory: str
    segment_bytes: int = 1 << 20
    checkpoint_every_commits: int = 0

    def __post_init__(self):
        assert self.segment_bytes >= 4096
        assert self.checkpoint_every_commits >= 0


def offered_by_kind(kinds) -> dict:
    """Ops offered per kind name (the SLO report's ``offered``)."""
    k = np.asarray(kinds)
    return {name: int((k == kk).sum()) for kk, name in KIND_NAMES.items()}


def rows_at(batch: OpBatch, idx) -> OpBatch:
    """The batch's rows at ``idx`` (an index array or a boolean mask)."""
    return OpBatch(batch.kinds[idx], batch.keys[idx], batch.vals[idx],
                   batch.his[idx])


def write_rows(batch: OpBatch) -> tuple:
    """``(kinds, keys, vals)`` of the batch's INSERT/DELETE rows — what one
    WAL record holds (fresh arrays, safe to keep as the acked oracle)."""
    w = np.isin(batch.kinds, WRITE_KINDS)
    return batch.kinds[w], batch.keys[w], batch.vals[w]


class SharedFifo:
    """Admission seam: one global FIFO under one global bound.

    The single engine admits through it as tenant 0; for several tenants
    it is the unfair baseline, shed charged to whichever tenant's op hit
    the full queue.  Same surface as
    :class:`~repro.tenancy.fair_queue.WeightedFairQueue`.
    """

    def __init__(self, max_queue: int):
        self.max_queue = int(max_queue)
        self._q: collections.deque = collections.deque()
        self._counters: dict[int, dict] = {}
        self._depth: dict[int, int] = {}

    def add_tenant(self, tenant_id: int, *, weight: float = 1.0,
                   max_queue: int = 0) -> None:
        self._counters[int(tenant_id)] = {
            "weight": float(weight), "offered": 0, "shed": 0, "served": 0}
        self._depth[int(tenant_id)] = 0

    def offer(self, tenant_id: int, item) -> bool:
        c = self._counters[int(tenant_id)]
        c["offered"] += 1
        if len(self._q) >= self.max_queue:
            c["shed"] += 1
            return False
        self.requeue(tenant_id, item)
        return True

    def requeue(self, tenant_id: int, item) -> None:
        """Queue an op admitted earlier (a parked op whose range came
        back): it already held a slot, so the bound is not checked."""
        self._q.append((int(tenant_id), item))
        self._depth[int(tenant_id)] += 1

    def take(self, max_ops: int) -> list:
        out = []
        while self._q and len(out) < max_ops:
            tid, item = self._q.popleft()
            self._counters[tid]["served"] += 1
            self._depth[tid] -= 1
            out.append((tid, item))
        return out

    def heads(self) -> list:
        return [self._q[0]] if self._q else []

    def backlog(self, tenant_id: int | None = None) -> int:
        return len(self._q) if tenant_id is None \
            else self._depth[int(tenant_id)]

    def stats(self) -> dict:
        return {str(tid): dict(c, max_queue=self.max_queue,
                               backlog=self.backlog(tid), depth_max=None)
                for tid, c in self._counters.items()}


class CommitSeam:
    """Commit seam: where :func:`serve` sends each group commit.

    Subclasses implement :meth:`commit` and :meth:`finish`; the other
    hooks default to a target that is always up (the replicated seam
    parks ops whose range is down).
    """

    parked = 0                  # ops held outside the admission queue
    service_model = "charged"   # "virtual": VIRTUAL_OP_SERVICE_S per op

    def __init__(self, config: FrontendConfig | None, obs: ObsConfig | None):
        self.config = config or FrontendConfig()
        # observability is strictly opt-in: when ``obs`` is None (or
        # disabled) every hook is a single attribute check, so the
        # serving loop's timings are identical with and without it.
        self.obs = obs if (obs is not None and obs.enabled) else None
        self.tracer = None if self.obs is None \
            else Tracer(capacity=self.obs.trace_capacity)

    def advance(self, now: float, queue) -> None:
        """Bring the seam's own state up to ``now`` (a loop tick)."""

    def wakeups(self, now: float) -> list:
        """Instants at which the seam changes state on its own."""
        return []

    def take(self, queue, max_ops: int, t_commit: float) -> list:
        """The ``(tenant, item)`` ops the commit at ``t_commit`` serves."""
        return queue.take(max_ops)

    def commit(self, batch: OpBatch, arrive: np.ndarray, t_commit: float,
               qdepth: int) -> tuple:
        """Serve one group commit: ``(done, service_s, maintain_s, debt)``,
        ``done`` being each op's completion instant."""
        raise NotImplementedError

    def finish(self, t_end: float, drain: bool) -> dict:
        """End the run on the target; the report fields that follow
        ``service_model``."""
        raise NotImplementedError


def serve(traces: dict, queue, seam: CommitSeam, *,
          trackers: dict | None = None, on_commit=None,
          drain: bool = True) -> tuple:
    """Serve ``traces`` (tenant id -> :class:`ArrivalTrace`) open-loop
    through the admission seam ``queue`` and the commit seam ``seam``.

    The run's :class:`SLOTracker` becomes ``seam.tracker``; ``trackers``
    (tenant id -> tracker) record each tenant's share; ``on_commit(seam,
    t_commit)`` runs after every commit lands.  Returns ``(report,
    t_end)``: the report's shared head and the makespan.
    """
    cfg, obs, tracer = seam.config, seam.obs, seam.tracer
    tracker = seam.tracker = SLOTracker(stall_factor=cfg.stall_factor)
    trackers = trackers or {}
    # the merged timeline: op k arrives at mt[k] from tenant msid[k] and is
    # ops[k]; the admission seam queues these positions k
    mt, msid, mloc = multiplex(traces)
    sids = sorted(traces)
    starts = np.cumsum([0] + [len(traces[s]) for s in sids])[:-1]
    ops = rows_at(OpBatch.concat([traces[s].ops for s in sids]),
                  starts[np.searchsorted(sids, msid)] + mloc)
    n, i = len(mt), 0           # i: next arrival not yet admitted or shed
    wm = None if obs is None else WindowedMetrics(
        obs.window_s, stall_k=obs.stall_k, stall_trailing=obs.stall_trailing)

    def admit_until(t: float) -> None:
        """Offer every arrival with t_arrive <= t, in order.

        Occupancy only grows between commits, so evaluating arrivals in
        timestamp order against the live queue gives each op the
        admission decision it would see at its own arrival instant.
        """
        nonlocal i
        # shed instants are coalesced per poll (one event per tenant and
        # kind with a count) so a long overload burst cannot flood the
        # trace ring and evict the spans stall attribution needs
        shed_t0: dict[tuple, float] = {}
        shed_n: collections.Counter = collections.Counter()
        while i < n and mt[i] <= t:
            tid = int(msid[i])
            mine = trackers.get(tid)
            if queue.offer(tid, i):
                if mine is not None:
                    mine.record_queue_depth(queue.backlog(tid))
                tracker.record_queue_depth(queue.backlog())
            else:
                kname = KIND_NAMES[int(ops.kinds[i])]
                if mine is not None:
                    mine.record_shed(kname)
                tracker.record_shed(kname)
                if obs is not None:
                    shed_t0.setdefault((tid, kname), mt[i])
                    shed_n[tid, kname] += 1
                    wm.record_shed(mt[i])
            i += 1
        for (tid, kname), t0 in shed_t0.items():
            tracer.instant("shed", kname, t0, tenant=tid,
                           count=shed_n[tid, kname])

    def next_event(now: float) -> float | None:
        future = [c for c in ([mt[i]] if i < n else []) + seam.wakeups(now)
                  if c > now]
        return min(future) if future else None

    t_free = 0.0                # server becomes available at this time
    while queue.backlog() or seam.parked or i < n:
        admit_until(t_free)
        seam.advance(t_free, queue)
        if not queue.backlog():
            # idle: jump the clock to the next arrival or seam event.
            t_next = next_event(t_free)
            if t_next is None:
                break               # nothing left can ever happen
            t_free = t_next
            continue
        t0 = max(t_free, min(mt[k] for _, k in queue.heads()))

        # ---- group commit: size or deadline, whichever first --------------
        # (arrivals are in time order: the commit is full once arrival j,
        # the last it has room for, lands)
        deadline = t0 + cfg.linger_s
        j = i + cfg.commit_ops - queue.backlog() - 1
        if j < i or i >= n:
            t_commit = t0
        elif j < n and mt[j] <= deadline:
            t_commit = max(t0, mt[j])
        else:
            t_commit = deadline
        admit_until(t_commit)

        take = seam.take(queue, cfg.commit_ops, t_commit)
        if not take:
            # every op the seam looked at was parked or shed: wait for the
            # next event (the linger step guards against an idle spin).
            t_next = next_event(t_commit)
            t_free = t_commit + cfg.linger_s if t_next is None else t_next
            continue
        sel = np.asarray([k for _, k in take], np.int64)
        batch, arr, sel_t = rows_at(ops, sel), mt[sel], msid[sel]
        qdepth = queue.backlog()
        done, service_s, maintain_s, debt = seam.commit(batch, arr, t_commit,
                                                        qdepth)
        debt = int(debt)
        knames = [KIND_NAMES[int(k)] for k in batch.kinds]
        # the run's tracker records the whole commit, each tenant's its share
        for tr, w, depth in [(tracker, np.ones(len(sel), bool), qdepth)] + [
                (trackers[tid], sel_t == tid, queue.backlog(tid))
                for tid in map(int, np.unique(sel_t)) if tid in trackers]:
            tr.record_commit(
                t_commit=t_commit,
                kinds=[kn for kn, hit in zip(knames, w) if hit],
                e2e_s=done[w] - arr[w], queue_delay_s=t_commit - arr[w],
                qdepth_after=depth, service_s=service_s,
                maintain_s=maintain_s, debt=debt)
        if obs is not None:
            wm.record(t_commit, done - arr, ops=len(sel), queue_depth=qdepth,
                      debt=debt)
        t_free = t_commit + service_s + maintain_s
        if on_commit is not None:
            on_commit(seam, t_commit)

    # ---- report head: SLO summary, the target's end of run, obs ----------
    report = tracker.report(offered=offered_by_kind(ops.kinds), t_end=t_free)
    report["service_model"] = seam.service_model
    report.update(seam.finish(t_free, drain))
    report["config"] = dataclasses.asdict(cfg)
    if obs is not None:
        # close the timeline, attribute stalls against the span buffer,
        # optionally save the Chrome trace
        block = report["obs"] = wm.finish(t_free)
        block["stalls"] = attribute_stalls(
            detect_stalls(wm.windows, k=obs.stall_k,
                          trailing=obs.stall_trailing), tracer.events())
        block["trace"] = {"events": len(tracer),
                          "dropped_events": tracer.dropped_events,
                          "categories": sorted(tracer.categories())}
        if obs.trace_path:
            tracer.save(obs.trace_path)
            block["trace"]["path"] = obs.trace_path
    return report, t_free


class IngestFrontend(CommitSeam):
    """Single-server open-loop serving over one engine; it is also the
    commit seam for one engine behind an optional WAL and checkpointer."""

    def __init__(self, engine: StorageEngine, config: FrontendConfig | None = None,
                 durability: DurabilityConfig | None = None,
                 injector: FaultInjector | None = None,
                 obs: ObsConfig | None = None,
                 chaos: FaultSchedule | None = None):
        super().__init__(config, obs)
        self.engine = engine
        self.durability = durability
        self._injector = injector
        # chaos harness (DESIGN.md §12): the single-engine frontend owns the
        # schedule's default target, ``"wal"``.  When ``chaos`` is None every
        # hook below is one attribute check — the serving loop is unchanged.
        self.chaos = chaos
        self._chaos_stall_s = 0.0       # one-shot: next commit's fsync pays it
        self._chaos_spike = 1.0         # service multiplier while spike active
        self._chaos_spike_until = 0.0
        if chaos is not None:
            chaos.register("wal", self._on_chaos)
        if self.tracer is not None:
            engine.attach_tracer(self.tracer)
        # the engine self-reports its clock domain via stats(); adapters set
        # a class attribute, so probing one snapshot is cheap and universal.
        self.sim_clock = engine.stats().clock == "sim"
        self.service_model = "charged" if self.sim_clock else "virtual"
        self._n_commits = 0         # group commits served (checkpoint cadence)
        self._wal = None
        self._ckpt = None
        #: every acked group commit as ``(lsn, kinds, keys, vals)`` — the
        #: ground truth the crash-matrix tests build their oracle from (an
        #: op is in here iff its fsync returned, i.e. iff it was acked).
        self.acked: list = []
        self.last_acked_lsn = 0
        if durability is not None:
            from repro.checkpoint.checkpointer import EngineCheckpointer
            from repro.wal import (CHECKPOINT_SUBDIR, WAL_SUBDIR,
                                   WriteAheadLog)
            self._wal_dir = os.path.join(durability.directory, WAL_SUBDIR)
            self._wal = WriteAheadLog(
                self._wal_dir,
                segment_bytes=durability.segment_bytes, injector=injector)
            self._ckpt = EngineCheckpointer(
                os.path.join(durability.directory, CHECKPOINT_SUBDIR),
                injector=injector)
            # restart-after-crash: opening an existing directory resumes the
            # durable LSN chain where the previous frontend left it, so a
            # resumed run's first commit is ``last_lsn + 1`` (LSN
            # continuity) and its report never claims a stale watermark.
            self.last_acked_lsn = self._wal.last_lsn
            # fsync cost is charged on the engine's own device constants
            # when it has any (sim tiers); the device tier measures wall
            # time instead, so its device constant is never read.
            cm = getattr(engine, "cm", None)
            self._wal_device = cm.device if cm is not None else SSD
            self._wal_service_s = 0.0
            #: the report's ``durability.checkpoints`` block
            self._ckpt_stats = {"taken": 0, "last_lsn": 0,
                                "last_snapshot_pairs": 0,
                                "service_s_total": 0.0}

    # ------------------------------------------------------------------ chaos
    def _on_chaos(self, ev: ChaosEvent) -> None:
        """Apply one due chaos event to this frontend (target ``"wal"``).

        Performance faults mutate charging state consumed at the next
        commit; ``CRASH`` propagates like an injector kill (the crash-
        recovery tests' ``except SimulatedCrash`` path); the corruption
        kinds physically damage the newest WAL segment so the *next
        recovery* — not this run — sees a torn/corrupt tail.
        """
        if ev.kind is ChaosKind.FSYNC_STALL:
            self._chaos_stall_s += ev.arg
        elif ev.kind is ChaosKind.LATENCY_SPIKE:
            self._chaos_spike = max(float(ev.arg), 1.0)
            self._chaos_spike_until = ev.t + ev.dur_s
        elif ev.kind is ChaosKind.CRASH:
            # fires at a commit boundary, before the next WAL append: none
            # of the still-queued ops were acked, exactly BEFORE_WAL_APPEND.
            raise SimulatedCrash(CrashPoint.BEFORE_WAL_APPEND, 1)
        elif ev.kind is ChaosKind.TORN_SEGMENT and self._wal is not None:
            tear_wal_tail(self._wal_dir)
        elif ev.kind is ChaosKind.BIT_FLIP and self._wal is not None:
            flip_wal_byte(self._wal_dir)

    # ------------------------------------------------------------- durability
    def _wal_commit(self, batch: OpBatch) -> float:
        """Durably log the commit's writes; returns charged seconds.

        The ack instant for every write in the batch is the fsync return
        inside ``append_commit`` — a crash before it means the ops were
        never acked (and a torn record is truncated on recovery); a crash
        after it means recovery must replay them.
        """
        rows = write_rows(batch)
        if not len(rows[0]):
            return 0.0              # read-only commit: nothing to make durable
        t0 = time.perf_counter()
        lsn, nbytes = self._wal.append_commit(*rows)
        sec = self._charged(nbytes, time.perf_counter() - t0)
        self._wal_service_s += sec
        self.acked.append((lsn, *rows))
        self.last_acked_lsn = lsn
        # the fsync returned, so the ops above ARE acked — this crash point
        # therefore means "durable + acked, not yet applied": replay owes it.
        _reach(self._injector, CrashPoint.AFTER_WAL_FSYNC)
        return sec

    def _checkpoint(self) -> float:
        """Snapshot the engine's live table at the current commit LSN and
        truncate the WAL past it; returns charged seconds."""
        lsn = self._wal.last_lsn
        t0 = time.perf_counter()
        keys, vals = self.engine.dump_live()
        self._ckpt.save_snapshot(lsn, keys, vals)
        _reach(self._injector, CrashPoint.AFTER_CHECKPOINT)
        self._wal.truncate_upto(lsn)
        sec = self._charged(len(keys) * PAIR_BYTES, time.perf_counter() - t0)
        c = self._ckpt_stats
        c["taken"] += 1
        c["last_lsn"], c["last_snapshot_pairs"] = lsn, len(keys)
        c["service_s_total"] += sec
        return sec

    def _charged(self, nbytes: int, wall_s: float) -> float:
        """Seconds one durable write costs on this clock: a seek plus
        sequential bytes on the engine's device (sim tiers), else wall."""
        if not self.sim_clock:
            return wall_s
        dev = self._wal_device
        return dev.seek_s + nbytes / dev.write_bw

    def _maintain(self, budget: int) -> int:
        """``engine.maintain`` with the mid-cascade crash point threaded in
        (unit-at-a-time only when an injector is armed — the production
        path stays one call)."""
        if self._injector is None or budget <= 0:
            return self.engine.maintain(budget)
        debt = self.engine.maintain(0)
        for _ in range(int(budget)):
            if not debt:
                break
            debt = self.engine.maintain(1)
            _reach(self._injector, CrashPoint.MID_CASCADE)
        return debt

    # ------------------------------------------------------------ commit seam
    def commit(self, batch: OpBatch, arrive: np.ndarray, t_commit: float,
               qdepth: int) -> tuple:
        """WAL append + fsync, apply, interleaved maintenance and the
        periodic checkpoint — one group commit on the engine."""
        cfg, eng, tracer = self.config, self.engine, self.tracer
        # ---- chaos: due events fire at the commit boundary ----------------
        if self.chaos is not None:
            for ev in self.chaos.fire_due(t_commit):
                if tracer is not None:
                    tracer.instant("chaos", ev.kind.value, t_commit,
                                   target=ev.target, arg=ev.arg)

        # ---- durability: WAL append + fsync BEFORE apply ------------------
        # (write-ahead rule; the fsync return is the ack instant, and its
        # cost is part of the commit's service time on this clock.)
        # a pending FSYNC_STALL charges the next commit exactly once
        stall, self._chaos_stall_s = self._chaos_stall_s, 0.0
        wal_s = stall
        if self._wal is not None:
            wal_s += self._wal_commit(batch)
            self._wal_service_s += stall

        # ---- service (engine clock -> simulated clock) --------------------
        # apply cost is charged through per-op latencies (the engine's
        # foreground share); maintenance through the charged-I/O delta.
        res = eng.apply(batch)
        if self._wal is not None:
            eng.note_applied(self.last_acked_lsn)
            _reach(self._injector, CrashPoint.AFTER_APPLY)
        op_service = np.asarray(res.latency_s, np.float64) if self.sim_clock \
            else np.full(len(batch), VIRTUAL_OP_SERVICE_S)
        if self._chaos_spike > 1.0 and t_commit < self._chaos_spike_until:
            # LATENCY_SPIKE window: every charged second costs ``arg``×
            op_service = op_service * self._chaos_spike
            wal_s *= self._chaos_spike
        service_s = wal_s + float(op_service.sum())

        # ---- interleaved maintenance + debt snapshot ----------------------
        io1 = eng.io_time_s()
        debt = self._maintain(cfg.maintain_budget)
        io2 = eng.io_time_s()
        maintain_s = io2 - io1 if self.sim_clock \
            else VIRTUAL_OP_SERVICE_S * cfg.maintain_budget

        # ---- periodic checkpoint: snapshot @ LSN, truncate WAL ------------
        self._n_commits += 1
        ckpt_s = 0.0
        if (self._ckpt is not None
                and self.durability.checkpoint_every_commits
                and self._n_commits
                % self.durability.checkpoint_every_commits == 0
                and self._wal.last_lsn > self._ckpt_stats["last_lsn"]):
            ckpt_s = self._checkpoint()
            maintain_s += ckpt_s

        if tracer is not None:
            # spans carry *charged* durations on the same clock as the
            # latency math, so the saved trace is byte-deterministic on
            # sim tiers (and virtual-clock-consistent on the device tier)
            if wal_s > 0.0:
                tracer.complete("wal_fsync", "fsync", t_commit, wal_s,
                                lsn=int(self.last_acked_lsn))
            tracer.complete("commit", "group_commit", t_commit, service_s,
                            ops=len(batch), qdepth=qdepth)
            cascade_s = maintain_s - ckpt_s
            if cascade_s > 0.0:
                tracer.complete("cascade", "maintain", t_commit + service_s,
                                cascade_s, budget=cfg.maintain_budget,
                                debt=int(debt))
            if ckpt_s > 0.0:
                c = self._ckpt_stats
                tracer.complete("checkpoint", "snapshot",
                                t_commit + service_s + cascade_s, ckpt_s,
                                lsn=int(c["last_lsn"]),
                                pairs=int(c["last_snapshot_pairs"]))
        done = t_commit + wal_s + np.cumsum(op_service)
        return done, service_s, maintain_s, debt

    # ----------------------------------------------------------------- running
    def _load(self, preload: OpBatch) -> None:
        """Load phase: closed-loop, before the clock starts (not offered
        load).  Preload is never WAL-logged, so durability snapshots it
        before the clock starts (uncharged, like the load phase itself)."""
        if not len(preload):
            return
        self.engine.apply(preload)
        self.engine.drain()
        if self._ckpt is not None:
            self._checkpoint()
            self._ckpt_stats["service_s_total"] = 0.0

    def finish(self, t_end: float, drain: bool) -> dict:
        """End the run on the engine: its debt left, then the drain."""
        debt_final = self.engine.maintain(0)
        if drain:
            self.engine.drain()
        return {"pending_debt_at_end": int(debt_final)}

    def _durability_report(self) -> dict:
        """Close the WAL; the report's ``durability`` block."""
        self._wal.close()
        return {
            "config": dataclasses.asdict(self.durability),
            "wal": self._wal.stats()
            | {"service_s_total": self._wal_service_s},
            "checkpoints": dict(self._ckpt_stats),
            "acked_commits": len(self.acked),
            "last_acked_lsn": self.last_acked_lsn,
        }

    def run(self, trace: ArrivalTrace, *, drain: bool = True) -> dict:
        """Serve ``trace``; returns the JSON-ready open-loop report."""
        self._load(trace.preload)
        queue = SharedFifo(self.config.max_queue)
        queue.add_tenant(0)
        report, _ = serve({0: trace}, queue, self, drain=drain)
        if self._wal is not None:
            report["durability"] = self._durability_report()
        if self.chaos is not None:
            report["chaos"] = self.chaos.describe()
        return report


def run_open_loop(engine: StorageEngine, trace: ArrivalTrace, *,
                  config: FrontendConfig | None = None,
                  durability: DurabilityConfig | None = None,
                  obs: ObsConfig | None = None,
                  chaos: FaultSchedule | None = None) -> dict:
    """One-call harness: serve ``trace`` on ``engine``, full JSON report.

    The returned dict mirrors the closed-loop driver report shape (engine
    name, arrival description, final ``stats()`` snapshot) with the
    open-loop SLO section under ``"open_loop"``.
    """
    fe = IngestFrontend(engine, config, durability=durability, obs=obs,
                        chaos=chaos)
    ol = fe.run(trace)
    stats = engine.stats()
    return {
        "engine": engine.name,
        "arrival": dict(trace.arrival),
        "trace": {"n_ops": len(trace), "duration_s": trace.duration_s,
                  "seed": trace.seed, "preload_pairs": len(trace.preload)},
        "open_loop": ol,
        "stats": dataclasses.asdict(stats),
    }
