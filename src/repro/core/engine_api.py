"""Unified StorageEngine protocol over every index tier (DESIGN.md §5).

The paper's headline claims are *comparative* — NB-tree vs LSM-tree vs
B+-tree vs B^eps-tree on insertion rate, query latency, and worst-case
delay — so every benchmark, test and demo must be able to stream the same
operation sequence through any engine and read back the same shaped
answers.  This module is that surface:

* :class:`OpBatch` — a columnar batch of operations (``INSERT`` /
  ``DELETE`` / ``QUERY`` / ``RANGE``), the only way work enters an engine;
* :class:`OpResult` — per-op visible results plus per-op latency (simulated
  I/O seconds on the cost-model tiers, host wall-clock on the device tier);
* :class:`StorageEngine` — ``apply(OpBatch) -> OpResult``,
  ``maintain(budget) -> pending``, ``drain()``, and a uniform ``stats()``
  snapshot (:class:`EngineStats`);
* thin adapters that retrofit the five tiers (``refimpl.NBTree``,
  ``lsm.LSMTree``, ``btree.BPlusTree``, ``bepsilon.BEpsilonTree`` and the
  device-tier ``jax_nbtree.NBTreeIndex``) onto the protocol, keeping the
  existing classes as the implementation core;
* an engine registry (:func:`register_engine` / :func:`make_engine`), with
  :data:`FIVE_TIERS` naming the paper's comparison set; the
  ``sharded:<base>`` prefix builds a range-partitioned ensemble of any
  registered engine (``repro.shard``, DESIGN.md §6).

Semantics are sequential within a batch: op i+1 observes op i.  Adapters
may still vectorize — the device adapter groups maximal same-kind runs into
one fused device call, which preserves the sequential semantics because
``insert_batch`` resolves intra-batch duplicates newest-wins and queries
cannot appear inside an insert group.

Key/value domain: keys are uint64 on the cost-model tiers and uint32 on the
device tier, so a workload that must run on *all* tiers keeps its keys in
``[1, 2^31)``; values must be non-negative int32-representable (the
tombstone sentinels ``sorted_run.TOMBSTONE`` = -1 and ``TOMBSTONE32`` are
reserved).  The workload generator (``repro.workloads``) enforces both.
"""
from __future__ import annotations

import abc
import dataclasses
import enum
import time

import numpy as np

from repro.obs.metrics import LogBucketHistogram

from .bepsilon import BEpsilonTree
from .btree import BPlusTree, BPlusTreeBulk
from .cost_model import HDD, CostModel, Device
from .lsm import LSMTree
from .refimpl import NBTree
from .sorted_run import KEY_DTYPE, TOMBSTONE, VAL_DTYPE


class OpKind(enum.IntEnum):
    INSERT = 0
    DELETE = 1
    QUERY = 2
    RANGE = 3


class UnsupportedOp(RuntimeError):
    """Raised by engines that cannot serve an op kind (e.g. bulk B+-tree inserts)."""


@dataclasses.dataclass
class OpBatch:
    """Columnar operation batch: parallel arrays, one row per op.

    ``keys`` is the op key (RANGE: inclusive lower bound), ``vals`` the
    INSERT payload (ignored elsewhere), ``his`` the RANGE inclusive upper
    bound (ignored elsewhere).
    """

    kinds: np.ndarray   # int8   (B,)
    keys: np.ndarray    # uint64 (B,)
    vals: np.ndarray    # int64  (B,)
    his: np.ndarray     # uint64 (B,)

    def __post_init__(self):
        self.kinds = np.asarray(self.kinds, np.int8)
        self.keys = np.asarray(self.keys, KEY_DTYPE)
        self.vals = np.asarray(self.vals, VAL_DTYPE)
        self.his = np.asarray(self.his, KEY_DTYPE)
        n = len(self.kinds)
        assert self.keys.shape == self.vals.shape == self.his.shape == (n,), \
            "OpBatch arrays must be parallel 1-d arrays of one length"

    def __len__(self) -> int:
        return len(self.kinds)

    # ------------------------------------------------------------- constructors
    @staticmethod
    def inserts(keys, vals) -> "OpBatch":
        keys = np.asarray(keys, KEY_DTYPE)
        return OpBatch(np.full(len(keys), OpKind.INSERT, np.int8), keys,
                       np.asarray(vals, VAL_DTYPE), np.zeros(len(keys), KEY_DTYPE))

    @staticmethod
    def deletes(keys) -> "OpBatch":
        keys = np.asarray(keys, KEY_DTYPE)
        z = np.zeros(len(keys), KEY_DTYPE)
        return OpBatch(np.full(len(keys), OpKind.DELETE, np.int8), keys,
                       np.zeros(len(keys), VAL_DTYPE), z)

    @staticmethod
    def queries(keys) -> "OpBatch":
        keys = np.asarray(keys, KEY_DTYPE)
        z = np.zeros(len(keys), KEY_DTYPE)
        return OpBatch(np.full(len(keys), OpKind.QUERY, np.int8), keys,
                       np.zeros(len(keys), VAL_DTYPE), z)

    @staticmethod
    def ranges(los, his) -> "OpBatch":
        los = np.asarray(los, KEY_DTYPE)
        return OpBatch(np.full(len(los), OpKind.RANGE, np.int8), los,
                       np.zeros(len(los), VAL_DTYPE), np.asarray(his, KEY_DTYPE))

    @staticmethod
    def empty() -> "OpBatch":
        return OpBatch(np.zeros(0, np.int8), np.zeros(0, KEY_DTYPE),
                       np.zeros(0, VAL_DTYPE), np.zeros(0, KEY_DTYPE))

    @staticmethod
    def concat(batches) -> "OpBatch":
        """Concatenate batches in order (mixed kinds welcome; the result
        keeps sequential semantics).  An empty input list — or a list of
        zero-length batches — yields the empty batch instead of tripping
        ``np.concatenate`` on an empty sequence."""
        batches = [b for b in batches if len(b)]
        if not batches:
            return OpBatch.empty()
        return OpBatch(np.concatenate([b.kinds for b in batches]),
                       np.concatenate([b.keys for b in batches]),
                       np.concatenate([b.vals for b in batches]),
                       np.concatenate([b.his for b in batches]))


@dataclasses.dataclass
class OpResult:
    """Visible results + per-op latency for one applied :class:`OpBatch`.

    ``found``/``values`` are meaningful on QUERY rows, ``range_hits[i]`` is
    a ``(keys, vals)`` pair on RANGE rows (None elsewhere), ``latency_s``
    on every row (the engine's clock: simulated I/O seconds on cost-model
    tiers, amortized host wall-clock on the device tier).
    ``range_truncated[i]`` flags RANGE rows whose result hit an engine
    capacity limit and is incomplete (device tier only — the cost-model
    tiers are always exact); callers needing exactness must check it.
    """

    kinds: np.ndarray
    found: np.ndarray        # bool  (B,)
    values: np.ndarray       # int64 (B,) — -1 where not found / not a query
    range_hits: list         # list[Optional[tuple[np.ndarray, np.ndarray]]]
    latency_s: np.ndarray    # float64 (B,)
    range_truncated: np.ndarray = None  # bool (B,)

    def __post_init__(self):
        if self.range_truncated is None:
            self.range_truncated = np.zeros(len(self.kinds), bool)

    def latencies(self, kind: OpKind | None = None) -> np.ndarray:
        if kind is None:
            return self.latency_s
        return self.latency_s[self.kinds == int(kind)]


@dataclasses.dataclass
class EngineStats:
    """Uniform engine snapshot; every field is cumulative-since-construction.

    ``io_time_s`` is the engine's charged cost (simulated seconds on the
    cost-model tiers, accumulated host wall-clock on the device tier) and
    must never decrease.  ``total_pairs`` is the *logical* live pair count
    (distinct non-deleted keys — what an all-keyspace range scan would
    return); ``physical_pairs`` is the implementation's resident count,
    which may include stale duplicates and tombstones awaiting compaction.
    ``pending_debt`` is the deferred maintenance still owed (0 = fully
    maintained), the deamortization ledger of paper Sec. 5.1.

    ``bloom_probes`` / ``bloom_negative_skips`` / ``bloom_false_positives``
    are the Bloom-filter effectiveness counters of paper Sec. 5.2 (probes
    issued on point-query descents, negatives that skipped a run search,
    and positives whose search then missed).  Engines without per-run
    filters — or with filters disabled, e.g. ``nbtree-nobloom`` — report
    zeros, which is what lets saturation/query reports attribute the
    nbtree-vs-nbtree-nobloom query savings from driver JSON alone.

    ``maintain_units`` / ``maintain_wall_s`` / ``maintain_unit_p50_s`` /
    ``maintain_unit_p99_s`` / ``maintain_unit_p100_s`` record the *real*
    wall-clock cost of maintenance work units on the device tier (each
    ``maintain(1)`` step timed individually; totals are cumulative, and
    percentiles come from the shared bounded log-bucket histogram of
    :mod:`repro.obs.metrics` — exact p100, bucket-resolution p50/p99 —
    so long runs stay O(1) per snapshot), so open-loop runs — which
    charge a deterministic virtual
    service time on wall-clock engines — still report the measured
    service cost of the fused emptying cascade.  Sim-clock tiers report
    zeros (their maintenance cost is already the charged I/O delta).

    Sharded ensembles (``sharded:<base>``, DESIGN.md §6) aggregate: I/O
    counters are *summed* across shards (still monotone — retired shards'
    totals are folded in on rebalance), ``height`` is the max, and
    ``shards`` / ``shard_debt`` carry the ensemble width and the per-shard
    debt vector (single engines report ``shards=1``, ``shard_debt=[]``).
    Maintain-unit counters sum ``maintain_units``/``maintain_wall_s`` and
    take the max of the per-shard percentiles (a conservative ensemble
    tail: units run shard-local, so no shard's tail can exceed it).
    """

    engine: str
    clock: str               # "sim" (cost model) or "wall" (device tier)
    io_time_s: float
    io_seeks: int
    io_bytes_read: int
    io_bytes_written: int
    height: int
    total_pairs: int
    physical_pairs: int
    pending_debt: int
    n_inserts: int
    n_deletes: int
    n_queries: int
    n_ranges: int
    shards: int = 1
    shard_debt: list = dataclasses.field(default_factory=list)
    bloom_probes: int = 0
    bloom_negative_skips: int = 0
    bloom_false_positives: int = 0
    maintain_units: int = 0
    maintain_wall_s: float = 0.0
    maintain_unit_p50_s: float = 0.0
    maintain_unit_p99_s: float = 0.0
    maintain_unit_p100_s: float = 0.0
    #: host->device kernel dispatches issued by THIS engine (device tier;
    #: sharded ensembles sum across shards).  Per-instance — two engines
    #: in one process count independently, unlike the former module-global
    #: shim.  Sim tiers report 0.
    device_dispatches: int = 0
    #: device->host waits and reads of the device tier's fused path
    #: (``NBTreeIndex.sync_count``; sharded ensembles sum).  Sim tiers
    #: report 0.
    device_syncs: int = 0
    #: highest WAL commit LSN applied to this engine (0 = never ran under a
    #: durable frontend).  Written by the durable ingest path via
    #: :meth:`StorageEngine.note_applied`; the recovery invariant is that a
    #: recovered engine's live table equals the acked prefix <= this LSN
    #: (``repro.wal``, DESIGN.md §9).
    applied_lsn: int = 0


class StorageEngine(abc.ABC):
    """The unified engine protocol (see module docstring).

    Subclasses implement the four scalar hooks (or override :meth:`apply`
    wholesale, as the device adapter does) plus :meth:`stats` /
    :meth:`count_live`; :meth:`maintain` and :meth:`drain` default to
    no-debt engines.
    """

    name: str = "engine"

    def __init__(self):
        self._counts = {k: 0 for k in OpKind}
        self.applied_lsn = 0        # highest durably-logged commit applied

    # ------------------------------------------------------------------ apply
    def apply(self, batch: OpBatch) -> OpResult:
        n = len(batch)
        found = np.zeros(n, bool)
        values = np.full(n, -1, VAL_DTYPE)
        range_hits: list = [None] * n
        lat = np.zeros(n, np.float64)
        for i in range(n):
            kind = OpKind(int(batch.kinds[i]))
            k = int(batch.keys[i])
            if kind is OpKind.INSERT:
                lat[i] = self._do_insert(k, int(batch.vals[i]))
            elif kind is OpKind.DELETE:
                lat[i] = self._do_delete(k)
            elif kind is OpKind.QUERY:
                found[i], values[i], lat[i] = self._do_query(k)
            else:
                rk, rv, lat[i] = self._do_range(k, int(batch.his[i]))
                range_hits[i] = (rk, rv)
            self._counts[kind] += 1
        return OpResult(batch.kinds.copy(), found, values, range_hits, lat)

    # ------------------------------------------------------------ scalar hooks
    def _do_insert(self, key: int, val: int) -> float:
        raise UnsupportedOp(f"{self.name} does not support INSERT")

    def _do_delete(self, key: int) -> float:
        raise UnsupportedOp(f"{self.name} does not support DELETE")

    def _do_query(self, key: int):
        raise UnsupportedOp(f"{self.name} does not support QUERY")

    def _do_range(self, lo: int, hi: int):
        raise UnsupportedOp(f"{self.name} does not support RANGE")

    # ------------------------------------------------------------ observability
    def attach_tracer(self, tracer) -> None:
        """Attach a :class:`repro.obs.trace.Tracer` for span emission.

        Base implementation is a no-op — engines with nothing structured
        to report (the scalar cost-model tiers) simply ignore it.  The
        device adapter records ``nbtree.*`` spans (``Tracer.span``: the
        ring buffer and a running profiler's trace); sharded ensembles
        forward to every shard and emit split/debt events themselves.
        ``None`` detaches.  Called by the ingest frontends when
        observability is enabled.
        """

    # ------------------------------------------------------------- maintenance
    def maintain(self, budget: int = 1) -> int:
        """Run up to ``budget`` units of deferred work; returns pending debt."""
        return 0

    def drain(self) -> None:
        """Finish all deferred work (tests / shutdown)."""
        while self.maintain(64):
            pass

    # -------------------------------------------------------------- durability
    def note_applied(self, lsn: int) -> None:
        """Record that every WAL commit up to ``lsn`` has been applied.

        Called by the durable ingest frontend after each group commit's
        ``apply`` and by WAL replay during recovery; surfaced as
        ``EngineStats.applied_lsn``.  Monotone by construction.
        """
        if lsn > self.applied_lsn:
            self.applied_lsn = int(lsn)

    def dump_live(self) -> tuple:
        """``(keys, vals)`` of every visible pair, key-sorted, cost-free.

        The snapshot primitive of the durability subsystem: an engine-table
        checkpoint is exactly this dump keyed by the commit LSN it reflects.
        Like :meth:`count_live` it is an observer — it must charge no I/O
        cost — and O(n).
        """
        raise UnsupportedOp(f"{self.name} does not support dump_live")

    def dump_live_range(self, lo: int, hi: int) -> tuple:
        """``(keys, vals)`` of visible pairs with ``lo <= key <= hi``.

        Cost-free observer like :meth:`dump_live`.  A tenant namespace
        (``repro.tenancy``) is a contiguous encoded key interval, so this
        is the per-namespace snapshot/stats primitive; sharded ensembles
        override it to consult only intersecting shards.
        """
        keys, vals = self.dump_live()
        a = int(np.searchsorted(keys, np.asarray(lo, KEY_DTYPE), "left"))
        b = int(np.searchsorted(keys, np.asarray(hi, KEY_DTYPE), "right"))
        return keys[a:b], vals[a:b]

    def count_live_range(self, lo: int, hi: int) -> int:
        """Exact number of visible keys in ``[lo, hi]`` (cost-free)."""
        return len(self.dump_live_range(lo, hi)[0])

    # ------------------------------------------------------------------- stats
    @abc.abstractmethod
    def io_time_s(self) -> float:
        """Cumulative charged cost (O(1)) — the cheap per-step poll.

        ``stats()`` carries the same number plus the full snapshot; use
        this accessor in hot loops that only need the monotone cost.
        """

    @abc.abstractmethod
    def height(self) -> int:
        """Index height / level count (O(height)) — cheap, like io_time_s."""

    @abc.abstractmethod
    def stats(self) -> EngineStats:
        """Full snapshot.  O(n): ``total_pairs`` is an exact logical count
        (a complete scan of resident pairs), so poll sparingly — per run,
        not per op; use :meth:`io_time_s` for cheap cost polling."""

    @abc.abstractmethod
    def count_live(self) -> int:
        """Exact number of visible (non-deleted, deduplicated) keys.

        Must not charge I/O cost — it is an observer, not an operation.
        O(n): scans all resident pairs.
        """


# =========================================================== cost-model tiers
class CostModelEngine(StorageEngine):
    """Adapter base for the host tiers: scalar impl + explicit CostModel."""

    clock = "sim"

    def __init__(self, impl):
        super().__init__()
        self.impl = impl

    @property
    def cm(self) -> CostModel:
        return self.impl.cm

    def _do_insert(self, key, val):
        return float(self.impl.insert(key, val))

    def _do_delete(self, key):
        return float(self.impl.delete(key))

    def _do_query(self, key):
        v, t = self.impl.query(key)
        return v is not None, -1 if v is None else int(v), float(t)

    def _do_range(self, lo, hi):
        rk, rv = self.impl.range_query(lo, hi)
        return rk, rv, float(self.impl._last_query_time)

    def dump_live(self) -> tuple:
        # an all-keyspace range scan is exact on every host tier; snapshot
        # and restore the cost counters so observation charges nothing.
        cm = self.cm
        saved = (cm.seeks, cm.bytes_read, cm.bytes_written, cm.pages)
        try:
            rk, rv = self.impl.range_query(0, int(np.iinfo(KEY_DTYPE).max))
        finally:
            cm.seeks, cm.bytes_read, cm.bytes_written, cm.pages = saved
        return (np.asarray(rk, KEY_DTYPE), np.asarray(rv, VAL_DTYPE))

    def count_live(self) -> int:
        return len(self.dump_live()[0])

    def height(self) -> int:
        return 1

    def _pending_debt(self) -> int:
        return 0

    def _bloom_stats(self) -> tuple:
        """(probes, negative_skips, false_positives); zeros by default."""
        return (0, 0, 0)

    def io_time_s(self) -> float:
        return self.cm.time

    def stats(self) -> EngineStats:
        cm = self.cm
        probes, skips, fps = self._bloom_stats()
        return EngineStats(
            engine=self.name, clock=self.clock, io_time_s=cm.time,
            io_seeks=cm.seeks, io_bytes_read=cm.bytes_read,
            io_bytes_written=cm.bytes_written, height=self.height(),
            total_pairs=self.count_live(),
            physical_pairs=int(self.impl.total_pairs()),
            pending_debt=self._pending_debt(),
            n_inserts=self._counts[OpKind.INSERT],
            n_deletes=self._counts[OpKind.DELETE],
            n_queries=self._counts[OpKind.QUERY],
            n_ranges=self._counts[OpKind.RANGE],
            bloom_probes=int(probes), bloom_negative_skips=int(skips),
            bloom_false_positives=int(fps),
            applied_lsn=self.applied_lsn)


class RefNBTreeEngine(CostModelEngine):
    """The paper-faithful NB-tree (refimpl) under the protocol."""

    name = "nbtree"

    def __init__(self, f: int = 3, sigma: int = 4096, *, device: Device = HDD,
                 **kw):
        super().__init__(NBTree(f=f, sigma=sigma, device=device, **kw))

    def maintain(self, budget: int = 1) -> int:
        """Advance the pending cascade by up to ``budget`` page quanta."""
        t = self.impl
        if t._cascade is None:
            return 0
        try:
            for _ in range(budget):
                next(t._cascade)
        except StopIteration:
            t._cascade = None
            t._frozen = None
        return 0 if t._cascade is None else 1

    def height(self) -> int:
        return self.impl.height

    def _pending_debt(self) -> int:
        return 0 if self.impl._cascade is None else 1

    def _bloom_stats(self) -> tuple:
        t = self.impl
        return (t.bloom_probes, t.bloom_negative_skips,
                t.bloom_false_positives)


class LSMEngine(CostModelEngine):
    name = "lsm"

    def __init__(self, mem_pairs: int = 4096, ratio: int = 10, *,
                 device: Device = HDD, **kw):
        super().__init__(LSMTree(mem_pairs=mem_pairs, ratio=ratio,
                                 device=device, **kw))

    def height(self) -> int:
        return len(self.impl.levels)

    def _bloom_stats(self) -> tuple:
        t = self.impl
        return (t.bloom_probes, t.bloom_negative_skips,
                t.bloom_false_positives)


class BTreeEngine(CostModelEngine):
    """Incremental B+-tree (per-insert leaf read-modify-write)."""

    name = "btree"

    def __init__(self, *, device: Device = HDD, **kw):
        super().__init__(BPlusTree(device=device, **kw))


class BEpsilonEngine(CostModelEngine):
    name = "bepsilon"

    def __init__(self, *, fanout: int = 16, node_bytes: int = 4 << 20,
                 cached_levels: int = 2, device: Device = HDD, **kw):
        super().__init__(BEpsilonTree(fanout=fanout, node_bytes=node_bytes,
                                      cached_levels=cached_levels,
                                      device=device, **kw))

    def height(self) -> int:
        h, node = 0, self.impl.root
        while not node.is_leaf:
            node = node.children[0]
            h += 1
        return h


class BulkBTreeEngine(CostModelEngine):
    """Static bulk-loaded B+-tree: QUERY/RANGE only (the paper's yardstick)."""

    name = "btree-bulk"

    def __init__(self, keys, vals, *, device: Device = HDD, **kw):
        super().__init__(BPlusTreeBulk(keys, vals, device=device, **kw))

    def _do_insert(self, key, val):
        raise UnsupportedOp("btree-bulk is static: INSERT unsupported")

    def _do_delete(self, key):
        raise UnsupportedOp("btree-bulk is static: DELETE unsupported")

    def count_live(self) -> int:
        return len(self.impl.keys)


# ================================================================ device tier
def _pow2(n: int) -> int:
    """The power-of-two bucket ``_pad_pow2`` pads a run of ``n`` ops to."""
    return 1 << max(0, n - 1).bit_length()


def _pad_pow2(a: np.ndarray) -> np.ndarray:
    """Pad a 1-d array to the next power-of-two length by repeating a[-1]."""
    n = len(a)
    target = _pow2(n)
    if n in (0, target):
        return a
    return np.concatenate([a, np.repeat(a[-1:], target - n)])


class DeviceNBTreeEngine(StorageEngine):
    """The jax/Pallas device tier under the protocol.

    ``apply`` groups maximal same-kind op runs into one fused device call
    (sequential semantics preserved — see module docstring); latency is the
    group's host wall-clock amortized over its ops, and ``stats().clock`` is
    ``"wall"`` so drivers never mix it with simulated seconds.

    Mixed workloads produce same-kind runs of arbitrary length, and the
    fused device calls are shape-specialized jits — so every group is padded
    to a power-of-two bucket to bound recompiles: QUERY/RANGE pads repeat
    the last op and drop the extra outputs (read-only), INSERT/DELETE pads
    repeat the last op verbatim, a blind re-write of the same (key, value)
    that newest-wins dedup makes logically invisible (the physical duplicate
    is retired at the next leaf compaction, like any stale copy).

    With a tracer attached (:meth:`attach_tracer`), ``apply`` records an
    ``nbtree.apply`` span (``ops``, ``seq``: this engine's commit number)
    holding one ``nbtree.run`` span per same-kind run (``kind``, ``n``,
    ``padded``), and the index adds its ``nbtree.unit`` /
    ``nbtree.dispatch`` / ``nbtree.sync`` spans (``core/jax_nbtree.py``).
    """

    name = "jax-nbtree"
    clock = "wall"

    def __init__(self, f: int = 4, sigma: int = 2048, *, max_nodes: int = 256,
                 max_results: int = 512, **kw):
        super().__init__()
        from .jax_nbtree import NBTreeIndex, TOMBSTONE32  # jax import deferred
        self._tombstone32 = TOMBSTONE32
        self.idx = NBTreeIndex(f=f, sigma=sigma, max_nodes=max_nodes, **kw)
        self._max_results = max_results
        self._wall_s = 0.0
        # wall-clock per maintenance work unit (each maintain(1) timed
        # individually) — the real service cost of the fused emptying
        # cascade, surfaced as EngineStats maintain-unit percentiles.
        # Shared log-bucket histogram (repro.obs.metrics): O(#buckets)
        # memory forever, exact count/total/p100, bucket-interpolated
        # p50/p99 — so long-running servers pay O(1) per unit and per
        # stats() snapshot.
        self._maintain_unit_s = LogBucketHistogram()
        self._commits = 0     # apply calls: the ``seq`` of nbtree.apply
        self._tracer = None

    # ------------------------------------------------------------------ apply
    def apply(self, batch: OpBatch) -> OpResult:
        self._commits += 1
        if self._tracer is None:
            return self._apply(batch)
        with self._tracer.span("dispatch", "nbtree.apply", ops=len(batch),
                               seq=self._commits):
            return self._apply(batch)

    def _apply(self, batch: OpBatch) -> OpResult:
        n = len(batch)
        kinds = np.asarray(batch.kinds)
        res = OpResult(kinds.copy(), np.zeros(n, bool),
                       np.full(n, -1, VAL_DTYPE), [None] * n,
                       np.zeros(n, np.float64), np.zeros(n, bool))
        i = 0
        while i < n:
            j = i + 1
            while j < n and kinds[j] == kinds[i]:
                j += 1
            kind = OpKind(int(kinds[i]))
            sl = slice(i, j)
            t0 = time.perf_counter()
            if self._tracer is None:
                self._run(kind, batch, sl, res)
            else:
                with self._tracer.span("dispatch", "nbtree.run",
                                       kind=kind.name.lower(), n=j - i,
                                       padded=_pow2(j - i)):
                    self._run(kind, batch, sl, res)
            dt = time.perf_counter() - t0
            self._wall_s += dt
            res.latency_s[sl] = dt / (j - i)
            self._counts[kind] += j - i
            i = j
        return res

    def _run(self, kind: OpKind, batch: OpBatch, sl: slice,
             res: OpResult) -> None:
        """One same-kind run ``batch[sl]``: pad, put, call, and wait for
        (or read back) its results into ``res``."""
        import jax

        idx = self.idx
        keys = _pad_pow2(batch.keys[sl].astype(np.uint32))
        if kind is OpKind.INSERT:
            idx.insert_batch(keys, _pad_pow2(batch.vals[sl].astype(np.int32)))
            idx._fetch("ack", idx.run_keys, jax.block_until_ready)
        elif kind is OpKind.DELETE:
            idx.delete_batch(keys)
            idx._fetch("ack", idx.run_keys, jax.block_until_ready)
        elif kind is OpKind.QUERY:
            real = sl.stop - sl.start
            pres, vals = idx.query_batch(keys)
            pres = idx._fetch("found", pres)[:real]
            vals = idx._fetch("values", vals)[:real]
            res.found[sl] = pres
            res.values[sl] = np.where(pres, vals.astype(np.int64), -1)
        else:
            self._apply_ranges(keys, batch, sl, res)

    def _apply_ranges(self, los: np.ndarray, batch: OpBatch, sl: slice,
                      res: OpResult) -> None:
        idx = self.idx
        his = _pad_pow2(batch.his[sl].astype(np.uint32))
        while True:
            rk, rv, cnt, trunc = idx.range_query_batch(
                los, his, max_results=self._max_results)
            trunc = idx._fetch("range_truncated", trunc)
            if not trunc.any() or self._max_results >= (1 << 20):
                break
            self._max_results *= 2      # sticky: later batches start larger
        rk = idx._fetch("range_keys", rk)
        rv = idx._fetch("range_vals", rv)
        cnt = idx._fetch("range_count", cnt)
        for b in range(sl.stop - sl.start):
            c = int(cnt[b])
            res.range_hits[sl.start + b] = (rk[b, :c].astype(KEY_DTYPE),
                                            rv[b, :c].astype(VAL_DTYPE))
            res.range_truncated[sl.start + b] = bool(trunc[b])

    # ------------------------------------------------------------- maintenance
    def maintain(self, budget: int = 1) -> int:
        """Run up to ``budget`` units, timing each unit individually.

        ``budget <= 0`` is the conventional free debt poll.  Units run one
        at a time so every flush/split gets its own wall-clock sample —
        the p50/p99/p100 the stats snapshot reports.
        """
        if budget <= 0:
            return self.idx.maintain(0)
        pending = self.idx.maintain(0)
        for _ in range(int(budget)):
            if not pending:
                break
            u0 = self.idx.units_done
            t0 = time.perf_counter()
            pending = self.idx.maintain(1)
            dt = time.perf_counter() - t0
            self._wall_s += dt
            if self.idx.units_done > u0:   # not a stale-entry-only pop
                self._maintain_unit_s.add(dt)
        return pending

    def drain(self) -> None:
        while self.maintain(64):
            pass

    # ------------------------------------------------------------------- stats
    def dump_live(self) -> tuple:
        run_keys = np.asarray(self.idx.run_keys)
        run_vals = np.asarray(self.idx.run_vals)
        # pre-order (ancestors first) + leftmost-first within a run is the
        # freshest-copy-wins order both query paths resolve by, so the
        # first occurrence of each key in that order is its live copy.
        ks, vs, stack = [], [], [self.idx.root]
        while stack:
            node = stack.pop()
            ks.append(run_keys[node.nid, : node.count])
            vs.append(run_vals[node.nid, : node.count])
            stack.extend(reversed(node.children))
        keys, first = np.unique(np.concatenate(ks), return_index=True)
        vals = np.concatenate(vs)[first]
        live = vals != self._tombstone32
        return keys[live].astype(KEY_DTYPE), vals[live].astype(VAL_DTYPE)

    def count_live(self) -> int:
        return len(self.dump_live()[0])

    def io_time_s(self) -> float:
        return self._wall_s

    def height(self) -> int:
        return self.idx.height

    def attach_tracer(self, tracer) -> None:
        """Record ``nbtree.apply`` / ``nbtree.run`` spans here and forward
        to the index for its unit, dispatch and sync spans (class
        docstring); ``None`` detaches both."""
        self._tracer = tracer
        self.idx.attach_tracer(tracer)

    def stats(self) -> EngineStats:
        mu = self._maintain_unit_s
        return EngineStats(
            engine=self.name, clock=self.clock, io_time_s=self._wall_s,
            io_seeks=0, io_bytes_read=0, io_bytes_written=0,
            height=self.height(), total_pairs=self.count_live(),
            physical_pairs=int(self.idx.total_pairs()),
            pending_debt=len(self.idx._pending),
            n_inserts=self._counts[OpKind.INSERT],
            n_deletes=self._counts[OpKind.DELETE],
            n_queries=self._counts[OpKind.QUERY],
            n_ranges=self._counts[OpKind.RANGE],
            bloom_probes=self.idx.bloom_probes,
            bloom_negative_skips=self.idx.bloom_negative_skips,
            bloom_false_positives=self.idx.bloom_false_positives,
            maintain_units=mu.count,
            maintain_wall_s=mu.total,
            maintain_unit_p50_s=mu.quantile(0.50),
            maintain_unit_p99_s=mu.quantile(0.99),
            maintain_unit_p100_s=mu.max if mu.count else 0.0,
            device_dispatches=self.idx.dispatch_count,
            device_syncs=self.idx.sync_count,
            applied_lsn=self.applied_lsn)


# =================================================================== registry
_REGISTRY: dict = {}

#: the paper's comparison set — one engine per tier, every benchmark's axis.
FIVE_TIERS = ("nbtree", "lsm", "btree", "bepsilon", "jax-nbtree")


def register_engine(name: str, factory) -> None:
    assert name not in _REGISTRY, f"duplicate engine name {name!r}"
    _REGISTRY[name] = factory


def make_engine(name: str, **kw) -> StorageEngine:
    if name.startswith("sharded:"):
        # range-partitioned ensemble of any registered engine (DESIGN.md §6):
        # make_engine("sharded:nbtree", shards=4, **base_kw).  Imported
        # lazily — repro.shard programs against this module.
        from repro.shard import ShardedEngine
        base = name.split(":", 1)[1]
        if base not in _REGISTRY:
            raise KeyError(f"unknown base engine {base!r} for {name!r}; "
                           f"registered: {sorted(_REGISTRY)}")
        eng = ShardedEngine(base, **kw)
        eng.name = name
        return eng
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown engine {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None
    eng = factory(**kw)
    eng.name = name
    return eng


def available_engines() -> tuple:
    return tuple(sorted(_REGISTRY))


register_engine("nbtree", RefNBTreeEngine)
register_engine("nbtree-basic",
                lambda **kw: RefNBTreeEngine(deamortize=False, **kw))
register_engine("nbtree-nobloom",
                lambda **kw: RefNBTreeEngine(use_bloom=False, **kw))
register_engine("lsm", LSMEngine)
register_engine("blsm", lambda **kw: LSMEngine(**{"max_levels": 3, **kw}))
register_engine("btree", BTreeEngine)
register_engine("bepsilon", BEpsilonEngine)
register_engine("jax-nbtree", DeviceNBTreeEngine)
