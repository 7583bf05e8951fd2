"""Device-tier spans and the sync counter (DESIGN.md §11).

* **Profiler nesting** — with a :class:`Tracer` attached and a
  ``jax.profiler`` session running, every ``nbtree.dispatch`` and
  ``nbtree.sync`` lands in the trace's host plane inside an ``nbtree.run``
  or ``nbtree.unit``, and their counts equal the index's
  ``dispatch_count`` / ``sync_count`` deltas.
* **Sync budget** — a read run makes 5 device->host syncs (three Bloom
  tallies, found, values), an insert or delete run 1 (the ack), a range
  run 4, a flush unit 1 and a leaf split unit 1: the regression guard for
  the blocking reads ROADMAP speed item 2 wants gone, in the manner of
  ``test_flush_unit_is_one_dispatch``.
* **Detached** — after ``attach_tracer(None)`` an engine (single or
  sharded) calls no tracer method and records nothing.
"""
import glob

import numpy as np
import pytest

from repro.core.engine_api import OpBatch, make_engine
from repro.obs import Tracer

#: sigma 64 keeps a run to one 1,024-key tile (interpret-mode Pallas on the
#: CPU); 512 rows never grow the tables, so nothing recompiles mid-test.
TINY = {"f": 4, "sigma": 64, "max_nodes": 512}
INSERTS, READS, DELETES, RANGES = 32, 16, 8, 4


def _round(eng, rng, mixed: bool = True) -> None:
    """One commit (inserts, then reads, deletes and ranges) + maintain(1)."""
    k = rng.integers(1, 2**31, INSERTS, dtype=np.uint64)
    parts = [OpBatch.inserts(k, np.arange(INSERTS))]
    if mixed:
        q = rng.integers(1, 2**31, READS, dtype=np.uint64)
        lo = rng.integers(1, 2**31 - 2**20, RANGES, dtype=np.uint64)
        parts += [OpBatch.queries(np.concatenate([k[:READS // 2],
                                                  q[:READS // 2]])),
                  OpBatch.deletes(k[:DELETES]),
                  OpBatch.ranges(lo, lo + 2**20)]
    eng.apply(OpBatch.concat(parts))
    eng.maintain(1)


def _host_events(log_dir: str) -> dict:
    """``nbtree.*`` events of the one trace under ``log_dir``, by host
    line: ``{line: [(name, start_ns, end_ns, stats)]}``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nbtree."):
                    out.setdefault((plane.name, line.name), []).append(
                        (ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def test_profiler_nests_dispatch_and_sync_in_run_or_unit(tmp_path):
    import jax

    rng = np.random.default_rng(5)
    eng = make_engine("jax-nbtree", **TINY)
    for _ in range(3):                  # compile outside the profiled part
        _round(eng, rng)
    tr = Tracer()
    eng.attach_tracer(tr)
    d0, s0 = eng.idx.dispatch_count, eng.idx.sync_count
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(24):
            _round(eng, rng)
    finally:
        jax.profiler.stop_trace()
    eng.attach_tracer(None)
    dispatches = eng.idx.dispatch_count - d0
    syncs = eng.idx.sync_count - s0

    lines = _host_events(str(tmp_path))
    assert len(lines) == 1              # one serving thread
    (events,) = lines.values()
    names = [e[0] for e in events]
    assert names.count("nbtree.dispatch") == dispatches > 0
    assert names.count("nbtree.sync") == syncs > 0
    assert names.count("nbtree.apply") == 24
    holders = [(s, e) for n, s, e, _ in events
               if n in ("nbtree.run", "nbtree.unit")]
    for n, s, e, _ in events:
        if n in ("nbtree.dispatch", "nbtree.sync"):
            assert any(hs <= s and e <= he for hs, he in holders), (n, s, e)
    runs = [st for n, _, _, st in events if n == "nbtree.run"]
    assert {r["kind"] for r in runs} == {"insert", "query", "delete", "range"}
    assert all(r["padded"] >= r["n"] for r in runs)
    units = [st["kind"] for n, _, _, st in events if n == "nbtree.unit"]
    assert "flush" in units and "split_leaf" in units
    whats = {st["what"] for n, _, _, st in events if n == "nbtree.sync"}
    assert {"ack", "found", "values", "bloom_tallies", "flush_counts",
            "split_out", "range_keys"} <= whats
    # the ring buffer holds the same spans
    assert len(tr.spans("sync")) == syncs and tr.dropped_events == 0
    assert sum(e["name"] == "nbtree.dispatch"
               for e in tr.spans("dispatch")) == dispatches


def test_sync_budget_per_run_and_unit():
    rng = np.random.default_rng(9)
    eng = make_engine("jax-nbtree", **TINY)
    idx = eng.idx
    tr = Tracer()
    eng.attach_tracer(tr)

    def syncs(batch) -> int:
        s0 = idx.sync_count
        eng.apply(batch)
        return idx.sync_count - s0

    budget = {"flush": 1, "split_root": 1, "split_leaf": 1}
    seen: dict = {}
    for _ in range(40):
        k = rng.integers(1, 2**31, INSERTS, dtype=np.uint64)
        lo = rng.integers(1, 2**31 - 2**20, RANGES, dtype=np.uint64)
        assert syncs(OpBatch.inserts(k, np.arange(INSERTS))) == 1
        assert syncs(OpBatch.queries(k[:READS])) == 5
        assert syncs(OpBatch.deletes(k[:DELETES])) == 1
        assert syncs(OpBatch.ranges(lo, lo + 2**20)) == 4
        while idx._pending:
            n0, s0, h0 = len(tr.spans("flush_unit")), idx.sync_count, idx.height
            eng.maintain(1)
            units = tr.spans("flush_unit")[n0:]
            got = idx.sync_count - s0
            if not units:               # stale queue entries retire free
                assert got == 0
                continue
            (unit,) = units
            kind = unit["args"]["kind"]
            seen[kind] = seen.get(kind, 0) + 1
            assert unit["args"]["pairs"] > idx.sigma
            if kind in budget:
                assert got == budget[kind], (kind, got)
            else:                       # one more per internal node split
                assert 2 <= got <= h0 + 1, (kind, got)
            assert idx.height == h0 + (kind in ("split_root", "grow"))
    assert seen.get("flush", 0) > 10 and seen.get("split_leaf", 0) > 2
    assert seen.get("split_root") == 1 and seen.get("grow", 0) >= 1
    assert eng.stats().device_syncs == idx.sync_count


class _Spy(Tracer):
    """Counts every recording call."""

    def __init__(self):
        super().__init__()
        self.calls = 0

    def span(self, *a, **k):
        self.calls += 1
        return super().span(*a, **k)

    def complete(self, *a, **k):
        self.calls += 1
        return super().complete(*a, **k)

    def instant(self, *a, **k):
        self.calls += 1
        return super().instant(*a, **k)


@pytest.mark.parametrize("name", ["jax-nbtree", "sharded:jax-nbtree"])
def test_detached_engine_records_nothing(name):
    rng = np.random.default_rng(13)
    kw = {"shards": 2} if name.startswith("sharded:") else {}
    eng = make_engine(name, **kw, **TINY)
    spy = _Spy()
    eng.attach_tracer(spy)
    for _ in range(4):
        _round(eng, rng, mixed=False)
    assert spy.calls > 0 and len(spy) > 0
    eng.attach_tracer(None)
    calls, n = spy.calls, len(spy)
    devices = getattr(eng, "shard_engines", (eng,))
    assert all(e._tracer is None and e.idx._tracer is None for e in devices)
    for _ in range(4):
        _round(eng, rng, mixed=False)
    assert spy.calls == calls and len(spy) == n
    st = eng.stats()
    assert st.device_syncs == sum(e.idx.sync_count for e in devices) > 0
