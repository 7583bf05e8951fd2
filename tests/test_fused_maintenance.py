"""Fused emptying-cascade pipeline (DESIGN.md §8): parity, budgets, Blooms.

Three contracts of the one-dispatch maintenance path:

* **Physical parity** — the fused flush/split/insert/clear impls produce
  *bit-identical* device tables (runs, counts, filters, structure mirrors)
  to the pre-fusion eager path on random insert/delete/maintain/drain
  interleavings, and both agree with a sorted-dict oracle on every visible
  query/range result.
* **Dispatch budget** — a flush unit is exactly ONE device dispatch and a
  split unit a small constant, asserted through the ``_device_call``
  counting funnel (the regression guard for the >= 5x dispatch reduction
  recorded in BENCH_device_ingest.json).
* **Incremental-Bloom invariant** — ORing only an insert batch's bits into
  the root filter is bit-identical to a from-scratch rebuild over the grown
  run, at every step and for every node row after drain.
"""
import numpy as np
import pytest

import repro.core.jax_nbtree as jnb
from repro.core.jax_nbtree import NBTreeIndex, _build_bloom


def _pool(seed, n):
    rng = np.random.default_rng(seed)
    return rng.choice(np.arange(1, 2**31, dtype=np.uint32), n, replace=False)


def _assert_same_tables(a: NBTreeIndex, b: NBTreeIndex, tag: str) -> None:
    assert a.max_nodes == b.max_nodes, tag
    for name in ("run_keys", "run_vals", "run_count", "bloom",
                 "pivots", "children", "nchild"):
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), f"{tag}: {name}"
    assert a._next_id == b._next_id, tag
    assert [n.nid for n in a._pending] == [n.nid for n in b._pending], tag

    def shape(node):
        return (node.nid, node.count, tuple(node.skeys),
                tuple(shape(c) for c in node.children))

    assert shape(a.root) == shape(b.root), tag


def _apply_round(idx: NBTreeIndex, oracle: dict, rng, pool, cursor: int) -> int:
    """One randomized round of inserts/deletes/maintain; returns new cursor."""
    n = int(rng.integers(32, 193))
    ks = pool[cursor: cursor + n]
    vs = (np.arange(len(ks)) + cursor).astype(np.int32)
    idx.insert_batch(ks, vs)
    for k, v in zip(ks.tolist(), vs.tolist()):
        oracle[k] = v
    if rng.random() < 0.4 and cursor:
        dn = int(rng.integers(1, 64))
        dk = pool[max(0, cursor - dn): cursor]
        idx.delete_batch(dk)
        for k in dk.tolist():
            oracle[k] = None
    idx.maintain(int(rng.integers(0, 3)))
    return cursor + n


def test_fused_matches_eager_and_oracle():
    """Random interleavings: bit-identical tables + oracle-exact results.

    ``max_nodes=8`` forces the fused one-dispatch table growth on both
    paths mid-run, so ``_grow_impl`` parity is covered too.
    """
    rng_a, rng_b, rng_q = (np.random.default_rng(s) for s in (21, 21, 99))
    pool = _pool(20, 6000)
    fused = NBTreeIndex(f=3, sigma=256, max_nodes=8, fused=True)
    eager = NBTreeIndex(f=3, sigma=256, max_nodes=8, fused=False)
    oracle: dict = {}
    shadow: dict = {}
    ca = cb = 0
    for r in range(18):
        ca = _apply_round(fused, oracle, rng_a, pool, ca)
        cb = _apply_round(eager, shadow, rng_b, pool, cb)
        assert ca == cb and oracle == shadow   # identical op streams
        if r % 6 == 5:
            fused.drain()
            eager.drain()
        if r % 3 == 2:
            _assert_same_tables(fused, eager, f"round {r}")
    fused.drain()
    eager.drain()
    _assert_same_tables(fused, eager, "final")
    fused.check_invariants()
    eager.check_invariants()
    assert fused.max_nodes > 8          # growth actually happened

    # visible semantics vs the sorted-dict oracle, on both paths
    seen = pool[:ca]
    q = rng_q.choice(seen, 800, replace=False)
    for idx in (fused, eager):
        p, v = idx.query_batch(q)
        p, v = np.asarray(p), np.asarray(v)
        for j, k in enumerate(q.tolist()):
            want = oracle.get(k)
            assert p[j] == (want is not None), k
            if want is not None:
                assert v[j] == want, k
    live = sorted(k for k, v in oracle.items() if v is not None)
    lo, hi = live[len(live) // 4], live[3 * len(live) // 4]
    want_r = [(k, oracle[k]) for k in live if lo <= k <= hi]
    for idx in (fused, eager):
        rk, rv, cnt, trunc = idx.range_query_batch(
            np.asarray([lo]), np.asarray([hi]), max_results=len(want_r) + 8)
        assert not bool(np.asarray(trunc)[0])
        c = int(np.asarray(cnt)[0])
        got = list(zip(np.asarray(rk)[0, :c].tolist(),
                       np.asarray(rv)[0, :c].tolist()))
        assert got == want_r


def test_flush_unit_is_one_dispatch(monkeypatch):
    """Dispatch-budget regression: flush == 1 call, split a small constant."""
    calls: list = []
    real = jnb._device_call

    def counting(fn, *args, **kwargs):
        calls.append(getattr(fn, "__name__", repr(fn)))
        return real(fn, *args, **kwargs)

    monkeypatch.setattr(jnb, "_device_call", counting)
    idx = NBTreeIndex(f=4, sigma=256, max_nodes=64)
    pool = _pool(7, 8192)
    cursor = 0
    flush_units = split_units = 0
    while cursor < len(pool):
        idx.insert_batch(pool[cursor:cursor + 128],
                         np.arange(128, dtype=np.int32))
        cursor += 128
        while idx._pending:
            unit_node = next((n for n in idx._pending
                              if n.count > idx.sigma), None)
            # classify before running: a root-leaf split grows children
            # onto the *same* node object.
            was_leaf = unit_node.is_leaf if unit_node is not None else None
            calls.clear()
            idx.maintain(1)
            if unit_node is None:
                assert not calls       # stale entries retire for free
                continue
            if was_leaf:
                # split unit: split + clear + <= 4 structure syncs per
                # level of upward cascade (+ possibly one table grow)
                split_units += 1
                assert len(calls) <= 16, calls
            else:
                flush_units += 1
                assert calls == ["_flush_impl"], calls
    assert flush_units > 10 and split_units > 2   # both paths exercised


def test_incremental_bloom_equals_from_scratch():
    """bloom[0] after incremental ORs == rebuild over the grown run, always;
    every node row's filter == rebuild over its row after drain."""
    rng = np.random.default_rng(13)
    pool = _pool(12, 4096)
    idx = NBTreeIndex(f=3, sigma=256, max_nodes=32)
    cursor = 0
    for r in range(10):
        n = int(rng.integers(16, 160))
        ks = pool[cursor: cursor + n]
        cursor += n
        if r % 3 == 2:
            idx.delete_batch(ks[: n // 2])      # tombstones hash like keys
        idx.insert_batch(ks, np.arange(len(ks), dtype=np.int32))
        scratch = _build_bloom(idx.run_keys[0], idx.nbits, idx.h)
        assert np.array_equal(np.asarray(idx.bloom[0]), np.asarray(scratch)), r
        idx.maintain(int(rng.integers(0, 2)))
        scratch = _build_bloom(idx.run_keys[0], idx.nbits, idx.h)
        assert np.array_equal(np.asarray(idx.bloom[0]), np.asarray(scratch)), r
    idx.drain()
    blooms = np.asarray(idx.bloom)
    keys = np.asarray(idx.run_keys)
    for nid in range(idx._next_id):
        scratch = np.asarray(_build_bloom(keys[nid], idx.nbits, idx.h))
        assert np.array_equal(blooms[nid], scratch), nid


def test_pending_queue_bookkeeping():
    """Deque + membership counter stay consistent under churn."""
    idx = NBTreeIndex(f=3, sigma=64, max_nodes=32)
    pool = _pool(5, 2048)
    for i in range(0, 2048, 64):
        idx.insert_batch(pool[i:i + 64], np.arange(64, dtype=np.int32))
        assert sum(idx._pending_n.values()) == len(idx._pending)
        assert ({n.nid for n in idx._pending}
                == set(idx._pending_n)), "membership set out of sync"
        idx.maintain(1)
    idx.drain()
    assert not idx._pending and not idx._pending_n
    idx.check_invariants()


def test_maintain_budget_still_bounded_fused():
    """maintain(k) on the fused path keeps the deamortization contract."""
    rng = np.random.default_rng(6)
    idx = NBTreeIndex(f=4, sigma=512, max_nodes=128)
    keys = _pool(66, 8000)
    max_drop = 0
    for i in range(0, len(keys), 256):
        idx.insert_batch(keys[i:i + 256], np.arange(256, dtype=np.int32))
        before = len(idx._pending)
        idx.maintain(1)
        max_drop = max(max_drop, before - len(idx._pending))
    assert max_drop <= 1
    idx.drain()
    idx.check_invariants()


# ------------------------------------------------------------ chained flushes
#: f=4, sigma=64: a run is one 1,024-key tile (interpret-mode Pallas on the
#: CPU), 512 rows never grow, and 2,400 keys make a tree of height 3.
CHAIN = {"f": 4, "sigma": 64, "max_nodes": 512}


def _keys(order: str, n: int = 2400) -> np.ndarray:
    keys = np.arange(1, n + 1, dtype=np.uint32) * 3
    if order == "descending":
        return keys[::-1].copy()
    if order == "uniform":
        return np.random.default_rng(11).permutation(keys)
    return keys


def _pair():
    return (NBTreeIndex(fused=True, **CHAIN),
            NBTreeIndex(fused=False, **CHAIN))


def _stop_reason(idx: NBTreeIndex, last) -> str:
    """Why the host's rule runs no further flush right after ``last``'s:
    its largest child is a leaf to split, lacks room in a child of its own
    (a room-first descent), holds at most sigma pairs while the front of
    the queue is a stale entry, or none of these (``budget``: the rule
    would flush that child next)."""
    sizes = [c.count for c in last.children]
    big = last.children[int(np.argmax(sizes))]
    if big.count > idx.sigma:
        if big.is_leaf:
            return "leaf_split"
        if any(g.count + idx.sigma > idx.run_cap for g in big.children):
            return "room_first"
        return "budget"
    if idx._pending and idx._pending[0].count <= idx.sigma:
        return "stale_entry"
    return "other"


def _host_next(idx: NBTreeIndex, node) -> int:
    """The node the host's rule flushes right after ``node``'s flush, with
    nothing between (-1: none): its first largest child, where that holds
    more than sigma pairs, is internal and has room in each child."""
    sizes = [c.count for c in node.children]
    big = node.children[int(np.argmax(sizes))]
    if (big.count > idx.sigma and not big.is_leaf and all(
            g.count + idx.sigma <= idx.run_cap for g in big.children)):
        return big.nid
    return -1


def _record_chains(idx: NBTreeIndex) -> list:
    """(units, stop reason) of every chain ``idx`` runs from now on; each
    flush program's next cursor must be the host rule's next node."""
    chains: list = []
    chain, flushed, dispatch = idx._flush_chain, idx._flushed, idx._dispatch
    last: list = []
    device: list = []      # (cursor in, cursor out) of each flush program
    host: list = []        # the host rule's next node after each flush

    def record_dispatch(fn, *args, **kwargs):
        if fn is not jnb._flush_impl:
            return dispatch(fn, *args, **kwargs)
        cursor = int(np.asarray(args[7]))
        out = dispatch(fn, *args, **kwargs)
        device.append((cursor, int(np.asarray(out[4]))))
        return out

    def record_flushed(node):
        last[:] = [node]
        flushed(node)
        host.append(_host_next(idx, node))

    def record_chain(node, budget):
        units = chain(node, budget)
        assert [nxt for cur, nxt in device if cur >= 0] == host
        assert all(nxt == -1 for cur, nxt in device if cur < 0)
        device.clear()
        host.clear()
        chains.append((units, _stop_reason(idx, last[0])))
        return units

    idx._flush_chain, idx._flushed = record_chain, record_flushed
    idx._dispatch = record_dispatch
    return chains


@pytest.mark.parametrize("budget", [1, 4, 64])
@pytest.mark.parametrize("order", ["ascending", "descending", "uniform"])
def test_chained_maintain_matches_eager(order, budget):
    """Chains against the one-unit-at-a-time eager oracle: every row,
    count, filter, structure mirror and queue entry equal after every
    ``maintain(budget)`` and after every commit, whose forced maintenance
    (commits over sigma) chains too; with deletes of earlier keys."""
    fused, eager = _pair()
    chains = _record_chains(fused)
    keys = _keys(order)
    batches = (48, 300)                  # sigma-sized, then backpressure
    i = r = 0
    while i < len(keys):
        ks = keys[i:i + batches[r % 2]]
        for idx in (fused, eager):
            idx.insert_batch(ks, np.arange(len(ks), dtype=np.int32) + i)
            if r % 3 == 2:
                idx.delete_batch(keys[i - 40:i - 20])
        _assert_same_tables(fused, eager, f"commit {r}")
        for idx in (fused, eager):
            idx.maintain(budget)
        _assert_same_tables(fused, eager, f"maintain {r}")
        i, r = i + len(ks), r + 1
    fused.drain()
    eager.drain()
    _assert_same_tables(fused, eager, "drained")
    fused.check_invariants()
    assert fused.units_done == eager.units_done
    assert max(u for u, _ in chains) >= 2 and fused.chained_units > 0
    assert fused.chained_units == sum(u - 1 for u, _ in chains)
    assert eager.chained_units == 0


def _make_tight_grandchild(idx: NBTreeIndex) -> None:
    """Drive ``idx`` to a room-first state: a root flush whose largest
    child A is internal and over sigma, with A's last child B short of
    room for a flush.  Ascending keys all go down the right spine; single
    flushes of the root and of A, with the queue emptied after each,
    pile pairs into B that no flush of B takes out."""
    keys = iter(range(10**6, 2 * 10**6, 3))

    def put(n):
        ks = np.fromiter(keys, np.uint32, n)
        idx.insert_batch(ks, np.arange(n, dtype=np.int32))

    def single_flush(node):
        idx._handle_full(node, 1)
        idx._pending.clear()
        idx._pending_n.clear()

    for k in range(0, 2400, 48):
        put(48)
        idx.maintain(4)
    idx.drain()
    a = idx.root.children[-1]
    b = a.children[-1]
    assert not b.is_leaf                 # height 3: root, A, B, leaves
    while b.count + idx.sigma <= idx.run_cap:
        put(idx.sigma)
        single_flush(idx.root)
        single_flush(a)
    while a.count <= idx.sigma:
        put(idx.sigma)
        single_flush(idx.root)
    put(idx.sigma + 1)                   # the root owes a flush again


@pytest.mark.parametrize("stop", ["leaf_split", "stale_entry", "budget",
                                  "room_first"])
def test_chain_stops_where_the_host_rule_does(stop):
    """A chain ends exactly where the host's rule would run something
    other than the largest child's flush, and the eager oracle, one unit
    at a time, ends in the same tables: at a leaf split, at a stale entry,
    at the budget (with more levels below), at a room-first descent."""
    fused, eager = _pair()
    if stop == "room_first":
        for idx in (fused, eager):
            _make_tight_grandchild(idx)
        _assert_same_tables(fused, eager, "set-up")
        chains = _record_chains(fused)
        for idx in (fused, eager):
            idx.maintain(4)
        _assert_same_tables(fused, eager, "room first")
        assert chains[0] == (1, "room_first")
    else:
        order, batch, budget = {"leaf_split": ("ascending", 300, 4),
                                "stale_entry": ("descending", 300, 4),
                                "budget": ("uniform", 64, 2)}[stop]
        chains = _record_chains(fused)
        keys = _keys(order)
        for i in range(0, len(keys), batch):
            for idx in (fused, eager):
                idx.insert_batch(keys[i:i + batch],
                                 np.arange(len(keys[i:i + batch]),
                                           dtype=np.int32))
                idx.maintain(budget)
            _assert_same_tables(fused, eager, f"commit at {i}")
        assert any(r == stop and u >= (budget if stop == "budget" else 1)
                   for u, r in chains), chains
    fused.drain()
    eager.drain()
    _assert_same_tables(fused, eager, "drained")
