"""Device-tier NB-tree (core/jax_nbtree): behaviour, invariants, ref-parity."""
import bisect

import numpy as np
import pytest

from repro.core.engine_api import _pad_pow2
from repro.core.jax_nbtree import NBTreeIndex
from repro.core.refimpl import NBTree as RefNBTree
from repro.kernels.ref import bloom_hash_ref


def _keys(rng, n):
    return rng.choice(np.arange(1, 2**31, dtype=np.uint32), n, replace=False)


@pytest.fixture(scope="module")
def loaded():
    rng = np.random.default_rng(3)
    keys = _keys(rng, 20_000)
    idx = NBTreeIndex(f=4, sigma=1024, max_nodes=64)
    B = 512
    for i in range(0, len(keys), B):
        idx.insert_batch(keys[i:i + B], np.arange(i, i + len(keys[i:i + B]), dtype=np.int32))
        idx.maintain(2)
    idx.drain()
    return idx, keys


def test_roundtrip_and_invariants(loaded):
    idx, keys = loaded
    idx.check_invariants()
    present, vals = idx.query_batch(keys[:4096])
    assert np.array(present).all()
    assert np.array_equal(np.array(vals), np.arange(4096, dtype=np.int32))


def test_negatives(loaded):
    idx, keys = loaded
    rng = np.random.default_rng(4)
    neg = rng.integers(2**31, 2**32 - 2, 2048).astype(np.uint32)
    present, _ = idx.query_batch(neg)
    assert not np.array(present).any()


def test_delete_update():
    rng = np.random.default_rng(5)
    keys = _keys(rng, 6000)
    idx = NBTreeIndex(f=4, sigma=512, max_nodes=64)
    idx.insert_batch(keys, np.arange(len(keys), dtype=np.int32))
    idx.drain()
    idx.delete_batch(keys[:100])
    idx.insert_batch(keys[100:200], np.full(100, 42, np.int32))
    idx.drain()
    p, v = idx.query_batch(keys[:200])
    p, v = np.array(p), np.array(v)
    assert not p[:100].any()
    assert p[100:].all() and (v[100:] == 42).all()


def test_maintenance_budget_bounded():
    """maintain(k) performs at most k units — the deamortization contract."""
    rng = np.random.default_rng(6)
    idx = NBTreeIndex(f=4, sigma=512, max_nodes=128)
    keys = _keys(rng, 8000)
    max_pending_drop = 0
    for i in range(0, len(keys), 256):
        idx.insert_batch(keys[i:i + 256], np.arange(256, dtype=np.int32))
        before = len(idx._pending)
        idx.maintain(1)
        after = len(idx._pending)
        # one unit can retire at most one queue entry (it may also enqueue)
        max_pending_drop = max(max_pending_drop, before - after)
    assert max_pending_drop <= 1
    idx.drain()
    idx.check_invariants()


def test_parity_with_refimpl():
    """Same ops through both tiers -> same visible key-value map."""
    rng = np.random.default_rng(7)
    keys = _keys(rng, 4000)
    dev = NBTreeIndex(f=3, sigma=256, max_nodes=128)
    ref = RefNBTree(f=3, sigma=256)
    dev.insert_batch(keys, np.arange(len(keys), dtype=np.int32))
    dev.drain()
    for i, k in enumerate(keys):
        ref.insert(np.uint64(k), i)
    ref.drain()
    q = rng.choice(keys, 500, replace=False)
    p, v = dev.query_batch(q)
    p, v = np.array(p), np.array(v)
    for j, k in enumerate(q):
        rv = ref.get(np.uint64(k))
        assert p[j] and v[j] == rv, (k, v[j], rv)


def test_grow_tables():
    rng = np.random.default_rng(8)
    idx = NBTreeIndex(f=3, sigma=64, max_nodes=8)   # forces growth
    keys = _keys(rng, 3000)
    idx.insert_batch(keys, np.arange(len(keys), dtype=np.int32))
    idx.drain()
    idx.check_invariants()
    assert idx.max_nodes > 8
    p, _ = idx.query_batch(keys[:512])
    assert np.array(p).all()


def _bloom_walk(idx, q):
    """(probes, negative skips, false positives) of a point-read batch,
    counted by walking the host tree in numpy: one probe per distinct node
    with a non-empty run on each query's root-to-leaf path, until a run
    holds the key."""
    bloom = np.asarray(idx.bloom)
    run_keys = np.asarray(idx.run_keys)
    run_count = np.asarray(idx.run_count)
    pos = np.asarray(bloom_hash_ref(q, idx.h, idx.nbits))      # (h, B)
    probes = neg = fp = 0
    for b, k in enumerate(q):
        node = idx.root
        while True:
            cnt = run_count[node.nid]
            if cnt:
                probes += 1
                p = pos[:, b]
                bits = (bloom[node.nid, p // 32] >> (p % 32).astype(np.uint32)) & 1
                if not bits.all():
                    neg += 1
                elif k in run_keys[node.nid, :cnt]:
                    break
                else:
                    fp += 1
            if node.is_leaf:
                break
            node = node.children[bisect.bisect_right(node.skeys, int(k))]
    return probes, neg, fp


def test_query_batch_is_one_sync_with_exact_bloom_tallies():
    """A point-read batch is one device->host transfer returning host
    arrays, and its Bloom tallies equal an independent count."""
    rng = np.random.default_rng(11)
    keys = _keys(rng, 2048)
    idx = NBTreeIndex(f=4, sigma=64, max_nodes=512)
    for i in range(0, len(keys), 64):       # runs left partly full
        idx.insert_batch(keys[i:i + 64], np.arange(i, i + 64, dtype=np.int32))
        idx.maintain(1)
    dead = keys[-64:]
    idx.delete_batch(dead)                  # tombstones in the root run
    assert idx.height >= 2
    absent = rng.integers(2**31, 2**32 - 2, 16).astype(np.uint32)
    want = {int(k): i for i, k in enumerate(keys[:-64])}
    mixed = np.concatenate([keys[:24], keys[900:916], absent, dead[:8]])
    padded = _pad_pow2(np.concatenate([keys[1000:1024], absent[:8],
                                       dead[8:13]]))
    assert len(padded) == len(mixed) == 64 and (padded[36:] == dead[12]).all()
    for q in (mixed, padded):
        s0 = idx.sync_count
        t0 = (idx.bloom_probes, idx.bloom_negative_skips,
              idx.bloom_false_positives)
        present, vals = idx.query_batch(q)
        assert idx.sync_count - s0 == 1
        assert isinstance(present, np.ndarray) and present.dtype == bool
        assert isinstance(vals, np.ndarray) and vals.dtype == np.int32
        assert present.shape == vals.shape == q.shape
        assert present.tolist() == [int(k) in want for k in q]
        assert vals[present].tolist() == [want[int(k)] for k in q[present]]
        got = (idx.bloom_probes - t0[0], idx.bloom_negative_skips - t0[1],
               idx.bloom_false_positives - t0[2])
        assert got == _bloom_walk(idx, q)
        assert got[0] > len(q) and got[1] > 0
