"""Hypothesis property tests: NB-tree == dict semantics + structural invariants.

The model-based oracle: any interleaving of insert/update/delete followed by
drain must make the NB-tree (both tiers) indistinguishable from a python
dict, while every intermediate state keeps the cross-s-node linkage and
fanout properties.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # collection degrades to skip without it
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.refimpl import NBTree

ops_strategy = st.lists(
    st.tuples(
        st.sampled_from(["insert", "delete", "update", "query"]),
        st.integers(min_value=1, max_value=400),
        st.integers(min_value=0, max_value=2**31 - 1),
    ),
    min_size=1, max_size=300,
)


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=ops_strategy,
       f=st.integers(min_value=2, max_value=5),
       sigma=st.sampled_from([16, 32, 64]))
def test_matches_dict_model(ops, f, sigma):
    nb = NBTree(f=f, sigma=sigma)
    model = {}
    for op, key, val in ops:
        if op == "insert" or op == "update":
            nb.insert(key, val)
            model[np.uint64(key)] = val
        elif op == "delete":
            nb.delete(key)
            model.pop(np.uint64(key), None)
        else:
            got = nb.get(key)
            want = model.get(np.uint64(key))
            assert (got is None) == (want is None)
            if want is not None:
                assert got == want
    nb.drain()
    nb.check_invariants()
    for k, v in model.items():
        assert nb.get(k) == v, k


@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=ops_strategy,
       f=st.integers(min_value=2, max_value=5),
       sigma=st.sampled_from([16, 32, 64]),
       ranges=st.lists(st.tuples(st.integers(0, 450), st.integers(0, 450)),
                       min_size=1, max_size=6))
def test_range_query_matches_dict_model(ops, f, sigma, ranges):
    """Inclusive range scans == the dict model at every interleaving point,
    including empty ranges (lo > hi), lo == hi, and ranges spanning the
    whole key space (hence every node split)."""
    nb = NBTree(f=f, sigma=sigma)
    model = {}
    for op, key, val in ops:
        if op == "insert" or op == "update":
            nb.insert(key, val)
            model[int(key)] = val
        elif op == "delete":
            nb.delete(key)
            model.pop(int(key), None)
    for lo, hi in [*ranges, (0, 500), (17, 17), (400, 10)]:
        rk, rv = nb.range_query(lo, hi)
        want = sorted((k, v) for k, v in model.items() if lo <= k <= hi)
        assert rk.tolist() == [k for k, _ in want], (lo, hi)
        assert rv.tolist() == [v for _, v in want], (lo, hi)
    nb.drain()
    nb.check_invariants()
    rk, rv = nb.range_query(0, 500)
    want = sorted(model.items())
    assert rk.tolist() == [k for k, _ in want]
    assert rv.tolist() == [v for _, v in want]


@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(n=st.integers(min_value=50, max_value=2000),
       seed=st.integers(min_value=0, max_value=2**16))
def test_invariants_under_bulk_load(n, seed):
    rng = np.random.default_rng(seed)
    # draw with room for collisions, keep first occurrences in draw order
    draw = rng.integers(1, 1 << 40, n + 64, dtype=np.uint64)
    _, first = np.unique(draw, return_index=True)
    keys = draw[np.sort(first)][:n]
    assert len(keys) == n
    nb = NBTree(f=3, sigma=64)
    for i, k in enumerate(keys):
        nb.insert(k, i)
    nb.drain()
    nb.check_invariants()
    assert nb.total_pairs() == n


@settings(max_examples=20, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(min_value=0, max_value=2**16))
def test_sorted_order_monotone_keys(seed):
    """Adversarial pattern for B-tree splits: monotonically increasing keys."""
    nb = NBTree(f=3, sigma=32)
    for i in range(1500):
        nb.insert(i * 7 + seed % 7, i)
    nb.drain()
    nb.check_invariants()
    assert nb.get(7 * 100 + seed % 7) == 100
