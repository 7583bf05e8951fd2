"""Key order through the device tier (DESIGN.md §8, "Room for a flush").

Ascending (time-keyed), descending and hashed key streams go through the
served path, ``make_engine("jax-nbtree")`` -> ``apply`` / ``maintain``,
on the fused and the eager write path, with commits of sigma
(``maintain(1)`` after each) and commits larger than sigma (the
backpressure maintenance inside ``insert_batch``, whose flushes run as
chains).  After every unit, or chain of units, the tree keeps its
structure and run bounds and holds exactly the pairs inserted so far, and
no flush ever targets a child without room for sigma more pairs.  At the end the engine answers like a dict and like the
cost-model ``refimpl``.  Last, the ``ordered.insert`` benchmark cell is
rehearsed at a tiny size through the harness.
"""
import copy
import time

import numpy as np
import pytest

from repro.core.engine_api import OpBatch, make_engine
from repro.core.refimpl import NBTree

#: sigma 64 keeps a run to one 1,024-key tile (interpret-mode Pallas on the
#: CPU); 512 rows hold every stream below without growing the tables.
TINY = {"f": 4, "sigma": 64, "max_nodes": 512}
#: stream lengths that reach height >= 4 at f=4, sigma=64.
LENGTH = {"ascending": 4000, "descending": 4000, "hashed": 4600}


def _stream(order: str) -> np.ndarray:
    keys = np.arange(1, LENGTH[order] + 1, dtype=np.uint64) * 3
    if order == "descending":
        return keys[::-1].copy()
    if order == "hashed":
        return np.random.default_rng(7).permutation(keys)
    return keys


def _watch(eng, oracle: dict) -> dict:
    """Check the index after every unit or chain; keep ``oracle`` equal to
    the pairs inserted so far; refuse a flush into a child without room.
    Every flush, a chain's later links included, is the node that
    ``_make_room`` hands back, right before the flush runs."""
    idx = eng.idx
    handle, room, insert = idx._handle_full, idx._make_room, idx._insert_chunk
    seen = {"units": 0, "flushes": 0}

    def insert_chunk(keys, vals):
        insert(keys, vals)
        oracle.update(zip(np.asarray(keys).tolist(),
                          np.asarray(vals).tolist()))

    def flush_into_room(node):
        node = room(node)
        if not node.is_leaf:
            for c in node.children:
                assert c.count + idx.sigma <= idx.run_cap, \
                    "flush into a child without room"
            seen["flushes"] += 1
        return node

    def checked_unit(node, budget=1):
        units = handle(node, budget)
        seen["units"] += units
        idx.check_invariants()
        got_k, got_v = eng.dump_live()
        want = sorted(oracle.items())
        assert got_k.tolist() == [k for k, _ in want]
        assert got_v.tolist() == [v for _, v in want]
        return units

    idx._insert_chunk, idx._make_room = insert_chunk, flush_into_room
    idx._handle_full = checked_unit
    return seen


@pytest.mark.parametrize("fused,batch", [(True, 64), (True, 500),
                                         (False, 500)],
                         ids=["fused-sigma", "fused-backpressure",
                              "eager-backpressure"])
@pytest.mark.parametrize("order", ["ascending", "descending", "hashed"])
def test_key_order_matches_dict_and_refimpl(order, fused, batch):
    keys = _stream(order)
    vals = np.arange(len(keys), dtype=np.int64) * 7 + 1
    eng = make_engine("jax-nbtree", fused=fused, **TINY)
    oracle: dict = {}
    seen = _watch(eng, oracle)
    for i in range(0, len(keys), batch):
        eng.apply(OpBatch.inserts(keys[i:i + batch], vals[i:i + batch]))
        eng.maintain(1)
    forced = eng.stats().backpressure_units
    eng.drain()
    idx = eng.idx
    assert not idx._pending
    idx.check_invariants()
    assert idx.height >= 4 and seen["flushes"] > 0
    assert forced > 0 and seen["units"] == idx.units_done
    assert len(oracle) == len(keys)

    ref = NBTree(f=4, sigma=64)
    for k, v in zip(keys.tolist(), vals.tolist()):
        ref.insert(k, v)
    ref.drain()
    ref.check_invariants()
    rk, rv = ref.range_query(0, 2**32 - 2)
    got_k, got_v = eng.dump_live()
    assert got_k.tolist() == rk.tolist() == sorted(oracle)
    assert got_v.tolist() == rv.tolist()

    # point reads through apply: every third key written, and absent keys
    probe = np.concatenate([keys[::3], keys[::5] + 1])
    res = eng.apply(OpBatch.queries(probe))
    want = [oracle.get(k, -1) for k in probe.tolist()]
    assert res.values.tolist() == want
    assert res.found.tolist() == [w != -1 for w in want]


def test_tree_taller_than_max_levels_is_refused():
    """The point-read descent visits ``max_levels + 1`` nodes, so the tree
    refuses to grow past that instead of answering from part of a path."""
    eng = make_engine("jax-nbtree", max_levels=2, **TINY)
    keys = np.arange(1, 6001, dtype=np.uint64)
    with pytest.raises(AssertionError, match="max_levels"):
        for i in range(0, len(keys), 500):
            eng.apply(OpBatch.inserts(keys[i:i + 500], keys[i:i + 500]))
            eng.maintain(1)
        eng.drain()


# ------------------------------------------------- the benchmark's cell, tiny
def _tiny_ordered_cell():
    from bench import harness

    cell = copy.deepcopy(harness.load_cell("ordered.insert"))
    assert cell.config["insertorder"] == "ordered"
    assert cell.traffic["commit_cap"] > cell.config["engine_args"]["sigma"]
    cell.config.update({"recordcount": 1 << 11, "load_batch": 1024,
                        "engine_args": {"f": 4, "sigma": 64,
                                        "max_levels": 8}})
    # 4 writers as in the cell; commits of 400 are still over sigma, so the
    # window runs insert_batch's backpressure path
    cell.traffic.update({"ops_per_request": 100, "commit_cap": 400,
                         "sizing_rate": 2000})
    cell.run_seconds = 1
    return cell


@pytest.mark.parametrize("fault", ["none", "write_behind"])
def test_ordered_insert_rehearsal(fault):
    from bench import faults, harness

    out = harness.run_cell(_tiny_ordered_cell(), seed=2**33 + 5,
                           seconds=0.5, trace=False,
                           t_process=time.perf_counter(),
                           engine_factory=faults.factory(fault))
    if fault == "none":
        assert out["correct"], out["checks"]
        assert out["failed"] == 0 and out["attempted"] > 0
        assert all(c == {"value": 0, "limit": 0}
                   for c in out["checks"].values())
        assert set(out["metrics"]) == {"ops_per_s", "p50_ms", "setup_s"} | (
            {"p999_ms"} & {m["name"] for m in harness.load_cell(
                "ordered.insert").end_to_end})
    else:
        assert not out["correct"]
        assert out["checks"]["live_pairs_wrong"]["value"] > 0
