"""Device-tier NB-tree: the paper's index as a composable JAX module.

Architecture (DESIGN.md §2-3) — the split every production serving engine
uses (vLLM block manager, LevelDB manifest): a *host control plane* runs the
paper's s-tree algorithm (flush / SNodeSplit / single-recursive-call /
bounded maintenance quota = deamortization), while the *device data plane*
keeps all key/value runs, pivot tables and Bloom bit-arrays as flat padded
arrays in (simulated) HBM and executes the hot operations with the Pallas
kernels:

  * ``insert_batch``  — sorted-batch merge into the root run (merge kernel),
  * ``query_batch``   — one fused jitted descent: Bloom probe + lockstep
                        binary search per level, first (= freshest) hit wins,
  * ``maintain``      — up to ``max_units`` child-merge/split work units per
                        call: the serving-loop analogue of the paper's
                        1/sigma-per-insert deamortization (no allocator or
                        compaction stall can exceed the per-step budget).

Fused maintenance pipeline (DESIGN.md §8): the write path is dispatched the
same way the query path has been since PR 1 — as a handful of fused jitted
device calls, not a chatty eager loop.  Each maintenance primitive is ONE
device dispatch:

  * ``_insert_impl``  — batch sort + root merge + count bump + *incremental*
                        Bloom update (OR only the batch's bits: O(batch),
                        not O(run_cap), and bit-identical to a rebuild —
                        see ``kernels.ref.bloom_update_ref``),
  * ``_flush_impl``   — the whole emptying cascade step for the node a
                        device cursor names, and the next cursor by the
                        host's rule: duplicate-safe cut, pivot partition,
                        batched merge-path merge into all <= f children
                        (``merge_sorted_batch``, a single 2-d-grid kernel
                        launch), fused tombstone compaction, parent-run
                        compaction, and child/parent Bloom rebuilds, with
                        buffer donation on the node tables so no full-table
                        copy survives the call,
  * ``_split_impl`` / ``_clear_impl`` / ``_sync_impl`` / ``_grow_impl`` —
                        run split (+ filters), row clear, structure mirror,
                        and capacity doubling, one dispatch each.

Host control metadata (node ids, split keys) is routed in as scalars; a
flush reads its node's child ids, pivots and count from the device's
structure mirror, so the flushes that follow one another down the tree
run as a chain of programs with one device->host read of their count log
(DESIGN.md §8, "Chained flushes").  Every device computation the index
launches goes through the ``_device_call`` funnel (``_dispatch``), and every
device->host wait or read of the fused path through ``_fetch``, so dispatch
and sync budgets are observable (per-instance ``dispatch_count`` and
``sync_count``) and regression-tested.  With a
:class:`repro.obs.trace.Tracer` attached, each maintenance unit, forced
maintenance call, chain of flushes, dispatch and sync is also a span
(``nbtree.unit`` / ``nbtree.backpressure`` / ``nbtree.chain`` /
``nbtree.dispatch`` / ``nbtree.sync``) in the ring buffer and in a running
profiler's trace.
The pre-fusion eager path is kept under ``fused=False`` as the
differential-testing and benchmarking baseline
(``benchmarks/bench_ingest_device.py`` measures the before/after); its own
reads do not go through ``_fetch``.

Range queries (DESIGN.md §4): ``range_query_batch(lo, hi, max_results)``
serves inclusive scans ``[lo, hi]`` with the same host/device split as point
lookups.  The *host control plane* routes each query over its pivot
structure, collecting — in pre-order, so ancestors (fresher data) come
first — the ids of every node whose key interval intersects the range; the
*device data plane* then runs one fused jitted pass that (a) lower/upper
bound binary-searches every candidate run in lockstep, (b) gathers the
matching spans into a fixed-capacity candidate tile, (c) resolves per-key
freshness by a single stable sort over the level-major candidates (the
range generalization of the point lookup's first-hit-wins rule: for
duplicate keys, the copy from the shallower level — or leftmost in-run
position — survives), (d) filters ``TOMBSTONE32`` delta-deletes, and (e)
returns sorted, KEY_MAX-padded results with a live count and a truncation
flag.  Bloom filters are not consulted: they cannot answer range
predicates.  The standalone ``ops.range_scan`` Pallas kernel implements the
same search+gather step for single-run scans (LSM-style baselines,
microbenchmarks).

Static-shape adaptations vs. the paper (recorded in DESIGN.md §2): runs are
fixed-capacity rows of a node table (RUN_CAP >= f*(sigma+1) + sigma, the
paper's Sec. 5.1 sibling bound plus one incoming flush); device rows are
always compacted on rewrite, the lazy-removal watermark living in the host
control plane only (rewriting an HBM row is a stream copy, the thing the
paper's lazy removal avoids on *disk* seeks).

Device keys are uint32 (TPU lane width), values int32 payload references;
``TOMBSTONE32`` realizes delta-record deletions (paper Sec. 3.2.2).
"""
from __future__ import annotations

import functools
import math
from collections import Counter, deque

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..kernels.merge_sorted import merge_sorted as _merge_pair
from ..kernels.merge_sorted import merge_sorted_batch as _merge_batch
from ..kernels.ref import bloom_build_ref, bloom_hash_ref

KEY_MAX32 = np.uint32(0xFFFFFFFF)
TOMBSTONE32 = np.int32(-(2**31))
TILE = 1024

def _device_call(fn, *args, **kwargs):
    """Single funnel for every device computation the index launches.

    One call == one device dispatch (each ``fn`` here is either a fused
    jitted impl or a single eager XLA op).  Kept as a module-level
    indirection so tests can monkeypatch it to intercept dispatches;
    *counting* is per-instance (``NBTreeIndex.dispatch_count``, routed
    through :meth:`NBTreeIndex._dispatch`), so concurrent engines —
    sharded ensembles, fused-vs-eager side-by-side benchmarks — no longer
    share mutable global state.
    """
    return fn(*args, **kwargs)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class _HostNode:
    """Control-plane view of an s-node (structure only, no key data)."""

    __slots__ = ("nid", "skeys", "children", "count", "parent", "requeued")

    def __init__(self, nid: int, parent=None):
        self.nid = nid
        self.skeys: list[int] = []
        self.children: list[_HostNode] = []
        self.count = 0           # live pairs in the device run row
        self.parent: _HostNode | None = parent
        self.requeued = False    # queued by the split that made it

    @property
    def is_leaf(self):
        return not self.children


# --------------------------------------------------------------------- jit fns
@functools.partial(jax.jit, donate_argnums=(0,))
def _write_row(table, row, data):
    return table.at[row].set(data)


@functools.partial(jax.jit, static_argnames=("cap",))
def _window(row_keys, row_vals, start, length, cap: int):
    """Fixed-size (cap,) slice [start, start+length) padded with KEY_MAX."""
    idx = start + jnp.arange(cap, dtype=jnp.int32)
    k = jnp.take(row_keys, idx, mode="clip")
    v = jnp.take(row_vals, idx, mode="clip")
    mask = jnp.arange(cap, dtype=jnp.int32) < length
    return jnp.where(mask, k, jnp.uint32(KEY_MAX32)), jnp.where(mask, v, 0)


@jax.jit
def _prepare_batch(keys, vals):
    """Sort an incoming batch descending-recency-stable (newest copy first)."""
    # stable argsort keeps earlier (older) duplicates first; we want the
    # newest first, so sort the *reversed* batch.
    keys, vals = keys[::-1], vals[::-1]
    order = jnp.argsort(keys, stable=True)
    return keys[order], vals[order]


@functools.partial(jax.jit, static_argnames=("nbits", "h"))
def _build_bloom(keys, nbits: int, h: int):
    return ops.bloom_build(keys, nbits, h)


def _compact_rows(keys, vals, cap: int):
    """Leaf-level delta resolution (Sec. 3.2.2): dedup then drop deletes.

    The merge kernel keeps duplicate keys (newest copy leftmost — that is
    what makes leftmost-match point lookups see the freshest record), so a
    leaf run accumulates stale copies.  Compaction must retire the stale
    duplicates *together with* the tombstone records: dropping only the
    tombstone would resurrect the older copy it deleted.  Traced by both
    the eager jit wrapper below and (vmapped) the fused flush impl.
    """
    first = jnp.concatenate(
        [jnp.ones(1, bool), keys[1:] != keys[:-1]])   # leftmost = freshest
    dead = ~first | (vals == TOMBSTONE32)
    keys = jnp.where(dead, jnp.uint32(KEY_MAX32), keys)
    order = jnp.argsort(keys, stable=True)
    keys, vals = keys[order], vals[order]
    live = jnp.sum((keys != KEY_MAX32).astype(jnp.int32))
    return keys[:cap], vals[:cap], live


_compact_tombstones = jax.jit(_compact_rows, static_argnames=("cap",))


# ----------------------------------------------------- fused maintenance impls
@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("run_cap", "nbits", "h", "interpret"))
def _insert_impl(run_keys, run_vals, run_count, bloom, keys, vals, *,
                 run_cap: int, nbits: int, h: int, interpret: bool):
    """One-dispatch root ingest: sort batch, merge, incremental Bloom OR."""
    bk, bv = _prepare_batch(keys, vals)
    mk, mv = _merge_pair(bk, bv, run_keys[0], run_vals[0], interpret=interpret)
    run_keys = run_keys.at[0].set(mk[:run_cap])
    run_vals = run_vals.at[0].set(mv[:run_cap])
    run_count = run_count.at[0].add(jnp.int32(keys.shape[0]))
    # O(batch) incremental filter maintenance; == from-scratch rebuild
    # because OR over a grown key set is associative (DESIGN.md §8).
    bloom = bloom.at[0].set(ops.bloom_update(bloom[0], bk, nbits, h))
    return run_keys, run_vals, run_count, bloom


def _flush_rows(row_k, row_v, count, piv, ck_all, cv_all, counts_all,
                blooms_all, *, nc: int, leaf: bool, sigma: int,
                sigma_pad: int, run_cap: int, nbits: int, h: int,
                interpret: bool):
    """The rows one flush writes, for a node with ``nc`` children that are
    leaves (``leaf``) or internal: duplicate-safe cut and pivot partition,
    one batched merge across the ``nc`` children, vmapped tombstone
    compaction (leaf level), parent-run compaction, Bloom rebuilds for
    every touched row.  Untouched children (empty partition) keep rows,
    counts and filters bit-for-bit, matching the eager path exactly.

    ``*_all`` hold ``f`` child rows, of which the first ``nc`` are the
    node's; the rest come back as they went in, with count -1.  Returns
    the child rows, counts and filters, then the parent's row, count and
    filter.
    """
    f = ck_all.shape[0]
    ck, cv = ck_all[:nc], cv_all[:nc]
    old_counts, old_blooms = counts_all[:nc], blooms_all[:nc]
    piv = piv[:nc - 1]
    # ---- duplicate-safe cut (was 2-3 blocking host round trips) -----------
    # Never split a duplicate group across the moved boundary: runs keep
    # duplicate copies newest-first, so flushing the fresh copy while the
    # stale one stays behind would invert the ancestors-are-fresher rule
    # both query paths rely on.  Back the cut up to the group start; if the
    # whole prefix is one key, move the entire group (progress guaranteed:
    # RUN_CAP >= f*(sigma+1) + sigma gives the child sigma headroom).
    moved0 = jnp.minimum(count, sigma)
    k_cut = row_k[jnp.clip(moved0, 0, run_cap - 1)]
    left = jnp.searchsorted(row_k, k_cut, side="left").astype(jnp.int32)
    right = jnp.searchsorted(row_k, k_cut, side="right").astype(jnp.int32)
    adj = jnp.where(left > 0, jnp.minimum(left, moved0),
                    jnp.minimum(right, count))
    moved = jnp.where(moved0 < count, adj, moved0)

    # ---- pivot partition of the moved prefix ------------------------------
    cuts = jnp.minimum(
        jnp.searchsorted(row_k, piv, side="left").astype(jnp.int32), moved)
    bounds = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), cuts, jnp.reshape(moved, (1,))])
    starts, lens = bounds[:-1], bounds[1:] - bounds[:-1]

    def window(start, ln, cap):
        idx = start + jnp.arange(cap, dtype=jnp.int32)
        m = jnp.arange(cap, dtype=jnp.int32) < ln
        return (jnp.where(m, jnp.take(row_k, idx, mode="clip"),
                          jnp.uint32(KEY_MAX32)),
                jnp.where(m, jnp.take(row_v, idx, mode="clip"), 0))

    pk, pv = jax.vmap(lambda s, ln: window(s, ln, sigma_pad))(starts, lens)

    # ---- one batched merge across all children ----------------------------
    mk, mv = _merge_batch(pk, pv, ck, cv, interpret=interpret)
    if leaf:
        mk, mv, new_counts = jax.vmap(
            lambda k, v: _compact_rows(k, v, run_cap))(mk, mv)
    else:
        mk, mv = mk[:, :run_cap], mv[:, :run_cap]
        new_counts = old_counts + lens
    touched = lens > 0
    mk = jnp.where(touched[:, None], mk, ck)
    mv = jnp.where(touched[:, None], mv, cv)
    new_counts = jnp.where(touched, new_counts, old_counts)
    # unrolled over the static child count: measurably faster than vmap for
    # the scatter-heavy build, and nc <= f is tiny.
    new_blooms = jnp.stack([bloom_build_ref(mk[i], nbits, h)
                            for i in range(nc)])
    new_blooms = jnp.where(touched[:, None], new_blooms, old_blooms)

    # ---- parent remainder (immediate compaction, DESIGN.md §2) ------------
    rest = count - moved
    rk, rv = window(moved, rest, run_cap)
    pb = bloom_build_ref(rk, nbits, h)
    return (jnp.concatenate([mk, ck_all[nc:]]),
            jnp.concatenate([mv, cv_all[nc:]]),
            jnp.concatenate([new_counts, jnp.full(f - nc, -1, jnp.int32)]),
            jnp.concatenate([new_blooms, blooms_all[nc:]]),
            rk, rv, rest, pb)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 7, 8),
                   static_argnames=("sigma", "sigma_pad", "run_cap", "nbits",
                                    "h", "interpret"))
def _flush_impl(run_keys, run_vals, run_count, bloom, pivots, children,
                nchild, cursor, log, *, sigma: int, sigma_pad: int,
                run_cap: int, nbits: int, h: int, interpret: bool):
    """One-dispatch emptying-cascade step for the internal node ``cursor``
    (an int32 node id on the device; -1: nothing to do).

    Replaces the eager per-child loop (merge + compact + 3 row writes +
    full Bloom rebuild per child, with host-synced ``searchsorted`` cuts in
    the middle, ~25 dispatches at f=4) with a single call.  The node's
    child ids, pivots, fanout and count come from the device tables, and
    a ``lax.switch`` picks the body specialised for its fanout and child
    level (:func:`_flush_rows`), or a no-op for cursor -1.

    Returns the updated tables, the next cursor and ``log`` rolled up by
    one row that holds ``[nid, the f child counts (-1: no child), the
    node's remaining count]``.  The next cursor is the node the host's
    rule runs next (DESIGN.md §8, "Chained flushes"): the first largest
    child, where it holds more than sigma pairs, is internal and has room
    for a flush in each of its own children; -1 otherwise.  So the host
    enqueues a chain of these programs without waiting and reads the log
    once.
    """
    f = children.shape[1]
    n_rows = run_count.shape[0]
    live = cursor >= 0
    nid = jnp.maximum(cursor, 0)
    ids = children[nid]
    nc = jnp.clip(nchild[nid], 2, f)
    leaf = nchild[ids[0]] == 0
    branch = jnp.where(live, 1 + 2 * (nc - 2) + leaf.astype(jnp.int32), 0)
    static = dict(sigma=sigma, sigma_pad=sigma_pad, run_cap=run_cap,
                  nbits=nbits, h=h, interpret=interpret)

    def skip(row_k, row_v, count, piv, ck, cv, old_counts, old_blooms):
        return (ck, cv, jnp.full(f, -1, jnp.int32), old_blooms, row_k, row_v,
                jnp.int32(-1), old_blooms[0])

    bodies = [skip] + [functools.partial(_flush_rows, nc=c, leaf=lf, **static)
                       for c in range(2, f + 1) for lf in (False, True)]
    mk, mv, counts, blooms, rk, rv, rest, pb = jax.lax.switch(
        branch, bodies, run_keys[nid], run_vals[nid], run_count[nid],
        pivots[nid], run_keys[ids], run_vals[ids], run_count[ids], bloom[ids])

    # absent children and the no-op write nothing: their index is out of
    # range and the scatter drops it.
    tgt = jnp.where(live & (jnp.arange(f) < nc), ids, n_rows)
    own = jnp.where(live, nid, n_rows)
    run_keys = run_keys.at[tgt].set(mk, mode="drop").at[own].set(
        rk, mode="drop")
    run_vals = run_vals.at[tgt].set(mv, mode="drop").at[own].set(
        rv, mode="drop")
    run_count = run_count.at[tgt].set(counts, mode="drop").at[own].set(
        rest, mode="drop")
    bloom = bloom.at[tgt].set(blooms, mode="drop").at[own].set(
        pb, mode="drop")

    # ---- the next unit, by the host's rule ---------------------------------
    big = jnp.argmax(counts)                       # first largest, as np
    child = ids[big]
    grand = children[child]
    room = (jnp.arange(f) >= nchild[child]) | (
        run_count[grand] + sigma <= run_cap)
    chain = (counts[big] > sigma) & (nchild[child] > 0) & jnp.all(room)
    nxt = jnp.where(chain, child, -1).astype(jnp.int32)
    row = jnp.concatenate([jnp.reshape(cursor, (1,)), counts,
                           jnp.reshape(rest, (1,))])
    log = jnp.roll(log, -1, axis=0).at[-1].set(row)
    return run_keys, run_vals, run_count, bloom, nxt, log


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("has_key", "run_cap", "nbits", "h",
                                    "compact"))
def _split_impl(run_keys, run_vals, run_count, bloom, nid, left_id, right_id,
                count, at_key, *, has_key: bool, run_cap: int, nbits: int,
                h: int, compact: bool = False):
    """One-dispatch run split: windows, counts and filters for both halves.

    ``compact`` first resolves the run's duplicates and deletes as a leaf
    flush does (the root leaf's run has never been compacted).  Returns the
    updated tables plus ``[k_m, cut, count]`` (uint32) — the split key for
    the host pivot structure, the left-half length and the pairs split.
    """
    row_k = run_keys[nid]
    row_v = run_vals[nid]
    if compact:
        row_k, row_v, count = _compact_rows(row_k, row_v, run_cap)
    if has_key:
        k_m = at_key
        cut = jnp.minimum(
            jnp.searchsorted(row_k, k_m, side="left").astype(jnp.int32),
            count)
    else:
        k_m = row_k[jnp.clip(count // 2, 0, run_cap - 1)]
        cut = jnp.searchsorted(row_k, k_m, side="left").astype(jnp.int32)

    def window(start, ln):
        idx = start + jnp.arange(run_cap, dtype=jnp.int32)
        m = jnp.arange(run_cap, dtype=jnp.int32) < ln
        return (jnp.where(m, jnp.take(row_k, idx, mode="clip"),
                          jnp.uint32(KEY_MAX32)),
                jnp.where(m, jnp.take(row_v, idx, mode="clip"), 0))

    halves_k, halves_v = jax.vmap(window)(
        jnp.stack([jnp.int32(0), cut]), jnp.stack([cut, count - cut]))
    ids = jnp.stack([left_id, right_id])
    run_keys = run_keys.at[ids].set(halves_k)
    run_vals = run_vals.at[ids].set(halves_v)
    run_count = run_count.at[ids].set(jnp.stack([cut, count - cut]))
    bloom = bloom.at[ids].set(
        jnp.stack([bloom_build_ref(halves_k[i], nbits, h) for i in range(2)]))
    return (run_keys, run_vals, run_count, bloom,
            jnp.stack([k_m, cut.astype(jnp.uint32), count.astype(jnp.uint32)]))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _clear_impl(run_keys, run_vals, run_count, bloom, nid):
    """One-dispatch row retire: keys, values, count and filter of one node."""
    return (run_keys.at[nid].set(jnp.uint32(KEY_MAX32)),
            run_vals.at[nid].set(jnp.int32(0)),
            run_count.at[nid].set(0),
            bloom.at[nid].set(jnp.uint32(0)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _sync_impl(pivots, children, nchild, nid, pv, ch, n):
    """One-dispatch structure mirror: pivots, child ids, fanout of one node."""
    return (pivots.at[nid].set(pv), children.at[nid].set(ch),
            nchild.at[nid].set(n))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
def _grow_impl(pivots, children, nchild, run_keys, run_vals, run_count, bloom):
    """One-dispatch capacity doubling of all seven node tables.

    Donating every table lets XLA release each old buffer as soon as its
    copy lands, so growth never holds 2x of *every* table at once the way
    seven sequential eager concatenates did.
    """
    def pad(t, fill):
        return jnp.concatenate([t, jnp.full(t.shape, fill, t.dtype)])

    return (pad(pivots, KEY_MAX32), pad(children, 0), pad(nchild, 0),
            pad(run_keys, KEY_MAX32), pad(run_vals, 0), pad(run_count, 0),
            pad(bloom, 0))


@functools.partial(
    jax.jit, static_argnames=("f", "levels", "run_cap", "nbits", "h", "steps")
)
def _query_batch_impl(pivots, nchild, children, run_keys, run_vals, run_count,
                      bloom, q, *, f, levels, run_cap, nbits, h, steps):
    B = q.shape[0]
    node = jnp.zeros(B, jnp.int32)
    found = jnp.zeros(B, bool)
    out = jnp.full(B, -1, jnp.int32)
    # Bloom-effectiveness tallies (paper Sec. 5.2), reduced on device and
    # packed with the results so the call stays one round trip: probes
    # issued, negatives that skipped a run search, and positives whose
    # search then missed (false positives).
    n_probe = jnp.int32(0)
    n_neg = jnp.int32(0)
    n_fp = jnp.int32(0)
    # the descent parks on its leaf for any iterations left after reaching
    # it; `prev` masks those repeats out of the tallies (one logical probe
    # per distinct node on each query's root-to-leaf path).
    prev = jnp.full(B, -1, jnp.int32)

    pos = bloom_hash_ref(q, h, nbits)  # (h, B), shared across levels

    for _ in range(levels + 1):
        cnt = run_count[node]
        # ---- Bloom probe (skip the run search on negative) ----------------
        w = bloom[node[None, :], pos // 32]              # (h, B)
        bit = (w >> (pos % 32).astype(jnp.uint32)) & jnp.uint32(1)
        positive = jnp.all(bit == 1, axis=0)
        probe = ~found & (cnt > 0) & (node != prev)      # filter consulted
        do = positive & probe
        # ---- lockstep binary search over the node's run -------------------
        lo = jnp.zeros(B, jnp.int32)
        hi = cnt
        for _s in range(steps):
            mid = (lo + hi) >> 1
            key = run_keys[node, jnp.clip(mid, 0, run_cap - 1)]
            right = (lo < hi) & (key < q)
            lo = jnp.where(right, mid + 1, lo)
            hi = jnp.where(right, hi, mid)
        hitk = run_keys[node, jnp.clip(lo, 0, run_cap - 1)]
        hit = do & (lo < cnt) & (hitk == q)
        out = jnp.where(hit & ~found, run_vals[node, jnp.clip(lo, 0, run_cap - 1)], out)
        found = found | hit
        n_probe += jnp.sum(probe.astype(jnp.int32))
        n_neg += jnp.sum((probe & ~positive).astype(jnp.int32))
        n_fp += jnp.sum((do & ~hit).astype(jnp.int32))
        # ---- descend via pivots (cross-s-node linkage) ---------------------
        pv = pivots[node]                                # (B, f-1)
        ci = jnp.sum((q[:, None] >= pv).astype(jnp.int32), axis=1)
        child = children[node, jnp.clip(ci, 0, f - 1)]
        prev = node
        node = jnp.where(nchild[node] > 0, child, node)
    present = found & (out != TOMBSTONE32)
    # one int32 vector, so the caller reads everything in one transfer:
    # present (0/1) | out | n_probe, n_neg, n_fp
    return jnp.concatenate([present.astype(jnp.int32), out,
                            jnp.stack([n_probe, n_neg, n_fp])])


@functools.partial(
    jax.jit, static_argnames=("cap", "max_results", "run_cap", "steps"))
def _range_query_batch_impl(run_keys, run_vals, run_count, nodes, lo, hi, *,
                            cap, max_results, run_cap, steps):
    B, M = nodes.shape
    valid_node = nodes >= 0                      # (B, M), -1 = padding
    nid = jnp.maximum(nodes, 0)
    cnt = jnp.where(valid_node, run_count[nid], 0)
    lo_b, hi_b = lo[:, None], hi[:, None]

    # ---- lockstep lower/upper bound over every candidate run --------------
    def bound(q, closed):
        l = jnp.zeros((B, M), jnp.int32)
        h = cnt                                  # excludes KEY_MAX padding
        for _ in range(steps):
            mid = (l + h) >> 1
            key = run_keys[nid, jnp.clip(mid, 0, run_cap - 1)]
            go = (l < h) & ((key <= q) if closed else (key < q))
            l = jnp.where(go, mid + 1, l)
            h = jnp.where(go, h, mid)
        return l

    start = bound(lo_b, False)
    end = bound(hi_b, True)
    n_match = jnp.maximum(end - start, 0)        # per-node matches (pre-cap)

    # ---- masked gather of each matching span ------------------------------
    idx = start[..., None] + jnp.arange(cap, dtype=jnp.int32)   # (B, M, cap)
    valid = idx < end[..., None]
    safe = jnp.clip(idx, 0, run_cap - 1)
    gk = run_keys[nid[..., None], safe]
    gv = run_vals[nid[..., None], safe]
    ck = jnp.where(valid, gk, jnp.uint32(KEY_MAX32)).reshape(B, M * cap)
    cv = jnp.where(valid, gv, 0).reshape(B, M * cap)

    # ---- freshness resolution ---------------------------------------------
    # Candidates are level-major with m ordered pre-order (ancestors first)
    # and in-run position order within m (newer duplicate copies first, the
    # merge kernel's tie-break), so a *stable* sort by key puts the freshest
    # copy of every key first — the range generalization of first-hit-wins.
    order = jnp.argsort(ck, axis=1, stable=True)
    sk = jnp.take_along_axis(ck, order, axis=1)
    sv = jnp.take_along_axis(cv, order, axis=1)
    fresh = jnp.concatenate(
        [jnp.ones((B, 1), bool), sk[:, 1:] != sk[:, :-1]], axis=1)
    live = fresh & (sk != KEY_MAX32) & (sv != TOMBSTONE32)
    sk = jnp.where(live, sk, jnp.uint32(KEY_MAX32))
    sv = jnp.where(live, sv, 0)
    order2 = jnp.argsort(sk, axis=1, stable=True)
    sk = jnp.take_along_axis(sk, order2, axis=1)
    sv = jnp.take_along_axis(sv, order2, axis=1)
    total = jnp.sum(live.astype(jnp.int32), axis=1)
    truncated = (total > max_results) | jnp.any(n_match > cap, axis=1)
    return (sk[:, :max_results], sv[:, :max_results],
            jnp.minimum(total, max_results), truncated)


class NBTreeIndex:
    """Composable device-backed NB-tree index (see module docstring).

    ``fused=True`` (the default) runs the one-dispatch maintenance
    pipeline; ``fused=False`` keeps the pre-fusion eager write path —
    physically identical state, ~25x the dispatches per flush — as the
    differential-test oracle and benchmark baseline.
    """

    def __init__(self, f: int = 4, sigma: int = 4096, *, bits_per_key: int = 10,
                 num_hashes: int = 3, max_nodes: int = 256, max_levels: int = 12,
                 fused: bool = True):
        assert f >= 2 and sigma >= 2 * f
        self.f, self.sigma = f, sigma
        self.h = num_hashes
        self.sigma_pad = _round_up(sigma, TILE)
        self.run_cap = _round_up(f * (sigma + 1) + sigma, TILE)
        self.nbits = _round_up(self.run_cap * bits_per_key, 32 * 128)
        self.max_levels = max_levels
        self._steps = math.ceil(math.log2(self.run_cap + 1)) + 1
        self._fused = bool(fused)

        self.max_nodes = max_nodes
        nw = self.nbits // 32
        self.pivots = jnp.full((max_nodes, f - 1), KEY_MAX32, jnp.uint32)
        self.children = jnp.zeros((max_nodes, f), jnp.int32)
        self.nchild = jnp.zeros((max_nodes,), jnp.int32)
        self.run_keys = jnp.full((max_nodes, self.run_cap), KEY_MAX32, jnp.uint32)
        self.run_vals = jnp.zeros((max_nodes, self.run_cap), jnp.int32)
        self.run_count = jnp.zeros((max_nodes,), jnp.int32)
        self.bloom = jnp.zeros((max_nodes, nw), jnp.uint32)

        self.root = _HostNode(0)
        self._next_id = 1
        # oversized nodes awaiting work: deque + membership counter so the
        # hot loop's dequeue and the per-chunk "already queued?" check are
        # O(1) (they were O(n) list.pop(0) / `in` scans).
        self._pending: deque[_HostNode] = deque()
        self._pending_n: Counter = Counter()
        self.n_items = 0
        self.units_done = 0   # cumulative flush/split work units executed
        #: units ``insert_batch`` ran to make room at the root (surfaced as
        #: ``EngineStats.backpressure_units``).
        self.backpressure_units = 0
        #: flush units that ran as a later link of a chain, with no sync of
        #: their own (surfaced as ``EngineStats.chained_units``).
        self.chained_units = 0
        # the chained flushes' log (see _flush_impl): one row a flush, the
        # newest last; a chain never runs more flushes than the tree has
        # levels.
        self._chain_log = jnp.full((max_levels, f + 2), -1, jnp.int32)
        # Bloom effectiveness (paper Sec. 5.2); see query_batch.
        self.bloom_probes = 0
        self.bloom_negative_skips = 0
        self.bloom_false_positives = 0
        #: device dispatches issued by THIS index (per-instance; surfaced
        #: as ``EngineStats.device_dispatches``).
        self.dispatch_count = 0
        #: device->host waits and reads through ``_fetch`` (surfaced as
        #: ``EngineStats.device_syncs``).
        self.sync_count = 0
        self._tracer = None

    # ------------------------------------------------------------ dispatch
    def attach_tracer(self, tracer) -> None:
        """Record ``nbtree.unit``, ``nbtree.backpressure``, ``nbtree.chain``,
        ``nbtree.dispatch`` and ``nbtree.sync``
        spans on ``tracer`` (a :class:`repro.obs.trace.Tracer`); ``None``
        detaches."""
        self._tracer = tracer

    def _dispatch(self, fn, *args, **kwargs):
        """Per-instance dispatch shim over the module :func:`_device_call`
        funnel (still monkeypatchable there).  Counting is always on and
        O(1); the ``nbtree.dispatch`` span only while a tracer is
        attached, so the untraced hot path stays a counter bump."""
        self.dispatch_count += 1
        if self._tracer is None:
            return _device_call(fn, *args, **kwargs)
        with self._tracer.span("dispatch", "nbtree.dispatch",
                               fn=getattr(fn, "__name__", None) or repr(fn)):
            return _device_call(fn, *args, **kwargs)

    def _fetch(self, what: str, x, read=np.asarray):
        """The sync counterpart of :meth:`_dispatch`: every device->host
        wait or read of the fused path is ``read(x)`` here (``np.asarray``
        by default, ``jax.block_until_ready`` for a bare wait).  ``what``
        names it (``ack``, ``query``, ``flush_counts``, ``split_out``,
        ``range_*``) on the ``nbtree.sync`` span; ``sync_count`` counts
        every call."""
        self.sync_count += 1
        if self._tracer is None:
            return read(x)
        with self._tracer.span("sync", "nbtree.sync", what=what):
            return read(x)

    # --------------------------------------------------------- pending queue
    def _enqueue(self, node: _HostNode, front: bool = False) -> None:
        (self._pending.appendleft if front else self._pending.append)(node)
        self._pending_n[node.nid] += 1

    def _dequeue(self) -> _HostNode:
        node = self._pending.popleft()
        self._pending_n[node.nid] -= 1
        if not self._pending_n[node.nid]:
            del self._pending_n[node.nid]
        return node

    # ------------------------------------------------------------------ public
    def insert_batch(self, keys, vals) -> None:
        """Merge a batch into the root run (device merge kernel).

        Oversized batches are split into sigma-sized chunks with
        backpressure maintenance between them — the bounded-latency
        contract holds per chunk (a caller that submits a giant batch has
        asked for the work; it is never deferred into later steps).
        """
        keys = jnp.asarray(keys, jnp.uint32)
        vals = jnp.asarray(vals, jnp.int32)
        n = int(keys.shape[0])
        if self.root.count + n > self.run_cap or n > self.sigma:
            for i in range(0, n, self.sigma):
                while self.root.count + self.sigma > self.run_cap:
                    if (self._backpressure(i // self.sigma) == 0 and
                            self.root.count + self.sigma > self.run_cap):
                        break  # tree fully maintained; capacity guaranteed
                self._insert_chunk(keys[i:i + self.sigma], vals[i:i + self.sigma])
            return
        self._insert_chunk(keys, vals)

    def _backpressure(self, chunk: int) -> int:
        """One ``maintain(4)`` forced by a root without room for chunk
        ``chunk`` of a batch: its units count in ``backpressure_units``,
        and with a tracer attached it is an ``nbtree.backpressure`` span
        (``chunk``; ``units``: the call's budget)."""
        u0 = self.units_done
        if self._tracer is None:
            pending = self.maintain(4)
        else:
            with self._tracer.span("cascade", "nbtree.backpressure",
                                   chunk=chunk, units=4):
                pending = self.maintain(4)
        self.backpressure_units += self.units_done - u0
        return pending

    def _insert_chunk(self, keys, vals) -> None:
        n = int(keys.shape[0])
        if self._fused:
            (self.run_keys, self.run_vals, self.run_count, self.bloom) = \
                self._dispatch(_insert_impl, self.run_keys, self.run_vals,
                             self.run_count, self.bloom, keys, vals,
                             run_cap=self.run_cap, nbits=self.nbits,
                             h=self.h, interpret=ops._interpret())
            self.root.count += n
        else:
            bk, bv = self._dispatch(_prepare_batch, keys, vals)
            merged_k, merged_v = self._dispatch(
                ops.merge_sorted, bk, bv,
                self.run_keys[0, : self.run_cap], self.run_vals[0])
            self.run_keys = self._dispatch(
                _write_row, self.run_keys, 0, merged_k[: self.run_cap])
            self.run_vals = self._dispatch(
                _write_row, self.run_vals, 0, merged_v[: self.run_cap])
            self.root.count += n
            self.run_count = self._dispatch(
                self.run_count.at[0].set, self.root.count)
            self.bloom = self._dispatch(
                _write_row, self.bloom, 0,
                self._dispatch(_build_bloom, self.run_keys[0], self.nbits,
                             self.h))
        assert self.root.count <= self.run_cap, "root run overflow: call maintain()"
        self.n_items += n
        if self.root.count > self.sigma and self.root.nid not in self._pending_n:
            self._enqueue(self.root)

    def delete_batch(self, keys) -> None:
        keys = jnp.asarray(keys, jnp.uint32)
        self.insert_batch(keys, jnp.full(keys.shape, TOMBSTONE32, jnp.int32))

    def query_batch(self, keys):
        """Host numpy ``(present: bool (B,), vals: int32 (B,))`` — one
        fused device call and one device->host transfer (``query``).

        Bloom-effectiveness tallies for the batch (probes / negative skips /
        false positives, reduced on device, read back in the same transfer)
        accumulate into the host ints ``bloom_probes`` /
        ``bloom_negative_skips`` / ``bloom_false_positives`` — the paper
        Sec. 5.2 attribution counters surfaced through ``EngineStats``.
        """
        q = jnp.asarray(keys, jnp.uint32)
        B = int(q.shape[0])
        packed = self._fetch("query", self._dispatch(
            _query_batch_impl, self.pivots, self.nchild, self.children,
            self.run_keys, self.run_vals, self.run_count, self.bloom, q,
            f=self.f, levels=self.max_levels, run_cap=self.run_cap,
            nbits=self.nbits, h=self.h, steps=self._steps))
        n_probe, n_neg, n_fp = packed[2 * B:].tolist()
        self.bloom_probes += n_probe
        self.bloom_negative_skips += n_neg
        self.bloom_false_positives += n_fp
        return packed[:B] != 0, packed[B:2 * B]

    def range_query_batch(self, lo, hi, max_results: int = 256):
        """Batched inclusive range scan [lo_b, hi_b] — one fused device call.

        Returns ``(keys uint32 (B, max_results), vals int32 (B, max_results),
        count int32 (B,), truncated bool (B,))``: per query the up-to-
        ``max_results`` freshest live pairs in the range, sorted by key and
        KEY_MAX-padded; ``count`` is the number of valid slots; ``truncated``
        flags queries whose full result did not fit (re-issue with a larger
        ``max_results`` for exact results).  ``lo > hi`` is an empty range.

        The host control plane routes each query to the nodes whose key
        interval intersects it (pre-order, ancestors first — see module
        docstring); the device pass searches, gathers, freshness-resolves
        and tombstone-filters in one jitted call.  Recompiles per distinct
        (B, routed-node-count-bucket, max_results) combination; the node
        bucket is padded to a power of two to bound recompiles.
        """
        lo = np.asarray(lo, np.uint32)
        hi = np.asarray(hi, np.uint32)
        assert lo.shape == hi.shape and lo.ndim == 1
        B = lo.shape[0]
        routes = [self._route_range(int(l), int(h)) for l, h in zip(lo, hi)]
        M = max(1, *(len(r) for r in routes)) if routes else 1
        M = 1 << (M - 1).bit_length()
        nodes = np.full((B, M), -1, np.int32)
        for b, r in enumerate(routes):
            nodes[b, : len(r)] = r
        return self._dispatch(
            _range_query_batch_impl,
            self.run_keys, self.run_vals, self.run_count,
            jnp.asarray(nodes), jnp.asarray(lo), jnp.asarray(hi),
            cap=int(max_results), max_results=int(max_results),
            run_cap=self.run_cap, steps=self._steps)

    def _route_range(self, lo: int, hi: int) -> list[int]:
        """Pre-order ids of nodes whose key interval intersects [lo, hi]."""
        if lo > hi:
            return []
        out: list[int] = []

        def rec(node, nlo, nhi):
            out.append(node.nid)
            if node.is_leaf:
                return
            bounds = [nlo, *node.skeys, nhi]
            for i, c in enumerate(node.children):
                clo, chi = bounds[i], bounds[i + 1]
                if (chi is None or lo < chi) and (clo is None or hi >= clo):
                    rec(c, clo, chi)

        rec(self.root, None, None)
        return out

    def maintain(self, max_units: int = 1) -> int:
        """Run up to ``max_units`` flush/split units; returns pending count.

        This is the deamortization knob: a serving loop calls
        ``maintain(k)`` once per step, so index upkeep can never stall a
        step for longer than k units — the paper's bounded worst-case
        insertion transplanted to the engine level.  On the fused path a
        flush unit is ONE device dispatch and a split unit at most four —
        the per-unit dispatch budget is regression-tested — and the
        flushes that follow one another down the tree run as one chain
        with one count readback (:meth:`_flush_chain`).  The ``nbtree.unit``
        span covers a chain's first unit and holds the rest.
        """
        units = 0
        while self._pending and units < max_units:
            node = self._dequeue()
            if node.count <= self.sigma:
                continue
            node = self._make_room(node)
            requeued, node.requeued = node.requeued, False
            budget = max_units - units
            if self._tracer is None:
                units += self._handle_full(node, budget)
            else:
                with self._tracer.span("flush_unit", "nbtree.unit",
                                       kind=self._unit_kind(node),
                                       pairs=node.count, requeued=requeued):
                    units += self._handle_full(node, budget)
        return len(self._pending)

    def _make_room(self, node: _HostNode) -> _HostNode:
        """The node whose unit runs now.  A flush moves at most sigma pairs
        into a child, so it may target only children with ``count + sigma
        <= run_cap``; where ``node`` has a child without that room, the
        child's unit runs first (a flush takes sigma out of it, a split
        halves it) and ``node`` goes back to the front of the queue, and so
        on down.  This keeps every flush inside ``run_cap`` for any key
        order (DESIGN.md §8)."""
        while not node.is_leaf:
            full = [c for c in node.children
                    if c.count + self.sigma > self.run_cap]
            if not full:
                break
            self._enqueue(node, front=True)
            node = max(full, key=lambda c: c.count)
        return node

    def _unit_kind(self, node: _HostNode) -> str:
        """What :meth:`_handle_full` will do to ``node``: ``flush`` an
        internal node; ``split_root`` the root leaf (the first split);
        ``split_leaf`` a leaf whose parent absorbs the new sibling;
        ``split_internal`` when that overflows internal ancestors too; and
        ``grow`` when the cascade reaches the root (height + 1)."""
        if not node.is_leaf:
            return "flush"
        if node is self.root:
            return "split_root"
        kind, anc = "split_leaf", node.parent
        while len(anc.children) >= self.f:   # one more child overflows it
            if anc is self.root:
                return "grow"
            kind, anc = "split_internal", anc.parent
        return kind

    def drain(self) -> None:
        while self.maintain(64):
            pass

    # -------------------------------------------------------- paper operations
    def _handle_full(self, node: _HostNode, budget: int = 1) -> int:
        """One HandleFullSNode step (Sec. 5.1), or on the fused path a
        chain of up to ``budget`` flush steps.  Returns work units done."""
        if node.is_leaf:
            self.units_done += 1
            if node is self.root:
                self._split_root_leaf()
            else:
                self._split_upward(node)
            return 1
        if self._fused:
            return self._flush_chain(node, budget)
        self._flush_eager(node)
        self._flushed(node)
        return 1

    def _flushed(self, node: _HostNode) -> None:
        """Queue what a flush of ``node`` leaves owing; its counts are
        already taken."""
        self.units_done += 1
        sizes = [c.count for c in node.children]
        big = int(np.argmax(sizes))
        if sizes[big] > self.sigma:
            # single recursive call — queued as a separate work unit.
            self._enqueue(node.children[big], front=True)
        if node.count > self.sigma:
            # node absorbed multiple batches; it still owes another flush.
            self._enqueue(node)

    def _alloc(self, parent) -> _HostNode:
        if self._next_id >= self.max_nodes:
            self._grow_tables()
        n = _HostNode(self._next_id, parent)
        self._next_id += 1
        return n

    def _grow_tables(self) -> None:
        (self.pivots, self.children, self.nchild, self.run_keys,
         self.run_vals, self.run_count, self.bloom) = self._dispatch(
            _grow_impl, self.pivots, self.children, self.nchild,
            self.run_keys, self.run_vals, self.run_count, self.bloom)
        self.max_nodes *= 2

    def _flush_chain(self, node: _HostNode, budget: int) -> int:
        """Flush ``node``, then the flushes the host's rule runs next, as
        back-to-back ``_flush_impl`` programs that pass the next node on
        the device (DESIGN.md §8, "Chained flushes"): ``k`` = the budget or
        the levels from ``node`` down to the leaf parents, whichever is
        less, then one read of the log.  Replaying the log row by row is
        what ``maintain`` would have done one unit at a time: each later
        link is the unit at the front of the queue.  Returns the units
        run; those after the first count in ``chained_units``."""
        depth, anc = 0, node.parent
        while anc is not None:
            depth, anc = depth + 1, anc.parent
        k = min(budget, self.height - depth)
        if self._tracer is None or k == 1:
            rows = self._launch_chain(node.nid, k)
        else:
            with self._tracer.span("dispatch", "nbtree.chain", launched=k):
                rows = self._launch_chain(node.nid, k)
        units = 0
        for nid, *counts, rest in rows:
            if nid < 0:
                break                     # the chain stopped on the device
            if units:
                node = self._dequeue()
                room = self._make_room(node)
                assert node.nid == nid and room is node, \
                    "chain left the host's unit order"
                node.requeued = False
                self.chained_units += 1
            for child, c in zip(node.children, counts):
                child.count = c
                assert c <= self.run_cap, "child run overflow"
            node.count = rest
            self._flushed(node)
            units += 1
        return units

    def _launch_chain(self, nid: int, k: int) -> list:
        """``k`` chained flush programs from node ``nid`` and the one read
        of their log (``flush_counts``); returns their ``k`` rows."""
        cursor, log = jax.device_put(np.int32(nid)), self._chain_log
        for _ in range(k):
            (self.run_keys, self.run_vals, self.run_count, self.bloom,
             cursor, log) = self._dispatch(
                _flush_impl, self.run_keys, self.run_vals, self.run_count,
                self.bloom, self.pivots, self.children, self.nchild, cursor,
                log, sigma=self.sigma, sigma_pad=self.sigma_pad,
                run_cap=self.run_cap, nbits=self.nbits, h=self.h,
                interpret=ops._interpret())
        self._chain_log = log
        return self._fetch("flush_counts", log)[-k:].tolist()  # one sync

    def _flush_eager(self, node: _HostNode) -> None:
        """Pre-fusion write path: ~25 dispatches + host syncs per flush."""
        nid = node.nid
        moved = min(node.count, self.sigma)
        row_k, row_v = self.run_keys[nid], self.run_vals[nid]
        if moved < node.count:
            # Never split a duplicate group across the moved boundary (see
            # _flush_impl).
            k_cut = jnp.uint32(int(row_k[moved]))
            left = int(self._dispatch(jnp.searchsorted, row_k, k_cut,
                                    side="left"))
            if left > 0:
                moved = min(left, moved)
            else:
                moved = min(int(self._dispatch(jnp.searchsorted, row_k, k_cut,
                                             side="right")), node.count)
        piv = jnp.asarray([int(k) for k in node.skeys], jnp.uint32)
        cuts = jnp.minimum(
            self._dispatch(jnp.searchsorted, row_k, piv, side="left"), moved)
        cuts = np.asarray(cuts)                          # host ints, f-1 of them
        bounds = [0, *cuts.tolist(), moved]
        for i, child in enumerate(node.children):
            lo, hi = bounds[i], bounds[i + 1]
            if hi <= lo:
                continue
            part_k, part_v = self._dispatch(_window, row_k, row_v, jnp.int32(lo),
                                          jnp.int32(hi - lo), self.sigma_pad)
            mk, mv = self._dispatch(ops.merge_sorted, part_k, part_v,
                                  self.run_keys[child.nid],
                                  self.run_vals[child.nid])
            new_count = child.count + (hi - lo)
            if child.is_leaf:
                mk, mv, live = self._dispatch(_compact_tombstones, mk, mv,
                                            self.run_cap)
                new_count = int(live)
            else:
                mk, mv = mk[: self.run_cap], mv[: self.run_cap]
            assert new_count <= self.run_cap, "child run overflow"
            self.run_keys = self._dispatch(_write_row, self.run_keys,
                                         child.nid, mk)
            self.run_vals = self._dispatch(_write_row, self.run_vals,
                                         child.nid, mv)
            child.count = new_count
            self.run_count = self._dispatch(
                self.run_count.at[child.nid].set, new_count)
            self.bloom = self._dispatch(
                _write_row, self.bloom, child.nid,
                self._dispatch(_build_bloom, mk, self.nbits, self.h))
        # the paper advances a lazy watermark; a device row rewrite is a
        # stream copy, so we compact immediately (DESIGN.md §2).
        rest = node.count - moved
        rk, rv = self._dispatch(_window, row_k, row_v, jnp.int32(moved),
                              jnp.int32(rest), self.run_cap)
        self.run_keys = self._dispatch(_write_row, self.run_keys, nid, rk)
        self.run_vals = self._dispatch(_write_row, self.run_vals, nid, rv)
        node.count = rest
        self.run_count = self._dispatch(self.run_count.at[nid].set, rest)
        self.bloom = self._dispatch(
            _write_row, self.bloom, nid,
            self._dispatch(_build_bloom, rk, self.nbits, self.h))

    def _split_root_leaf(self) -> None:
        """First split: the root leaf becomes a root with two leaf children."""
        left, right = self._alloc(self.root), self._alloc(self.root)
        # the root leaf's run is the one leaf run no flush has compacted;
        # compacting it keeps every leaf run free of duplicates, so a leaf
        # split always halves its run (DESIGN.md §8)
        k_m = self._split_run(self.root, left, right, compact=True)
        if left.count + right.count == 0:
            # every pair was a stale copy or a delete: the tree is empty
            self._clear_run(self.root)
            return
        self.root.skeys = [k_m]
        self.root.children = [left, right]
        self._sync_structure(self.root)
        # root keeps an empty run (the in-memory buffer of the paper).
        self._clear_run(self.root)

    def _split_upward(self, node: _HostNode) -> None:
        self._split_node(node)
        anc = node.parent
        while anc is not None and len(anc.children) > self.f:
            if anc is self.root:
                self._split_root_internal()
                return
            self._split_node(anc)
            anc = anc.parent

    def _split_node(self, node: _HostNode) -> None:
        parent = node.parent
        left, right = self._alloc(parent), self._alloc(parent)
        k_m = self._split_structure(node, left, right)
        i = parent.children.index(node)
        parent.children[i: i + 1] = [left, right]
        parent.skeys.insert(i, k_m)
        self._sync_structure(parent)

    def _split_root_internal(self) -> None:
        """Root fanout exceeded f: grow the s-tree height by one."""
        old = self.root
        left = self._alloc(None)
        right = self._alloc(None)
        k_m = self._split_structure(old, left, right)
        old.skeys = [k_m]
        old.children = [left, right]
        left.parent = right.parent = old
        self._sync_structure(old)
        # the query descent visits max_levels + 1 nodes: a taller tree
        # would answer from a prefix of the path.
        assert self.height <= self.max_levels, "tree taller than max_levels"

    def _split_structure(self, node, left, right) -> int:
        """Split node's run (and pivots/children for internal nodes)."""
        if node.is_leaf:
            k_m = self._split_run(node, left, right)
        else:
            mid = len(node.skeys) // 2
            k_m = node.skeys[mid]
            left.skeys, right.skeys = node.skeys[:mid], node.skeys[mid + 1:]
            left.children, right.children = node.children[: mid + 1], node.children[mid + 1:]
            for c in left.children:
                c.parent = left
            for c in right.children:
                c.parent = right
            self._split_run(node, left, right, at_key=k_m)
            self._sync_structure(left)
            self._sync_structure(right)
        # the original node id is retired (host-side free list elided: ids
        # are cheap; production would recycle).
        self._clear_run(node)
        node.count = 0
        return k_m

    def _split_run(self, node, left, right, at_key: int | None = None,
                   compact: bool = False) -> int:
        """Split ``node``'s run into ``left`` and ``right`` at ``at_key``
        (its median key where None; ``compact``: the run's duplicates and
        deletes resolved first).  An internal half holding more than sigma
        pairs goes to the front of the queue, as :meth:`_handle_full` does
        with the largest child after a flush: it holds pairs in transit,
        and the split retired the node's own queue entry.  A leaf half owes
        no flush, and splitting it again only spends rows."""
        if self._fused:
            has_key = at_key is not None
            (self.run_keys, self.run_vals, self.run_count, self.bloom,
             out) = self._dispatch(
                _split_impl, self.run_keys, self.run_vals, self.run_count,
                self.bloom, jnp.int32(node.nid), jnp.int32(left.nid),
                jnp.int32(right.nid), jnp.int32(node.count),
                jnp.uint32(at_key if has_key else 0),
                has_key=has_key, run_cap=self.run_cap, nbits=self.nbits,
                h=self.h, compact=compact)
            out = self._fetch("split_out", out)  # the split's one sync
            k_m, cut, count = (int(x) for x in out)
            left.count, right.count = cut, count - cut
        else:
            k_m = self._split_run_eager(node, left, right, at_key, compact)
        for half in (right, left):
            if not half.is_leaf and half.count > self.sigma:
                half.requeued = True
                self._enqueue(half, front=True)
        return k_m

    def _split_run_eager(self, node, left, right, at_key, compact) -> int:
        nid = node.nid
        row_k, row_v = self.run_keys[nid], self.run_vals[nid]
        count = node.count
        if compact:
            row_k, row_v, live = self._dispatch(_compact_tombstones, row_k,
                                                row_v, self.run_cap)
            count = int(live)
        if at_key is None:
            mid = count // 2
            k_m = int(np.asarray(row_k[mid]))
            cut = int(np.asarray(self._dispatch(
                jnp.searchsorted, row_k, jnp.uint32(k_m), side="left")))
        else:
            k_m = int(at_key)
            cut = int(np.asarray(self._dispatch(
                jnp.searchsorted, row_k, jnp.uint32(k_m), side="left")))
            cut = min(cut, count)
        for dst, lo, ln in ((left, 0, cut), (right, cut, count - cut)):
            dk, dv = self._dispatch(_window, row_k, row_v, jnp.int32(lo),
                                  jnp.int32(ln), self.run_cap)
            self.run_keys = self._dispatch(_write_row, self.run_keys, dst.nid, dk)
            self.run_vals = self._dispatch(_write_row, self.run_vals, dst.nid, dv)
            dst.count = ln
            self.run_count = self._dispatch(self.run_count.at[dst.nid].set, ln)
            self.bloom = self._dispatch(
                _write_row, self.bloom, dst.nid,
                self._dispatch(_build_bloom, dk, self.nbits, self.h))
        return k_m

    def _clear_run(self, node) -> None:
        nid = node.nid
        if self._fused:
            (self.run_keys, self.run_vals, self.run_count, self.bloom) = \
                self._dispatch(_clear_impl, self.run_keys, self.run_vals,
                             self.run_count, self.bloom, jnp.int32(nid))
        else:
            self.run_keys = self._dispatch(
                _write_row, self.run_keys, nid,
                jnp.full(self.run_cap, KEY_MAX32, jnp.uint32))
            self.run_vals = self._dispatch(
                _write_row, self.run_vals, nid,
                jnp.zeros(self.run_cap, jnp.int32))
            self.run_count = self._dispatch(self.run_count.at[nid].set, 0)
            self.bloom = self._dispatch(
                _write_row, self.bloom, nid,
                jnp.zeros(self.nbits // 32, jnp.uint32))
        node.count = 0

    def _sync_structure(self, node: _HostNode) -> None:
        """Mirror a host node's pivots/children into the device tables."""
        nid = node.nid
        pv = np.full(self.f - 1, KEY_MAX32, np.uint32)
        ch = np.zeros(self.f, np.int32)
        for i, k in enumerate(node.skeys[: self.f - 1]):
            pv[i] = np.uint32(k)
        for i, c in enumerate(node.children[: self.f]):
            ch[i] = c.nid
        if self._fused:
            (self.pivots, self.children, self.nchild) = self._dispatch(
                _sync_impl, self.pivots, self.children, self.nchild,
                jnp.int32(nid), jnp.asarray(pv), jnp.asarray(ch),
                jnp.int32(len(node.children)))
        else:
            self.pivots = self._dispatch(self.pivots.at[nid].set,
                                       jnp.asarray(pv))
            self.children = self._dispatch(self.children.at[nid].set,
                                         jnp.asarray(ch))
            self.nchild = self._dispatch(self.nchild.at[nid].set,
                                       len(node.children))

    # ------------------------------------------------------------- invariants
    def check_invariants(self) -> None:
        """Structure, key order and run bounds of the whole tree; they hold
        between any two units, so work may still be pending."""
        run_keys = np.asarray(self.run_keys)
        run_count = np.asarray(self.run_count)

        def rec(node, lo, hi_excl, depth, depths):
            assert node.count <= self.run_cap, "run over run_cap"
            assert run_count[node.nid] == node.count, "device count differs"
            ks = run_keys[node.nid][: node.count]
            if len(ks):
                assert np.all(ks[:-1] <= ks[1:]), "run not sorted"
                assert lo is None or ks[0] >= lo
                assert hi_excl is None or ks[-1] < hi_excl
            if node.is_leaf:
                depths.add(depth)
                return
            assert len(node.children) == len(node.skeys) + 1 <= self.f
            bounds = [lo, *node.skeys, hi_excl]
            for i, c in enumerate(node.children):
                assert c.parent is node
                rec(c, bounds[i], bounds[i + 1], depth + 1, depths)

        depths: set = set()
        rec(self.root, None, None, 0, depths)
        assert len(depths) <= 1, "leaves at non-uniform depth"

    @property
    def height(self) -> int:
        h, n = 0, self.root
        while not n.is_leaf:
            n, h = n.children[0], h + 1
        return h

    def total_pairs(self) -> int:
        total, stack = 0, [self.root]
        while stack:
            n = stack.pop()
            total += n.count
            stack.extend(n.children)
        return total
