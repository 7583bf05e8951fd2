"""The plain reference: the same ops on the same data, in numpy.

The guarantee under test is that a write is visible to every read after
it: to later ops of its own commit (a commit's ops run in order) and to
every op of later commits.  So the reference replays every op the engine
applied, in the order it applied them (load, warm-up, window), and answers
each read with the last write to its key before it.  Deletes do not occur
in the traffic; a key never written is absent.
"""
from __future__ import annotations

import numpy as np

from .generator import INSERT, QUERY


def answers(kinds: np.ndarray, keys: np.ndarray, vals: np.ndarray,
            at: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(found, value or -1) of the reads at positions ``at`` of the op
    sequence, each seeing every write before it."""
    w = np.flatnonzero(kinds == INSERT)
    if len(keys) >= 1 << 32 or (len(keys) and keys.max() >= 1 << 32):
        raise ValueError("keys and positions each need 32 bits")
    order_w = (keys[w] << np.uint64(32)) | w.astype(np.uint64)
    sort = np.argsort(order_w, kind="stable")
    order_w, w = order_w[sort], w[sort]
    at = np.asarray(at, np.int64)
    order_r = (keys[at] << np.uint64(32)) | at.astype(np.uint64)
    i = np.searchsorted(order_w, order_r, side="left") - 1
    ok = i >= 0
    i = np.maximum(i, 0)
    found = ok & (keys[w[i]] == keys[at])
    return found, np.where(found, vals[w[i]], -1)


def final_state(kinds: np.ndarray, keys: np.ndarray,
                vals: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Sorted live keys and each key's last written value."""
    w = kinds == INSERT
    k, v = keys[w][::-1], vals[w][::-1]
    last, first = np.unique(k, return_index=True)
    return last, v[first]


def table_mismatches(got_keys, got_vals, ref_keys, ref_vals) -> int:
    """Pairs that differ between two key-sorted tables: keys in one only,
    plus keys in both with different values."""
    got_keys = np.asarray(got_keys, np.uint64)
    ref_keys = np.asarray(ref_keys, np.uint64)
    common, gi, ri = np.intersect1d(got_keys, ref_keys, assume_unique=True,
                                    return_indices=True)
    only = len(got_keys) + len(ref_keys) - 2 * len(common)
    diff = int(np.sum(np.asarray(got_vals)[gi] != np.asarray(ref_vals)[ri]))
    return int(only) + diff


def read_mismatches(kinds, keys, vals, at, found, values) -> int:
    """Reads at ``at`` whose (found, value) differ from the reference."""
    want_f, want_v = answers(kinds, keys, vals, at)
    return int(np.sum((found != want_f) | (np.where(found, values, -1)
                                            != want_v)))


__all__ = ["INSERT", "QUERY", "answers", "final_state", "table_mismatches",
           "read_mismatches"]
