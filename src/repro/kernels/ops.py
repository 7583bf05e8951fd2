"""Public jit'd wrappers for the Pallas kernels.

Single dispatch point: on TPU the kernels compile natively; on the CPU
they run under ``interpret=True`` (the Pallas interpreter executes the
kernel body), so all call sites — the NB-tree device tier, the serving
engine, tests, benchmarks — use exactly one code path.  Any other backend
is an error: there is no silent fallback that hides the device.
"""
from __future__ import annotations

import jax

from .bloom_filter import bloom_probe as _bloom_probe
from .merge_sorted import merge_sorted as _merge_sorted
from .merge_sorted import merge_sorted_batch as _merge_sorted_batch
from .paged_attention import paged_attention as _paged_attention
from .range_scan import range_scan as _range_scan
from .ref import bloom_build_ref, bloom_update_ref
from .sorted_search import sorted_search as _sorted_search


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run natively on 'tpu' and "
                           f"interpreted on 'cpu'; backend is {backend!r}")
    return backend == "cpu"


def merge_sorted(a_keys, a_vals, b_keys, b_vals):
    return _merge_sorted(a_keys, a_vals, b_keys, b_vals, interpret=_interpret())


def merge_sorted_batch(a_keys, a_vals, b_keys, b_vals):
    """Merge R pairs of sorted runs in one launch (fused-flush fan-out)."""
    return _merge_sorted_batch(a_keys, a_vals, b_keys, b_vals,
                               interpret=_interpret())


def sorted_search(run_keys, run_vals, queries):
    return _sorted_search(run_keys, run_vals, queries, interpret=_interpret())


def range_scan(run_keys, run_vals, lo, hi, *, max_results: int = 128):
    return _range_scan(run_keys, run_vals, lo, hi, max_results=max_results,
                       interpret=_interpret())


def bloom_probe(words, queries, *, nbits: int, h: int = 3):
    return _bloom_probe(words, queries, nbits=nbits, h=h, interpret=_interpret())


def bloom_build(keys, nbits: int, h: int = 3):
    """Filter build: once-per-rewrite XLA path (see bloom_filter.py docstring)."""
    return bloom_build_ref(keys, nbits, h)


def bloom_update(words, keys, nbits: int, h: int = 3):
    """Incremental filter maintenance: OR a batch's bits into ``words``.

    O(batch) instead of O(run_cap); bit-identical to a from-scratch rebuild
    over the grown run (see ref.bloom_update_ref) — the per-insert-batch
    path of the fused ingest pipeline.
    """
    return bloom_update_ref(words, keys, nbits, h)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens):
    return _paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                            interpret=_interpret())
