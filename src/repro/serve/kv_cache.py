"""Paged KV cache with an NB-tree block index (the paper -> serving bridge).

vLLM-style paging: physical KV pages are rows of (L, KVH, P, S, D) device
arrays; the *logical -> physical* page mapping is the NB-tree
(core/jax_nbtree.NBTreeIndex) keyed by pack(seq_id, logical_block):

  * decode inserts one mapping per sequence per S tokens — the
    insertion-intensive workload of the paper, at engine rate;
  * block-table construction is a batched NB-tree query (Bloom-gated
    descent, one fused device call);
  * ``maintain(budget)`` runs per engine step with a bounded unit budget —
    the deamortization guarantee: index upkeep can never stall a serve
    step beyond the budget (paper Sec. 5.1 transplanted).

Keys pack seq_id in the high bits so a sequence's blocks are contiguous in
key space (its block list is one range scan; frees are a contiguous batch).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.jax_nbtree import NBTreeIndex

SEQ_BITS = 18
BLOCK_BITS = 32 - SEQ_BITS
MAX_BLOCKS_PER_SEQ = (1 << BLOCK_BITS) - 1


def pack_key(seq_id, block) -> np.ndarray:
    seq_id = np.asarray(seq_id, np.uint32)
    block = np.asarray(block, np.uint32)
    assert (block < MAX_BLOCKS_PER_SEQ).all()
    return (seq_id << np.uint32(BLOCK_BITS)) | block


class PagedKVCache:
    def __init__(self, n_layers: int, n_kv_heads: int, head_dim: int, *,
                 n_pages: int = 256, page_size: int = 16,
                 dtype=jnp.bfloat16, f: int = 4, sigma: int = 2048):
        self.L, self.KVH, self.D = n_layers, n_kv_heads, head_dim
        self.P, self.S = n_pages, page_size
        self.k_pages = jnp.zeros((n_layers, n_kv_heads, n_pages, page_size, head_dim), dtype)
        self.v_pages = jnp.zeros((n_layers, n_kv_heads, n_pages, page_size, head_dim), dtype)
        # page 0 is reserved as the null page (masked-out reads land there).
        self.free = list(range(n_pages - 1, 0, -1))
        self.index = NBTreeIndex(f=f, sigma=sigma)
        self.seq_len: dict[int, int] = {}

    # ------------------------------------------------------------- allocation
    def add_sequence(self, seq_id: int, length: int = 0) -> None:
        assert seq_id not in self.seq_len
        self.seq_len[seq_id] = 0
        if length:
            self.extend(seq_id, length)

    def extend(self, seq_id: int, new_len: int) -> list[int]:
        """Ensure pages exist to hold ``new_len`` tokens; returns new pages."""
        have = -(-self.seq_len[seq_id] // self.S) if self.seq_len[seq_id] else 0
        need = -(-new_len // self.S)
        fresh = []
        for b in range(have, need):
            if not self.free:
                raise RuntimeError("KV cache out of pages (preemption needed)")
            fresh.append((b, self.free.pop()))
        if fresh:
            keys = pack_key(seq_id, np.asarray([b for b, _ in fresh]))
            vals = np.asarray([p for _, p in fresh], np.int32)
            self.index.insert_batch(keys, vals)
        self.seq_len[seq_id] = new_len
        return [p for _, p in fresh]

    def free_sequence(self, seq_id: int) -> None:
        n_blocks = -(-self.seq_len[seq_id] // self.S)
        if n_blocks:
            keys = pack_key(seq_id, np.arange(n_blocks))
            present, pages = self.index.query_batch(keys)
            self.free.extend(int(p) for p in pages[present])
            self.index.delete_batch(keys)
        del self.seq_len[seq_id]

    def maintain(self, budget: int = 2) -> int:
        """Bounded per-step index upkeep (deamortization)."""
        return self.index.maintain(budget)

    # ------------------------------------------------------------ block table
    def block_tables(self, seq_ids, max_pages: int) -> jnp.ndarray:
        """(B, max_pages) int32 physical page table for paged_attention."""
        seq_ids = np.asarray(seq_ids)
        keys = pack_key(seq_ids[:, None], np.arange(max_pages)[None, :]).reshape(-1)
        present, pages = self.index.query_batch(keys)
        table = jnp.where(present, pages, 0).reshape(len(seq_ids), max_pages)
        return table.astype(jnp.int32)

    def seq_lens(self, seq_ids) -> jnp.ndarray:
        return jnp.asarray([self.seq_len[int(s)] for s in np.asarray(seq_ids)],
                           jnp.int32)

    # ---------------------------------------------------------------- writes
    def write_token(self, layer: int, seq_ids, positions, k, v) -> None:
        """Write per-sequence new-token KV: k/v (B, KVH, D) at ``positions``."""
        seq_ids = np.asarray(seq_ids)
        positions = np.asarray(positions)
        blocks = positions // self.S
        slots = positions % self.S
        keys = pack_key(seq_ids, blocks)
        present, pages = self.index.query_batch(keys)
        assert present.all(), "write to unallocated block"
        # batched scatter; advanced indices (pages, slots) broadcast to (B,)
        # and land in front, so the update value is exactly k/v (B, KVH, D).
        self.k_pages = self.k_pages.at[layer, :, pages, slots].set(k)
        self.v_pages = self.v_pages.at[layer, :, pages, slots].set(v)

    def layer_pages(self, layer: int):
        return self.k_pages[layer], self.v_pages[layer]
