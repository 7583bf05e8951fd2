"""Pallas TPU kernel: streaming merge of two sorted runs (the flush hot loop).

TPU adaptation of the paper's merge-sort flush (Sec. 4.1).  A sequential
two-pointer merge is hostile to a vector machine, so the merge is split the
*merge-path* way into independent output tiles of ``TILE`` = 8x128
elements, and each tile is merged with a fixed compare-exchange network
that moves data only inside one ``(8, 128)`` vreg tile (Mosaic lowers no
gather that crosses tiles):

1. **Diagonal splits** (XLA, outside the kernel): for every output tile
   ``t``, ``i0 = i(t*TILE)`` is the number of ``a`` elements among the
   first ``t*TILE`` merged ones, found by two sorted searches over the
   ``a`` elements' merged ranks.  The splits are scalar-prefetched into
   SMEM.
2. **Windows** (BlockSpecs): the tile's output is the first ``TILE``
   elements of ``merge(a[i0:], b[j0:])`` with ``j0 = t*TILE - i0``; the
   index maps DMA just the two aligned ``(8, 128)`` blocks of each run that
   cover ``[i0, i0 + TILE)`` and ``[j0, j0 + TILE)``.
3. **In-tile merge** (kernel body): dynamic rolls align each window to
   its start (``b`` is reversed once in XLA, so its window comes out
   reversed), one half-cleaner keeps the smaller ``TILE`` of the bitonic
   ``a ++ reverse(b)`` sequence, and ten more half-cleaners, each a pair
   of rolls, sort it.

A bitonic network is not stable, so every element carries a tag that makes
the order total: its window position for ``a`` (``0..TILE-1``), ``TILE`` +
its position for ``b``, and ``EXCLUDED`` for the guard padding past the run
ends.  Comparing ``(key, tag)`` reproduces the reference order exactly,
including the tie-break: equal keys take the ``a`` element first — ``a`` is
the newer stream, so leftmost-match queries see the freshest record
(delta-record resolution, paper Sec. 3.2.2).

Two entry points share one ``(run, out-tile)`` grid: ``merge_sorted`` (one
pair of runs) and ``merge_sorted_batch`` (R independent pairs — the
one-dispatch fan-out the fused NB-tree emptying cascade uses to merge all
children of a node at once).

VMEM: each grid step holds eight ``(8, 128)`` 32-bit input blocks and two
output blocks, double-buffered by the pipeline — 80 KiB whatever the run
length, far under v5e's 16 MiB default scoped VMEM limit.  Run length is
bounded by HBM: the kernel compiles for v5e with two runs of 2^22 keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import KEY_MAX32

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES  # output elements per grid step
_LOG_LANES = LANES.bit_length() - 1
#: tag of guard-padding elements: sorts after every real (key, tag) pair.
EXCLUDED = 2 * TILE


def _flat_iota(shape):
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, col


def _window(blk0_ref, blk1_ref, off):
    """The TILE elements starting ``off`` (< TILE) into two adjacent blocks.

    A row roll then a lane roll, both by dynamic amounts; the lane roll's
    wrap-around is taken from the next row.
    """
    x = jnp.concatenate([blk0_ref[...], blk1_ref[...]], axis=0)
    rows = x.shape[0]
    q, r = off >> _LOG_LANES, off & (LANES - 1)
    # jnp.roll semantics: roll(x, k)[p] = x[p - k]
    x = pltpu.roll(x, (rows - q) % rows, 0)          # x[row + q]
    lane = pltpu.roll(x, (LANES - r) % LANES, 1)     # x[row, (col + r) % 128]
    carry = pltpu.roll(lane, rows - 1, 0)            # lane[row + 1]
    _, col = _flat_iota(x.shape)
    return jnp.where(col < LANES - r, lane, carry)[:SUBLANES]


def _half_cleaners(k, v, g, pos, *, axis: int):
    """The half-cleaner stages of a bitonic merge whose partners lie along
    ``axis`` of an (8, 128) tile, largest stride first.

    Stage b pairs flat position p with p ^ s, the ``(key, tag)`` smaller of
    the two staying at the lower position; its partner is a roll by the
    stride, dynamic so that the stages are one loop body.
    """
    size = k.shape[axis]
    unit = LANES if axis == 0 else 1            # flat stride of one step

    def stage(b, kvg):
        k, v, g = kvg
        sh = size >> (b + 1)
        upper = (pos & (sh * unit)) != 0
        # jnp.roll semantics: roll(x, k)[p] = x[p - k]
        partner = lambda x: jnp.where(upper, pltpu.roll(x, sh, axis),
                                      pltpu.roll(x, size - sh, axis))
        pk, pv, pg = partner(k), partner(v), partner(g)
        keep = _less(k, g, pk, pg) != upper
        return (jnp.where(keep, k, pk), jnp.where(keep, v, pv),
                jnp.where(keep, g, pg))

    return jax.lax.fori_loop(0, size.bit_length() - 1, stage, (k, v, g))


def _less(ka, ta, kb, tb):
    return (ka < kb) | ((ka == kb) & (ta < tb))


def _merge_kernel(split_ref, a0k, a1k, a0v, a1v, b0k, b1k, b0v, b1v,
                  ok_ref, ov_ref, *, n: int, m: int, tiles: int):
    t = pl.program_id(1)
    i0 = split_ref[pl.program_id(0) * tiles + t]
    j0 = t * TILE - i0
    row, col = _flat_iota((SUBLANES, LANES))
    pos = row * LANES + col

    ak = _window(a0k, a1k, i0 & (TILE - 1))
    av = _window(a0v, a1v, i0 & (TILE - 1))
    at = jnp.where(i0 + pos < n, pos, EXCLUDED)
    # b arrives reversed, so its window b[j0 + TILE - 1 - p] starts at
    # m + TILE - j0 and a ++ reverse(b) is bitonic.
    bk = _window(b0k, b1k, (m + TILE - j0) & (TILE - 1))
    bv = _window(b0v, b1v, (m + TILE - j0) & (TILE - 1))
    bt = jnp.where(j0 + (TILE - 1 - pos) < m, 2 * TILE - 1 - pos, EXCLUDED)

    # half-cleaner over the 2*TILE bitonic sequence: keep the smaller half.
    take_a = _less(ak, at, bk, bt)
    k = jnp.where(take_a, ak, bk)
    v = jnp.where(take_a, av, bv)
    g = jnp.where(take_a, at, bt)
    # bitonic merge of the kept half: strides TILE/2 .. LANES across
    # sublanes, then LANES/2 .. 1 across lanes.
    kvg = _half_cleaners(k, v, g, pos, axis=0)
    k, v, _ = _half_cleaners(*kvg, pos, axis=1)
    ok_ref[...] = k
    ov_ref[...] = v


def _diagonal_splits(a_keys, b_keys, n: int, m: int):
    """Merge-path split ``i(t*TILE)`` of every output tile (a-first ties).

    ``a[i]`` lands at merged position ``i + |{b < a[i]}|``, an increasing
    sequence, so the number of ``a`` elements among the first ``k`` merged
    ones is a sorted search of ``k`` in it.
    """
    pos_a = jnp.arange(n, dtype=jnp.int32) + jnp.searchsorted(
        b_keys[:m], a_keys[:n], side="left").astype(jnp.int32)
    k = jnp.arange((n + m) // TILE, dtype=jnp.int32) * TILE
    return jnp.searchsorted(pos_a, k, side="left").astype(jnp.int32)


def _padded_len(n_raw: int) -> int:
    return max(TILE, -(-n_raw // TILE) * TILE)


def _pad(keys, vals, pad_to):
    """Pad the last axis with KEY_MAX keys / zero values to ``pad_to``."""
    pad = [(0, 0)] * (keys.ndim - 1) + [(0, pad_to - keys.shape[-1])]
    return (jnp.pad(keys, pad, constant_values=KEY_MAX32),
            jnp.pad(vals, pad, constant_values=0))


def _call(a_keys, a_vals, b_keys, b_vals, *, interpret: bool):
    """Merge R pairs of runs given as ``(R, n)`` / ``(R, m)`` arrays."""
    R, n_raw = a_keys.shape
    n, m = _padded_len(n_raw), _padded_len(b_keys.shape[1])
    # two KEY_MAX guard tiles past each run: window blocks never leave it.
    a_keys, a_vals = _pad(a_keys, a_vals, n + 2 * TILE)
    b_keys, b_vals = _pad(b_keys, b_vals, m + 2 * TILE)
    tiles = (n + m) // TILE
    splits = jax.vmap(functools.partial(_diagonal_splits, n=n, m=m))(
        a_keys, b_keys).reshape(-1)
    b_keys, b_vals = b_keys[:, ::-1], b_vals[:, ::-1]

    def blk(r, t, sp, *, run_a: bool, second: int):
        i0 = sp[r * tiles + t]
        start = i0 if run_a else m + TILE - (t * TILE - i0)
        # b's window starts at m + TILE when j0 = 0: aligned, so its second
        # block is unused, and the clamp keeps that DMA inside the run.
        last = (n if run_a else m) // TILE + 1
        return (r, jnp.minimum(start // TILE + second, last), 0)

    specs = [pl.BlockSpec((None, SUBLANES, LANES),
                          functools.partial(blk, run_a=run_a, second=second))
             for run_a in (True, False) for _ in ("keys", "vals")
             for second in (0, 1)]
    out_spec = pl.BlockSpec((None, SUBLANES, LANES),
                            lambda r, t, sp: (r, t, 0))
    rows = lambda x: x.reshape(R, -1, LANES)
    ok, ov = pl.pallas_call(
        functools.partial(_merge_kernel, n=n, m=m, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, tiles), in_specs=specs,
            out_specs=[out_spec, out_spec]),
        out_shape=[
            jax.ShapeDtypeStruct((R, (n + m) // LANES, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((R, (n + m) // LANES, LANES), a_vals.dtype),
        ],
        interpret=interpret,
    )(splits, rows(a_keys), rows(a_keys), rows(a_vals), rows(a_vals),
      rows(b_keys), rows(b_keys), rows(b_vals), rows(b_vals))
    return ok.reshape(R, n + m), ov.reshape(R, n + m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted(a_keys, a_vals, b_keys, b_vals, *, interpret: bool = True):
    """Merged (keys, vals) of length n+m (padded to a TILE multiple).

    Inputs are sorted uint32 runs (KEY_MAX padding allowed); outputs keep
    KEY_MAX padding at the tail.  ``interpret=True`` runs the kernel body on
    CPU; pass False on real TPU.
    """
    ok, ov = _call(a_keys[None], a_vals[None], b_keys[None], b_vals[None],
                   interpret=interpret)
    return ok[0], ov[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted_batch(a_keys, a_vals, b_keys, b_vals, *, interpret: bool = True):
    """Merge R independent pairs of sorted runs in ONE kernel launch.

    ``a_keys``/``a_vals`` are ``(R, n)``, ``b_keys``/``b_vals`` ``(R, m)``;
    returns ``(R, n+m)`` merged runs (both dims padded to TILE multiples,
    KEY_MAX tails).  Row r is exactly ``merge_sorted(a[r], b[r])`` — same
    merge-path formulation, same a-first tie-break — on a 2-d
    ``(run, out-tile)`` grid, which is what lets the NB-tree emptying
    cascade merge all <= f children of a node in a single device dispatch
    instead of one launch per child.
    """
    assert b_keys.shape[0] == a_keys.shape[0]
    return _call(a_keys, a_vals, b_keys, b_vals, interpret=interpret)
