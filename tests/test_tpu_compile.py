"""Compile the served path's kernels and programs for a TPU v5e chip.

Interpret-mode tests run the kernel bodies on the CPU and cannot see what
the TPU compiler refuses (a gather across tiles, a misaligned block, too
much VMEM or HBM).  These tests compile, without running, for one chip of
a v5e topology that is described but not attached.  The topology is
described inside a fixture, so importing this file touches no TPU library;
the persistent compilation cache is off around the compiles, since an
entry compiled for a chip cannot be read back without one.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import jax_nbtree as J
from repro.kernels import merge_sorted as ms

SIGMA, FANOUT = 2048, 4          # the jax-nbtree registry defaults


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def idx():
    """An index at the registry defaults; only its sizes are used."""
    return J.NBTreeIndex(f=FANOUT, sigma=SIGMA, max_nodes=1)


@pytest.fixture(scope="module")
def rows(chip_smoke):
    """Node-table rows of ``chip_smoke.py``'s default load."""
    return chip_smoke.table_rows(1 << chip_smoke.LOG2_KEYS, SIGMA)


def _spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_text(fn, *args, **static):
    return fn.lower(*args, **static).compile().as_text()


def _tables(S, idx, rows):
    return (S((rows, idx.run_cap), jnp.uint32), S((rows, idx.run_cap), jnp.int32),
            S((rows,), jnp.int32), S((rows, idx.nbits // 32), jnp.uint32))


@pytest.mark.parametrize("batched", [False, True], ids=["insert", "flush"])
def test_merge_kernel_lowers_at_served_widths(one_chip, idx, batched):
    """merge_sorted at insert widths (sigma x run_cap), merge_sorted_batch
    at flush widths (f x sigma, f x run_cap)."""
    S = _spec(one_chip)
    lead = (FANOUT,) if batched else ()
    a, b = (*lead, SIGMA), (*lead, idx.run_cap)
    fn = ms.merge_sorted_batch if batched else ms.merge_sorted
    text = _compiled_text(fn, S(a, jnp.uint32), S(a, jnp.int32),
                          S(b, jnp.uint32), S(b, jnp.int32), interpret=False)
    assert "tpu_custom_call" in text


def test_insert_impl_holds_merge_kernel(one_chip, idx, rows):
    S = _spec(one_chip)
    text = _compiled_text(
        J._insert_impl, *_tables(S, idx, rows), S((SIGMA,), jnp.uint32),
        S((SIGMA,), jnp.int32), run_cap=idx.run_cap, nbits=idx.nbits,
        h=idx.h, interpret=False)
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def flush_text(one_chip, idx, rows):
    """The compiled chained flush program (a device cursor and log)."""
    S = _spec(one_chip)
    return _compiled_text(
        J._flush_impl, *_tables(S, idx, rows),
        S((rows, FANOUT - 1), jnp.uint32), S((rows, FANOUT), jnp.int32),
        S((rows,), jnp.int32), S((), jnp.int32),
        S((idx.max_levels, FANOUT + 2), jnp.int32), sigma=SIGMA,
        sigma_pad=idx.sigma_pad, run_cap=idx.run_cap, nbits=idx.nbits,
        h=idx.h, interpret=False)


@pytest.mark.parametrize("leaf", [True, False], ids=["leaf", "internal"])
def test_flush_impl_holds_merge_kernel(flush_text, rows, leaf):
    """One branch of the flush program per fanout 2..f, for leaf children
    (those compact: a sort) and for internal ones, each with the merge
    kernel, beside the no-op branch; and no copy of a whole node table."""
    comps = {c.split(" ", 1)[0]: c
             for c in re.split(r"\n(?=%)", flush_text)}
    (cond,) = [ln for ln in flush_text.splitlines() if " conditional(" in ln]
    branches = re.search(r"branch_computations=\{([^}]*)\}",
                         cond).group(1).split(", ")
    assert len(branches) == 1 + 2 * (FANOUT - 1)
    merging = [comps[b] for b in branches if "tpu_custom_call" in comps[b]]
    assert sum(("sort(" in c) == leaf for c in merging) == FANOUT - 1
    assert not re.search(rf"= \w+\[{rows},\d+\]\S* copy\(", flush_text)


def test_query_batch_impl_compiles(one_chip, idx, rows):
    S = _spec(one_chip)
    B = 4096
    compiled = J._query_batch_impl.lower(
        S((rows, FANOUT - 1), jnp.uint32), S((rows,), jnp.int32),
        S((rows, FANOUT), jnp.int32), *_tables(S, idx, rows),
        S((B,), jnp.uint32), f=FANOUT, levels=idx.max_levels,
        run_cap=idx.run_cap, nbits=idx.nbits, h=idx.h,
        steps=idx._steps).compile()
    assert compiled.as_text()


def test_range_query_batch_impl_compiles(one_chip, idx, rows):
    S = _spec(one_chip)
    B, M, cap = 256, 32, 512
    tables = _tables(S, idx, rows)[:3]
    compiled = J._range_query_batch_impl.lower(
        *tables, S((B, M), jnp.int32), S((B,), jnp.uint32),
        S((B,), jnp.uint32), cap=cap, max_results=cap, run_cap=idx.run_cap,
        steps=idx._steps).compile()
    assert compiled.as_text()


def test_served_tables_fit_one_chip(idx, rows):
    """The smoke's node tables take a modest share of 16 GB of HBM."""
    row_bytes = 4 * (2 * idx.run_cap + idx.nbits // 32 + 2 * FANOUT + 1)
    assert rows * row_bytes < 0.35 * 16e9
