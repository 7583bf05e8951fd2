"""CPU rehearsal of ``chip_smoke.py``: the same load/serve/verify function at
a tiny size, with the Pallas kernels in interpret mode, plus the checks that
keep the script honest off the chip."""
import jax
import numpy as np
import pytest

from repro.core.engine_api import OpBatch, OpKind

# small engine: sigma=64 keeps run_cap at one 1024-key tile, so the CPU
# compiles of the query and range programs stay short.
TINY = dict(f=4, sigma=64, max_levels=6, max_results=64)


def test_smoke_matches_reference_at_tiny_size(chip_smoke):
    lines = []
    out = chip_smoke.run_smoke(seed=3, log2_keys=12, serve_ops=256,
                               n_points=512, n_ranges=32, log=lines.append,
                               **TINY)
    assert out["mismatches"] == 0, lines
    assert out["keys"] == 4096
    v = out["verify"]
    # every probe class was exercised, at the sizes asked for
    assert sum(n for k, (n, _) in v.items()
               if k not in ("ranges", "live_table")) >= 512
    assert all(v[k][0] > 0 for k in ("loaded", "new", "deleted", "absent"))
    assert v["ranges"][0] == 32
    assert any(line.startswith("serve:") and "shed=0" in line
               for line in lines)
    assert not any("GREW" in line for line in lines)


def test_final_state_matches_sequential_replay(chip_smoke):
    """The vectorized reference equals a plain dict replay of the writes."""
    rng = np.random.default_rng(7)
    load_k = rng.choice(np.arange(1, 200, dtype=np.uint64), 60, replace=False)
    load_v = rng.integers(0, 1000, 60)
    n = 300
    kinds = rng.choice([int(k) for k in OpKind], n).astype(np.int8)
    keys = rng.integers(1, 200, n).astype(np.uint64)
    vals = rng.integers(0, 1000, n)
    ops = OpBatch(kinds, keys, vals, keys)
    ref = dict(zip(load_k.tolist(), load_v.tolist()))
    for k, key, val in zip(kinds, keys.tolist(), vals.tolist()):
        if k == OpKind.INSERT:
            ref[key] = val
        elif k == OpKind.DELETE:
            ref[key] = -1
    rk, rv = chip_smoke.final_state(load_k, load_v, ops)
    assert rk.tolist() == sorted(ref)
    assert rv.tolist() == [ref[k] for k in sorted(ref)]


def test_distinct_keys_and_table_rows(chip_smoke):
    keys = chip_smoke.distinct_keys(np.random.default_rng(0), 5000, 6000)
    assert len(np.unique(keys)) == 5000
    assert keys.min() >= 1 and keys.max() <= 6000
    # the default load at sigma=2048 ends with 2.2 ids per sigma keys
    n = 1 << chip_smoke.LOG2_KEYS
    rows = chip_smoke.table_rows(n, 2048)
    assert 2.2 * n / 2048 < rows <= 3 * n / 2048 + 1024


def test_compile_counter_splits_cache_hits(chip_smoke):
    c = chip_smoke.CompileCounter()
    compile_ev = "/jax/core/compile/backend_compile_duration"
    c(compile_ev, 2.0)                                   # compiled
    c("/jax/compilation_cache/cache_retrieval_time_sec", 0.01)
    c(compile_ev, 0.02)                                  # loaded
    c(compile_ev, 3.0)                                   # compiled
    c("/jax/compilation_cache/cache_misses", 0.0)        # other events
    assert (c.cold, c.warm) == (2, 1)
    assert (c.cold_s, c.warm_s) == (5.0, 0.02)


def test_main_refuses_a_backend_without_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""                      # no result line, nothing else
    assert "needs a TPU" in err


def test_compile_cache_placement(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.compile_cache import CHECKOUT, place_compile_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved[0]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT / ".jax_cache")
        assert (CHECKOUT / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        cc.reset_cache()


def test_interpret_mode_only_on_cpu(monkeypatch):
    from repro.kernels import ops

    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()
