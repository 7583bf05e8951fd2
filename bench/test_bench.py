"""CPU checks of the benchmark: the trace reduction, the generator, the
reference, a tiny rehearsal of each cell through the harness (Pallas in
interpret mode), the planted faults that the comparison must catch, and
the refusals of ``bench.run`` off the chip."""
from __future__ import annotations

import copy
import json
import math
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import faults, generator, harness, reference, trace
from bench.generator import INSERT, QUERY

ROOT = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

# ----------------------------------------------------------- trace reduction
#: a recorded trace, written out by hand: one chip, two programs (2 us and
#: 1 us) in a 10 us window, one merge launch of (1, 96 x 128) merged pairs
#: lasting 0.5 us, and one benchmark ``apply`` span.
TRACE = r'''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 300000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__insert_impl(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__query_batch_impl(77)" } }
  event_metadata { key: 3 value { id: 3 name: "%merge_sorted.1 = (u32[1,96,128]{2,1,0:T(8,128)S(1)}, s32[1,96,128]{2,1,0}) custom-call(s32[12]{0} %b), custom_call_target=\"tpu_custom_call\"" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.3 = u32[64]{0} fusion(u32[64]{0} %rev.0), kind=kCustom" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 3500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "apply" } } }
'''


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(TRACE)
    return trace.reduce_xspace(ProfileData.from_serialized_xspace(raw))


def test_trace_busy_idle_and_gaps(recorded):
    t = recorded
    assert t.n_chips == 1
    assert t.window == pytest.approx((0.0, 10e-6))
    assert t.busy_s == pytest.approx(3e-6)
    # idle 7 of 10 us; gaps: [0,1) client, [3,6) apply (midpoint 4.5 is
    # inside apply's 1.5..5.0), [7,10) client
    gaps = sorted((n, round(s * 1e9)) for n, s in t.idle_gaps())
    assert gaps == [("apply", 3000), ("client", 1000), ("client", 3000)]
    assert t.module_seconds("jit__query_batch_impl") == pytest.approx(1e-6)
    assert t.top_modules()[0] == ["jit__insert_impl", pytest.approx(2e-6)]


def test_trace_merge_bytes_and_roofline(recorded):
    t = recorded
    # only the tpu_custom_call merge counts; 96 x 128 merged pairs, read
    # once and written once at 8 B a pair
    assert [b for _, _, b in t.merges] == [2 * 96 * 128 * 8]
    assert trace.merge_bytes(4, 13312) == 2 * 4 * 13312 * 8
    want = 100.0 * (2 * 96 * 128 * 8 / 819e9) / 0.5e-6
    assert t.merge_roofline(819e9) == pytest.approx(want)
    assert trace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks("TPU v9")


def test_metric_readers_on_recorded_trace(recorded):
    run = harness.Run()
    run.trace, run.peaks = recorded, trace.peaks("TPU v5 lite")
    run.commits = np.array([[0.0, 0.5, 0.6, 64, 4], [0.6, 1.0, 1.5, 64, 2]])
    run.traced_from = 0.5
    run.dispatches = 32
    run.queue_s = np.linspace(0.0, 1.0, 128)
    got = {m["name"]: harness.read_metric(m["name"], run)
           for m in SPEC["per_layer"]}
    assert got["device_idle.ingest"] == pytest.approx(70.0)
    assert got["apply_us_per_op.serve"] == pytest.approx(0.4 / 64 * 1e6)
    assert got["maintain_share.ingest"] == pytest.approx(100 * 0.5 / 1.0)
    assert got["dispatches_per_op.ingest"] == pytest.approx(0.5)
    assert got["query_device_us_per_op"] == pytest.approx(0.5)
    assert got["queue_wait_p99_ms"] == pytest.approx(
        np.percentile(run.queue_s[64:], 99) * 1e3)
    assert 0 < got["merge_roofline"] < 100


# ----------------------------------------------------------------- generator
def test_hashed_keys_are_a_seeded_bijection():
    cfg = {"insertorder": "hashed"}
    a = generator.RecordKeys(cfg, 2**40 + 7).keys(0, 50_000)
    assert len(np.unique(a)) == 50_000
    # the whole 32-bit key domain, the engine's padding key left out
    assert a.max() < generator.KEY_LIMIT == 0xFFFFFFFF
    assert np.mean(a >= 1 << 31) == pytest.approx(0.5, abs=0.02)
    assert np.array_equal(a, generator.RecordKeys(cfg, 2**40 + 7).keys(0, 50_000))
    assert not np.array_equal(a, generator.RecordKeys(cfg, 3).keys(0, 50_000))
    f = generator.Feistel32(1)
    x = np.array([0, generator.KEY_LIMIT - 1], np.uint64)
    assert np.all(f(x) < generator.KEY_LIMIT)


def test_ordered_keys_increase_across_calls():
    rk = generator.RecordKeys({"insertorder": "ordered", "gap_max": 64}, 9)
    k = np.concatenate([rk.keys(0, 1000), rk.keys(1000, 500)])
    gaps = np.diff(k.astype(np.int64))
    assert k[0] >= 1 and gaps.min() >= 1 and gaps.max() <= 64
    with pytest.raises(ValueError):
        rk.keys(0, 10)


def test_zipf_matches_ycsb_constant():
    n, theta = 1 << 16, 0.99
    u = generator.stratified(np.random.default_rng(0), 200_000)
    r = generator.zipf_ranks(u, n, theta)
    p0 = 1.0 / generator.zeta(n, theta)
    assert np.mean(r == 0) == pytest.approx(p0, rel=0.02)
    assert r.min() == 0 and r.max() < n


def test_mix_and_arrivals_are_the_same_work_for_every_seed():
    cfg = {"insertorder": "hashed", "recordcount": 1 << 12}
    tr = {"mix": {"read": 0.95, "update": 0.05}, "rate": 1000.0,
          "requestdistribution": "zipfian", "zipf_theta": 0.99}
    reqs = [generator.Traffic(cfg, tr, s).open_loop(2.0) for s in (1, 2)]
    for r in reqs:
        assert len(r) == 2000
        assert np.sum(r.kinds == INSERT) == 100
        assert 0 < r.t_due[0] and r.t_due[-1] < 2.0
        assert np.all(np.diff(r.t_due) > 0)
    assert not np.array_equal(reqs[0].keys, reqs[1].keys)
    assert np.sort(np.diff(reqs[0].t_due))[-1] == pytest.approx(
        np.sort(np.diff(reqs[1].t_due))[-1], rel=0.2)


# ----------------------------------------------------------------- reference
def test_reference_equals_sequential_replay():
    rng = np.random.default_rng(4)
    n = 3000
    kinds = rng.choice([INSERT, QUERY], n).astype(np.int8)
    # 300 keys spread over the 32-bit domain, the top one near 2^32
    keys = (rng.integers(0, 300, n) * 14_300_000 + 7).astype(np.uint64)
    vals = rng.integers(0, 1000, n)
    state, want_f, want_v = {}, [], []
    for k, key, v in zip(kinds, keys.tolist(), vals.tolist()):
        if k == INSERT:
            state[key] = v
        else:
            want_f.append(key in state)
            want_v.append(state.get(key, -1))
    at = np.flatnonzero(kinds == QUERY)
    f, v = reference.answers(kinds, keys, vals, at)
    assert f.tolist() == want_f and v.tolist() == want_v
    rk, rv = reference.final_state(kinds, keys, vals)
    assert rk.tolist() == sorted(state)
    assert rv.tolist() == [state[k] for k in sorted(state)]
    assert reference.table_mismatches(rk, rv, rk, rv) == 0
    assert reference.table_mismatches(rk[1:], rv[1:], rk, rv + 1) == len(rk)


# ------------------------------------------------------ rehearsal and faults
#: engine and data set small enough for interpret-mode Pallas on a CPU:
#: sigma 64 keeps a run to one 1,024-key tile.
TINY_CONFIG = {"recordcount": 1 << 11, "load_batch": 1024,
               "engine_args": {"f": 4, "sigma": 64, "max_levels": 6}}
TINY_TRAFFIC = {"ycsb.insert": {"sizing_rate": 2000},
                "ycsb-b.open": {"rate": 400, "commit_cap": 4}}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell = copy.deepcopy(cell)
    cell.config.update(copy.deepcopy(TINY_CONFIG))
    cell.traffic.update(TINY_TRAFFIC[name])
    return cell


def tiny_run(name: str, fault: str = "none", seed: int = 2**31 + 11):
    import time

    return harness.run_cell(tiny_cell(name), seed=seed, seconds=0.5,
                            trace=False, t_process=time.perf_counter(),
                            engine_factory=faults.factory(fault))


@pytest.mark.parametrize("name", sorted(TINY_TRAFFIC))
def test_rehearsal_is_correct(name):
    out = tiny_run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    want = {m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(out["metrics"]) == want
    assert out["device"]["platform"] == "cpu"
    if name == "ycsb-b.open":
        assert "window_reads_wrong" in out["checks"]


@pytest.mark.parametrize("fault", ["write_behind", "unchanged", "half_batch",
                                   "altered"])
@pytest.mark.parametrize("name", sorted(TINY_TRAFFIC))
def test_planted_fault_is_not_correct(name, fault):
    out = tiny_run(name, fault)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


# ----------------------------------------------------- the command and spec
def test_run_refuses_a_backend_without_tpu(capsys):
    from bench import run

    assert run.main(["--workload", "ycsb.insert", "--seed", "1",
                     "--seconds", "1"]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "TPU" in err


def test_run_refuses_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "ycsb.insert",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout == ""


def test_every_name_in_the_spec_has_its_files():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in SPEC["workloads"]:
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
        cell = harness.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_tables_hold_a_full_window():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        rows = harness.table_rows(cell.config, cell.traffic, SPEC["run_seconds"])
        sigma = cell.config["engine_args"]["sigma"]
        keys = (cell.config["recordcount"] + cell.traffic["sizing_rate"]
                * cell.traffic["mix"].get("insert", 0) * SPEC["run_seconds"])
        assert rows >= math.ceil(2.2 * keys / sigma)
