"""Observability layer tests (DESIGN.md §11).

Covers the shared log-bucket histogram (property-tested against exact
numpy percentiles: bucket-bounded error on p50/p99, exact count/mean/
p100), windowed-metrics rollover (empty windows under clock jumps,
partial-window flush, fluctuation/stall-free scoring), the span tracer's
Chrome trace_event emission (schema validity, ring-buffer bounds,
round-trip through JSON), stall detection + attribution against a
synthetic injected stall, byte-determinism of obs-instrumented open-loop
reports, the disabled-mode zero-overhead contract (obs off == obs absent,
to the byte), the driver histogram facade, the wall-clock ``Tracer.span``,
and the chip peaks table.
"""
import json

import numpy as np
import pytest

from repro.core.engine_api import make_engine
from repro.ingest import FrontendConfig, PoissonArrivals, make_trace, \
    run_open_loop
from repro.obs import (LogBucketHistogram, ObsConfig, SPAN_CATEGORIES,
                       Tracer, WindowedMetrics, attribute_stalls,
                       detect_stalls, validate_chrome_trace)
from repro.obs.metrics import BUCKET_EDGES_S
from repro.workloads import make_workload
from repro.workloads.driver import LatencyHistogram

# ------------------------------------------------------------- histogram


#: adjacent bucket edges are a factor of 10^(1/4) apart, so a
#: bucket-interpolated quantile can be off by at most one bucket width.
_BUCKET_RATIO = float(BUCKET_EDGES_S[1] / BUCKET_EDGES_S[0])


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_quantiles_within_one_bucket_of_exact(dist):
    rng = np.random.default_rng(hash(dist) % (1 << 32))
    if dist == "lognormal":
        xs = rng.lognormal(mean=-7.0, sigma=2.0, size=20_000)
    elif dist == "uniform":
        xs = rng.uniform(1e-6, 1e-2, size=20_000)
    else:
        xs = np.concatenate([rng.normal(1e-4, 1e-5, 10_000),
                             rng.normal(5e-2, 5e-3, 10_000)]).clip(1e-9)
    h = LogBucketHistogram()
    h.add_many(xs)
    assert h.count == len(xs)
    assert h.mean == pytest.approx(xs.mean())
    assert h.max == pytest.approx(xs.max())          # p100 exact
    assert h.min == pytest.approx(xs.min())
    assert int(h.counts.sum()) == len(xs)
    # compare against the order statistic ("lower"): the bucket rank is
    # floor(q*(n-1)), and linear interpolation across an empty gap
    # between modes is not within any bucket's reach by construction.
    for q in (0.50, 0.90, 0.99, 0.999):
        exact = float(np.quantile(xs, q, method="lower"))
        est = h.quantile(q)
        assert est <= exact * _BUCKET_RATIO * 1.0001
        assert est >= exact / _BUCKET_RATIO / 1.0001
    # monotone and clamped to the exact extremes
    qs = [h.quantile(q) for q in (0.0, 0.5, 0.9, 0.99, 1.0)]
    assert qs == sorted(qs)
    assert qs[0] == h.min and qs[-1] == h.max


def test_histogram_scalar_add_matches_vector_add():
    xs = [1e-6, 3e-4, 2e-1, 5.0, 1e-12, 1e9]     # includes out-of-range
    a, b = LogBucketHistogram(), LogBucketHistogram()
    for x in xs:
        a.add(x)
    b.add_many(xs)
    assert np.array_equal(a.counts, b.counts)
    assert a.count == b.count and a.total == b.total
    assert a.min == b.min and a.max == b.max


def test_histogram_merge_and_empty():
    h = LogBucketHistogram()
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    assert h.summary()["count"] == 0
    a, b = LogBucketHistogram(), LogBucketHistogram()
    a.add_many([1e-4, 2e-4])
    b.add_many([5e-3])
    a.merge(b)
    assert a.count == 3
    assert a.max == pytest.approx(5e-3)
    s = a.summary()
    assert s["p50_s"] <= s["p99_s"] <= s["p100_s"]
    assert sum(s["bucket_counts"]) == 3


def test_driver_latency_histogram_facade():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(-8.0, 1.5, 5000)
    h = LatencyHistogram()
    h.add(xs)
    assert h.count == 5000
    d = h.to_dict()
    assert d["count"] == 5000
    assert d["p100_s"] == pytest.approx(xs.max())     # exact, not bucketed
    assert d["mean_s"] == pytest.approx(xs.mean())
    assert d["p50_s"] <= d["p99_s"] <= d["p100_s"]
    assert sum(d["bucket_counts"]) == d["count"]
    assert len(d["bucket_counts"]) == len(d["bucket_edges_s"]) - 1
    assert "p999_s" not in d                          # per-kind block shape
    assert h.percentile(100) == pytest.approx(xs.max())


# ------------------------------------------------------- windowed metrics


def test_windowed_metrics_clock_jump_emits_empty_windows():
    wm = WindowedMetrics(1.0)
    wm.record(0.5, 1e-3)
    wm.record(4.2, 2e-3)          # jumps over windows 1..3
    out = wm.finish()
    tl = out["timeline"]
    assert out["n_windows"] == 5
    assert [w["ops"] for w in tl] == [1, 0, 0, 0, 1]
    assert out["n_active_windows"] == 2
    # empty windows report zeroed gauges, not stale state
    assert tl[1]["p99_s"] == 0.0 and tl[2]["queue_peak"] == 0
    # window boundaries tile the timeline exactly
    for i, w in enumerate(tl):
        assert w["t_start_s"] == pytest.approx(float(i))
        assert w["t_end_s"] == pytest.approx(float(i + 1))


def test_windowed_metrics_finish_extends_to_t_end():
    wm = WindowedMetrics(0.5)
    wm.record(0.1, 1e-3)
    out = wm.finish(t_end=2.6)
    assert out["n_windows"] == 5    # [0,.5) + 4 empties through t=2.6
    assert [w["ops"] for w in out["timeline"]] == [1, 0, 0, 0, 0]


def test_windowed_metrics_shed_only_window_is_emitted():
    wm = WindowedMetrics(1.0)
    wm.record_shed(0.2, 3)
    out = wm.finish()
    assert out["n_windows"] == 1
    assert out["timeline"][0]["shed"] == 3
    assert out["timeline"][0]["ops"] == 0


def test_windowed_metrics_rejects_bad_width():
    with pytest.raises(ValueError):
        WindowedMetrics(0.0)


def test_fluctuation_score_flat_vs_sawtooth():
    flat, saw = WindowedMetrics(1.0), WindowedMetrics(1.0)
    for i in range(16):
        for _ in range(100):
            flat.record(i + 0.5, 1e-3)
        for _ in range(25 if i % 2 else 175):
            saw.record(i + 0.5, 1e-3)
    f, s = flat.finish(), saw.finish()
    assert f["fluctuation_score"] == pytest.approx(0.0)
    assert s["fluctuation_score"] > 0.5


# ------------------------------------------------------------- stalls


def _mk_windows(p99s, window_s=1.0):
    return [{"t_start_s": i * window_s, "t_end_s": (i + 1) * window_s,
             "ops": 100, "p99_s": p, "p50_s": p / 2} for i, p in
            enumerate(p99s)]


def test_detect_stalls_flags_spike_not_baseline():
    p99s = [1e-3] * 10 + [10e-3] + [1e-3] * 5      # 10x spike at index 10
    stalls = detect_stalls(_mk_windows(p99s), k=4.0)
    assert [s["index"] for s in stalls] == [10]
    assert stalls[0]["baseline_p99_s"] == pytest.approx(1e-3)


def test_detect_stalls_excludes_stalled_windows_from_baseline():
    # consecutive stalls must all be flagged: the first must not drag the
    # trailing median up and mask the rest.
    p99s = [1e-3] * 8 + [20e-3] * 3 + [1e-3] * 4
    stalls = detect_stalls(_mk_windows(p99s), k=4.0)
    assert [s["index"] for s in stalls] == [8, 9, 10]


def test_detect_stalls_min_history_exempts_warmup():
    p99s = [50e-3, 1e-3, 1e-3, 1e-3, 1e-3]
    assert detect_stalls(_mk_windows(p99s), k=4.0, min_history=4) == []


def test_attribute_stalls_picks_dominant_overlap():
    tr = Tracer()
    # window [10, 11): a long cascade span dominates a short commit span
    tr.complete("cascade", "empty", 10.1, 0.7)
    tr.complete("commit", "group", 10.2, 0.1)
    tr.complete("wal_fsync", "append", 9.0, 0.5)   # outside the window
    stalls = [{"index": 10, "t_start_s": 10.0, "t_end_s": 11.0,
               "p99_s": 1.0, "baseline_p99_s": 0.1}]
    out = attribute_stalls(stalls, tr.events())
    assert out[0]["cause"] == "cascade"
    assert out[0]["cause_overlap_s"]["cascade"] == pytest.approx(0.7)
    assert "wal_fsync" not in out[0]["cause_overlap_s"]


def test_attribute_stalls_unknown_when_no_overlap():
    stalls = [{"index": 0, "t_start_s": 0.0, "t_end_s": 1.0,
               "p99_s": 1.0, "baseline_p99_s": 0.1}]
    out = attribute_stalls(stalls, [])
    assert out[0]["cause"] == "unknown"


# ------------------------------------------------------------- tracer


def test_tracer_chrome_json_roundtrip(tmp_path):
    tr = Tracer()
    tr.complete("commit", "group_commit", 0.001, 0.0005, ops=64)
    tr.complete("wal_fsync", "append_commit", 0.0012, 0.0001, lsn=1)
    tr.instant("shed", "queue_full", 0.002, n=3)
    path = tmp_path / "trace.json"
    tr.save(str(path))
    obj = json.loads(path.read_text())
    assert validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    # metadata rows name one process per span category
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} >= {"commit", "wal_fsync",
                                                "shed"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    assert xs[0]["ts"] == pytest.approx(1000.0)       # microseconds
    assert xs[0]["dur"] == pytest.approx(500.0)
    assert xs[0]["args"]["ops"] == 64
    insts = [e for e in evs if e["ph"] == "i"]
    assert len(insts) == 1 and insts[0]["s"] == "g"


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(capacity=16)
    for i in range(100):
        tr.complete("commit", "c", i * 1e-3, 1e-4)
    assert len(tr) == 16
    assert tr.dropped_events == 84
    # survivors are the newest events
    ts = [e["ts"] for e in tr.events()]
    assert ts == sorted(ts) and ts[0] == pytest.approx(84_000.0)


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    tr.complete("commit", "c", 0.0, 1e-3)
    tr.instant("shed", "s", 0.0)
    assert len(tr) == 0 and tr.dropped_events == 0


def test_validate_chrome_trace_catches_malformed():
    assert validate_chrome_trace({"traceEvents": "nope"})
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "x", "ts": 0}]})  # no dur
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "i", "name": "x", "ts": "zero"}]})
    assert validate_chrome_trace({"traceEvents": []}) == []


def test_span_records_wall_interval_with_args():
    tr = Tracer()
    with tr.span("sync", "nbtree.sync", what="ack"):
        with tr.span("dispatch", "nbtree.dispatch", fn="_insert_impl"):
            pass
    inner, outer = tr.spans()            # recorded as each one closes
    assert (outer["cat"], outer["name"], outer["args"]) == (
        "sync", "nbtree.sync", {"what": "ack"})
    assert inner["args"] == {"fn": "_insert_impl"}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["pid"] == SPAN_CATEGORIES.index("sync")
    assert not validate_chrome_trace(tr.to_chrome())


def test_disabled_tracer_span_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("sync", "nbtree.sync", what="ack"):
        pass
    assert len(tr) == 0 and tr.dropped_events == 0


def test_span_categories_cover_serving_pipeline():
    assert {"commit", "wal_fsync", "flush_unit", "cascade", "shard_split",
            "checkpoint", "recovery", "shed", "tenant_throttle", "dispatch",
            "sync"} <= set(SPAN_CATEGORIES)


# ------------------------------------------- end-to-end open-loop contract


def _open_loop_report(obs):
    wl = make_workload("insert-heavy", key_space=1 << 16, n_ops=2048,
                       preload=256, batch_size=128, seed=3)
    trace = make_trace(wl, PoissonArrivals(150_000.0))
    eng = make_engine("nbtree", f=3, sigma=1024)
    cfg = FrontendConfig(max_queue=256, commit_ops=64, linger_s=2e-4)
    return run_open_loop(eng, trace, config=cfg, obs=obs)


def test_open_loop_obs_deterministic_across_runs():
    a = _open_loop_report(ObsConfig(window_s=0.005))
    b = _open_loop_report(ObsConfig(window_s=0.005))
    assert json.dumps(a["open_loop"]["obs"], sort_keys=True) == \
        json.dumps(b["open_loop"]["obs"], sort_keys=True)
    ob = a["open_loop"]["obs"]
    assert ob["n_windows"] >= 2
    assert ob["trace"]["events"] > 0
    assert "commit" in ob["trace"]["categories"]


def test_open_loop_disabled_obs_identical_to_absent():
    base = _open_loop_report(None)
    off = _open_loop_report(ObsConfig(enabled=False))
    assert json.dumps(base, sort_keys=True, default=str) == \
        json.dumps(off, sort_keys=True, default=str)
    assert "obs" not in base["open_loop"]


def test_open_loop_obs_windows_cover_trace_duration():
    rep = _open_loop_report(ObsConfig(window_s=0.002))
    ob = rep["open_loop"]["obs"]
    tl = ob["timeline"]
    done = sum(w["ops"] for w in tl)
    shed = sum(w["shed"] for w in tl)
    assert done == rep["open_loop"]["n_done"]
    assert shed == rep["open_loop"]["n_shed"]
    # windows tile [0, t_last) with no gaps
    for prev, nxt in zip(tl, tl[1:]):
        assert nxt["t_start_s"] == pytest.approx(prev["t_end_s"])


# ------------------------------------------------------------- roofline


def test_chip_peaks_keyed_by_device_kind():
    from repro.roofline import hardware as hw

    v5e = hw.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16 * 10**9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="cpu"):
        hw.peaks("cpu")
