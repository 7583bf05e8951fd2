"""One run of one cell: build the engine, load it, warm it, serve for
``seconds`` on the wall clock, check every answer, reduce to metrics.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration file (``configs[].file``) holds the data set and the engine's
settings, and ``bench/traffic/<traffic>.json`` the op mix and the loop.
Metrics are readers in ``bench/metrics/<name>.py``.  Nothing here names a
cell, a configuration, a mix or a metric.

The serving loop (the same for warm-up and window) takes the ops that are
due, in issue order, up to the traffic's ``commit_cap``; hands them to
``StorageEngine.apply`` as one ``OpBatch``, which returns once the device
has finished them; then calls ``StorageEngine.maintain(maintain_budget)``.
Kinds are never regrouped inside a commit.  An op's latency runs from its
due time (open loop) or issue time (closed loop) to the return of the
``apply`` that served it.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from . import reference
from .generator import INSERT, QUERY, Requests, Traffic

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the traced run traces the last seconds of its window.
TRACE_SECONDS = 4.0
#: an open loop serves its backlog at most this long past the window.
DRAIN_SECONDS = 60.0
#: commits of a closed loop run before the window (warm-up).
WARM_COMMITS = 8
#: reads of the final state after the window, by class.
PROBE_LOADED, PROBE_WRITTEN, PROBE_ABSENT = 4096, 4096, 1024
#: the comparison with the reference is exact.
LIMIT = 0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the cell
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    end_to_end: list          # BENCHMARK.json metric entries this cell reports
    per_layer: list
    chips: int
    run_seconds: int          # BENCHMARK.json's window length


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def here(m, default):
        return workload in m.get("workloads", default)

    e2e = [m for m in spec["end_to_end"] if here(m, [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if here(m, [workload] if m["moves"] in names else [])]
    return Cell(workload, config, traffic, e2e, layer, int(w["chips"]),
                int(spec["run_seconds"]))


def read_metric(name: str, run: "Run"):
    """Value of metric ``name`` for ``run``, from ``bench/metrics/<name>.py``;
    None where that reader finds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------------- the record
@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""

    setup_s: float = 0.0
    window_s: float = 0.0             # first commit start .. last commit end
    latency_s: np.ndarray = None      # every op served in the window
    queue_s: np.ndarray = None        # due -> commit start (open loop)
    commits: np.ndarray = None        # rows: start, applied, end, ops, reads
    attempted: int = 0
    failed: int = 0
    traced_from: float | None = None  # host clock where the trace began
    dispatches: int | None = None     # device dispatches while traced
    trace: object = None              # bench.trace.Trace of the traced part
    peaks: dict | None = None

    def traced(self) -> np.ndarray:
        """Mask of the commits that started inside the traced part."""
        if self.traced_from is None:
            return np.zeros(len(self.commits), bool)
        return self.commits[:, 0] >= self.traced_from

    def traced_op_mask(self) -> np.ndarray:
        """Mask of the window's ops served by a traced commit."""
        counts = self.commits[:, 3].astype(np.int64)
        return np.repeat(self.traced(), counts)


class CompileCounter:
    """JAX monitoring listener: programs compiled by XLA (cold) and loaded
    from the persistent cache (warm), with the seconds each took.  JAX times
    every compile request, cache hit or not, under one event; a hit also
    records its cache retrieval just before that event ends."""

    def __init__(self):
        self.cold = self.warm = 0
        self.cold_s = self.warm_s = 0.0
        self._hit = False

    def __call__(self, event: str, duration_s: float, **_):
        if event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self._hit = True
        elif event == "/jax/core/compile/backend_compile_duration":
            if self._hit:
                self.warm += 1
                self.warm_s += duration_s
            else:
                self.cold += 1
                self.cold_s += duration_s
            self._hit = False

    @property
    def total(self) -> int:
        return self.cold + self.warm


# ------------------------------------------------------------------- engine
def table_rows(config: dict, traffic: dict, seconds: float) -> int:
    """Node-table rows for the load plus the most a window of ``seconds``
    can insert at the traffic's ``sizing_rate``, so tables never grow (and
    nothing recompiles) inside a window."""
    inserts = (traffic["mix"].get("insert", 0.0) * traffic["sizing_rate"]
               * seconds)
    keys = config["recordcount"] + inserts + 8 * traffic["commit_cap"]
    sigma = config["engine_args"]["sigma"]
    rows = config["rows_per_sigma_keys"] * keys / sigma
    return int(max(1024, math.ceil(rows / 1024) * 1024))


def make_engine(cell: Cell):
    """The system under test, its tables sized for a window of the
    benchmark's ``run_seconds``, whatever ``--seconds`` says, so every run
    of a cell compiles the same programs."""
    from repro.core.engine_api import make_engine as make

    rows = table_rows(cell.config, cell.traffic, cell.run_seconds)
    return make(cell.config["engine"], max_nodes=rows,
                **cell.config["engine_args"])


# --------------------------------------------------------------- the server
class Server:
    """The serving loop: one commit = one ``apply`` + one ``maintain``."""

    def __init__(self, engine, traffic: dict):
        from repro.core.engine_api import OpBatch

        self.engine = engine
        self.cap = int(traffic["commit_cap"])
        self.budget = int(traffic["maintain_budget"])
        self.OpBatch = OpBatch
        self.spans = False            # TraceAnnotation spans (traced run)
        # every op applied, in order, for the reference
        self.applied: list = []

    def commit(self, req: Requests, i: int, j: int):
        """Serve ops ``i:j`` of ``req``; returns (start, applied, end, result)."""
        zeros = np.zeros(j - i, np.uint64)
        batch = self.OpBatch(req.kinds[i:j], req.keys[i:j], req.vals[i:j],
                             zeros)
        if self.spans:
            import jax

            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("apply"):
                res = self.engine.apply(batch)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("maintain"):
                self.engine.maintain(self.budget)
        else:
            t0 = time.perf_counter()
            res = self.engine.apply(batch)
            t1 = time.perf_counter()
            self.engine.maintain(self.budget)
        return t0, t1, time.perf_counter(), res

    def record(self, req: Requests, found=None, values=None,
               phase: str = "warm") -> None:
        """Keep ops the engine applied (``phase``: load, warm or window)."""
        self.applied.append((phase, req, found, values))


def preload(server: Server, traffic: Traffic, config: dict) -> float:
    """Insert the ``recordcount`` records in batches, then drain."""
    import jax

    t0 = time.perf_counter()
    n = traffic.recordcount
    keys, vals = traffic.load(n)
    traffic.remember_load(keys)
    b = int(config["load_batch"])
    for i in range(0, n, b):
        server.engine.apply(server.OpBatch.inserts(keys[i:i + b],
                                                   vals[i:i + b]))
    server.engine.drain()
    jax.block_until_ready(server.engine.idx.run_keys)
    server.record(Requests(np.full(n, INSERT, np.int8), keys, vals),
                  phase="load")
    return time.perf_counter() - t0


def _pow2_upto(n: int):
    b = 1
    while b <= n:
        yield b
        b *= 2


def warm_open(server: Server, traffic: Traffic) -> None:
    """Every shape an open loop reaches: each op kind of the mix in runs
    of every power of two up to the commit cap (``apply`` pads a run of
    one kind to the next power of two)."""
    for name in traffic.op_names:
        for b in _pow2_upto(server.cap):
            req = traffic.draw_only(name, b)
            *_, res = server.commit(req, 0, b)
            server.record(req, res.found, res.values, "warm")


def _commits(rows: list) -> np.ndarray:
    """Per-commit rows (start, applied, end, ops, reads) as one array."""
    return np.asarray(rows, np.float64).reshape(-1, 5)


class ClosedLoop:
    """``clients`` callers, each with one request of ``ops_per_request`` ops
    outstanding; a caller issues its next request when the ``apply`` that
    finished its last op returns.  ``reserve`` ops are drawn up front;
    a window that outruns them draws more as it goes."""

    def __init__(self, traffic: Traffic, server: Server, reserve: int):
        t = traffic.traffic
        self.k = int(t["ops_per_request"])
        self.clients = int(t["clients"])
        self.traffic, self.server = traffic, server
        self.block = max(1 << 16, self.clients * self.k)
        self.req = traffic.draw(max(self.block, reserve))
        self.pos = 0

    def _ensure(self, n: int) -> None:
        if self.pos + n > len(self.req):
            more = self.traffic.draw(max(self.block, n))
            r = self.req
            self.req = Requests(np.concatenate([r.kinds, more.kinds]),
                                np.concatenate([r.keys, more.keys]),
                                np.concatenate([r.vals, more.vals]))

    def run(self, until: float, *, n_commits: int | None = None,
            on_commit=None, phase: str = "window") -> dict:
        """Commit until the clock passes ``until`` (or ``n_commits``)."""
        cap = self.server.cap
        now = time.perf_counter()
        pending = collections.deque([now, self.k] for _ in range(self.clients))
        pos0 = self.pos
        rows, lat_n, lat_t, answers = [], [], [], []
        while (time.perf_counter() < until if n_commits is None
               else len(rows) < n_commits):
            if on_commit is not None:
                on_commit()
            take, issued, done = 0, [], 0
            while pending and take < cap:
                r = pending[0]
                n = min(r[1], cap - take)
                issued.append((n, r[0]))
                take += n
                r[1] -= n
                if r[1] == 0:
                    pending.popleft()
                    done += 1
            self._ensure(take)
            i = self.pos
            t0, t1, t2, res = self.server.commit(self.req, i, i + take)
            for n, t_issue in issued:
                lat_n.append(n)
                lat_t.append(t1 - t_issue)
            for _ in range(done):
                pending.append([t1, self.k])
            reads = int(np.count_nonzero(self.req.kinds[i:i + take] == QUERY))
            if reads:
                answers.append((i, res.found, res.values))
            rows.append((t0, t1, t2, take, reads))
            self.pos += take
        served = slice(pos0, self.pos)
        req = Requests(self.req.kinds[served], self.req.keys[served],
                       self.req.vals[served])
        found = np.zeros(len(req), bool)
        values = np.full(len(req), -1, np.int64)
        for i, f, v in answers:
            found[i - pos0:i - pos0 + len(f)] = f
            values[i - pos0:i - pos0 + len(f)] = v
        self.server.record(req, found, values, phase)
        return {"commits": _commits(rows),
                "latency": np.repeat(np.asarray(lat_t), lat_n),
                "queue": None, "attempted": len(req), "failed": 0}


def open_loop(server: Server, req: Requests, t0: float, *,
              on_commit=None) -> dict:
    """Serve ``req`` as its ops fall due (``t0 + t_due``), up to the commit
    cap at a time; wait while none is due; give up on the backlog
    DRAIN_SECONDS after the last op fell due."""
    import jax

    due = t0 + req.t_due
    n = len(req)
    give_up = due[-1] + DRAIN_SECONDS
    lat = np.full(n, np.nan)
    queue = np.full(n, np.nan)
    found = np.zeros(n, bool)
    values = np.full(n, -1, np.int64)
    rows = []
    cap = server.cap
    i = 0
    while i < n:
        now = time.perf_counter()
        if now > give_up:
            break
        if due[i] > now:
            if server.spans:
                with jax.profiler.TraceAnnotation("wait"):
                    time.sleep(due[i] - now)
            else:
                time.sleep(due[i] - now)
            continue
        if on_commit is not None:
            on_commit()
        j = min(i + cap, int(np.searchsorted(due, now, side="right")))
        c0, c1, c2, res = server.commit(req, i, j)
        lat[i:j] = c1 - due[i:j]
        queue[i:j] = c0 - due[i:j]
        found[i:j] = res.found
        values[i:j] = res.values
        rows.append((c0, c1, c2, j - i,
                     int(np.count_nonzero(req.kinds[i:j] == QUERY))))
        i = j
    server.record(Requests(req.kinds[:i], req.keys[:i], req.vals[:i]),
                  found[:i], values[:i], "window")
    return {"commits": _commits(rows), "latency": lat[:i], "queue": queue[:i],
            "attempted": n, "failed": n - i}


# ---------------------------------------------------------------- the check
def check(server: Server, seed: int) -> dict:
    """Numbers compared with the reference, each ``(value, limit)``.

    * ``window_reads_wrong``: every read answered in the window;
    * ``loaded_reads_wrong``: reads, after the window, of loaded records;
    * ``written_reads_wrong``: reads of keys the warm-up or window wrote;
    * ``absent_reads_wrong``: reads of keys never written;
    * ``live_pairs_wrong``: the engine's whole live table (``dump_live``).
    """
    from .generator import KEY_LIMIT, sub_seed

    segs = server.applied
    kinds = np.concatenate([r.kinds for _, r, _, _ in segs])
    keys = np.concatenate([r.keys for _, r, _, _ in segs]).astype(np.uint64)
    vals = np.concatenate([r.vals for _, r, _, _ in segs])
    out = {}
    start = 0
    for phase, req, found, values in segs:
        at = np.flatnonzero(req.kinds == QUERY)
        if phase == "window" and len(at):
            out["window_reads_wrong"] = reference.read_mismatches(
                kinds, keys, vals, at + start, found[at], values[at])
        start += len(req)
    ref_k, ref_v = reference.final_state(kinds, keys, vals)
    load = segs[0][1]
    n_load = len(load)
    rng = np.random.default_rng(sub_seed(seed, 0xC4EC))
    loaded = rng.choice(load.keys, min(PROBE_LOADED, n_load), replace=False)
    written = np.unique(keys[n_load:][kinds[n_load:] == INSERT])
    written = rng.permutation(written)[:PROBE_WRITTEN]
    absent = rng.integers(0, KEY_LIMIT, 4 * PROBE_ABSENT, dtype=np.uint64)
    absent = np.setdiff1d(absent, ref_k)[:PROBE_ABSENT]
    for name, q in (("loaded", loaded), ("written", written),
                    ("absent", absent)):
        f, v = _query(server, q)
        i = np.minimum(np.searchsorted(ref_k, q), len(ref_k) - 1)
        want_f = ref_k[i] == q
        want_v = np.where(want_f, ref_v[i], -1)
        out[f"{name}_reads_wrong"] = int(np.sum((f != want_f)
                                                | (v != want_v)))
    dk, dv = server.engine.dump_live()
    out["live_pairs_wrong"] = reference.table_mismatches(dk, dv, ref_k, ref_v)
    return {k: (int(v), LIMIT) for k, v in out.items()}


def _query(server: Server, q: np.ndarray):
    """Point reads through ``apply``, 1,024 at a time."""
    found = np.zeros(len(q), bool)
    values = np.full(len(q), -1, np.int64)
    step = 1024
    for i in range(0, len(q), step):
        res = server.engine.apply(server.OpBatch.queries(q[i:i + step]))
        found[i:i + step] = res.found
        values[i:i + step] = np.where(res.found, res.values, -1)
    return found, values


# ------------------------------------------------------------------ one run
def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, engine_factory=make_engine) -> dict:
    """One run; returns the result object that ``bench.run`` prints.

    ``engine_factory(cell)`` builds the system under test (tests plant
    faults through it).
    """
    import jax

    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    dev = jax.devices()[0]
    t = Traffic(cell.config, cell.traffic, seed)
    engine = engine_factory(cell)
    server = Server(engine, cell.traffic)
    load_s = preload(server, t, cell.config)
    log(f"load: {t.recordcount} records in {load_s:.3f} s "
        f"({t.recordcount / load_s:.1f} ops/s); "
        f"{engine.idx._next_id} of {engine.idx.max_nodes} table rows used")

    closed = cell.traffic["loop"] == "closed"
    if closed:
        # about one window at the rate the tables are sized for
        reserve = (WARM_COMMITS * server.cap
                   + math.ceil(cell.traffic["sizing_rate"] * seconds))
        loop = ClosedLoop(t, server, reserve)
        loop.run(0.0, n_commits=WARM_COMMITS, phase="warm")
    else:
        warm_open(server, t)
        window_req = t.open_loop(seconds)
    jax.block_until_ready(engine.idx.run_keys)
    rows_before = engine.idx.max_nodes

    run = Run()
    tracer = _Tracer(run, engine) if trace else None
    c0 = compiles.total
    t0 = time.perf_counter()
    run.setup_s = t0 - t_process
    t_end = t0 + seconds
    on_commit = None
    if tracer is not None:
        def on_commit():
            if not tracer.started and time.perf_counter() >= (
                    t_end - min(TRACE_SECONDS, seconds)):
                tracer.start(server)
    if closed:
        out = loop.run(t_end, on_commit=on_commit)
    else:
        out = open_loop(server, window_req, t0, on_commit=on_commit)
    if tracer is not None:
        tracer.stop(server)
    in_window = compiles.total - c0
    run.commits = out["commits"]
    run.latency_s, run.queue_s = out["latency"], out["queue"]
    run.attempted, run.failed = out["attempted"], out["failed"]
    run.window_s = (float(run.commits[-1, 2] - t0) if len(run.commits)
                    else float(seconds))
    mem = dev.memory_stats() or {}
    log(f"window: {len(run.commits)} commits, {int(run.commits[:, 3].sum())} "
        f"ops in {run.window_s:.3f} s; failed {run.failed}")
    if len(run.commits):
        c = run.commits
        slow = np.argsort(c[:, 2] - c[:, 0])[-3:][::-1]
        dur = c[:, 2] - c[:, 0]
        log("latency ms: p50 %.3f p90 %.3f p95 %.3f p99 %.3f p99.9 %.3f max "
            "%.3f; commits over 50 ms: %d; slowest (apply, maintain) ms: %s" % (
                *np.percentile(run.latency_s, [50, 90, 95, 99, 99.9, 100]) * 1e3,
                int(np.sum(dur > 0.05)),
                [(round((c[k, 1] - c[k, 0]) * 1e3, 3),
                  round((c[k, 2] - c[k, 1]) * 1e3, 3)) for k in slow]))
    log(f"compiles inside the window: {in_window} (cold {compiles.cold}, "
        f"warm {compiles.warm} in the whole run)")
    if engine.idx.max_nodes != rows_before:
        log(f"node tables grew in the window: {rows_before} -> "
            f"{engine.idx.max_nodes} rows")
    if tracer is not None:
        t_reduce = time.perf_counter()
        tracer.reduce(dev)
        log(f"trace reduction: {time.perf_counter() - t_reduce:.3f} s")

    t_check = time.perf_counter()
    checks = check(server, seed)
    log(f"reference comparison: {time.perf_counter() - t_check:.3f} s")
    correct = run.failed == 0 and all(v <= lim for v, lim in checks.values())
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": bool(correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics, "device": device}
    if tracer is not None and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": run.trace.top_modules(10),
            "idle_gaps": [list(g) for g in run.trace.idle_gaps()[:10]]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


class _Tracer:
    """The profiler over the last TRACE_SECONDS of a traced window."""

    def __init__(self, run: Run, engine):
        self.run, self.engine = run, engine
        self.started = False
        self.dir = None

    def start(self, server: Server) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.d0 = self.engine.idx.dispatch_count
        self.run.traced_from = time.perf_counter()
        server.spans = True
        self.started = True

    def stop(self, server: Server) -> None:
        import jax

        if not self.started:
            return
        server.spans = False
        self.run.dispatches = self.engine.idx.dispatch_count - self.d0
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, dev) -> None:
        from .trace import peaks, read_trace_dir

        if self.dir is None:
            return
        try:
            self.run.trace = read_trace_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.run.peaks = peaks(dev.device_kind)
