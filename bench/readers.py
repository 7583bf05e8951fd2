"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader takes a :class:`bench.harness.Run` and returns a number, or
None where it finds nothing to read.  Per-layer numbers of a traced run
cover the traced part of its window: the commits that started after the
profiler did, and the device events inside the ``bench.window`` span.
"""
from __future__ import annotations

import numpy as np


def percentile_ms(samples, q: float):
    a = np.asarray(samples, np.float64)
    return None if a.size == 0 else float(np.percentile(a, q)) * 1e3


def _traced_commits(run):
    if run.commits is None or run.traced_from is None:
        return None
    c = run.commits[run.traced()]
    return c if len(c) else None


def apply_us_per_op(run):
    """Host seconds inside ``apply`` (which returns after the device has
    finished) per op served, in microseconds."""
    c = _traced_commits(run)
    return None if c is None else float(
        (c[:, 1] - c[:, 0]).sum() / c[:, 3].sum() * 1e6)


def maintain_share(run):
    """Share of the traced part's wall clock spent in ``maintain``, in %."""
    c = _traced_commits(run)
    if c is None:
        return None
    span = c[-1, 2] - run.traced_from
    return float(100.0 * (c[:, 2] - c[:, 1]).sum() / span)


def dispatches_per_op(run):
    """Device dispatches the index made per op served while traced."""
    c = _traced_commits(run)
    if c is None or run.dispatches is None:
        return None
    return float(run.dispatches / c[:, 3].sum())


def device_idle(run):
    """1 - device busy time over the traced window, in %."""
    t = run.trace
    if t is None or t.window_s <= 0 or not t.modules:
        return None
    return float(100.0 * (1.0 - t.busy_s / t.window_s))


def queue_wait_p99_ms(run):
    """99th percentile of due -> commit start over traced ops (open loop)."""
    if run.queue_s is None or run.traced_from is None:
        return None
    return percentile_ms(run.queue_s[run.traced_op_mask()], 99)


def merge_roofline(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    return t.merge_roofline(run.peaks["hbm_bytes_per_s"])


def query_device_us_per_op(run):
    """Device time of the point-query program per read served, in us."""
    c = _traced_commits(run)
    t = run.trace
    if c is None or t is None or c[:, 4].sum() == 0:
        return None
    dev_s = t.module_seconds("jit__query_batch_impl")
    return float(dev_s / c[:, 4].sum() * 1e6) if dev_s > 0 else None
