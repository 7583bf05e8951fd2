"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps, and the bytes the merge kernel's work needs.

The TPU's plane (``/device:TPU:<n>``) has a line ``XLA Modules`` with one
event per program run (``jit__insert_impl(<fingerprint>)``) and a line
``XLA Ops`` with one event per HLO instruction, named by the instruction's
text (``%merge_sorted.1 = (u32[1,96,128]{...}, ...) custom-call(...)``).
Host annotations that the benchmark opens with
``jax.profiler.TraceAnnotation`` are events of a host plane
(``/host:CPU``) on the same clock.

Busy time is the union of the program intervals; idle time is the rest of
the traced window.  Each idle gap is put down to the benchmark span the
host was in at the gap's midpoint (``apply``, ``maintain``, ``wait``), or
to ``client`` (the benchmark's own loop) where it was in none.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import pathlib
import re

import numpy as np

#: benchmark host spans, by the name the serving loop gives them.
SPANS = ("apply", "maintain", "wait")
#: the annotation that brackets the traced part of the window.
WINDOW_SPAN = "bench.window"
#: the merge kernel's instructions: ``merge_sorted`` and
#: ``merge_sorted_batch`` both lower to an instruction named after the
#: jitted entry point, with the Pallas call as a ``tpu_custom_call``.
MERGE = re.compile(r"^%merge_sorted[\w.]* = \((?:u32|s32)\[(\d+),(\d+),(\d+)\]")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a kind that is not in the table raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for {device_kind!r}; "
                       f"known: {sorted(k for k in table if k != '_source')}")
    return table[device_kind]


def merge_bytes(runs: int, merged_len: int) -> int:
    """HBM bytes a merge of ``runs`` pairs of sorted runs needs, for merged
    runs of ``merged_len`` (key, value) pairs: both inputs read once and the
    merged run written once, at 4 B a key and 4 B a value."""
    return 2 * runs * merged_len * 8


@dataclasses.dataclass
class Trace:
    """What the reduction keeps of one trace, times in seconds."""

    window: tuple                 # (start, end) of WINDOW_SPAN
    modules: list                 # (name, start, end, chip)
    merges: list                  # (start, end, bytes)
    spans: list                   # (name, start, end) benchmark host spans
    n_chips: int

    # ------------------------------------------------------------- busy/idle
    def busy_intervals(self, chip: int | None = None) -> np.ndarray:
        """Union of the program intervals inside the window, of one chip
        or of all: rows of (start, end)."""
        lo, hi = self.window
        iv = sorted((max(s, lo), min(e, hi)) for _, s, e, c in self.modules
                    if e > lo and s < hi and chip in (None, c))
        out = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return np.asarray(out, np.float64).reshape(-1, 2)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Busy seconds of each chip, averaged over the chips."""
        tot = 0.0
        for c in range(self.n_chips):
            iv = self.busy_intervals(c)
            tot += float((iv[:, 1] - iv[:, 0]).sum())
        return tot / max(1, self.n_chips)

    def idle_gaps(self) -> list:
        """(host span, seconds) of every gap in which no chip ran a
        program, longest first."""
        iv = self.busy_intervals()
        lo, hi = self.window
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        spans = sorted(self.spans, key=lambda x: x[1])
        starts = np.asarray([s for _, s, _ in spans])
        gaps = []
        for s, e in edges:
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            k = int(np.searchsorted(starts, mid, side="right")) - 1
            name = "client"
            # innermost span holding the midpoint (spans do not nest here)
            if k >= 0 and spans[k][2] >= mid:
                name = spans[k][0]
            gaps.append((name, e - s))
        gaps.sort(key=lambda g: -g[1])
        return gaps

    # --------------------------------------------------------------- device
    def module_seconds(self, prefix: str = "") -> float:
        return sum(e - s for n, s, e, _ in self.modules
                   if n.startswith(prefix))

    def top_modules(self, k: int = 10) -> list:
        tot: dict = {}
        for n, s, e, _ in self.modules:
            tot[n] = tot.get(n, 0.0) + (e - s)
        if self.merges:
            tot["merge_sorted kernel (inside the above)"] = sum(
                e - s for s, e, _ in self.merges)
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]

    def merge_roofline(self, hbm_bytes_per_s: float) -> float | None:
        """Share of the HBM roofline, in %, over every merge launch: the
        least time the needed bytes take at peak bandwidth, over the
        kernel's device time.  None where no merge ran."""
        if not self.merges:
            return None
        t = sum(e - s for s, e, _ in self.merges)
        need = sum(b for _, _, b in self.merges) / hbm_bytes_per_s
        return 100.0 * need / t


def _module_name(name: str) -> str:
    m = _MODULE.match(name)
    return m.group(1) if m else name


def reduce_xspace(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    modules, merges, spans = [], [], []
    window = None
    n_chips = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = n_chips
            n_chips += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((_module_name(ev.name),
                                        ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                        chip))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        m = MERGE.match(ev.name)
                        if m and "tpu_custom_call" in ev.name:
                            runs = int(m.group(1))
                            length = int(m.group(2)) * int(m.group(3))
                            merges.append((ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9,
                                           merge_bytes(runs, length)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
                    elif ev.name == WINDOW_SPAN:
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} annotation")
    return Trace(window, modules, merges, spans, n_chips)


def read_trace_dir(log_dir: str) -> Trace:
    """Reduce the one ``.xplane.pb`` that a profiler session wrote."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {log_dir}, found {files}")
    return reduce_xspace(ProfileData.from_file(files[0]))
