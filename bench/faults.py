"""Engines that break the guarantee the configurations state, to show that
the comparison with the reference catches them.

Each wraps the system under test and changes what ``apply`` does; every
other call goes through.  ``write_behind`` is the control: a tempting
group-commit shortcut that acknowledges a commit's writes at once but
makes them visible only with the next commit that writes.  The others are
the faults a cell can have: the state left unchanged, half of each batch
left out, and one answer or write altered where it is produced.
"""
from __future__ import annotations

import numpy as np

from .generator import INSERT, QUERY


class _Wrapped:
    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _sub(self, batch, mask):
        from repro.core.engine_api import OpBatch

        return OpBatch(batch.kinds[mask], batch.keys[mask], batch.vals[mask],
                       batch.his[mask])

    def _empty_result(self, batch):
        from repro.core.engine_api import OpResult

        n = len(batch)
        return OpResult(batch.kinds.copy(), np.zeros(n, bool),
                        np.full(n, -1, np.int64), [None] * n, np.zeros(n))

    def _merge(self, batch, mask, res, into=None):
        """Result rows of the ops under ``mask`` placed into a full result."""
        out = self._empty_result(batch) if into is None else into
        out.found[mask] = res.found
        out.values[mask] = res.values
        return out


class WriteBehind(_Wrapped):
    """The control: writes are acknowledged with their commit and applied
    with the next commit that writes; reads see only applied writes."""

    def __init__(self, engine):
        super().__init__(engine)
        self._held = None

    def apply(self, batch):
        w = batch.kinds == INSERT
        out = self._empty_result(batch)
        if (~w).any():
            self._merge(batch, ~w, self._engine.apply(self._sub(batch, ~w)),
                        out)
        if w.any():
            if self._held is not None:
                self._engine.apply(self._held)
            self._held = self._sub(batch, w)
        return out


class Unchanged(_Wrapped):
    """A step that returns the state unchanged: writes are acknowledged and
    dropped; reads are answered."""

    def apply(self, batch):
        r = batch.kinds == QUERY
        out = self._empty_result(batch)
        if r.any():
            self._merge(batch, r, self._engine.apply(self._sub(batch, r)), out)
        return out


class HalfBatch(_Wrapped):
    """Half of each batch left out: only its first half is applied."""

    def apply(self, batch):
        keep = np.arange(len(batch)) < (len(batch) + 1) // 2
        return self._merge(batch, keep,
                           self._engine.apply(self._sub(batch, keep)))


class Altered(_Wrapped):
    """One answer altered where it is produced: the first read of each
    batch answers a value one higher, and the first write of each batch
    writes a value one higher."""

    def apply(self, batch):
        w = np.flatnonzero(batch.kinds == INSERT)
        if len(w):
            batch = self._sub(batch, np.ones(len(batch), bool))
            batch.vals[w[0]] = (batch.vals[w[0]] + 1) % (1 << 31)
        res = self._engine.apply(batch)
        r = np.flatnonzero(batch.kinds == QUERY)
        if len(r):
            res.values[r[0]] += 1
        return res


FAULTS = {"write_behind": WriteBehind, "unchanged": Unchanged,
          "half_batch": HalfBatch, "altered": Altered}


def factory(name: str):
    """An ``engine_factory`` for ``bench.harness.run_cell`` whose engine has
    the fault ``name`` (``none``: the system as it is)."""
    from .harness import make_engine

    if name == "none":
        return make_engine
    wrap = FAULTS[name]
    return lambda cell: wrap(make_engine(cell))
