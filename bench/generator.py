"""Traffic from a seed: record keys, YCSB request choice, arrival times.

One general generator reads every configuration file (the data set) and
every traffic file (the op mix and the loop).  Everything here is numpy on
the host and depends only on ``(config, traffic, seed)``.

Records are numbered ``0, 1, 2, ...`` in insert order, as in YCSB's load
phase.  A record's key is

* ``hashed``: a seeded bijection of the record number onto the 32-bit
  keys ``[0, 2^32 - 1)`` (a four-round Feistel network over 32 bits,
  cycle-walked past the one key the engine reserves), so no two records
  share a key and key order is unrelated to insert order;
* ``ordered``: strictly increasing, each key a seeded gap of
  ``1..gap_max`` above the one before (YCSB's ``insertorder=ordered``, a
  time-keyed stream).

Request keys (``read``/``update``) choose a loaded record with YCSB's
``ScrambledZipfianGenerator``: a zipfian rank (Gray et al.'s generator,
the one YCSB's ``ZipfianGenerator`` implements) mapped through a seeded
permutation of the record numbers, so popular records are spread over the
insert order.  Draws are stratified (one uniform per stratum, strata in
seeded order): every seed gets the same multiset of ranks and arrival gaps
to within one stratum, only in another order, so seeds do not change how
much work a run does.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: keys live in [0, KEY_LIMIT): uint32 device keys, 0xFFFFFFFF (the
#: engine's padding key, ``KEY_MAX32``) reserved.
KEY_LIMIT = (1 << 32) - 1
#: OpKind values of the program's OpBatch, repeated here so that the
#: generator imports nothing of the program.
INSERT, QUERY = 0, 2
#: op name in a traffic file -> OpKind value; an update is an insert of an
#: existing record's key (a blind write, as YCSB issues it).
KIND = {"insert": INSERT, "update": INSERT, "read": QUERY}


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer, elementwise on uint64."""
    x = np.asarray(x, np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def sub_seed(seed: int, *tags: int) -> np.random.SeedSequence:
    """An independent stream per purpose; ``seed`` may be any integer >= 0."""
    return np.random.SeedSequence([int(seed), *tags])


class Feistel32:
    """A seeded bijection of ``[0, KEY_LIMIT)`` onto itself."""

    ROUNDS = 4

    def __init__(self, seed: int):
        rng = np.random.default_rng(sub_seed(seed, 0xFE15))
        self.keys = rng.integers(0, 1 << 63, self.ROUNDS, dtype=np.uint64)

    def _enc32(self, x: np.ndarray) -> np.ndarray:
        lo, hi = x & np.uint64(0xFFFF), x >> np.uint64(16)
        for k in self.keys:
            f = _mix64(lo ^ k) & np.uint64(0xFFFF)
            lo, hi = hi ^ f, lo
        return (hi << np.uint64(16)) | lo

    def __call__(self, r: np.ndarray) -> np.ndarray:
        x = self._enc32(np.asarray(r, np.uint64))
        bad = x >= np.uint64(KEY_LIMIT)
        while bad.any():                 # cycle-walk back into the domain
            x[bad] = self._enc32(x[bad])
            bad = x >= np.uint64(KEY_LIMIT)
        return x


class RecordKeys:
    """Key and first value of record ``r``, in insert order."""

    def __init__(self, config: dict, seed: int):
        self.order = config["insertorder"]
        self.seed = int(seed)
        if self.order == "hashed":
            self._perm = Feistel32(seed)
        elif self.order == "ordered":
            self.gap_max = int(config["gap_max"])
            self._next_key = 1          # key of the next record generated
            self._made = 0              # records generated so far
        else:
            raise ValueError(f"insertorder {self.order!r}")

    def keys(self, start: int, n: int) -> np.ndarray:
        """Keys of records ``start .. start + n - 1`` (uint64).

        Ordered keys are a running sum, so they are made in order: each
        call must start where the previous one ended.
        """
        r = np.arange(start, start + n, dtype=np.uint64)
        if self.order == "hashed":
            return self._perm(r)
        if start != self._made:
            raise ValueError("ordered keys are made in record order")
        gaps = (_mix64(r ^ np.uint64(self.seed * 0x5DEECE66D % (1 << 63)))
                % np.uint64(self.gap_max)) + np.uint64(1)
        keys = np.uint64(self._next_key) + np.cumsum(gaps) - gaps
        self._next_key = int(keys[-1] + gaps[-1]) if n else self._next_key
        self._made += n
        if n and keys[-1] >= KEY_LIMIT:
            raise ValueError("ordered keys ran past the key domain")
        return keys

    def values(self, start: int, n: int) -> np.ndarray:
        """Value written by a record's insert: int32, >= 0 (int64 array)."""
        r = np.arange(start, start + n, dtype=np.uint64)
        v = _mix64(r ^ np.uint64((self.seed * 0x9E37 + 0xA5) % (1 << 63)))
        return (v & np.uint64(0x7FFFFFFF)).astype(np.int64)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))


def zipf_ranks(u: np.ndarray, n: int, theta: float,
               zetan: float | None = None) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for uniforms ``u`` over ``n`` items
    (Gray et al., "Quickly generating billion-record synthetic databases")."""
    zetan = zeta(n, theta) if zetan is None else zetan
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    r = np.floor(n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    r = np.where(uz < 1.0 + 0.5 ** theta, 1, r)
    r = np.where(uz < 1.0, 0, r)
    return np.clip(r, 0, n - 1)


def stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` uniforms, one in each of ``n`` equal strata, in random order."""
    return (rng.permutation(n) + rng.random(n)) / n


@dataclasses.dataclass
class Requests:
    """A pre-drawn request stream: one row per op, in issue order."""

    kinds: np.ndarray        # int8 OpKind values
    keys: np.ndarray         # uint64
    vals: np.ndarray         # int64 (insert/update payload; 0 on reads)
    t_due: np.ndarray | None = None   # float64 s from window start (open loop)

    def __len__(self) -> int:
        return len(self.kinds)


class Traffic:
    """Draws the ops of one cell: ``config`` (data set) x ``traffic`` (mix)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.records = RecordKeys(config, seed)
        self.recordcount = int(config["recordcount"])
        self.next_record = 0            # records inserted so far (load first)
        self._rng = np.random.default_rng(sub_seed(seed, 0x7AF1C))
        mix = traffic["mix"]
        self.op_names = sorted(mix)
        self.op_probs = np.array([mix[k] for k in self.op_names], np.float64)
        if abs(self.op_probs.sum() - 1.0) > 1e-9 or set(mix) - set(KIND):
            raise ValueError(f"bad mix {mix}")
        perm_rng = np.random.default_rng(sub_seed(seed, 0x5C4A))
        n = self.recordcount
        if n & (n - 1):
            raise ValueError("recordcount must be a power of two")
        self._theta = float(traffic.get("zipf_theta", 0.0))
        self._zetan = zeta(n, self._theta) if self._theta else None
        self._scramble_a = int(perm_rng.integers(0, n)) * 2 + 1
        self._scramble_b = int(perm_rng.integers(0, n))

    # ------------------------------------------------------------------ load
    def load(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """The next ``n`` records' keys and values (the load phase)."""
        start = self.next_record
        self.next_record += n
        return self.records.keys(start, n), self.records.values(start, n)

    # ---------------------------------------------------------------- requests
    def _choose_records(self, u: np.ndarray) -> np.ndarray:
        """Loaded record numbers for uniforms ``u`` (scrambled zipfian)."""
        n = self.recordcount
        if self.traffic["requestdistribution"] == "uniform":
            ranks = np.minimum((u * n).astype(np.int64), n - 1)
        else:
            ranks = zipf_ranks(u, n, self._theta, self._zetan)
        return (ranks * self._scramble_a + self._scramble_b) % n

    def draw(self, n: int) -> Requests:
        """``n`` ops of the mix, in issue order.  Op kinds come in exact
        proportions (rounded), in seeded order; inserts take the next
        record numbers in that order."""
        rng = self._rng
        counts = np.floor(self.op_probs * n).astype(np.int64)
        counts[np.argmax(self.op_probs)] += n - counts.sum()
        names = np.repeat(np.arange(len(self.op_names)), counts)
        names = names[rng.permutation(n)]
        req = Requests(np.zeros(n, np.int8), np.zeros(n, np.uint64),
                       np.zeros(n, np.int64))
        for i, name in enumerate(self.op_names):
            self._fill(req, name, np.flatnonzero(names == i))
        return req

    def draw_only(self, name: str, n: int) -> Requests:
        """``n`` ops of the one kind ``name`` (warm-up)."""
        req = Requests(np.zeros(n, np.int8), np.zeros(n, np.uint64),
                       np.zeros(n, np.int64))
        self._fill(req, name, np.arange(n))
        return req

    def _fill(self, req: Requests, name: str, at: np.ndarray) -> None:
        req.kinds[at] = KIND[name]
        if name == "insert":
            req.keys[at], req.vals[at] = self.load(len(at))
            return
        rec = self._choose_records(stratified(self._rng, len(at)))
        req.keys[at] = self._record_keys(rec)
        if name == "update":
            req.vals[at] = self._rng.integers(0, 1 << 31, len(at))

    def _record_keys(self, rec: np.ndarray) -> np.ndarray:
        """Keys of already loaded records (any order)."""
        if self.records.order == "hashed":
            return self.records._perm(rec.astype(np.uint64))
        return self._loaded_keys[rec]

    def remember_load(self, keys: np.ndarray) -> None:
        """Keep the load's keys where record keys are a running sum."""
        if self.records.order == "ordered":
            self._loaded_keys = keys

    def open_loop(self, seconds: float) -> Requests:
        """Poisson arrivals at the traffic's ``rate`` over ``seconds``: the
        gaps are the exponential distribution's stratified quantiles in
        seeded order, so every seed offers the same number of ops."""
        rate = float(self.traffic["rate"])
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-stratified(self._rng, n)) / rate
        t = np.cumsum(gaps)
        t *= (seconds * (n - 0.5) / n) / t[-1]     # last op due inside
        req = self.draw(n)
        req.t_due = t
        return req
