"""The benchmark's one traffic generator.

A traffic mix is a data file under ``chipbench/traffic/``; this module reads
it and turns a seed into batches of int32 keys.  It never imports JAX: the
generator runs in a process of its own (:class:`SourceProcess`), ahead of the
job, and hands ready batches over through shared memory.

Keys follow a Zipf law over a fixed population of ids, drawn once per seed.
Ranks map to ids through a permutation.  Every ``drift_every_batches``
batches the drift moves the hot set: the ids of the heaviest
``drift_fraction`` of the ranks swap places with ids of as many ranks drawn
from the rest, so the heaviest ranks get ids that were cold while the set of
ids stays fixed.  A ``population_sweep`` prefill feeds every id once before
the Zipf traffic starts.  The generator also keeps the plain reference: exact
per-id counts over every event the job consumed (``np.bincount``).

Batch ``b`` is a function of the seed and ``b`` alone.
"""
from __future__ import annotations

import dataclasses
import json
import multiprocessing as mp
import os
import queue
import time
from multiprocessing import shared_memory
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
KEY_SENTINEL = 2**31 - 1  # the job's padding key; no id may equal it


@dataclasses.dataclass(frozen=True)
class Traffic:
    """A traffic mix, as its data file states it."""

    name: str
    arrivals: str                  # "backlog": a full batch always waits
    zipf_exponent: float
    population: int                # distinct ids the keys are drawn from
    id_range: int                  # ids are drawn from [0, id_range)
    drift_every_batches: int       # the hot set moves this often
    drift_fraction: float          # share of the ranks, the heaviest, whose ids move
    batch_events_per_chip: int     # events per micro-batch and chip
    prefill: str                   # "population_sweep" or "none"

    def __post_init__(self):
        if self.arrivals != "backlog":
            raise ValueError(f"{self.name}: unknown arrivals {self.arrivals!r}")
        if self.prefill not in ("population_sweep", "none"):
            raise ValueError(f"{self.name}: unknown prefill {self.prefill!r}")
        if not 0 < self.population <= self.id_range <= KEY_SENTINEL:
            raise ValueError(f"{self.name}: need 0 < population <= id_range <= 2^31-1")
        if min(self.drift_every_batches, self.batch_events_per_chip) <= 0:
            raise ValueError(f"{self.name}: sizes must be positive")
        if not 0 < self.drift_fraction <= 0.5:
            raise ValueError(f"{self.name}: drift_fraction must lie in (0, 0.5]")

    @classmethod
    def load(cls, name: str, directory: Path = TRAFFIC_DIR) -> "Traffic":
        data = json.loads((directory / f"{name}.json").read_text())
        fields = {f.name for f in dataclasses.fields(cls)} - {"name"}
        return cls(name=name, **{k: data[k] for k in fields})

    def batch_events(self, chips: int) -> int:
        return self.batch_events_per_chip * chips

    def sweep_batches(self, chips: int) -> int:
        """Batches the prefill sweep takes: every id once, the last batch
        filled up by feeding the first ids of the sweep a second time."""
        if self.prefill == "none":
            return 0
        return -(-self.population // self.batch_events(chips))


def population_ids(seed: int, population: int, id_range: int) -> np.ndarray:
    """``population`` distinct int32 ids from ``[0, id_range)``, sorted."""
    rng = np.random.default_rng([seed, 0])
    ids = np.unique(rng.integers(0, id_range, population + population // 8 + 64))
    while len(ids) < population:
        ids = np.unique(np.concatenate([ids, rng.integers(0, id_range, population)]))
    ids = rng.permutation(ids)[:population]
    return np.sort(ids).astype(np.int32)


def zipf_alias(n: int, exponent: float) -> tuple[np.ndarray, np.ndarray]:
    """Walker's alias table for ``P(rank r) ∝ (r + 1)^-exponent``, r < n.

    Built without a Python loop: ranks are sorted by probability, so the
    columns that hold more than their share (``q > 1``) come first.  Walking
    the others in order, each takes its deficit from the column that is
    current on the cumulative axis, and an overdrawn column takes its own
    deficit from the next one.  Returns ``(threshold, alias)`` as uint32 and
    int64: column ``j`` yields ``j`` when a uniform uint32 lies below
    ``threshold[j]`` and ``alias[j]`` otherwise (a full column has
    ``alias[j] == j``).
    """
    p = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    q = p * (n / p.sum())
    big = np.nonzero(q >= 1.0)[0]          # a prefix: q falls with the rank
    small = np.nonzero(q < 1.0)[0]
    thr = np.minimum(q, 1.0)
    alias = np.arange(n)
    if len(small):
        supply = np.cumsum(q[big] - 1.0)    # cumulative excess of the big columns
        demand = np.cumsum(1.0 - q[small])  # cumulative deficit of the others
        start = np.concatenate([[0.0], demand[:-1]])
        alias[small] = big[np.minimum(np.searchsorted(supply, start, side="right"),
                                      len(big) - 1)]
        # a big column is overdrawn by the part of a deficit that straddles
        # its end: it gives that part up and takes it from the next big one
        k = np.minimum(np.searchsorted(demand, supply, side="left"), len(demand) - 1)
        thr[big] = 1.0 - np.clip(demand[k] - supply, 0.0, 1.0)
        alias[big[:-1]] = big[1:]
        thr[big[-1]] = 1.0
    full = thr >= 1.0
    alias[full] = np.nonzero(full)[0]
    thr32 = np.minimum(np.round(thr * 2.0**32), 2.0**32 - 1).astype(np.uint32)
    return thr32, alias.astype(np.int64)


class Stream:
    """Seeded batches of one traffic mix, with the reference counts.

    ``make(b)`` returns batch ``b``'s population indices; batches are
    numbered from 0, first the prefill sweep, then Zipf batches.  The keys
    are ``ids[make(b)]``.  ``count(idx)`` adds a batch that the job took to
    ``counts``.
    """

    def __init__(self, traffic: Traffic, seed: int, chips: int):
        t = self.traffic = traffic
        self.seed = seed
        self.batch = t.batch_events(chips)
        self.sweep_batches = t.sweep_batches(chips)
        self.ids = population_ids(seed, t.population, t.id_range)
        thr, alias = zipf_alias(t.population, t.zipf_exponent)
        # one gather per event: the threshold in the high half, the alias low
        self.table = (thr.astype(np.uint64) << np.uint64(32)) | alias.astype(np.uint64)
        self.sweep = (np.resize(np.random.default_rng([seed, 4]).permutation(t.population),
                                self.sweep_batches * self.batch)
                      if self.sweep_batches else None)
        self._epoch = 0  # the drift epoch ``_perm`` holds
        self._perm = np.random.default_rng([seed, 3]).permutation(t.population)
        self.counts = np.zeros(t.population, np.int64)  # per population index

    def perm(self, epoch: int) -> np.ndarray:
        """Rank-to-index map of a drift epoch (epoch ``e`` holds Zipf batches
        ``e * drift_every_batches`` onwards)."""
        t = self.traffic
        if epoch < self._epoch:  # only going back restarts from epoch 0
            self._epoch = 0
            self._perm = np.random.default_rng([self.seed, 3]).permutation(t.population)
        hot = int(t.drift_fraction * t.population)
        while self._epoch < epoch:
            self._epoch += 1
            rng = np.random.default_rng([self.seed, 5, self._epoch])
            cold = hot + rng.choice(t.population - hot, hot, replace=False)
            p = self._perm
            p[:hot], p[cold] = p[cold], p[:hot].copy()
        return self._perm

    def make(self, b: int) -> np.ndarray:
        n, size = self.traffic.population, self.batch
        if b < self.sweep_batches:
            return self.sweep[b * size:(b + 1) * size]
        rng = np.random.default_rng([self.seed, 2, b])
        raw = rng.bit_generator.random_raw(size).view(np.uint32).reshape(-1, 2)
        col = ((raw[:, 1].astype(np.uint64) * np.uint64(n)) >> np.uint64(32)).astype(np.intp)
        packed = np.take(self.table, col).view(np.uint32).reshape(-1, 2)
        ranks = np.where(raw[:, 0] < packed[:, 1], col, packed[:, 0])
        epoch = (b - self.sweep_batches) // self.traffic.drift_every_batches
        return np.take(self.perm(epoch), ranks)

    def count(self, idx: np.ndarray) -> None:
        self.counts += np.bincount(idx, minlength=self.traffic.population)

    def take(self, b: int) -> np.ndarray:
        """Batch ``b``'s keys, counted as fed."""
        idx = self.make(b)
        self.count(idx)
        return np.take(self.ids, idx)


# -- the generator process --------------------------------------------------

def _serve(traffic: Traffic, seed: int, chips: int, slots: int, ring_name: str,
           counts_name: str, ready, free) -> None:
    """Generator process: make batches 0, 1, ... into ring slots as the job
    frees them.  A slot that comes back free held a batch the job took,
    which is then counted; on ``stop`` write the reference counts."""
    parent = os.getppid()
    stream = Stream(traffic, seed, chips)
    ring_shm = shared_memory.SharedMemory(name=ring_name)
    try:
        ring = np.ndarray((slots, stream.batch), np.int32, buffer=ring_shm.buf)
        held: dict[int, np.ndarray] = {}  # slot -> indices of a batch not yet taken
        b = 0
        while True:
            try:
                msg = free.get(timeout=5.0)
            except queue.Empty:
                if os.getppid() != parent:  # the run died: do not outlive it
                    return
                continue
            if msg[0] == "stop":
                out = shared_memory.SharedMemory(name=counts_name)
                try:
                    np.ndarray(stream.counts.shape, np.int64, buffer=out.buf)[:] = stream.counts
                finally:
                    out.close()
                ready.put(("done", None, b))
                return
            slot = msg[1]
            if slot in held:
                stream.count(held.pop(slot))
            held[slot] = stream.make(b)
            np.take(stream.ids, held[slot], out=ring[slot])
            ready.put(("batch", slot, b))
            b += 1
    finally:
        ring_shm.close()


class SourceProcess:
    """Parent side of the generator process.

    ``next_batch()`` returns the next batch (a copy out of the ring) and
    frees its slot; ``finish()`` stops the generator and returns the
    reference counts over exactly the batches taken.  ``waited_s`` sums the
    time ``next_batch`` blocked on the generator: ~0 means the job never
    waited for its input.
    """

    def __init__(self, traffic: Traffic, seed: int, chips: int, *, slots: int = 2):
        self.traffic = traffic
        self.batch = traffic.batch_events(chips)
        self.taken = 0
        self.waited_s = 0.0
        ctx = mp.get_context("spawn")
        self._ring = shared_memory.SharedMemory(create=True, size=slots * self.batch * 4)
        self._counts = shared_memory.SharedMemory(create=True, size=traffic.population * 8)
        self._view = np.ndarray((slots, self.batch), np.int32, buffer=self._ring.buf)
        self._ready, self._free = ctx.Queue(), ctx.Queue()
        self._proc = ctx.Process(target=_serve, daemon=True, args=(
            traffic, seed, chips, slots, self._ring.name, self._counts.name,
            self._ready, self._free))
        self._proc.start()
        for s in range(slots):
            self._free.put(("slot", s))

    def _get(self, timeout: float = 300.0):
        deadline = time.monotonic() + timeout
        while True:
            try:
                return self._ready.get(timeout=1.0)
            except queue.Empty:
                if not self._proc.is_alive() or time.monotonic() > deadline:
                    raise RuntimeError(
                        f"traffic generator silent (alive: {self._proc.is_alive()}, "
                        f"exit code {self._proc.exitcode})") from None

    def next_batch(self) -> np.ndarray:
        t = time.perf_counter()
        kind, slot, b = self._get()
        self.waited_s += time.perf_counter() - t
        if kind != "batch" or b != self.taken:
            raise RuntimeError(f"generator out of order: {kind} {b}, want {self.taken}")
        keys = self._view[slot].copy()
        self._free.put(("slot", slot))
        self.taken += 1
        return keys

    def finish(self) -> np.ndarray:
        """Reference counts per population index over the batches taken."""
        self._free.put(("stop",))
        while self._get()[0] != "done":
            pass  # a batch made after the last take
        total = np.ndarray((self.traffic.population,), np.int64,
                           buffer=self._counts.buf).copy()
        self._proc.join(timeout=60)
        self.close()
        return total

    def close(self) -> None:
        """Stop the generator, wait for it, and free the shared memory."""
        if self._proc is None:
            return
        if self._proc.is_alive():
            self._proc.terminate()
        self._proc.join(timeout=30)
        if self._proc.is_alive():
            self._proc.kill()
            self._proc.join()
        for q in (self._ready, self._free):
            q.close()
            q.join_thread()
        self._proc = None
        del self._view
        for shm in (self._ring, self._counts):
            shm.close()
            shm.unlink()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
