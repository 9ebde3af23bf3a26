"""System signals: what the control plane actually observes.

The paper's repartitioning decisions are "system-aware": they key on
measured load, not static assumptions.  :class:`Signals` is the one record
every consumer hands the policy stack at a safe point — per-partition
loads, per-worker throughput against a capacity target, overflow counts,
actual exchange-lane accounting (rows the active backend *shipped* vs. the
rows the spec *provisioned*, wall time, and the per-lane overflow vector
that localizes a hot lane), and serving queue depths.  :class:`Telemetry`
is the accumulator the runtimes feed during normal work (no extra
measurement passes — the DRW principle); a ``snapshot`` at a safe point
turns the window into a ``Signals`` record and opens the next window.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.compat import host_fetch, safe_point
from repro.core.migration import fold_to_workers
from repro.exchange.spec import ExchangeStats

__all__ = ["Signals", "Telemetry"]


@dataclasses.dataclass(frozen=True)
class Signals:
    """One safe point's view of the system, as the policies consume it.

    ``loads`` is the only required field: per-partition work observed over
    the window (record counts for the streaming job, queued tokens for the
    serving scheduler, routed-token shares for MoE shards).  Everything else
    defaults to "unknown" so host-side unit tests and the compat wrappers
    can build a minimal record.
    """

    loads: np.ndarray                      # float64[N] per-partition work
    num_workers: int = 1                   # physical workers under the N partitions
    records: float = 0.0                   # records processed this window
    window_wall_s: float = 0.0             # wall time the window spanned
    shuffle_overflow: int = 0              # shuffle rows dropped for capacity
    migration_overflow: int = 0            # migration rows dropped for capacity
    exchange_rows: int = 0                 # rows the backend shipped through lanes
    exchange_padded_rows: int = 0          # rows the specs provisioned (L * capacity)
    exchange_occupied_rows: int | None = None  # rows actually live in the
                                           # buffers — backend-independent
                                           # occupancy (what a ragged transport
                                           # would ship); None when the window
                                           # recorded no exchange (0 is a real
                                           # measurement: all-empty lanes)
    exchange_wall_s: float = 0.0           # wall time inside the exchange path
    exchange_count_wall_s: float = 0.0     # wall blocking on the start phase
                                           # (route + bucketize + count a2a)
    exchange_ship_wall_s: float = 0.0      # wall blocking on the finish phase
                                           # (row ship) — only drains block, so
                                           # an overlapped window shows the
                                           # *un-hidden* remainder
    exchange_hidden_wall_s: float = 0.0    # host decision-section wall that ran
                                           # while a finish was in flight (the
                                           # latency the overlap hid)
    backend_wall_ewma: dict | None = None  # backend name -> EWMA of exchange
                                           # wall per call; long-lived (not
                                           # window-reset) — the BackendPolicy's
                                           # measured-wall evidence
    lane_overflow: np.ndarray | None = None  # int64[L] capacity drops per lane
    exchange_replica_rows: np.ndarray | None = None  # int64[N] rows landed per
                                           # partition from *split* hot keys
                                           # this window (None: nothing split)
    exchange_rows_by_class: np.ndarray | None = None  # int64[C] shipped rows by
                                           # lane distance class (self /
                                           # intra-host / inter-host); None
                                           # when no exchange carried a
                                           # topology this window
    queue_depths: np.ndarray | None = None # serving replica queue depths
    lane_straggle_s: np.ndarray | None = None  # float64[L] injected/observed
                                           # per-lane straggle seconds this
                                           # window (None: no fault evidence)
    lane_retries: np.ndarray | None = None # int64[L] exchange retries per lane
                                           # this window (transient failures)
    degenerate_walls: int = 0              # NaN/negative wall samples clamped
                                           # this window (a faulted batch's
                                           # clock can run backwards)
    state_rows: int = 0                    # live keyed-state rows (migration scale)
    at_safe_point: bool = True             # decisions may act only when True
    consumer: str = ""                     # which runtime emitted this

    @property
    def imbalance(self) -> float:
        """max/mean per-partition load (1.0 when nothing was observed)."""
        loads = np.asarray(self.loads, np.float64)
        if loads.size == 0 or not loads.sum():
            return 1.0
        return float(loads.max() / max(loads.mean(), 1e-12))

    @property
    def worker_loads(self) -> np.ndarray:
        """Loads folded to worker granularity (partition p on worker p % W)."""
        return fold_to_workers(self.loads, self.num_workers)

    @property
    def worker_imbalance(self) -> float:
        w = self.worker_loads
        if w.size == 0 or not w.sum():
            return 1.0
        return float(w.max() / max(w.mean(), 1e-12))

    @property
    def throughput(self) -> float:
        """Records/s over the window; 0.0 when the window is unmeasured."""
        if self.records <= 0 or self.window_wall_s <= 0:
            return 0.0
        return self.records / self.window_wall_s

    @property
    def per_worker_throughput(self) -> float:
        """Records/s each worker sustained — compared against the capacity
        target (``DRConfig.target_throughput``) to catch idle-but-balanced
        streams the imbalance trigger can never see (ROADMAP: policy signals
        beyond imbalance)."""
        return self.throughput / max(self.num_workers, 1)

    @property
    def exchange_padding_fraction(self) -> float:
        """Occupied / provisioned rows over the window — how full the padded
        lanes actually ran, whatever transport moved them (0.0 when the
        window saw no exchange).  This is the :class:`~repro.control.policy
        .BackendPolicy`'s signal: a dense job whose fraction stays low is
        paying for padding a ragged transport would not ship; a ragged job
        whose fraction nears 1.0 is paying the count phase for nothing.
        Falls back to shipped rows when the consumer recorded no occupancy
        (for a dense job the two then coincide at 1.0); an *explicit*
        occupancy of zero is a real measurement — all-empty lanes — not a
        missing one."""
        if self.exchange_padded_rows <= 0:
            return 0.0
        rows = (self.exchange_rows if self.exchange_occupied_rows is None
                else self.exchange_occupied_rows)
        return rows / self.exchange_padded_rows

    @property
    def inter_host_fraction(self) -> float:
        """Share of the window's shipped rows that crossed a host boundary
        (the slow tier) — the topology layer's headline signal.  0.0 when no
        exchange carried a topology (the flat world: nothing is known to
        cross hosts)."""
        by = self.exchange_rows_by_class
        if by is None:
            return 0.0
        total = float(np.sum(by))
        if total <= 0.0:
            return 0.0
        return float(by[-1]) / total

    @property
    def overlap_fraction(self) -> float:
        """Share of the exchange's ship wall the split-phase pipeline hid
        behind host work this window: ``hidden / (hidden + ship)``.  0.0
        when no phase walls were recorded (a record with only the whole
        ``exchange_wall_s`` carries none)."""
        total = self.exchange_hidden_wall_s + self.exchange_ship_wall_s
        if total <= 0.0:
            return 0.0
        return self.exchange_hidden_wall_s / total

    @property
    def hot_lane(self) -> int:
        """Lane with the most capacity drops this window, or -1 when nothing
        overflowed — the localized view the scalar overflow can't give."""
        if self.lane_overflow is None or not np.any(self.lane_overflow):
            return -1
        return int(np.argmax(self.lane_overflow))


class Telemetry:
    """Windowed accumulator turning runtime counters into ``Signals``.

    The runtimes call the ``record_*`` hooks during normal work (shuffle,
    migration, request routing, router statistics); ``snapshot`` emits the
    window's :class:`Signals` at a safe point and — when the safe point
    consumes the window — resets for the next one.  Peeking at a non-safe
    point leaves the window accumulating, so a decision gated on checkpoint
    ticks sees everything since the previous tick.
    """

    def __init__(self, consumer: str = ""):
        self.consumer = consumer
        # backend -> EWMA of exchange wall per call; survives window resets
        # (evidence accumulated over the job's life, not one window)
        self.wall_ewma: dict[str, float] = {}
        # lifetime count of degenerate (NaN / negative) wall samples clamped
        # to zero; the per-window count rides Signals.degenerate_walls
        self.degenerate_walls_total = 0
        self._reset()

    def _reset(self) -> None:
        self._records = 0.0
        self._shuffle_overflow = 0
        self._migration_overflow = 0
        self._exchange_rows = 0
        self._exchange_padded_rows = 0
        self._exchange_occupied_rows: int | None = None
        self._exchange_wall_s = 0.0
        self._count_wall_s = 0.0
        self._ship_wall_s = 0.0
        self._hidden_wall_s = 0.0
        self._lane_overflow: np.ndarray | None = None
        self._replica_rows: np.ndarray | None = None
        self._rows_by_class: np.ndarray | None = None
        self._queues: np.ndarray | None = None
        self._lane_straggle: np.ndarray | None = None
        self._lane_retries: np.ndarray | None = None
        self._degenerate_walls = 0
        # exchanges recorded this window whose count fields may still live
        # on device — folded (one host fetch each) at the next snapshot, so
        # recording never blocks the pipeline between safe points
        self._pending_stats: list[ExchangeStats] = []
        # the window clock starts at the first recording, not at reset:
        # setup/idle time between construction (or a checkpoint) and the
        # next batch must not read as a throughput collapse
        self._t0: float | None = None

    def _touch(self) -> None:
        if self._t0 is None:
            self._t0 = time.perf_counter()

    # -- recording hooks (called during normal work) -----------------------
    def record_batch(self, records: float) -> None:
        self._touch()
        self._records += float(records)

    @staticmethod
    def _fold_vector(acc: np.ndarray | None, v) -> np.ndarray:
        """Accumulate a per-lane/per-partition vector across the window; a
        width change mid-window (elastic resize) folds both onto the wider
        vector so nothing is lost."""
        v = np.asarray(host_fetch(v), np.int64)
        if acc is None:
            return v.copy()
        if len(v) == len(acc):
            return acc + v
        w = max(len(v), len(acc))
        out = np.zeros(w, np.int64)
        out[: len(acc)] += acc
        out[: len(v)] += v
        return out

    def record_exchange(self, stats: ExchangeStats, *extra, **legacy) -> None:
        """Fold one exchange's :class:`ExchangeStats` into the window.

        ``stats`` is constructed *by the exchange plane* —
        ``ExchangeResult.stats()`` / ``PendingExchange.stats()`` for raw
        exchanges, ``repro.core.shuffle.shuffle_stats`` /
        ``migrate_stats`` for the mapped steps, ``MoEOut.exchange_stats()``
        for expert dispatch — so consumers never assemble measurement
        fields themselves and new fields (``replica_rows``,
        ``rows_by_class``) don't ripple through every call site.

        ``stats.backend`` (with a positive ``wall_s``) feeds the long-lived
        per-backend wall EWMA (``wall_ewma``) the BackendPolicy reads as
        measured evidence.

        Sync-free: the count fields (``rows`` / ``occupied_rows`` /
        ``lane_overflow`` / ...) may be *device* scalars and vectors —
        recording only queues the record; the host fetch happens at the
        next :meth:`snapshot` (the safe point), so the steady-state loop
        never blocks here.  The wall fields are host-measured floats and
        fold eagerly (the EWMA stays observable between snapshots).

        The historical keyword-pile form ``record_exchange(rows,
        wall_s=..., padded_rows=..., ...)`` was removed after its one
        deprecation release (the kwargs mapped 1:1 onto
        :class:`ExchangeStats` fields) — any extra argument is a
        :class:`TypeError` now.
        """
        if not isinstance(stats, ExchangeStats) or extra or legacy:
            raise TypeError(
                "record_exchange takes exactly one plane-constructed "
                "ExchangeStats (ExchangeResult.stats(), shuffle_stats(), "
                "migrate_stats()) — the loose-kwargs form was removed; put "
                "the measurements on the ExchangeStats record"
            )
        self._touch()
        # degenerate wall samples (NaN / negative clock deltas from a
        # faulted batch) clamp to zero and count the incident — they must
        # not poison the windowed sums or the per-backend EWMA the
        # BackendPolicy trusts as measured evidence
        wall = self._clean_wall(stats.wall_s)
        self._exchange_wall_s += wall
        if stats.count_wall_s is not None:
            self._count_wall_s += self._clean_wall(stats.count_wall_s)
        if stats.ship_wall_s is not None:
            self._ship_wall_s += self._clean_wall(stats.ship_wall_s)
        if stats.hidden_wall_s is not None:
            self._hidden_wall_s += self._clean_wall(stats.hidden_wall_s)
        if stats.backend is not None and wall > 0.0:
            prev = self.wall_ewma.get(stats.backend)
            self.wall_ewma[stats.backend] = (
                wall if prev is None else 0.7 * prev + 0.3 * wall
            )
        self._pending_stats.append(stats)

    def _clean_wall(self, wall) -> float:
        w = float(wall)
        if not np.isfinite(w) or w < 0.0:
            self._degenerate_walls += 1
            self.degenerate_walls_total += 1
            return 0.0
        return w

    def _flush_pending(self) -> None:
        """Fold the queued exchange records' count fields — the one place
        device telemetry becomes host ints, inside a sanctioned safe-point
        region."""
        if not self._pending_stats:
            return
        with safe_point():
            for stats in self._pending_stats:
                rows = int(host_fetch(stats.rows))
                self._exchange_rows += rows
                self._exchange_padded_rows += (
                    rows if stats.padded_rows is None
                    else int(host_fetch(stats.padded_rows))
                )
                add = (rows if stats.occupied_rows is None
                       else int(host_fetch(stats.occupied_rows)))
                self._exchange_occupied_rows = (
                    add if self._exchange_occupied_rows is None
                    else self._exchange_occupied_rows + add
                )
                if stats.lane_overflow is not None:
                    self._lane_overflow = self._fold_vector(
                        self._lane_overflow, stats.lane_overflow
                    )
                if stats.replica_rows is not None:
                    self._replica_rows = self._fold_vector(
                        self._replica_rows, stats.replica_rows
                    )
                if stats.rows_by_class is not None:
                    self._rows_by_class = self._fold_vector(
                        self._rows_by_class, stats.rows_by_class
                    )
        self._pending_stats.clear()

    def record_fault(self, lane: int, *, straggle_s: float = 0.0,
                     retries: int = 0) -> None:
        """Fold one lane's fault evidence for this window — injected or
        observed straggle seconds and exchange retry counts.  The driver
        drains its fault seam's report here; the lane-health layer reads
        the folded vectors off the ``Signals`` snapshot."""
        self._touch()
        lane = int(lane)
        width = lane + 1
        if self._lane_straggle is None or len(self._lane_straggle) < width:
            grown = np.zeros(width, np.float64)
            if self._lane_straggle is not None:
                grown[: len(self._lane_straggle)] = self._lane_straggle
            self._lane_straggle = grown
            grown_r = np.zeros(width, np.int64)
            if self._lane_retries is not None:
                grown_r[: len(self._lane_retries)] = self._lane_retries
            self._lane_retries = grown_r
        self._lane_straggle[lane] += max(float(straggle_s), 0.0)
        self._lane_retries[lane] += max(int(retries), 0)

    def record_overflow(self, shuffle: int = 0, migration: int = 0) -> None:
        self._touch()
        self._shuffle_overflow += int(shuffle)
        self._migration_overflow += int(migration)

    def record_queues(self, depths: np.ndarray) -> None:
        self._touch()
        self._queues = np.asarray(depths, np.float64)

    # -- safe point --------------------------------------------------------
    def snapshot(
        self,
        loads: np.ndarray,
        *,
        num_workers: int = 1,
        state_rows: int = 0,
        at_safe_point: bool = True,
    ) -> Signals:
        self._flush_pending()
        sig = Signals(
            loads=np.asarray(loads, np.float64),
            num_workers=int(num_workers),
            records=self._records,
            window_wall_s=(max(time.perf_counter() - self._t0, 0.0)
                           if self._t0 is not None else 0.0),
            shuffle_overflow=self._shuffle_overflow,
            migration_overflow=self._migration_overflow,
            exchange_rows=self._exchange_rows,
            exchange_padded_rows=self._exchange_padded_rows,
            exchange_occupied_rows=self._exchange_occupied_rows,
            exchange_wall_s=self._exchange_wall_s,
            exchange_count_wall_s=self._count_wall_s,
            exchange_ship_wall_s=self._ship_wall_s,
            exchange_hidden_wall_s=self._hidden_wall_s,
            backend_wall_ewma=dict(self.wall_ewma) if self.wall_ewma else None,
            lane_overflow=self._lane_overflow,
            exchange_replica_rows=self._replica_rows,
            exchange_rows_by_class=self._rows_by_class,
            queue_depths=self._queues,
            lane_straggle_s=self._lane_straggle,
            lane_retries=self._lane_retries,
            degenerate_walls=self._degenerate_walls,
            state_rows=int(state_rows),
            at_safe_point=at_safe_point,
            consumer=self.consumer,
        )
        if at_safe_point:
            self._reset()
        return sig
