"""Micro-batch streaming runtime with on-the-fly Dynamic Repartitioning.

The job graph is the paper's canonical stateful pipeline::

    source -> map -> [shuffle by key] -> stateful reduce (keyed state)

Per micro-batch the runtime executes the jitted shuffle step (which also
emits the DRW histograms and global loads), folds received records into the
keyed state, then gives the DRM a safe point.  The job is a thin driver for
the control plane (``repro.control``): telemetry gathered during normal
work (loads, overflow, exchange rows + wall time, throughput) snapshots
into a ``Signals`` record, ``DRMaster.evaluate`` runs the policy stack, and
the returned typed action (``NoOp``/``Repartition``/``Resize``) is executed
here — the jitted migrate step moves the keyed state before the next batch,
the Spark-style integration; setting ``checkpoint_interval > 1`` gates
decisions on checkpoint ticks, the Flink-style integration.

Both the shuffle and the migration ride the unified exchange plane
(``repro.exchange``) on the transport ``exchange_backend`` selects — the
dense capacity-padded all-to-all or the ragged count-first one; results are
bit-identical, only the traffic differs, and the DRM prices candidate
repartitions with the *same* backend's sizing rule.  Across workers a
migration routes the state on the device first; the host reads only the
``[W, W]`` counts of rows each worker sends to each other worker and sizes
the lanes to the peak x slack, so the all-to-all ships that instead of
``W * state_capacity`` rows.  Lane capacities are rounded up to powers of
two, and to at least a sixteenth of the table, so repeated repartitions
reuse a handful of jitted migrate steps instead of recompiling per size.
On one worker every row's destination is the worker it sits on, so a
migration is the identity and none runs: a repartition there swaps the
partitioner, which the next batch's shuffle routes under.

**Elastic resize** is the same mechanism one level up: changing the *number*
of partitions (the job's logical worker count) instead of their contents.
``resize(n)`` requests it explicitly; with ``DRConfig(elastic=True)`` the
DRM's ``decide_resize`` policy requests it on sustained imbalance.  Either
way it fires only at a checkpoint safe point: the partitioner is re-planned
cross-size (``DRMaster.replan_resize`` — shrink folds removed partitions,
grow re-bins hosts onto the new ones), the state ships through a migrate
step whose lanes are sized by the *cross-size* plan, the shuffle step is
rebuilt for the new topology, and the new topology lands in
``BatchMetrics`` and snapshots so a restore resumes resized.

**The transport is an actuator too**: with ``DRConfig(auto_backend=True)``
the ``BackendPolicy`` watches the measured lane occupancy
(``Signals.exchange_padding_fraction``) and flips dense <-> ragged at a
safe point when the padded lanes run empty (or the count phase stops
paying).  The job rebuilds its jitted steps for the new backend exactly
like a resize rebuilds them for a new lane count, the switch lands in the
``DecisionLog``/``BatchMetrics``, and snapshots carry the active backend so
a restore resumes on the switched transport.

**The schedule.**  Every batch runs split-phase (``repro.core.shuffle``):
the shuffle's *start* program (route + bucketize + the transport's count
phase) yields every control-plane input — loads, DRW histograms, overflow,
shipped rows — and its *finish* program ships the rows, which the merge
folds into the keyed state.  The driver enqueues batch N's start, enqueues
batch N-1's finish + merge behind it, and blocks only on batch N's start
outputs: the host-side decision section (telemetry, sketch update, policy
stack) runs while the device ships and merges batch N-1.  Two exchanges
are thus in flight at once, and their send buffers alternate between two
recycled sets.  State only materializes at *drains*: before any taken
action (a migration must see the previous batch merged, and a backend
switch rebuilds the programs the in-flight finish came from), and at
``snapshot``/``state_count``/direct state reads, all of which complete
the in-flight finish first.  A migration is split the same way: its count
phase blocks, its row ship + merge stays in flight across the safe point.
Per-phase walls land in telemetry (``Signals.exchange_count_wall_s`` /
``exchange_ship_wall_s`` / ``exchange_hidden_wall_s`` ->
``overlap_fraction``); the hidden wall of a batch is recorded when the
batch ends, so it lands one window late.

**Host-sync discipline**: every device->host read in the driver routes
through :func:`repro.compat.host_fetch` inside a
:func:`repro.compat.safe_point` region — the count-phase sync and the
decision section it feeds.  Between safe points the driver performs no
blocking transfers; ``compat.host_sync_count()`` stays flat across
steady-state batches (the bench gate ``fig6/host_syncs_per_batch``).

**Spans**: each batch and each phase of it is a named host span
(``jax.profiler.TraceAnnotation``, names in :data:`SPANS`).  While no
profiler trace runs a span costs about a microsecond of host CPU; under a
trace, the spans land on the host plane on the device ops' clock, so every
stretch in which the device waits on the host belongs to one phase.  ``stream.batch`` covers a
batch and carries its index; its children cover it with no holes:

* ``stream.feed`` — pad and cast the batch, build the step, put the keys,
  values and valid flags, enqueue the start, then the previous batch's
  finish + merge;
* ``stream.count_sync`` — wait for the start phase's loads;
* ``dr.observe`` — exchange stats, telemetry records, the DRW histogram
  fetch and ``DRMaster.observe``;
* ``dr.decide`` — ``Telemetry.snapshot`` and ``DRMaster.evaluate`` (KIP
  re-plan and the policy stack);
* ``stream.drain`` — complete the in-flight finish + merge, wherever that
  happens;
* ``dr.migrate`` — a migration, split into ``dr.migrate.fetch`` (enqueue
  the route and fetch its ``[W, W]`` counts; empty for full lanes),
  ``dr.migrate.plan`` (the lane size) and ``dr.migrate.start`` (the step,
  its enqueue, the control fetches); on one worker, where no migration
  runs, it holds none of them;
* ``stream.account`` — the rest: migration telemetry and ``BatchMetrics``.

``dr.resize``, ``dr.switch``, ``dr.lane`` (quarantine, evict) and
``dr.recover`` (re-admission, and recovery from a lost worker) each wrap
their action.  ``BatchMetrics.put_bytes`` / ``fetch_bytes`` count the bytes
each batch moved host->device and device->host, from shapes alone.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax.profiler import TraceAnnotation

from repro.compat import (
    host_fetch,
    host_fetch_bytes,
    native_ragged,
    safe_point,
)
from repro.control import (
    Evict,
    Quarantine,
    Recover,
    Repartition,
    Resize,
    Split,
    SwitchBackend,
    Telemetry,
    Unsplit,
)
from repro.core.drm import DRConfig, DRMaster
from repro.core.hashing import DEFAULT_NUM_HOSTS, KEY_SENTINEL
from repro.core.migration import lane_rows
from repro.core.partitioner import (
    Partitioner,
    heavy_capacity_for,
    split_replica_rows,
    uniform_partitioner,
)
from repro.core.shuffle import (
    make_migrate_route,
    make_migrate_step,
    make_shuffle_step,
    migrate_stats,
    shuffle_stats,
)
from repro.core.state import empty_state, merge_into
from repro.exchange import (
    ExchangeSpec,
    ExchangeStats,
    ExchangeTopology,
    FaultyBackend,
    TransientExchangeError,
    WorkerLostError,
    resolve_backend,
)
from repro.exchange.plane import route_path
from repro.exchange.spec import DISTANCE_CLASSES
from repro.launch.mesh import make_mesh

__all__ = ["StreamingJob", "BatchMetrics", "RecoveryStats", "SPANS"]

#: every host span the job writes (see the module docstring)
SPANS = (
    "stream.batch",
    "stream.feed",
    "stream.count_sync",
    "dr.observe",
    "dr.decide",
    "stream.drain",
    "dr.migrate",
    "dr.migrate.fetch",
    "dr.migrate.plan",
    "dr.migrate.start",
    "stream.account",
    "dr.resize",
    "dr.switch",
    "dr.lane",
    "dr.recover",
)


def _span(name: str, **args) -> TraceAnnotation:
    assert name in SPANS, name
    return TraceAnnotation(name, **args)


@dataclasses.dataclass
class BatchMetrics:
    batch: int
    imbalance: float            # measured per-partition record imbalance
    worker_imbalance: float     # per-worker (straggler view)
    repartitioned: bool
    relative_migration: float
    overflow: int               # shuffle + migration rows dropped for capacity
    state_rows: int
    wall_time_s: float
    reason: str
    migration_rows: int = 0     # rows of all-to-all buffer a repartition exchanged
    resized: bool = False       # an elastic resize fired at this safe point
    num_partitions: int = 0     # topology after this batch (post-resize)
    migration_plan_rows: int = 0  # rows a migration lane needs (pre-pow2)
    migration_peak_rows: int = 0  # largest (src, dst) worker count the device
                                # measured for the migration; 0 on one worker
                                # and on full-lane migrations
    action: str = "noop"        # control-plane action kind this safe point took
    shipped_rows: int = 0       # rows the backend moved this batch (per worker)
    padded_rows: int = 0        # rows the specs provisioned (per worker)
    backend: str = "dense"      # exchange backend the batch ran on
    exchange_wall_s: float = 0.0  # wall blocking on the shuffle exchange
                                  # path: the count phase only — the ship
                                  # is hidden behind host work
    overlap_fraction: float = 0.0  # hidden / (hidden + ship) wall this
                                # window (lags one batch: the hidden wall is
                                # only known at batch end)
    split_keys: int = 0         # hot keys replicated after this safe point
    shipped_rows_by_class: tuple = (0, 0, 0)  # shipped_rows split by lane
                                # distance class (self / intra-host /
                                # inter-host, per worker); zeros on flat jobs
    lanes: int = 0              # live workers after this batch (a health
                                # action or a loss shrinks this mid-stream)
    transport: str = ""         # collective the shuffle rode: the backend,
                                # and for ragged which row phase ran
                                # ("ragged/native" on TPU meshes,
                                # "ragged/masked-dense" elsewhere)
    route_path: str = ""        # route -> bucketize implementation
                                # (exchange.plane.route_path)
    put_bytes: int = 0          # bytes this batch put host -> device
                                # (keys, values, valid flags, re-laid state)
    fetch_bytes: int = 0        # bytes this batch fetched device -> host


@dataclasses.dataclass
class RecoveryStats:
    """One zero-loss recovery: the lane lost, how the job survived it
    (``evict`` = shrunk onto the survivors; ``restart`` = restored in place
    — the single-worker fallback), how many gap batches the replay buffer
    re-ran, the worker count after, and the end-to-end recovery wall
    (drain + restore + replay, up to the lost batch's successful retry)."""

    lane: int
    kind: str                   # "evict" | "restart"
    replayed: int
    workers: int
    wall_s: float = 0.0


class _Migration(NamedTuple):
    """What one state migration did, for ``BatchMetrics`` and telemetry
    (all zeros when the safe point moved no state)."""

    relative: float = 0.0   # moved rows / live rows
    overflow: int = 0       # rows dropped for lane capacity
    buffer_rows: int = 0    # rows received per worker (W x lane capacity)
    plan_rows: int = 0      # rows a lane needs, before pow2 rounding
    peak_rows: int = 0      # largest (src, dst) count the device measured
    shipped: int = 0        # rows the backend moved, per worker
    moved: int = 0          # rows that changed worker, all workers
    by_class: np.ndarray | None = None  # shipped rows by distance class, summed


def migrate_lane_capacity(plan_rows: int, state_capacity: int) -> int:
    """Rows a migration lane holds for a migration that needs ``plan_rows``.

    The next power of two, capped at the full state table, so the jit cache
    stays small across repartitions.  A lane holds at least a sixteenth of
    the table: the plans' sizes wander with the key drift, each size is
    programs of its own that take seconds to compile, and every plan below
    the floor shares one.
    """
    cap = max(8, state_capacity // 16)
    while cap < min(plan_rows, state_capacity):
        cap *= 2
    return min(cap, state_capacity)


def _default_mesh(axis: str = "data") -> Mesh:
    return make_mesh((len(jax.devices()),), (axis,))


def _make_merge(mesh: Mesh):
    """Jitted stateful reduce over the mesh: each worker folds the rows it
    received into its own ``[S]`` state table, on its own device — the
    stacked ``[W, ...]`` state and the received rows are both sharded over
    ``data``, so nothing leaves its chip."""

    def local(sk, sv, bk, bv, bva):
        k, v, _ = merge_into(sk[0], sv[0], bk[0], bv[0], bva[0])
        return k[None], v[None]

    spec = P("data")
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(spec,) * 5,
                             out_specs=(spec, spec)))


class StreamingJob:
    """Long-running stateful streaming job with DR.

    ``payload_dim`` is the record payload width (the reduce below is a
    per-key vector sum — the word-count family of stateful operators).
    """

    def __init__(
        self,
        *,
        num_partitions: int | None = None,
        mesh: Mesh | None = None,
        capacity_factor: float = 2.0,
        state_capacity: int = 4096,
        payload_dim: int = 1,
        dr: DRConfig | None = None,
        dr_enabled: bool = True,
        checkpoint_interval: int = 1,
        initial: Partitioner | None = None,
        hist_k: int = 64,
        seed: int = 0,
        exchange_backend: str | object | None = None,
        topology: ExchangeTopology | None = None,
    ):
        self.mesh = mesh or _default_mesh()
        self.num_workers = self.mesh.shape["data"]
        self.num_partitions = num_partitions or self.num_workers
        assert self.num_partitions >= self.num_workers
        self.capacity_factor = capacity_factor
        self.state_capacity = state_capacity
        self.payload_dim = payload_dim
        self.dr_enabled = dr_enabled
        self.checkpoint_interval = checkpoint_interval
        self.hist_k = hist_k
        self.seed = seed
        # the exchange transport both jitted steps ride (dense / ragged);
        # the DRM gets the same backend so policy costing prices the plan
        # by what this job's transport would actually move
        self.exchange_backend = resolve_backend(exchange_backend or "dense")
        # lane locality (``exchange_topology_of(mesh)``): rides every
        # ExchangeSpec the job builds, splits shipped-row telemetry by
        # distance class, and makes the DRM's plan pricing locality-aware.
        # ``None`` keeps the flat world — everything behaves as before.
        self.exchange_topology = topology
        cfg = dr or DRConfig()
        heavy_cap = heavy_capacity_for(cfg.lam, self.num_partitions)
        part = initial or uniform_partitioner(
            self.num_partitions, DEFAULT_NUM_HOSTS, seed, heavy_capacity=heavy_cap
        )
        self.drm = DRMaster(part, cfg, exchange_backend=self.exchange_backend,
                            exchange_topology=topology)
        self.telemetry = Telemetry("stream")
        self._put_bytes = 0  # running total of host -> device bytes (_shard)
        self._shuffle = None
        self._shuffle_sig = None  # (capacity, num_partitions) the step was built for
        self._shuffle_spec: ExchangeSpec | None = None  # for exchange-row accounting
        self._migrate_steps: dict[int, object] = {}  # lane capacity -> jitted step
        self._migrate_route = None  # the migration's route phase (jitted, lazily)
        self._pending_resize: int | None = None
        # per-worker keyed state, stacked [W, S] / [W, S, D] and sharded
        # over ``data``: one worker's table per device
        sk, sv = empty_state(state_capacity, payload_dim)
        self._sk = self._shard(jnp.tile(sk[None], (self.num_workers, 1)))
        self._sv = self._shard(jnp.tile(sv[None], (self.num_workers, 1, 1)))
        # split-phase schedule: the previous batch's in-flight finish+merge
        # (a callable that enqueues it), the host wall start of the section
        # a pending ship is hiding behind, and the state-row count as of the
        # last drain (reading it live would sync the in-flight merge chain)
        self._inflight = None
        self._hidden_since: float | None = None
        self._last_state_rows = 0
        # least-load split routing (``DRConfig.split_least_load``): the
        # previous batch's measured per-partition loads, fed to the route at
        # safe points; None until the first batch lands (and after a resize
        # changes the vector's width)
        self._part_loads: jax.Array | None = None
        # failure domains: current -> original lane map (plan lanes are
        # original ids), quarantined (original id, device) pairs oldest
        # first, the auto-snapshot + bounded replay buffer
        # (``DRConfig.snapshot_interval``), and the recovery record
        self._lane_ids: list[int] = list(range(self.num_workers))
        self._parked: list[tuple[int, object]] = []
        self._auto_snap: dict | None = None
        self._replay: list[tuple[np.ndarray, np.ndarray | None]] = []
        self.recoveries: list[RecoveryStats] = []
        self.metrics: list[BatchMetrics] = []
        self._merge = _make_merge(self.mesh)

    def _shard(self, x) -> jax.Array:
        """Place ``x`` split over the workers along its first axis (stacked
        ``[W, ...]`` state, or a batch's ``[W * n]`` records); a host array's
        bytes count as put."""
        if not isinstance(x, jax.Array):
            self._put_bytes += np.asarray(x).nbytes
        return jax.device_put(x, NamedSharding(self.mesh, P("data")))

    # -- keyed state access (drains any in-flight exchange first) ----------
    @property
    def state_keys(self):
        self._drain_inflight()
        return self._sk

    @state_keys.setter
    def state_keys(self, v):
        self._sk = v

    @property
    def state_vals(self):
        self._drain_inflight()
        return self._sv

    @state_vals.setter
    def state_vals(self, v):
        self._sv = v

    def _consume_inflight(self) -> None:
        """Enqueue the pending finish + merge (no sync)."""
        fin, self._inflight = self._inflight, None
        if fin is not None:
            fin()

    def _drain_inflight(self) -> None:
        """Complete the in-flight finish + merge, blocking, and account the
        un-hidden ship wall (plus whatever host wall it did hide)."""
        if self._inflight is None:
            return
        with _span("stream.drain"):
            t = time.perf_counter()
            hidden = None if self._hidden_since is None else t - self._hidden_since
            self._hidden_since = None
            self._consume_inflight()
            jax.block_until_ready(self._sk)
            self.telemetry.record_exchange(ExchangeStats(
                rows=0,
                ship_wall_s=time.perf_counter() - t,
                hidden_wall_s=hidden,
            ))
            with safe_point():  # a drain IS a safe point: the fetch is sanctioned
                self._last_state_rows = int(
                    host_fetch(jnp.sum(self._sk != KEY_SENTINEL)))

    # ------------------------------------------------------------------
    def _build(self, local_n: int):
        """(Re)build the jitted shuffle step when capacity *or topology*
        changed — an elastic resize invalidates the step because the loads
        vector and heavy-table shapes follow ``num_partitions``."""
        cap = int(np.ceil(self.capacity_factor * local_n / self.num_workers / 8.0) * 8)
        sig = (cap, self.num_partitions)
        if self._shuffle is not None and sig == self._shuffle_sig:
            return
        self._shuffle_sig = sig
        self._route_path = route_path(self.num_workers, cap, self.payload_dim)
        self._shuffle_spec = ExchangeSpec(
            num_lanes=self.num_workers, capacity=cap, axis="data",
            topology=self.exchange_topology,
        )
        self._shuffle = make_shuffle_step(
            self.mesh,
            num_partitions=self.num_partitions,
            capacity=cap,
            hist_k=self.hist_k,
            num_hosts=self.drm.partitioner.num_hosts,
            seed=self.seed,
            backend=self.exchange_backend,
            topology=self.exchange_topology,
        )

    def _migrate_step(self, lane_capacity: int):
        """Jitted migrate step with lanes >= ``lane_capacity`` rows, sized
        by :func:`migrate_lane_capacity`.  The step routes at worker
        granularity, so the same cache serves plain repartitions *and*
        cross-size resize migrations.
        """
        cap = migrate_lane_capacity(lane_capacity, self.state_capacity)
        if cap not in self._migrate_steps:
            self._migrate_steps[cap] = make_migrate_step(
                self.mesh,
                state_capacity=self.state_capacity,
                num_hosts=self.drm.partitioner.num_hosts,
                seed=self.seed,
                spec=ExchangeSpec(num_lanes=self.num_workers, capacity=cap,
                                  axis="data", topology=self.exchange_topology),
                backend=self.exchange_backend,
            )
        return self._migrate_steps[cap], cap

    # ------------------------------------------------------------------
    def process_batch(self, keys: np.ndarray, values: np.ndarray | None = None) -> BatchMetrics:
        """Run one micro-batch through shuffle + stateful reduce + DR.

        With ``DRConfig.snapshot_interval > 0`` this is also the zero-loss
        recovery protocol's outer loop: an initial auto-snapshot is taken
        lazily, every processed batch lands in the bounded replay buffer,
        and a :class:`~repro.exchange.WorkerLostError` surfacing from the
        exchange seam triggers recovery — quiesce the surviving in-flight
        stages, evict the lost lane (shrinking the mesh; a single-worker
        job restarts in place), restore the last snapshot, replay the gap
        batches, then retry this batch on the surviving topology.  No row
        is lost: every batch since the snapshot either replays or retries.
        With ``snapshot_interval == 0`` a loss propagates (failure stays an
        abort, the pre-PR-10 behavior).
        """
        cfg = self.drm.config
        if cfg.snapshot_interval > 0 and self._auto_snap is None:
            # lazy initial snapshot: the zero state is trivially consistent
            self._auto_snap = self.snapshot()
            self._replay = []
        pending_rec: tuple[RecoveryStats, float] | None = None
        replaying: list = []  # gap batches still to re-run before this one
        budget = self.num_workers + 1
        while True:
            try:
                while replaying:
                    rk, rv = replaying[0]
                    self._process_batch_inner(rk, rv)
                    replaying.pop(0)
                    # a completed batch is progress: the backstop budget
                    # guards against recovery that can't advance, not
                    # against a stream that keeps losing (distinct) workers
                    budget = self.num_workers + 1
                m = self._process_batch_inner(keys, values)
                break
            except WorkerLostError as loss:
                budget -= 1
                if budget <= 0 or cfg.snapshot_interval <= 0:
                    raise
                t_rec = time.perf_counter()
                kind = self._recover_from_loss(loss)
                replaying = list(self._replay)
                rec = RecoveryStats(lane=loss.lane, kind=kind,
                                    replayed=len(replaying),
                                    workers=self.num_workers)
                self.recoveries.append(rec)
                pending_rec = (rec, t_rec)
        if pending_rec is not None:
            rec, t_rec = pending_rec
            rec.wall_s = time.perf_counter() - t_rec
            rec.workers = self.num_workers
        if cfg.snapshot_interval > 0:
            if m.action in ("quarantine", "evict", "recover"):
                # the topology changed under the snapshot: re-snapshot now
                # so a later restore lands on the live worker layout
                self._auto_snap = self.snapshot()
                self._replay = []
            else:
                self._replay.append((keys, values))
                if len(self._replay) >= cfg.snapshot_interval:
                    self._auto_snap = self.snapshot()
                    self._replay = []
        return m

    def _process_batch_inner(self, keys: np.ndarray,
                             values: np.ndarray | None = None) -> BatchMetrics:
        put0, fetch0 = self._put_bytes, host_fetch_bytes()
        with _span("stream.batch", batch=len(self.metrics)):
            m = self._batch(keys, values)
            m.put_bytes = self._put_bytes - put0
            m.fetch_bytes = host_fetch_bytes() - fetch0
        return m

    def _batch(self, keys: np.ndarray, values: np.ndarray | None) -> BatchMetrics:
        t0 = time.perf_counter()
        w = self.num_workers
        with _span("stream.feed"):
            n = len(keys)
            local_n = int(np.ceil(n / w))
            pad = local_n * w - n
            keys = np.concatenate([keys, np.full(pad, KEY_SENTINEL, np.int64)]).astype(np.int32)
            if values is None:
                values = np.ones((len(keys), self.payload_dim), np.float32)
            else:
                values = np.concatenate(
                    [values, np.zeros((pad,) + values.shape[1:], np.float32)],
                    dtype=np.float32)
            valid = keys != KEY_SENTINEL
            self._build(local_n * w)
            batch_backend = self.exchange_backend.name  # the transport this batch rode

            t_ex = time.perf_counter()
            # enqueue this batch's start, then the previous batch's ship +
            # merge behind it, and block only on the start outputs — devices
            # drain their queue in order, so the loads sync below waits for
            # the count phase, not the ship, which runs while the host works
            # through the decision section
            shuffle = self._shuffle
            pending, res = shuffle.start(
                self.drm.partitioner.tables(), self._shard(keys),
                self._shard(values), self._shard(valid),
                self._part_loads,
            )
            self._consume_inflight()

            def _fin_shuffle(fin=shuffle.finish, pending=pending):
                rk, rv, rva, _rp = fin(pending)
                self._sk, self._sv = self._merge(self._sk, self._sv, rk, rv, rva)

            self._inflight = _fin_shuffle
        with _span("stream.count_sync"):
            # forces the start phase only
            with safe_point():
                loads = host_fetch(res.loads)
            exchange_wall = time.perf_counter() - t_ex
            # the next batch's route reads this batch's measured loads
            if self.drm.config.split_least_load:
                self._part_loads = jnp.asarray(loads, jnp.float32)

        with _span("dr.observe"):
            # everything the decision section reads below comes out of the
            # start phase
            self._hidden_since = time.perf_counter()

            # telemetry: signals gathered during normal work (no extra
            # passes).  shipped is the backend's measured traffic (per
            # worker, averaged), padded what the spec provisioned, occupied
            # the rows actually live in the lanes (backend-independent — the
            # BackendPolicy's signal; under dense shipped == padded while
            # occupied tracks the real load).
            with safe_point():
                stats = shuffle_stats(
                    res, self._shuffle_spec, w,
                    wall_s=exchange_wall,
                    count_wall_s=exchange_wall,
                    backend=batch_backend,
                    # per-replica routing of the split keys (host twin of the
                    # fused kernels' pick — exact, no extra device pass); only
                    # computed while splits are installed, and only for the
                    # stateless pick — the least-load tiebreak reads a load
                    # vector the host twin doesn't see
                    replica_rows=(split_replica_rows(self.drm.partitioner, keys, w, valid)
                                  if self.drm.split_keys
                                  and not self.drm.config.split_least_load else None),
                )
                # every fetch below reads a start-phase output the loads sync
                # already forced — no new device work blocks here
                shuffle_shipped = int(host_fetch(stats.rows))
                overflow_i = int(host_fetch(res.overflow))
                self.telemetry.record_exchange(stats)
                self.telemetry.record_overflow(shuffle=overflow_i)
                self.telemetry.record_batch(float(loads.sum()))
                # fault evidence: drain the seam's per-lane report (straggle
                # seconds, retries) into ordinary telemetry — the lane-health
                # layer's input.  Plans are keyed by original lane id; the
                # report re-maps onto current positions.  A plain transport
                # has no report; a never-firing plan drains empty — both
                # leave the telemetry bit-identical to a no-faults run.
                drain = getattr(self.exchange_backend, "drain_report", None)
                if drain is not None:
                    for orig, rec in drain().items():
                        if orig in self._lane_ids:
                            self.telemetry.record_fault(
                                self._lane_ids.index(orig),
                                straggle_s=rec.get("straggle_s", 0.0),
                                retries=rec.get("retries", 0))

                # DRM: ingest DRW histograms at the safe point
                self.drm.observe(host_fetch(res.hist_keys), host_fetch(res.hist_counts),
                                 total_records=float(loads.sum()))
        with _span("dr.decide"):
            # the policy stack, at the safe point
            at_checkpoint = (len(self.metrics) + 1) % self.checkpoint_interval == 0
            requested = None
            if at_checkpoint and self._pending_resize is not None:
                requested = self._pending_resize
                self._pending_resize = None
            signals = self.telemetry.snapshot(
                loads=loads,
                num_workers=w,
                # reading the live count would sync the in-flight merge chain
                # — the count as of the last drain (no policy keys on exact
                # state rows; a migration routes the real keys after the
                # pre-action drain below)
                state_rows=self._last_state_rows,
                at_safe_point=at_checkpoint,
            )
            action = self.drm.evaluate(signals, requested_resize=requested,
                                       policies_enabled=self.dr_enabled)

        # execute the action (state only moves here, at the safe point).
        # Any taken action drains the in-flight finish first: a migration
        # must see this batch's rows merged, and a backend switch rebuilds
        # the steps the in-flight finish came from.  On one worker a
        # repartition, a resize or an unsplit is a table swap: the DRM has
        # installed the new partitioner and no state moves.
        if action.taken:
            self._drain_inflight()
        mig = _Migration()
        if isinstance(action, Resize):
            mig = self._apply_resize(action.target)
        elif isinstance(action, Repartition):
            mig = self._migrate_state()
        elif isinstance(action, Unsplit):
            # combiner-side merge: the DRM already removed the key from the
            # replica table; a home-routed migration off the still-split
            # partitioner pulls every replica's partial aggregate back to
            # the key's home, where merge_into sums them.  full_lanes
            # provisions for every partial, the whole table.
            mig = self._migrate_state(full_lanes=True)
        elif isinstance(action, SwitchBackend):
            # the DRM already installed the new transport (note_backend_switch);
            # the job adopts it and rebuilds its jitted steps, exactly like a
            # resize rebuilds them for a new lane count.  No state moves.
            self._apply_backend_switch()
        elif isinstance(action, Quarantine):
            # circuit breaker open: the sick lane leaves the collective, its
            # device parks for a possible Recover, and the survivors adopt
            # its state (the modulo placement re-folds the partitions)
            self._apply_lane_removal(action.lane, park=True)
        elif isinstance(action, Evict):
            self._apply_lane_removal(action.lane, park=False)
        elif isinstance(action, Recover):
            # half-open probe: re-admit the oldest parked lane
            self._apply_recover()
        # a taken Split needs no execution here: the DRM stamped the replica
        # table and the very next batch's route kernels fan the key out
        with _span("stream.account"):
            with safe_point():  # migrations only fire at safe points
                if mig.buffer_rows:
                    self.telemetry.record_exchange(migrate_stats(
                        shipped_rows=mig.shipped * w,  # helper re-divides per worker
                        buffer_rows=mig.buffer_rows,
                        moved_rows=mig.moved,
                        overflow=mig.overflow,
                        num_workers=w,
                        shipped_rows_by_class=mig.by_class,
                    ))
                    self.telemetry.record_overflow(migration=mig.overflow)

                # per-class shipped rows (shuffle + migration, per worker) for
                # the locality benches; zeros when the job carries no topology
                by_class = np.zeros(DISTANCE_CLASSES, np.int64)
                if stats.rows_by_class is not None:
                    by_class += np.asarray(host_fetch(stats.rows_by_class), np.int64)
                if mig.by_class is not None:
                    by_class += np.asarray(mig.by_class, np.int64) // w

            m = BatchMetrics(
                batch=len(self.metrics),
                imbalance=signals.imbalance,
                worker_imbalance=signals.worker_imbalance,
                # a backend switch is taken but moves no state — it must not
                # count as a repartition (consumers divide migration rows by
                # this flag's sum)
                repartitioned=action.taken and action.moves_state,
                relative_migration=mig.relative,
                overflow=overflow_i + mig.overflow,
                # the count as of the last drain (exact state rows would sync
                # the in-flight merge)
                state_rows=self._last_state_rows,
                wall_time_s=time.perf_counter() - t0,
                reason=action.reason,
                migration_rows=mig.buffer_rows,
                resized=isinstance(action, Resize),
                num_partitions=self.num_partitions,
                migration_plan_rows=mig.plan_rows,
                migration_peak_rows=mig.peak_rows,
                action=action.kind,
                shipped_rows=shuffle_shipped + mig.shipped,
                padded_rows=self._shuffle_spec.rows + mig.buffer_rows,
                backend=batch_backend,
                exchange_wall_s=exchange_wall,
                overlap_fraction=signals.overlap_fraction,
                split_keys=len(self.drm.split_keys),
                shipped_rows_by_class=tuple(int(x) for x in by_class),
                lanes=self.num_workers,
                transport=(batch_backend if batch_backend != "ragged" else
                           "ragged/native" if native_ragged(self.mesh)
                           else "ragged/masked-dense"),
                route_path=self._route_path,
            )
            # the host wall since the count sync ran under this batch's (or
            # the migration's) in-flight ship — that's the latency the overlap
            # hid.  Recorded at batch end, so it lands in the *next* telemetry
            # window.
            if self._inflight is not None and self._hidden_since is not None:
                self.telemetry.record_exchange(ExchangeStats(
                    rows=0,
                    hidden_wall_s=time.perf_counter() - self._hidden_since,
                ))
            self._hidden_since = None
            self.metrics.append(m)
        return m

    def _state_rows(self) -> int:
        """Live keyed-state rows across all workers (the migration scale).
        Drains any in-flight exchange (via the ``state_keys`` property)."""
        with safe_point():
            self._last_state_rows = int(host_fetch(jnp.sum(self.state_keys != KEY_SENTINEL)))
        return self._last_state_rows

    # -- elastic resize -------------------------------------------------
    def resize(self, num_partitions: int) -> None:
        """Request an elastic grow/shrink to ``num_partitions``.

        The request is applied at the next checkpoint safe point (the same
        protocol as a repartition — state only moves when a consistent
        snapshot boundary exists).  Explicit requests work even with
        ``dr_enabled=False``.
        """
        n = int(num_partitions)
        if n < self.num_workers:
            raise ValueError(
                f"cannot resize to {n} partitions: mesh has {self.num_workers} workers"
            )
        self._pending_resize = n

    def _apply_backend_switch(self) -> None:
        """Adopt the DRM's newly installed transport at a safe point.

        The jitted shuffle/migrate steps were built for the old backend, so
        both caches drop — the next batch rebuilds them for the new
        transport (the same rebuild contract as an elastic resize).  A
        fault seam stays armed across the switch: the wrapper re-points
        its inner transport instead of being replaced."""
        with _span("dr.switch"):
            new = self.drm.exchange_backend
            if (isinstance(self.exchange_backend, FaultyBackend)
                    and not isinstance(new, FaultyBackend)):
                self.exchange_backend.inner = resolve_backend(new)
                self.drm.exchange_backend = self.exchange_backend
            else:
                self.exchange_backend = new
            self._shuffle = None
            self._shuffle_sig = None
            self._migrate_steps.clear()

    # -- failure domains: lane removal / re-admission / recovery ---------
    def _set_workers(self, devices: list) -> None:
        """Rebuild the mesh over ``devices`` and drop everything keyed to
        the old topology: jitted step caches (their shard_maps bound the old
        mesh), the in-flight finish, and the least-load vector.  The
        partitioner is untouched — partitions re-fold onto the new worker
        count through the modulo placement."""
        self.mesh = Mesh(np.asarray(devices), ("data",))
        self.num_workers = len(devices)
        self._merge = _make_merge(self.mesh)
        self._shuffle = None
        self._shuffle_sig = None
        self._migrate_steps.clear()
        self._migrate_route = None
        self._part_loads = None
        self._inflight = None
        self._hidden_since = None

    def _apply_lane_removal(self, lane: int, *, park: bool) -> None:
        """Execute a Quarantine (``park=True``) or Evict at a safe point:
        fetch the state (the pre-action drain already completed), remove
        the lane from the collective, and fold its rows onto the
        survivors."""
        with _span("dr.lane"):
            with safe_point():
                sk = np.asarray(host_fetch(self._sk))
                sv = np.asarray(host_fetch(self._sv))
            devices = list(self.mesh.devices.flat)
            device = devices.pop(lane)
            orig = self._lane_ids.pop(lane)
            if park:
                self._parked.append((orig, device))
            backend = self.exchange_backend
            if isinstance(backend, FaultyBackend):
                (backend.note_quarantined if park else backend.note_evicted)(orig)
            self._set_workers(devices)
            self._adopt_state(sk, sv)

    def _apply_recover(self) -> None:
        """Execute a Recover at a safe point: re-admit the oldest parked
        device and spread the state back over the grown collective."""
        with _span("dr.recover"):
            if not self._parked:
                # a restored ledger can outlive the physical parked list (the
                # snapshot predated the quarantine): reconcile and decline
                self.drm.quarantined.clear()
                return
            with safe_point():
                sk = np.asarray(host_fetch(self._sk))
                sv = np.asarray(host_fetch(self._sv))
            orig, device = self._parked.pop(0)
            self._lane_ids.append(orig)
            backend = self.exchange_backend
            if isinstance(backend, FaultyBackend):
                backend.note_recovered(orig)
            self._set_workers(list(self.mesh.devices.flat) + [device])
            self._adopt_state(sk, sv)

    def _adopt_state(self, sk: np.ndarray, sv: np.ndarray) -> None:
        """Redistribute host-side state tables onto the *current* worker
        count: merge duplicate keys (split partial aggregates from
        different source workers co-land here — the keyed reduce is a sum,
        so merging early is the combiner-side merge), route every key to
        its home partition's worker, and rebuild the stacked tables.
        Capacity overflow is surfaced through telemetry, never silent."""
        w, cap = self.num_workers, self.state_capacity
        keys = np.asarray(sk).reshape(-1)
        vals = np.asarray(sv).reshape(-1, np.asarray(sv).shape[-1])
        live = keys != KEY_SENTINEL
        keys, vals = keys[live], vals[live]
        uniq, inv = np.unique(keys, return_inverse=True)
        acc = np.zeros((len(uniq),) + vals.shape[1:], vals.dtype)
        np.add.at(acc, inv, vals)
        dest = self.drm.partitioner.lookup_np(uniq.astype(np.int32)) % w
        new_k = np.full((w, cap), KEY_SENTINEL, np.int32)
        new_v = np.zeros((w, cap) + vals.shape[1:], np.float32)
        overflow = 0
        for worker in range(w):
            rows = np.nonzero(dest == worker)[0]
            if len(rows) > cap:
                overflow += len(rows) - cap
                rows = rows[:cap]
            new_k[worker, : len(rows)] = uniq[rows]
            new_v[worker, : len(rows)] = acc[rows]
        self._sk = self._shard(new_k)
        self._sv = self._shard(new_v)
        self._last_state_rows = int((new_k != KEY_SENTINEL).sum())
        if overflow:
            self.telemetry.record_overflow(migration=overflow)

    def _recover_from_loss(self, loss: WorkerLostError) -> str:
        """Zero-loss recovery from a hard worker loss (the safe-point
        protocol's failure branch).  Quiesce the surviving in-flight
        stages, evict the lost lane (shrinking the mesh; the last worker
        restarts in place instead), restore the last auto-snapshot onto
        the surviving topology, and record the forced eviction.  The
        caller replays the gap and retries the lost batch."""
        with _span("dr.recover"):
            try:
                self._drain_inflight()  # quiesce survivors (state is discarded
                #                         below, but the device queue must empty)
            except (WorkerLostError, TransientExchangeError):
                # only the fault seam's own errors mean "that stage is gone";
                # a compile or runtime error of the device work propagates
                self._inflight = None
                self._hidden_since = None
            backend = self.exchange_backend
            kind = "evict"
            if self.num_workers > 1 and loss.lane in self._lane_ids:
                lane = self._lane_ids.index(loss.lane)
                devices = list(self.mesh.devices.flat)
                devices.pop(lane)
                self._lane_ids.pop(lane)
                self._set_workers(devices)
                if isinstance(backend, FaultyBackend):
                    backend.note_evicted(loss.lane)
            else:
                kind = "restart"  # single worker (or already-removed lane):
                #                   restore + replay in place.  The restarted
                #                   lane stays fault-eligible — only the
                #                   standing death clears
                if isinstance(backend, FaultyBackend):
                    backend.note_restarted(loss.lane)
            snap = self._auto_snap
            assert snap is not None, "recovery requires snapshot_interval > 0"
            self.restore(snap, _keep_recovery_log=True)
            # the restored DRM predates the loss: log the forced eviction so
            # the decision trail carries the failure, and reconcile its
            # quarantine ledger with the physically parked devices
            self.drm.note_lost(loss.lane, reason=str(loss))
            while len(self.drm.quarantined) > len(self._parked):
                self.drm.quarantined.pop()
            while len(self.drm.quarantined) < len(self._parked):
                self.drm.quarantined.append((-1, self.drm.batches_seen))
            return kind

    def _apply_resize(self, n: int):
        """Execute a resize at a safe point: re-plan cross-size, migrate
        state through freshly sized exchange lanes, rebuild the step cache."""
        with _span("dr.resize"):
            self.drm.replan_resize(n)
            stats = self._migrate_state()
            self.num_partitions = n
            # the shuffle step's lane count / loads vector followed the old
            # topology; _build re-derives the spec on the next batch, and the
            # least-load vector is re-seeded at the new width
            self._shuffle = None
            self._shuffle_sig = None
            self._part_loads = None
            return stats

    def _migrate_state(self, *, full_lanes: bool = False) -> _Migration:
        """Ship keyed state to where ``self.drm.partitioner`` now maps it.

        Sizes the exchange lanes from what moves, with no key table and no
        host plan: across workers, a route program finds each row's
        destination worker and counts the rows every worker sends to every
        other worker; the host fetches only that ``[W, W]`` matrix and
        sizes the lanes to its largest entry x slack
        (:func:`~repro.core.migration.lane_rows`); the sized step ships
        from the route, and the received rows fold back into the local
        state tables.  The counts are taken where the rows are, so they are
        what the all-to-all ships and no lane can overflow — cross-size
        (resize) migrations included.  ``full_lanes`` (and any installed
        split key) needs no count: it provisions the whole table, because
        split partial aggregates live *off home* and the home-routed
        migrate step ships every one of them back to its key's home; the
        step routes in its own start program and the host fetches nothing.

        On one worker a migration is the identity — every row's destination
        is the worker it sits on, a split key's replicas included, which
        the merge has already summed into one row — so it enqueues nothing
        and returns an empty :class:`_Migration`.
        """
        with _span("dr.migrate"):
            if self.num_workers == 1:
                return _Migration()
            full = full_lanes or bool(self.drm.split_keys)
            where = self.drm.partitioner.tables()
            peak = 0
            with _span("dr.migrate.fetch"):
                if not full:
                    if self._migrate_route is None:
                        self._migrate_route = make_migrate_route(
                            self.mesh, num_hosts=self.drm.partitioner.num_hosts,
                            seed=self.seed)
                    where = self._migrate_route(where, self.state_keys)
                    with safe_point():  # migrations are safe points
                        peak = int(np.max(host_fetch(where.counts)))
            with _span("dr.migrate.plan"):
                plan_rows = self.state_capacity if full else lane_rows(peak)
            with _span("dr.migrate.start"):
                return self._start_migration(where, plan_rows, peak)

    def _start_migration(self, where, plan_rows: int, peak: int) -> _Migration:
        """Enqueue the migrate step sized for ``plan_rows`` — from the new
        partitioner's tables or from a :class:`MigrateRoute` — and read its
        control outputs."""
        migrate, lane_cap = self._migrate_step(plan_rows)
        # the count phase (and every control output the metrics need)
        # blocks below; the row ship + merge stays in flight across the
        # safe point and drains under the next batch's host work
        (pending, kk, vv, moved, total, mig_ov, mig_lane_ov, mig_shipped,
         mig_by) = migrate.start(where, self._sk, self._sv)
        # interim state = kept rows only; the pending merge adds the
        # received rows (external readers drain first, so they never
        # observe the interim)
        self._sk, self._sv = kk, vv
        self._hidden_since = time.perf_counter()

        def _fin_migrate(fin=migrate.finish, pending=pending):
            rk, rv, rva = fin(pending)
            self._sk, self._sv = self._merge(self._sk, self._sv, rk, rv, rva)

        self._inflight = _fin_migrate
        # every control output below left the migrate start phase;
        # fetching them at this safe point blocks on work already forced
        # (the ship itself stays in flight)
        with safe_point():
            moved_i = int(host_fetch(moved))
            total_i = int(host_fetch(total))
            mig_by_np = np.asarray(host_fetch(mig_by), np.int64)
            mig_shipped_i = int(host_fetch(mig_shipped))
            mig_ov_i = int(host_fetch(mig_ov))
        # rows/wall are recorded by process_batch (one call per migration);
        # the hot-lane vector is only available here, so it rides a
        # zero-row record into the same telemetry window (device array —
        # Telemetry folds it at the next snapshot, not here)
        self.telemetry.record_exchange(ExchangeStats(
            rows=0, lane_overflow=mig_lane_ov
        ))
        return _Migration(
            relative=float(moved_i) / max(float(total_i), 1e-9),
            overflow=mig_ov_i,
            buffer_rows=self.num_workers * lane_cap,  # rows received per worker
            plan_rows=plan_rows,
            peak_rows=peak,
            shipped=mig_shipped_i // self.num_workers,
            moved=moved_i,
            by_class=mig_by_np,
        )

    # ------------------------------------------------------------------
    def run(self, batches: Iterable[np.ndarray]) -> list[BatchMetrics]:
        return [self.process_batch(b) for b in batches]

    # -- state inspection ----------------------------------------------
    def state_count(self, key: int) -> float:
        """Total aggregated value for one key across all workers (test hook)."""
        sk = np.asarray(self.state_keys)
        sv = np.asarray(self.state_vals)
        hit = sk == key
        return float(sv[hit].sum())

    # -- checkpoint / restore --------------------------------------------
    def snapshot(self) -> dict:
        return {
            "state_keys": np.asarray(self.state_keys),
            "state_vals": np.asarray(self.state_vals),
            **{f"drm_{k}": v for k, v in self.drm.snapshot().items()},
        }

    def restore(self, snap: dict, *, _keep_recovery_log: bool = False) -> None:
        # any in-flight finish belongs to the state being replaced: discard,
        # along with the least-load vector (measured pre-restore)
        self._inflight = None
        self._hidden_since = None
        self._part_loads = None
        drm_snap = {k[4:]: v for k, v in snap.items() if k.startswith("drm_")}
        self.drm = DRMaster.restore(drm_snap, self.drm.config)
        snap_keys = np.asarray(snap["state_keys"])
        if snap_keys.shape[0] != self.num_workers:
            # cross-topology restore: the snapshot was cut on a different
            # worker count (recovery shrank the mesh since, or the snapshot
            # rode over a quarantine) — re-fold the rows onto the live
            # layout instead of adopting the stale stacking
            self._adopt_state(snap_keys, np.asarray(snap["state_vals"]))
        else:
            self.state_keys = self._shard(snap_keys)
            self.state_vals = self._shard(np.asarray(snap["state_vals"]))
        if "exchange_backend" in drm_snap:
            # the snapshot's *active* transport wins: a BackendPolicy switch
            # taken before the snapshot survives the restore, whatever
            # backend this job object was constructed with — but an armed
            # fault seam survives too: the wrapper re-points its inner
            # transport rather than being dropped by the restore
            restored = self.drm.exchange_backend
            if (isinstance(self.exchange_backend, FaultyBackend)
                    and not isinstance(restored, FaultyBackend)):
                self.exchange_backend.inner = resolve_backend(restored)
                self.drm.exchange_backend = self.exchange_backend
            else:
                self.exchange_backend = restored
        else:  # legacy snapshot predating backends: job's transport stands
            self.drm.exchange_backend = self.exchange_backend
        if self.drm.exchange_topology is not None:
            # snapshots carry the lane topology: a restore resumes with the
            # same locality view (by-class telemetry + plan pricing) the
            # snapshotted job had, whatever this object was built with
            self.exchange_topology = self.drm.exchange_topology
        else:  # legacy / flat snapshot: construction-time topology stands
            self.drm.exchange_topology = self.exchange_topology
        # resume the snapshotted topology: the snapshot may have been taken
        # after an elastic resize or a backend switch, in which case this
        # job's construction-time partition count / transport is stale and
        # the step caches must be rebuilt
        n = self.drm.partitioner.num_partitions
        assert n >= self.num_workers, (n, self.num_workers)
        self.num_partitions = n
        self._shuffle = None
        self._shuffle_sig = None
        self._migrate_steps.clear()
        self._migrate_route = None
        self._pending_resize = None
        if not _keep_recovery_log:
            # an external restore starts a fresh failure epoch: the old
            # auto-snapshot and replay buffer describe a timeline this
            # job just left.  (The recovery protocol itself restores with
            # ``_keep_recovery_log=True`` — the gap batches in the buffer
            # are exactly what it is about to replay.)
            self._auto_snap = None
            self._replay = []
        # the restored quarantine ledger can disagree with the physically
        # parked devices (the snapshot predates a quarantine, or rode over
        # one): the parked list is ground truth for what can re-admit
        while len(self.drm.quarantined) > len(self._parked):
            self.drm.quarantined.pop()
        self._state_rows()  # refresh the drain-time row cache
