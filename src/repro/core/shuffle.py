"""Device-side keyed shuffle: the DDPS stage boundary on a JAX mesh.

One shuffle step, executed under ``shard_map`` over the ``data`` axis, built
entirely on the unified exchange plane (``repro.exchange``):

1. every worker routes its local keys with the fused
   lookup+dispatch+bucketize path (one Pallas kernel on TPU, the jnp twin
   elsewhere — bit-identical),
2. the exchange primitive runs the selected backend's collective — dense
   capacity-padded or ragged count-first — and unpacks the received rows
   (overflow is counted per lane, never silently lost),
3. the DRW hook emits the local top-k histogram + global per-partition loads
   (a ``psum`` — reusing normal DDPS communication, as the paper requires).

The step is **split-phase**: the factories below build two programs,
``.start`` and ``.finish``.  ``start`` runs route + bucketize + the
transport's control phase and returns every control-plane output (loads,
histograms, overflow, shipped rows) with the un-shipped buffers as an
opaque pending value; ``finish`` ships the rows.  The streaming driver
(``repro.core.streaming``) holds ``finish`` in flight across a batch
boundary; the factory's own return value runs ``finish(start(...))`` on
the host, for callers that want one exchange whole.

Partitions may outnumber workers (over-partitioning, paper Fig. 5);
``worker = partition % W``.

State migration (``make_migrate_step``) is the *same* exchange, routed
with the same fused ``route_dispatch`` pass the shuffle uses (worker
granularity), so its bucketize reuses the dispatch counts instead of
recomputing them.  Its lanes are sized to what moves instead of
``W * state_capacity`` rows: ``make_migrate_route`` routes first, in a
program of its own, and counts the rows each worker sends to each other
worker; the host sizes the lanes from those ``[W, W]`` counts (the peak x
slack, ``repro.core.migration.lane_rows``) and the sized step ships from
the route.  Both steps report the backend's measured ``shipped_rows``
(globally summed) next to the spec's padded provision, so the control
plane sees what the transport moved, not just what it reserved.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.hashing import KEY_SENTINEL
from repro.core.histogram import local_topk_histogram
from repro.core.partitioner import PartitionerTables
from repro.exchange.spec import DISTANCE_CLASSES
from repro.exchange import (
    ExchangeBackend,
    ExchangeResult,
    ExchangeSpec,
    ExchangeStats,
    ExchangeTopology,
    Payload,
    PendingExchange,
    SendInfo,
    make_exchange,
    maybe_inject,
    route_bucketize,
    route_dispatch,
)

__all__ = [
    "ShuffleResult",
    "ShuffleStart",
    "make_shuffle_step",
    "MigrateRoute",
    "make_migrate_route",
    "make_migrate_step",
    "shuffle_stats",
    "migrate_stats",
]


class ShuffleResult(NamedTuple):
    keys: jax.Array       # int32[W, W*cap]   received keys per worker (sentinel padded)
    values: jax.Array     # f32[W, W*cap, D]  received payloads
    valid: jax.Array      # bool[W, W*cap]
    part: jax.Array       # int32[W, W*cap]   destination partition of each record
    loads: jax.Array      # int32[N]          global per-partition record counts
    hist_keys: jax.Array  # int32[W, K]       DRW local top-k keys
    hist_counts: jax.Array  # int32[W, K]
    overflow: jax.Array   # int32[]           records dropped for capacity globally
    lane_overflow: jax.Array  # int32[W]      global per-lane capacity drops
    shipped_rows: jax.Array   # int32[]       rows the backend moved, all workers
    shipped_rows_by_class: jax.Array  # int32[C] shipped split by lane distance
                          # class (self/intra-host/inter-host); zeros when the
                          # spec carries no topology


class ShuffleStart(NamedTuple):
    """Control-plane outputs of the shuffle's start phase — everything a
    decision needs, available before (and without) the row ship."""

    loads: jax.Array          # int32[N]
    hist_keys: jax.Array      # int32[W, K]
    hist_counts: jax.Array    # int32[W, K]
    overflow: jax.Array       # int32[]
    lane_overflow: jax.Array  # int32[W]
    shipped_rows: jax.Array   # int32[]
    shipped_rows_by_class: jax.Array  # int32[C]


class _Pending(NamedTuple):
    """The in-flight exchange at the jit boundary: just the array leaves
    (send buffers + phase-1 counts), stacked ``[W, ...]`` per worker.
    ``SendInfo`` and the static fills are re-stamped at finish — the ship
    phase never reads them."""

    valid: jax.Array   # bool[W, L, cap]
    payloads: tuple    # each [W, L, cap, ...]
    lane_counts: jax.Array | None
    recv_counts: jax.Array | None


def _pack_pending(started: ExchangeResult) -> _Pending:
    return _Pending(
        started.valid[None],
        tuple(b[None] for b in started.payloads),
        None if started.lane_counts is None else started.lane_counts[None],
        None if started.recv_counts is None else started.recv_counts[None],
    )


def _unpack_pending(pending: _Pending, fills: tuple) -> ExchangeResult:
    return ExchangeResult(
        pending.valid[0],
        tuple(b[0] for b in pending.payloads),
        SendInfo(None, None, None, None, None),
        lane_counts=None if pending.lane_counts is None else pending.lane_counts[0],
        recv_counts=None if pending.recv_counts is None else pending.recv_counts[0],
        fills=fills,
    )


def _pool_sharding(mesh: Mesh, axis: str):
    """Sharding for freshly allocated send-buffer sets: identical to what
    the jitted ``start`` emits for its pending buffers (lane axis over the
    mesh; jit canonicalizes a size-1 axis out of the spec).  Committing the
    fresh set at allocation keeps the jit signature stable when the
    ping-pong pool first supplies a recycled (committed) set — otherwise
    the first pool hit recompiles the start program mid-stream."""
    spec = P(axis) if mesh.shape[axis] > 1 else P()
    return jax.sharding.NamedSharding(mesh, spec)


def make_shuffle_step(
    mesh: Mesh,
    *,
    num_partitions: int,
    capacity: int,
    hist_k: int = 64,
    num_hosts: int,
    seed: int = 0,
    axis: str = "data",
    backend: str | ExchangeBackend | None = None,
    topology: ExchangeTopology | None = None,
    least_load: bool = False,
):
    """Build the jitted shuffle step for a fixed mesh/capacity/topology.

    Returns ``step(tables, keys, vals, valid) -> ShuffleResult``, which
    runs the two jitted phases back to back on the host, with the phases
    attached for the streaming driver:

    * ``step.start(tables, keys, vals, valid) -> (pending, ShuffleStart)``
    * ``step.finish(pending) -> (keys, values, valid, part)`` stacked [W, ...]

    An elastic resize rebuilds the step: ``num_partitions``
    fixes the loads vector width, so the new topology needs a new closure
    (the migrate step does *not* — it routes at worker granularity, see
    :func:`make_migrate_step`).  ``backend`` selects the exchange transport
    (dense / ragged / an :class:`ExchangeBackend` instance).

    The split-phase halves double-buffer their ``[L, cap]`` send buffers:
    ``finish`` recycles each drained pending's buffer set into a two-set
    ping-pong pool and the next ``start`` scatters into a recycled set
    (donated, so XLA rewrites it in place) instead of allocating fresh —
    the driver enqueues batch N's start before batch N-1's finish, so one
    set is still in flight while the other is being filled.  Values are
    bit-identical to the fresh path.

    ``least_load=True`` (static) switches the split-key replica pick to
    the two-choice least-load tiebreak: ``step``/``step.start`` accept a
    ``part_loads`` vector (fed from ``Signals`` at safe points) and route
    on the jnp twin — the Pallas kernel keeps the stateless hash, so the
    gate is per-factory, never per-batch.
    """
    num_workers = mesh.shape[axis]
    ex = make_exchange(
        ExchangeSpec(num_lanes=num_workers, capacity=capacity, axis=axis,
                     topology=topology),
        backend,
    )
    fills = (KEY_SENTINEL, 0, 0)

    def _start_core(tables, keys, vals, valid, bufs, part_loads):
        # keys [n] local records of this worker; the fused route pass
        # produces partition ids, slots, per-lane counts AND the bucketized
        # send buffers in one chain (one Pallas kernel on TPU) — bucketize
        # derives nothing again, and the ragged backend's count phase
        # reuses the counts
        tables = PartitionerTables(*tables)
        # num_partitions switches the split-key replica pick on: heavy keys
        # whose tables.heavy_repl > 1 fan out over their replica partitions
        # (an all-ones column routes bit-identically to the pre-split path)
        part, buffers = route_bucketize(
            ex, tables, keys, valid, vals, num_hosts=num_hosts, seed=seed,
            num_partitions=num_partitions,
            buffers=None if bufs is None else (bufs[0][0], tuple(b[0] for b in bufs[1])),
            part_loads=part_loads if least_load else None,
        )
        dest = jnp.where(valid, part, 0)
        started = ex.start_from(buffers).buffers
        # DRW: sample local keys during normal work (no extra pass)
        hk, hc, _ = local_topk_histogram(keys, valid, hist_k)
        # global per-partition loads (normal DDPS comms: one psum)
        my_loads = jnp.zeros(num_partitions, jnp.int32).at[dest].add(valid.astype(jnp.int32))
        loads = jax.lax.psum(my_loads, axis)
        overflow = jax.lax.psum(started.send.overflow, axis)
        lane_overflow = jax.lax.psum(started.send.lane_overflow, axis)
        shipped = jax.lax.psum(started.shipped_rows, axis)
        by_class = started.shipped_rows_by_class
        if by_class is None:  # flat spec: no topology, keep zeros
            by_class = jnp.zeros(DISTANCE_CLASSES, jnp.int32)
        by_class = jax.lax.psum(by_class, axis)
        start = ShuffleStart(loads, hk[None], hc[None], overflow, lane_overflow,
                             shipped, by_class)
        return _pack_pending(started), start

    # the mapped functions' names become the programs' names in a device
    # trace: jit_shuffle_start, jit_shuffle_finish
    def shuffle_start(tables, keys, vals, valid, bufs, part_loads):
        return _start_core(tables, keys, vals, valid, bufs, part_loads)

    def shuffle_finish(pending):
        res = ex.finish(PendingExchange(_unpack_pending(pending, fills)))
        rva, (rk, rv, rp) = res.unpack()
        return rk[None], rv[None], rva[None], rp[None]

    in_specs = (
        (P(), P(), P(), P()),  # partitioner tables replicated
        P(axis),  # keys sharded over workers
        P(axis),
        P(axis),
    )
    bufs_spec = (P(axis), (P(axis), P(axis), P(axis)))
    start_mapped = shard_map(
        shuffle_start, mesh=mesh, in_specs=in_specs + (bufs_spec, P()),
        out_specs=(P(axis), ShuffleStart(P(), P(axis), P(axis), P(), P(), P(), P())),
        check_vma=False,
    )
    finish_mapped = shard_map(
        shuffle_finish, mesh=mesh, in_specs=(P(axis),),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
        check_vma=False,
    )

    # donate the per-batch buffers so the exchange compaction reuses them
    # instead of double-allocating; the recycled send-buffer set (arg 4 of
    # start) is donated too — its reset+scatter rewrites it in place.  The
    # finish phase must NOT donate: each drained pending's buffers re-enter
    # the ping-pong pool, so they have to survive the ship.  (CPU has no
    # donation — skip the warning.)
    start_donate = () if jax.default_backend() == "cpu" else (1, 2, 3, 4)
    jstart = jax.jit(start_mapped, donate_argnums=start_donate)
    jfinish = jax.jit(finish_mapped)

    zero_loads = jnp.zeros(num_partitions, jnp.float32)
    recycled: list = []  # drained send-buffer sets, ping-pong pool (<= 2)
    buf_sharding = _pool_sharding(mesh, axis)

    def _fresh_bufs(vals):
        shape = (num_workers, num_workers, capacity)
        return jax.device_put((
            jnp.zeros(shape, bool),
            (jnp.full(shape, KEY_SENTINEL, jnp.int32),
             jnp.zeros(shape + vals.shape[1:], vals.dtype),
             jnp.zeros(shape, jnp.int32)),
        ), buf_sharding)

    def start(tables: PartitionerTables, keys, vals, valid, part_loads=None):
        maybe_inject(ex.backend, "shuffle")
        bufs = recycled.pop() if recycled else None
        if bufs is not None and (bufs[1][1].shape[3:] != vals.shape[1:]
                                 or bufs[1][1].dtype != vals.dtype):
            bufs = None  # payload width changed: the set cannot be reused
        if bufs is None:
            bufs = _fresh_bufs(vals)
        pl = zero_loads if part_loads is None else part_loads
        return jstart(tuple(tables), keys, vals, valid, bufs, pl)

    def finish(pending: _Pending):
        out = jfinish(pending)
        if len(recycled) < 2:
            # the drained pending's buffers become the next idle set — two
            # sets bound the pool because at most two exchanges are in
            # flight (batch N's start is enqueued before N-1's finish)
            recycled.append((pending.valid, pending.payloads))
        return out

    def step(tables: PartitionerTables, keys, vals, valid,
             part_loads=None) -> ShuffleResult:
        pending, started = start(tables, keys, vals, valid, part_loads)
        return ShuffleResult(*finish(pending), *started)

    step.start = start
    step.finish = finish
    return step


class MigrateRoute(NamedTuple):
    """The first phase of a state migration, on the device, stacked
    ``[W, ...]`` per worker: where each state row goes and how many rows
    each worker sends to each other worker."""

    dest: jax.Array    # int32[W, S]  destination worker; the row's own worker if it stays
    slot: jax.Array    # int32[W, S]  rank of the row within its destination lane
    counts: jax.Array  # int32[W, W]  rows worker i sends to worker j (diagonal 0)
    moved: jax.Array   # int32[]      rows that change worker, all workers
    total: jax.Array   # int32[]      live state rows, all workers


def _route_state(new_tables, state_keys, *, num_hosts: int, seed: int,
                 num_workers: int, axis: str) -> MigrateRoute:
    """One worker's half of a migration route (unstacked): its rows'
    destinations and lane slots under ``new_tables``, and its row of the
    ``[W, W]`` counts."""
    me = jax.lax.axis_index(axis)
    valid = state_keys != KEY_SENTINEL
    # home routing on purpose (no num_partitions): a migration is where
    # a split key's scattered partials converge — every replica's rows
    # ship to the key's home partition, whose merge_into sums them.
    # Routing state by replica pick would scatter it instead.
    part, slot, counts = route_dispatch(
        PartitionerTables(*new_tables), state_keys, valid,
        num_hosts=num_hosts, seed=seed, num_lanes=num_workers,
    )
    dest = jnp.where(valid, part % num_workers, me)
    # the fused route ranked *all* valid rows; rows on lane `me` stay put,
    # so their lane count is zeroed — on every other lane the slots/counts
    # coincide with ranking the moving rows alone
    counts = counts.at[me].set(0)
    moved = jax.lax.psum(jnp.sum(dest != me), axis)
    total = jax.lax.psum(jnp.sum(valid), axis)
    return MigrateRoute(dest, slot, counts, moved, total)


def make_migrate_route(mesh: Mesh, *, num_hosts: int, seed: int = 0, axis: str = "data"):
    """Jitted first phase of an operator-state migration, for lanes that
    must be sized from what moves: ``route(new_tables, state_keys) ->
    MigrateRoute``.

    Every worker routes its stored keys under the new partitioner with the
    fused ``route_dispatch`` pass the shuffle uses, at worker granularity
    (``lookup % W``), so one route serves any partition count.  The counts
    are taken where the rows are: row ``i`` of ``counts`` is what worker
    ``i``'s table sends, which is what the all-to-all ships, so lanes sized
    to its largest entry cannot overflow.  Nothing here depends on the
    lane size; a step of :func:`make_migrate_step` takes the route in place
    of the tables and ships from it.  The state is read, not donated.
    """
    num_workers = mesh.shape[axis]

    # jit_migrate_start in a device trace, like the sized start after it
    def migrate_start(new_tables, state_keys):
        r = _route_state(new_tables, state_keys[0], num_hosts=num_hosts, seed=seed,
                         num_workers=num_workers, axis=axis)
        return MigrateRoute(r.dest[None], r.slot[None], r.counts[None], r.moved, r.total)

    jroute = jax.jit(shard_map(
        migrate_start, mesh=mesh, in_specs=((P(), P(), P(), P()), P(axis)),
        out_specs=MigrateRoute(P(axis), P(axis), P(axis), P(), P()),
        check_vma=False,
    ))

    def route(new_tables: PartitionerTables, state_keys) -> MigrateRoute:
        return jroute(tuple(new_tables), state_keys)

    return route


def make_migrate_step(
    mesh: Mesh,
    *,
    state_capacity: int,
    num_hosts: int,
    lane_capacity: int | None = None,
    seed: int = 0,
    axis: str = "data",
    spec: ExchangeSpec | None = None,
    backend: str | ExchangeBackend | None = None,
    topology: ExchangeTopology | None = None,
):
    """Jitted operator-state migration for a partitioner swap.

    Each worker re-evaluates the new partitioner on its stored keys and
    ships rows whose worker changed through the exchange plane.  Every
    call takes, first, where the rows go: either the new partitioner's
    tables, and the step routes in its own program, or the
    :class:`MigrateRoute` that :func:`make_migrate_route` computed, and the
    step starts from it.  The route is the part that does not depend on the
    lane size; the second form lets the host size the lanes between the two
    phases from the route's ``[W, W]`` counts (the largest entry x slack,
    ``repro.core.migration.lane_rows``).  Either way the bucketize reuses
    the route's slots and counts instead of recomputing them, and lane
    ``me`` never ships (its rows stay put).
    ``lane_capacity`` bounds the per-(src, dst) rows of the all-to-all
    (defaults to ``state_capacity``, the correctness-first upper bound).
    ``spec`` overrides the derived :class:`ExchangeSpec` entirely (the
    elastic-resize path re-derives the shuffle's spec); ``backend`` selects
    the transport.  Routing is at *worker* granularity (``lookup % W``), so
    one step serves any partition count — a resize migration reuses the
    same jit cache.

    Returns ``migrate(where, state_keys, state_vals)``, which runs the two
    jitted phases back to back on the host (kept state + received rows +
    moved and live rows + overflow + per-lane overflow + globally shipped
    rows + shipped rows by distance class), with the phases attached:
    ``start`` keeps every control output and the kept state local (the
    ship stays pending), ``finish`` ships the moving rows — the streaming
    driver leaves it in flight across the safe point.  ``start`` donates
    the state tables, which a separate route only read.
    """
    num_workers = mesh.shape[axis]
    if spec is None:
        cap = state_capacity if lane_capacity is None else min(lane_capacity, state_capacity)
        spec = ExchangeSpec(num_lanes=num_workers, capacity=cap, axis=axis,
                            topology=topology)
    ex = make_exchange(spec, backend)
    fills = (KEY_SENTINEL, 0)
    route_spec = MigrateRoute(P(axis), P(axis), P(axis), P(), P())
    tables_spec = (P(), P(), P(), P())

    def _start_core(where, state_keys, state_vals, bufs):
        # the state tables (and a route) arrive stacked [1, ...] per shard
        state_keys, state_vals = state_keys[0], state_vals[0]
        if isinstance(where, MigrateRoute):
            r = MigrateRoute(where.dest[0], where.slot[0], where.counts[0],
                             where.moved, where.total)
        else:
            r = _route_state(where, state_keys, num_hosts=num_hosts, seed=seed,
                             num_workers=num_workers, axis=axis)
        moving = r.dest != jax.lax.axis_index(axis)
        buffers = ex.bucketize(
            r.dest,
            moving,
            [
                Payload(jnp.where(moving, state_keys, KEY_SENTINEL), KEY_SENTINEL),
                Payload(state_vals, 0),
            ],
            slot=r.slot,
            counts=r.counts,
            buffers=None if bufs is None else (bufs[0][0], tuple(b[0] for b in bufs[1])),
        )
        started = ex.start_from(buffers).buffers
        kept_keys = jnp.where(moving, KEY_SENTINEL, state_keys)
        overflow = jax.lax.psum(started.send.overflow, axis)
        lane_overflow = jax.lax.psum(started.send.lane_overflow, axis)
        shipped = jax.lax.psum(started.shipped_rows, axis)
        by_class = started.shipped_rows_by_class
        if by_class is None:  # flat spec: no topology, keep zeros
            by_class = jnp.zeros(DISTANCE_CLASSES, jnp.int32)
        by_class = jax.lax.psum(by_class, axis)
        return (_pack_pending(started), kept_keys[None], state_vals[None],
                r.moved, r.total, overflow, lane_overflow, shipped, by_class)

    # programs jit_migrate_start and jit_migrate_finish in a device trace,
    # apart from the shuffle's
    def migrate_start(where, state_keys, state_vals, bufs):
        return _start_core(where, state_keys, state_vals, bufs)

    def migrate_finish(pending):
        res = ex.finish(PendingExchange(_unpack_pending(pending, fills)))
        rva, (rk, rv) = res.unpack()
        return rk[None], rv[None], rva[None]

    bufs_spec = (P(axis), (P(axis), P(axis)))
    control = (P(),) * 6
    # donate the state tables: the kept outputs alias them, so the
    # exchange compaction doesn't double-allocate the state; the recycled
    # send-buffer set (arg 3 of start) is donated and rewritten in place.
    # finish keeps its pending alive — drained sets re-enter the ping-pong
    # pool (CPU: no donation at all).
    start_donate = () if jax.default_backend() == "cpu" else (1, 2, 3)
    jstart = {}  # keyed by whether the call brings a MigrateRoute
    for routed, where_spec in ((False, tables_spec), (True, route_spec)):
        in_specs = (where_spec, P(axis), P(axis))
        jstart[routed] = jax.jit(shard_map(
            migrate_start, mesh=mesh, in_specs=in_specs + (bufs_spec,),
            out_specs=(P(axis),) * 3 + control, check_vma=False,
        ), donate_argnums=start_donate)
    jfinish = jax.jit(shard_map(
        migrate_finish, mesh=mesh, in_specs=(P(axis),),
        out_specs=(P(axis), P(axis), P(axis)), check_vma=False,
    ))

    recycled: list = []  # drained send-buffer sets, ping-pong pool (<= 2)
    buf_sharding = _pool_sharding(mesh, axis)

    def _fresh_bufs(state_vals):
        shape = (num_workers, spec.num_lanes, spec.capacity)
        return jax.device_put((
            jnp.zeros(shape, bool),
            (jnp.full(shape, KEY_SENTINEL, jnp.int32),
             jnp.zeros(shape + state_vals.shape[2:], state_vals.dtype)),
        ), buf_sharding)

    def _where(where):
        routed = isinstance(where, MigrateRoute)
        return routed, (where if routed else tuple(where))

    def start(where, state_keys, state_vals):
        maybe_inject(ex.backend, "migrate")
        bufs = recycled.pop() if recycled else None
        if bufs is not None and (bufs[1][1].shape[3:] != state_vals.shape[2:]
                                 or bufs[1][1].dtype != state_vals.dtype):
            bufs = None  # payload width changed: the set cannot be reused
        if bufs is None:
            bufs = _fresh_bufs(state_vals)
        routed, where = _where(where)
        return jstart[routed](where, state_keys, state_vals, bufs)

    def finish(pending: _Pending):
        out = jfinish(pending)
        if len(recycled) < 2:
            recycled.append((pending.valid, pending.payloads))
        return out

    def migrate(where, state_keys, state_vals):
        pending, kept_keys, kept_vals, *control = start(where, state_keys, state_vals)
        return (kept_keys, kept_vals, *finish(pending), *control)

    migrate.start = start
    migrate.finish = finish
    return migrate


# ---------------------------------------------------------------------------
# Plane-side telemetry constructors (the ExchangeStats API): consumers hand
# these records whole to ``Telemetry.record_exchange(stats)`` instead of
# assembling keyword soup at every call site.
# ---------------------------------------------------------------------------


def shuffle_stats(
    res: "ShuffleResult | ShuffleStart",
    spec: ExchangeSpec,
    num_workers: int,
    *,
    wall_s: float = 0.0,
    count_wall_s: float | None = None,
    backend: str | None = None,
    replica_rows: np.ndarray | None = None,
) -> ExchangeStats:
    """:class:`ExchangeStats` for one shuffle step.

    ``ShuffleResult`` and ``ShuffleStart`` share every control field this
    reads (loads / overflow / lane_overflow / shipped_rows), so either
    constructs the same record.  Rows are per worker (the globally-psummed
    counters divided by ``num_workers``); ``padded`` is the spec's
    per-worker provision.

    Sync-free: device inputs stay device-side — the per-worker arithmetic
    runs as (async) jnp ops and the record carries device scalars, which
    ``Telemetry.record_exchange`` accepts and folds to host ints only at
    ``snapshot()`` (the safe point).  Host inputs produce a host record as
    before.
    """
    dev = isinstance(res.shipped_rows, jax.Array)
    if dev:
        shipped = res.shipped_rows // num_workers
        occupied = jnp.maximum(jnp.sum(res.loads) - res.overflow, 0) // num_workers
    else:
        shipped = int(np.asarray(res.shipped_rows)) // num_workers
        occupied = max(int(np.asarray(res.loads).sum()) - int(res.overflow), 0) // num_workers
    by_class = None
    if spec.topology is not None and res.shipped_rows_by_class is not None:
        by_class = (res.shipped_rows_by_class // num_workers if dev
                    else np.asarray(res.shipped_rows_by_class, np.int64) // num_workers)
    return ExchangeStats(
        rows=shipped,
        wall_s=wall_s,
        padded_rows=spec.rows,
        occupied_rows=occupied,
        lane_overflow=res.lane_overflow if dev else np.asarray(res.lane_overflow),
        count_wall_s=count_wall_s,
        backend=backend,
        replica_rows=replica_rows,
        rows_by_class=by_class,
    )


def migrate_stats(
    *,
    shipped_rows,
    buffer_rows: int,
    moved_rows: int,
    overflow: int,
    num_workers: int,
    lane_overflow=None,
    wall_s: float = 0.0,
    backend: str | None = None,
    shipped_rows_by_class=None,
) -> ExchangeStats:
    """:class:`ExchangeStats` for one state migration.

    ``buffer_rows`` is the per-worker lane provision (``W * lane_cap``),
    ``moved_rows`` the rows that actually crossed workers (globally summed,
    like ``shipped_rows`` and ``overflow``); ``shipped_rows_by_class`` the
    globally-summed per-distance-class split (``None`` on a flat spec).

    Migrations only happen at safe points (the driver drains before acting),
    so the host conversions here are sanctioned — they route through
    :func:`repro.compat.host_fetch` so the sync audit sees them.
    """
    from repro.compat import host_fetch

    by_class = None
    if shipped_rows_by_class is not None:
        by_class = np.asarray(host_fetch(shipped_rows_by_class), np.int64)
        by_class = None if not by_class.any() else by_class // num_workers
    return ExchangeStats(
        rows=int(host_fetch(shipped_rows)) // num_workers,
        wall_s=wall_s,
        padded_rows=int(buffer_rows),
        occupied_rows=max(int(moved_rows) - int(overflow), 0) // num_workers,
        lane_overflow=None if lane_overflow is None else host_fetch(lane_overflow),
        backend=backend,
        rows_by_class=by_class,
    )
