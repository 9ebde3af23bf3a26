"""The unified exchange plane: ``route -> bucketize -> all_to_all -> unpack``.

The paper's DR module works because repartitioning "reuses normal DDPS
communication".  This module is that communication, implemented once and
split **spec + backend**: an :class:`~repro.exchange.spec.ExchangeSpec`
names the static shape of one exchange (lanes x capacity over an optional
mesh axis), an :class:`~repro.exchange.backends.ExchangeBackend` moves the
buffers (dense capacity-padded, ragged count-first, or local no-collective),
and :class:`Exchange` binds the two for the consumers — the micro-batch
shuffle (``repro.core.shuffle``), operator-state migration
(``make_migrate_step``) and MoE expert dispatch (``repro.moe.layer``).
Following Partial Key Grouping / AutoFlow, the routing+exchange primitive is
the pluggable unit; the balancing policy (KIP, KIP placement, migration
planning) layers on top and never touches collectives directly — and the
backend's measured ``shipped_rows`` / ``cost`` feed the control plane, so
policy decisions price what the active transport would actually move.

The collective is **split-phase**: :meth:`Exchange.start` runs route +
bucketize + the transport's control phase (the ragged count all-to-all) and
returns an in-flight :class:`PendingExchange`; :meth:`Exchange.finish`
ships the payload rows and yields the final :class:`ExchangeResult`.
``Exchange.__call__`` is literally ``finish(start(...))`` — bit-identical
by construction — and everything the control plane reads (loads, overflow,
``shipped_rows``) is final at ``start``, so a driver can hold the pending
exchange and overlap the row ship with the next batch's routing and with
host-side policy decisions (see ``repro.core.streaming``).

All functions are pure jnp and run inside ``jit`` / ``shard_map``.  The
routing hot path has Pallas kernels with bit-identical jnp twins; the twin
is the default off-TPU.  On a TPU, :func:`route_path` picks the kernel by a
static size rule: the fused route -> bucketize kernel
(``repro.kernels.route_bucketize``) keeps the ``[L, capacity]`` send
buffers resident in VMEM, so it runs only while they fit
(``route_bucketize.fits``: L <= 16, capacity <= 2048, payload width <= 8);
larger exchanges — every streaming batch at production size — run the
two-pass path, the ``lookup_dispatch`` kernel followed by the plane's
scatter.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import KEY_SENTINEL
from repro.core.partitioner import PartitionerTables
from repro.exchange.backends import ExchangeBackend, resolve_backend
from repro.exchange.spec import (
    ExchangeResult,
    ExchangeSpec,
    ExchangeStats,
    ExchangeTopology,
    Payload,
    SendInfo,
    take_from,
)
from repro.kernels import ref as kref

__all__ = [
    "ExchangeSpec",
    "ExchangeStats",
    "ExchangeTopology",
    "Payload",
    "SendInfo",
    "ExchangeResult",
    "Exchange",
    "PendingExchange",
    "make_exchange",
    "route_dispatch",
    "route_bucketize",
    "route_path",
    "take_from",
]


class PendingExchange(NamedTuple):
    """An exchange whose control phase ran but whose rows have not shipped.

    ``buffers`` is the bucketized :class:`ExchangeResult` with every
    control-plane field stamped by the backend's ``a2a_start`` —
    ``shipped_rows``, ``lane_counts``, ``recv_counts``, and the full
    ``send`` accounting are final and safe to consume; ``valid`` /
    ``payloads`` still hold the *send*-side buffers until
    :meth:`Exchange.finish` moves them.
    """

    buffers: ExchangeResult

    def stats(self, spec: ExchangeSpec | None = None, **kw) -> ExchangeStats:
        """Telemetry record from the control phase (all control-plane fields
        are final at ``start``; see :meth:`ExchangeResult.stats`)."""
        return self.buffers.stats(spec, **kw)


def route_path(num_lanes: int, capacity: int, payload_dim: int, *,
               use_pallas: bool | None = None, least_load: bool = False) -> str:
    """Which route -> bucketize implementation :func:`route_bucketize` runs
    for an exchange of this static shape: ``"fused kernel"``,
    ``"two-pass kernel"`` or ``"jnp twin"``.

    The Pallas kernels run on TPU (``use_pallas=None``) unless the
    least-load replica pick is on, which only the jnp twin implements.
    Among the kernels the choice is the static size rule
    ``route_bucketize.fits``: the fused kernel while its resident send
    buffers fit VMEM, else the ``lookup_dispatch`` kernel plus the jnp
    scatter."""
    from repro.kernels.route_bucketize import fits

    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and not least_load
    if not use_pallas:
        return "jnp twin"
    return "fused kernel" if fits(num_lanes, capacity, payload_dim) else "two-pass kernel"


def route_dispatch(
    tables: PartitionerTables,
    keys: jax.Array,
    valid: jax.Array,
    *,
    num_hosts: int,
    seed: int,
    num_lanes: int,
    num_partitions: int = 0,
    use_pallas: bool | None = None,
    part_loads: jax.Array | None = None,
):
    """Fused key -> partition lookup + lane slot assignment.

    Returns ``(part[n], slot[n], counts[num_lanes])`` where ``slot`` ranks
    each valid record within its ``part % num_lanes`` lane and ``counts``
    is the per-lane occupancy the same pass already tallied — hand both to
    ``bucketize`` so it derives neither again (the ragged backend's count
    phase and the per-lane overflow both reuse them).  On TPU this is one
    fused Pallas kernel (``repro.kernels.lookup_dispatch``); elsewhere the
    bit-identical jnp twin.

    ``num_partitions > 0`` activates hot-key splitting: heavy keys with
    ``tables.heavy_repl > 1`` fan out over their replica partitions.  Leave
    it 0 (the default) to route every key to its home — the state-migration
    path *must*, since homes are where split partials converge and merge.

    ``part_loads`` (a ``[num_partitions]`` load vector, jnp path only)
    switches the split-replica pick from the stateless hash offset to the
    two-choice least-load tiebreak — see
    :func:`repro.kernels.ref.split_choice_ref`.  The Pallas kernel keeps
    the hash, so callers must gate ``use_pallas=False`` statically when
    they feed loads (asserted here).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and part_loads is None
    if use_pallas:
        assert part_loads is None, (
            "the Pallas route kernel keeps the stateless hash replica pick; "
            "pass use_pallas=False to use the least-load tiebreak"
        )
        from repro.kernels import ops

        part, slot, counts = ops.route_slots(
            keys, valid, tables, num_hosts=num_hosts, seed=seed,
            num_lanes=num_lanes, num_partitions=num_partitions,
        )
    else:
        part, slot, counts = kref.lookup_dispatch_ref(
            keys, valid, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
            seed=seed, num_hosts=num_hosts, num_lanes=num_lanes,
            heavy_repl=tables.heavy_repl if num_partitions > 0 else None,
            num_partitions=num_partitions,
            part_loads=part_loads if num_partitions > 0 else None,
        )
    return part, slot, counts


def route_bucketize(
    exchange: "Exchange",
    tables: PartitionerTables,
    keys: jax.Array,
    valid: jax.Array,
    vals: jax.Array,
    *,
    num_hosts: int,
    seed: int,
    key_fill: int = KEY_SENTINEL,
    num_partitions: int = 0,
    use_pallas: bool | None = None,
    buffers: tuple | None = None,
    part_loads: jax.Array | None = None,
):
    """Fused route -> bucketize for the shuffle's ``(keys, vals, part)``
    payload triple.

    Returns ``(part, buffers)`` — the per-record partition ids plus a
    bucketized :class:`~repro.exchange.spec.ExchangeResult` ready for the
    collective.  On TPU the whole key -> partition -> lane -> slot ->
    send-buffer chain runs in one Pallas kernel
    (``repro.kernels.route_bucketize``) so the routed block never leaves
    VMEM between the route and the scatter — while the send buffers fit,
    see :func:`route_path`; otherwise it is :func:`route_dispatch` (the
    ``lookup_dispatch`` kernel on TPU, the jnp twin elsewhere) +
    ``bucketize`` — bit-identical by the kernels' ref-twin contract.

    ``buffers`` is the double-buffer reuse seam (see
    :meth:`Exchange.bucketize`): a recycled ``(valid_buf, payload_bufs)``
    set the jnp scatter resets and writes into.  The Pallas kernel writes
    its own kernel-managed outputs, so the seam is a no-op on that path —
    still bit-identical, just without the realloc saving.  ``part_loads``
    is the least-load split-replica feed (jnp path only, see
    :func:`route_dispatch`).
    """
    spec = exchange.spec
    path = route_path(spec.num_lanes, spec.capacity, int(np.prod(vals.shape[1:])),
                      use_pallas=use_pallas, least_load=part_loads is not None)
    if path != "jnp twin":
        assert part_loads is None, (
            "least-load replica pick requires the jnp route path "
            "(use_pallas=False)"
        )
    if path == "fused kernel":
        from repro.kernels import ops

        part, slot, counts, buf_valid, bk, bv, bp = ops.route_bucketize(
            keys, valid, tables, vals,
            num_hosts=num_hosts, seed=seed,
            num_lanes=spec.num_lanes, capacity=spec.capacity, key_fill=key_fill,
            num_partitions=num_partitions,
        )
        lane = jnp.where(valid, part % spec.num_lanes, 0).astype(jnp.int32)
        ok = valid & (slot >= 0) & (slot < spec.capacity)
        # lanes are `part % L`, always in range: the capacity drops per lane
        # (and their sum, the scalar) fall out of the dispatch counts — the
        # same O(L) accounting the two-pass `_bucketize` counts path uses
        lane_overflow = jnp.maximum(counts - spec.capacity, 0).astype(jnp.int32)
        overflow = jnp.sum(lane_overflow).astype(jnp.int32)
        buffers = ExchangeResult(
            buf_valid, (bk, bv, bp),
            SendInfo(lane, slot, ok, overflow, lane_overflow),
            shipped_rows=jnp.zeros((), jnp.int32),
            lane_counts=jnp.minimum(counts, spec.capacity).astype(jnp.int32),
            fills=(key_fill, 0, 0),
        )
    else:
        part, slot, counts = route_dispatch(
            tables, keys, valid, num_hosts=num_hosts, seed=seed,
            num_lanes=spec.num_lanes, num_partitions=num_partitions,
            use_pallas=path == "two-pass kernel", part_loads=part_loads,
        )
        dest = jnp.where(valid, part, 0)
        buffers = exchange.bucketize(
            dest % spec.num_lanes, valid,
            [Payload(keys, key_fill), Payload(vals, 0), Payload(dest, 0)],
            slot=slot, counts=counts, buffers=buffers,
        )
    return part, buffers


class Exchange:
    """One :class:`ExchangeSpec` bound to one :class:`ExchangeBackend`.

    Calling it runs the full ``bucketize -> all_to_all -> unpack`` sequence;
    ``bucketize`` alone builds the lane-major send buffers (local dispatch),
    and ``backhaul`` runs the reverse collective for request-response
    patterns (MoE combine).  The backend decides *how* buffers move and what
    ``shipped_rows`` the move costs; the call sites are identical across
    backends.
    """

    def __init__(self, spec: ExchangeSpec, backend: str | ExchangeBackend | None = None):
        self.spec = spec
        self.backend = resolve_backend(backend, spec)

    # -- step 2: capacity-padded send-buffer builder -----------------------
    def bucketize(
        self,
        lane: jax.Array,
        valid: jax.Array,
        payloads: Sequence[Payload],
        slot: jax.Array | None = None,
        counts: jax.Array | None = None,
        buffers: tuple | None = None,
    ) -> ExchangeResult:
        """Build the lane-major send buffers.

        ``buffers`` is the double-buffer reuse seam: a recycled
        ``(valid_buf, payload_bufs)`` set from a drained exchange that the
        scatter resets and writes into instead of allocating fresh — values
        bit-identical either way (see ``backends._bucketize``).
        """
        return self.backend.bucketize(
            self.spec, lane, valid, payloads, slot=slot, counts=counts,
            buffers=buffers,
        )

    # -- step 3: the collective (split-phase) ------------------------------
    def start(
        self,
        lane: jax.Array,
        valid: jax.Array,
        payloads: Sequence[Payload],
        slot: jax.Array | None = None,
        counts: jax.Array | None = None,
        buffers: tuple | None = None,
    ) -> PendingExchange:
        """Bucketize + run the transport's control phase; rows stay local.

        Every control-plane output (``send`` accounting, ``shipped_rows``,
        ``lane_counts``, ``recv_counts``) is final on the returned
        :class:`PendingExchange`; :meth:`finish` ships the payload rows.
        ``finish(start(...))`` is bit-identical to calling the exchange.
        ``buffers`` recycles a drained send-buffer set (see
        :meth:`bucketize`).
        """
        return self.start_from(self.bucketize(
            lane, valid, payloads, slot=slot, counts=counts, buffers=buffers))

    def start_from(self, buffers: ExchangeResult) -> PendingExchange:
        """Start the collective from already-bucketized buffers (the fused
        route path hands these in directly)."""
        return PendingExchange(self.backend.a2a_start(self.spec, buffers))

    def finish(self, pending: PendingExchange) -> ExchangeResult:
        """Ship the payload rows of a started exchange."""
        return self.backend.a2a_finish(self.spec, pending.buffers)

    def all_to_all(self, buffers: ExchangeResult) -> ExchangeResult:
        return self.backend.all_to_all(self.spec, buffers)

    def backhaul(
        self, buffers: jax.Array, forward: ExchangeResult | None = None
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Reverse collective for already-laned response buffers.

        ``forward`` is the exchanged result of the request hop; when it
        carries counts (the ragged transport's phase 1) the response ships
        compacted rows with no second count phase — the response occupancy
        *is* the forward ``recv_counts``, and what comes back is the forward
        ``lane_counts``.  Returns ``(rows, shipped_rows, occupied_rows)``:
        the response buffers, the rows this worker's transport measured
        moving, and the rows actually live in the shipped lanes (on the
        dense path shipped is the full pad while occupied tracks the counts
        — the honest utilization for ``Telemetry.record_exchange``).
        """
        send_counts = forward.recv_counts if forward is not None else None
        recv_counts = forward.lane_counts if forward is not None else None
        if send_counts is None and forward is not None:
            # a dense forward hop never ran a count phase, but its exchanged
            # valid mask is the same information: rows live in each received
            # lane — enough for the backhaul to report counted occupancy
            send_counts = jnp.sum(forward.valid, axis=-1).astype(jnp.int32)
        return self.backend.backhaul(
            self.spec, buffers, send_counts=send_counts, recv_counts=recv_counts
        )

    # -- the full primitive ------------------------------------------------
    def __call__(
        self,
        lane: jax.Array,
        valid: jax.Array,
        payloads: Sequence[Payload],
        slot: jax.Array | None = None,
        counts: jax.Array | None = None,
    ) -> ExchangeResult:
        # the fused call IS the two phases run back to back: one
        # implementation, whether a caller holds the finish in flight or not
        return self.finish(self.start(lane, valid, payloads, slot=slot, counts=counts))


def make_exchange(
    spec: ExchangeSpec, backend: str | ExchangeBackend | None = None
) -> Exchange:
    """Build the exchange primitive for one static spec.

    ``backend`` selects the transport — ``"dense"`` / ``"ragged"`` /
    ``"local"``, an :class:`ExchangeBackend` instance, or ``None`` to
    auto-select (local when ``spec.axis is None``, else dense).
    """
    return Exchange(spec, backend)
