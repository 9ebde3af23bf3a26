"""Exchange backends: the *how* of a routed exchange.

An :class:`ExchangeBackend` implements the verbs of the plane —
``bucketize`` / ``a2a_start`` / ``a2a_finish`` / ``backhaul`` / ``cost`` —
against one :class:`~repro.exchange.spec.ExchangeSpec`.  The collective is
split-phase: ``a2a_start`` runs everything the *control plane* needs (for
the ragged transport that is the phase-1 count all-to-all plus the traffic
accounting; for dense it is only the statically-known accounting) and
``a2a_finish`` moves the payload rows.  ``all_to_all`` is defined as the
composition ``a2a_finish(a2a_start(buffers))`` — bit-identical to the
fused call by construction — so drivers may hold the started exchange
in flight and overlap the row ship with unrelated work.  Three transports
ship:

* :class:`DenseBackend` — the capacity-padded all-to-all: every lane is
  padded to ``spec.capacity`` and the collective moves the whole
  ``[L, capacity]`` buffer.  Simple, one device round, and the worst case
  under skew: every consumer ships ``L * capacity`` rows even when the
  observed key distribution leaves most lanes nearly empty.
* :class:`RaggedBackend` — the count-first two-phase exchange: phase 1
  all-to-alls the per-lane *counts* (one int per lane), phase 2 ships
  row-compacted lanes sized by the measured occupancy, so traffic tracks
  real rows instead of padding (Partial Key Grouping's bounded per-worker
  load, AutoFlow's load-adapted routing).  The row phase rides
  :func:`repro.compat.ragged_all_to_all`: on a TPU mesh that is the
  native ragged collective — only the measured rows cross the
  interconnect, so the wall-clock follows the row counts — and elsewhere
  the bit-identical masked-dense form that ships the dense pad with the
  receive buffer masked to the exchanged counts (``shipped_rows`` reports
  the ragged traffic either way).  The same counts make the *return* trip ragged for free: a
  ``backhaul`` handed the forward hop's counts ships compacted response
  rows with no second count phase.
* :class:`LocalBackend` — the ``axis=None`` single-host fast path: pure
  bucketize, no collective, zero shipped rows.
* :class:`HierarchicalBackend` — the topology-aware two-tier exchange:
  a dense all-to-all *within* each host followed by a stride-grouped hop
  *across* hosts (:func:`_two_hop_a2a`), composing to the flat collective's
  permutation bit for bit while every link round stays inside one tier.
  Traffic is accounted per distance class — the intra tier dense-priced,
  the inter tier by measured row counts.

``cost(spec, plan_rows)`` is each backend's sizing rule on a candidate
migration plan — what the control plane's
:func:`repro.core.migration.exchange_lane_cost` evaluates so
``RepartitionPolicy`` prices a repartition by what the *active* transport
would move: the dense rule pads every lane to the peak, the ragged rule
averages real rows over the lanes, a local exchange is free.

All device code is pure jnp and runs inside ``jit`` / ``shard_map``.
Backends are stateless; one instance may serve any number of specs.
"""
from __future__ import annotations

from typing import Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import ragged_all_to_all
from repro.exchange.spec import (
    DISTANCE_CLASSES,
    ExchangeResult,
    ExchangeSpec,
    Payload,
    SendInfo,
)
from repro.kernels import ref as kref

__all__ = [
    "ExchangeBackend",
    "DenseBackend",
    "RaggedBackend",
    "LocalBackend",
    "HierarchicalBackend",
    "resolve_backend",
    "backend_name",
]


@runtime_checkable
class ExchangeBackend(Protocol):
    """The verbs every exchange transport implements.

    ``all_to_all`` must equal ``a2a_finish(a2a_start(buffers))`` bit for
    bit; after ``a2a_start`` every control-plane output (``shipped_rows``,
    ``lane_counts``, ``recv_counts``) is final — ``a2a_finish`` only moves
    payload rows and stamps the received-validity mask.
    """

    name: str

    def bucketize(
        self,
        spec: ExchangeSpec,
        lane: jax.Array,
        valid: jax.Array,
        payloads: Sequence[Payload],
        slot: jax.Array | None = None,
        counts: jax.Array | None = None,
        buffers: tuple | None = None,
    ) -> ExchangeResult: ...

    def a2a_start(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult: ...

    def a2a_finish(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult: ...

    def all_to_all(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult: ...

    def backhaul(
        self,
        spec: ExchangeSpec,
        buffers: jax.Array,
        *,
        send_counts: jax.Array | None = None,
        recv_counts: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array, jax.Array]: ...

    def cost(self, spec: ExchangeSpec | None, plan_rows: np.ndarray,
             slack: float = 1.25) -> float: ...


def _bucketize(
    spec: ExchangeSpec,
    lane: jax.Array,
    valid: jax.Array,
    payloads: Sequence[Payload],
    slot: jax.Array | None = None,
    counts: jax.Array | None = None,
    buffers: tuple | None = None,
) -> ExchangeResult:
    """Scatter records into ``[L, capacity]`` buffers; count overflow.

    Shared by every backend — the send-side layout is transport-independent
    (a backend that wanted a different layout would override).  ``slot`` and
    ``counts`` may be precomputed (the fused route kernel emits both);
    otherwise they are derived with ``dispatch_count``.  With per-lane
    ``counts`` in hand the capacity drops per lane are just the excess over
    capacity — no second O(n) scatter pass.

    ``buffers`` is the reuse seam for the double-buffered pipeline: a
    ``(valid_buf, payload_bufs)`` set from a previous exchange (shapes and
    dtypes must match this call's buffers).  When provided, the scatter
    resets the passed-in set to its fill values and writes into it instead
    of materializing fresh ``zeros``/``full`` buffers — under a jit that
    donates the set, XLA performs both in place, so the steady-state loop
    never reallocates its ``[L, cap]`` send buffers.  The produced values
    are bit-identical to the fresh-allocation path by construction.
    """
    lane = jnp.where(valid, lane, 0).astype(jnp.int32)
    if slot is None:
        slot, counts = kref.dispatch_count_ref(lane, valid, num_parts=spec.num_lanes)
    # a valid record is lost either to a full lane or to a lane outside
    # [0, num_lanes) — both are counted, never silently dropped
    in_range = (lane >= 0) & (lane < spec.num_lanes)
    ok = valid & in_range & (slot >= 0) & (slot < spec.capacity)
    overflow = jnp.sum(valid & (~in_range | (slot >= spec.capacity))).astype(jnp.int32)
    if counts is not None:
        # per-lane capacity drops fall out of the dispatch counts (slots are
        # assigned 0..count-1, so the excess over capacity is exactly what
        # dropped); the buffer occupancy is the clipped count — both O(L)
        lane_overflow = jnp.maximum(counts - spec.capacity, 0).astype(jnp.int32)
        lane_counts = jnp.minimum(counts, spec.capacity).astype(jnp.int32)
    else:
        # per-lane view of the capacity drops: which lane filled up
        # (out-of-range records have no lane to charge — they count in the
        # scalar only)
        lane_overflow = (
            jnp.zeros(spec.num_lanes, jnp.int32)
            .at[lane]
            .add((valid & in_range & (slot >= spec.capacity)).astype(jnp.int32),
                 mode="drop")
        )
        lane_counts = None
    # rows without a slot land at column `capacity` and are dropped by
    # the out-of-range scatter (mode='drop') — counted above, never lost
    # silently.
    s = jnp.where(ok, slot, spec.capacity)
    shape = (spec.num_lanes, spec.capacity)
    if buffers is None:
        buf_valid = jnp.zeros(shape, bool).at[lane, s].set(ok, mode="drop")
        bufs = tuple(
            jnp.full(shape + p.data.shape[1:], p.fill, p.data.dtype)
            .at[lane, s].set(p.data, mode="drop")
            for p in payloads
        )
    else:
        prev_valid, prev_bufs = buffers
        assert prev_valid.shape == shape and len(prev_bufs) == len(payloads), (
            prev_valid.shape, shape, len(prev_bufs), len(payloads))
        # reset-then-scatter on the recycled set: same values as the fresh
        # path, but expressed as in-place updates so a donated set is
        # rewritten rather than reallocated
        buf_valid = prev_valid.at[:].set(False).at[lane, s].set(ok, mode="drop")
        bufs = tuple(
            b.at[:].set(jnp.asarray(p.fill, b.dtype))
            .at[lane, s].set(p.data, mode="drop")
            for b, p in zip(prev_bufs, payloads)
        )
    return ExchangeResult(
        buf_valid, bufs, SendInfo(lane, slot, ok, overflow, lane_overflow),
        shipped_rows=jnp.zeros((), jnp.int32),
        lane_counts=lane_counts,
        fills=tuple(p.fill for p in payloads),
    )


def _a2a(x: jax.Array, axis: str) -> jax.Array:
    """Tiled all-to-all over ``axis``: row j of the leading dim -> shard j."""
    return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)


def _static_axis_size(axis: str) -> int:
    """Mesh axis size as a static int (psum of a unit constant), or -1 when
    it cannot be resolved statically — callers treat -1 as "not usable"."""
    try:
        return int(jax.lax.psum(1, axis))
    except Exception:  # noqa: BLE001 - traced/unbound axis: no static size
        return -1


def _row_bytes(payloads: tuple) -> int:
    """Bytes one exchanged row carries across all payload buffers."""
    return max(1, sum(
        int(np.prod(b.shape[2:], dtype=np.int64)) * b.dtype.itemsize
        for b in payloads
    ))


def _count_phase_rows(spec: ExchangeSpec, payloads: tuple) -> int:
    """The count phase's traffic in row-equivalents: one int32 per lane,
    normalized by the payload row width so narrow-payload exchanges are not
    over-charged (a 4-byte count next to a 256-byte row is ~free; next to a
    4-byte row it is a full row)."""
    return int(np.ceil(4 * spec.num_lanes / _row_bytes(payloads)))


def _me(spec: ExchangeSpec) -> jax.Array:
    """This worker's lane index, clipped into the lane range so degenerate
    test meshes (axis size 1 simulating L lanes) stay in bounds."""
    return jnp.minimum(jax.lax.axis_index(spec.axis), spec.num_lanes - 1)


def _by_class_dense(spec: ExchangeSpec) -> jax.Array:
    """Dense-priced per-class traffic: every lane ships its full capacity,
    so the split is just (lanes of each class from this worker) x capacity.
    The class tables are cached numpy constants on the topology — computed
    once at spec construction, closed over by the jitted step."""
    counts = jnp.asarray(spec.topology.class_lane_counts)[_me(spec)]
    return (counts * spec.capacity).astype(jnp.int32)


def _by_class_counts(spec: ExchangeSpec, counts: jax.Array) -> jax.Array:
    """Count-priced per-class traffic: the measured per-lane occupancy
    reduced over each distance class (one matmul against the cached
    per-worker one-hot class masks)."""
    onehot = jnp.asarray(spec.topology.class_onehot)[_me(spec)]  # [C, L]
    return (onehot @ counts.astype(jnp.int32)).astype(jnp.int32)


def _count_phase_class(spec: ExchangeSpec) -> int:
    """Which distance class the ragged count phase is charged to: the count
    all-to-all crosses the full axis, so its traffic rides the slowest tier
    the topology has (statically known)."""
    if spec.topology.num_hosts > 1:
        return 2
    return 1 if spec.num_lanes > 1 else 0


def _ragged_ship(
    spec: ExchangeSpec,
    arrays_with_fill: Sequence[tuple[jax.Array, int | float]],
    send_sizes: jax.Array,
    recv_sizes: jax.Array,
) -> tuple[jax.Array, ...]:
    """Move lane-major ``[L, capacity, ...]`` buffers as compacted rows
    through :func:`repro.compat.ragged_all_to_all` (native collective on
    TPU meshes, masked dense elsewhere).

    ``bucketize`` packs each lane's rows contiguously from slot 0, so the
    flattened buffer is already in the shim's lane-major regular layout:
    lane ``i``'s rows start at ``i * capacity``, and this worker's rows land
    at ``axis_index * capacity`` on every receiver.  Valid only when lanes
    coincide with the shards on ``spec.axis`` — the shim's offset vectors
    are indexed by axis peer.  ``fill`` initializes the unreceived region of
    each output, matching what the dense collective would have shipped
    there (the sender's pad) bit for bit.
    """
    l, cap = spec.num_lanes, spec.capacity
    me = jax.lax.axis_index(spec.axis)
    in_off = jnp.arange(l, dtype=jnp.int32) * cap
    out_off = jnp.full((l,), me * cap, jnp.int32)
    out = []
    for b, fill in arrays_with_fill:
        flat = b.reshape((l * cap,) + b.shape[2:])
        out.append(ragged_all_to_all(
            flat, jnp.full_like(flat, fill), in_off, send_sizes, out_off,
            recv_sizes, axis_name=spec.axis,
        ).reshape(b.shape))
    return tuple(out)


class DenseBackend:
    """The capacity-padded transport (the pre-backend exchange, verbatim)."""

    name = "dense"

    def bucketize(self, spec, lane, valid, payloads, slot=None, counts=None,
                  buffers=None):
        return _bucketize(spec, lane, valid, payloads, slot=slot, counts=counts,
                          buffers=buffers)

    def a2a_start(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        """No count phase to run — only stamp the (statically known) traffic
        so control-plane reads never have to wait for the row ship."""
        if spec.axis is None:
            return buffers
        by = _by_class_dense(spec) if spec.topology is not None else None
        return buffers._replace(
            shipped_rows=jnp.asarray(spec.rows, jnp.int32),
            shipped_rows_by_class=by,
        )

    def a2a_finish(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        """Exchange lane-major buffers across ``spec.axis`` (row j -> shard j)."""
        if spec.axis is None:
            return buffers
        by = _by_class_dense(spec) if spec.topology is not None else None
        return buffers._replace(
            valid=_a2a(buffers.valid, spec.axis),
            payloads=tuple(_a2a(b, spec.axis) for b in buffers.payloads),
            shipped_rows=jnp.asarray(spec.rows, jnp.int32),  # the whole pad
            shipped_rows_by_class=by,
        )

    def all_to_all(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        return self.a2a_finish(self.a2a_start(spec, buffers))

    def backhaul(self, spec: ExchangeSpec, buffers: jax.Array, *,
                 send_counts: jax.Array | None = None,
                 recv_counts: jax.Array | None = None,
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Reverse collective for already-laned response buffers; ships the
        whole pad back, whatever the counts say — but when counts *are*
        supplied, the measured occupancy is reported alongside so telemetry
        sees honest utilization even on the padded path."""
        if spec.axis is None:
            z = jnp.zeros((), jnp.int32)
            return buffers, z, z
        occupied = (jnp.sum(send_counts).astype(jnp.int32) if send_counts is not None
                    else jnp.asarray(spec.rows, jnp.int32))
        return _a2a(buffers, spec.axis), jnp.asarray(spec.rows, jnp.int32), occupied

    def cost(self, spec: ExchangeSpec | None, plan_rows: np.ndarray,
             slack: float = 1.25) -> float:
        """Every lane provisions (and ships) the peak planned lane mass."""
        plan_rows = np.asarray(plan_rows, np.float64)
        if plan_rows.size == 0:
            return 0.0
        return float(plan_rows.max()) * slack


class RaggedBackend:
    """Count-first two-phase transport: ship counts, then compacted rows."""

    name = "ragged"

    def bucketize(self, spec, lane, valid, payloads, slot=None, counts=None,
                  buffers=None):
        return _bucketize(spec, lane, valid, payloads, slot=slot, counts=counts,
                          buffers=buffers)

    def _ship(self, spec: ExchangeSpec, buffers: ExchangeResult,
              recv_counts: jax.Array) -> ExchangeResult:
        """Phase 2: move the rows through :func:`repro.compat
        .ragged_all_to_all` — native on TPU meshes (only the counted rows
        cross the interconnect), the masked dense collective elsewhere.
        ``bucketize`` packs each lane's rows contiguously from slot 0, so
        the flattened ``[L * capacity]`` buffer is already in the shim's
        lane-major regular layout: send offsets are ``lane * capacity``,
        and this worker's rows land at ``axis_index * capacity`` on every
        receiver.  The received occupancy needs no collective at all — it
        is exactly the phase-1 counts.
        """
        l, cap = spec.num_lanes, spec.capacity
        valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < recv_counts[:, None]
        fills = buffers.fills or (0,) * len(buffers.payloads)
        # the shim's offset vectors are indexed by axis peer, so it applies
        # only when lanes coincide with shards (the production layout);
        # degenerate meshes (tests, axis size 1) ride the bare dense ship —
        # whose pad rows already carry the payload fill, matching the shim's
        # output bit for bit, and `valid` above masks them off either way
        if _static_axis_size(spec.axis) == l:
            payloads = _ragged_ship(
                spec, tuple(zip(buffers.payloads, fills)),
                buffers.lane_counts, recv_counts,
            )
        else:
            payloads = tuple(_a2a(b, spec.axis) for b in buffers.payloads)
        return buffers._replace(
            valid=valid, payloads=payloads, recv_counts=recv_counts,
        )

    def a2a_start(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        """Phase 1: exchange per-lane occupancy (one int32 per lane) so every
        receiver knows how many rows each peer actually sends.  Everything
        the control plane reads — ``shipped_rows``, ``lane_counts``,
        ``recv_counts`` — is final after this phase; the row ship in
        :meth:`a2a_finish` can stay in flight."""
        if spec.axis is None:
            return buffers
        counts = buffers.lane_counts
        if counts is None:  # bucketize had no dispatch counts to reuse
            counts = jnp.sum(buffers.valid, axis=1, dtype=jnp.int32)
        recv_counts = _a2a(counts, spec.axis)
        # measured traffic: the rows this worker's lanes actually hold plus
        # the count phase itself, priced in bytes-normalized row units
        phase_rows = _count_phase_rows(spec, buffers.payloads)
        shipped = (jnp.sum(counts) + phase_rows).astype(jnp.int32)
        by = None
        if spec.topology is not None:
            # the count phase crosses the whole axis: charge it to the
            # slowest tier present so by-class totals still sum to shipped
            by = _by_class_counts(spec, counts).at[_count_phase_class(spec)].add(
                jnp.asarray(phase_rows, jnp.int32))
        return buffers._replace(
            shipped_rows=shipped, lane_counts=counts, recv_counts=recv_counts,
            shipped_rows_by_class=by,
        )

    def a2a_finish(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        """Phase 2: ship the compacted rows sized by the started counts."""
        if spec.axis is None:
            return buffers
        return self._ship(spec, buffers, buffers.recv_counts)

    def all_to_all(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        return self.a2a_finish(self.a2a_start(spec, buffers))

    def backhaul(self, spec: ExchangeSpec, buffers: jax.Array, *,
                 send_counts: jax.Array | None = None,
                 recv_counts: jax.Array | None = None,
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Response rows ride the request lanes back.  With the forward
        hop's counts the return trip is ragged with *no second count phase*:
        this worker's response occupancy per lane is exactly what it
        received (``send_counts`` = the forward ``recv_counts``) and what
        comes back is exactly what it sent (``recv_counts`` = the forward
        ``lane_counts``).  Without counts (a caller that never ran the
        forward hop through this backend) the return trip ships dense.
        Rows beyond a lane's count are unspecified (zeros on the native
        path, the peer's pad on the fallback) — ``take_from`` never reads
        them.
        """
        if spec.axis is None:
            z = jnp.zeros((), jnp.int32)
            return buffers, z, z
        if send_counts is None or recv_counts is None:
            pad = jnp.asarray(spec.rows, jnp.int32)
            return _a2a(buffers, spec.axis), pad, pad
        shipped = jnp.sum(send_counts).astype(jnp.int32)
        if _static_axis_size(spec.axis) == spec.num_lanes:
            rows, = _ragged_ship(spec, ((buffers, 0),), send_counts, recv_counts)
        else:
            rows = _a2a(buffers, spec.axis)
        return rows, shipped, shipped

    def cost(self, spec: ExchangeSpec | None, plan_rows: np.ndarray,
             slack: float = 1.25) -> float:
        """A ragged transport moves real rows: the per-lane *average* planned
        mass (empty lanes are free), never more than the dense peak."""
        plan_rows = np.asarray(plan_rows, np.float64)
        if plan_rows.size == 0:
            return 0.0
        return float(plan_rows.sum()) / plan_rows.size * slack


def _two_hop_a2a(x: jax.Array, axis: str, num_hosts: int,
                 lanes_per_host: int) -> jax.Array:
    """The hierarchical all-to-all: intra-host hop, then inter-host hop.

    ``x`` is a lane-major ``[L, capacity, ...]`` send buffer over
    ``L = num_hosts * lanes_per_host`` lanes, lane ``j`` on host
    ``j // lanes_per_host`` at rank ``j % lanes_per_host``.  Hop 1 exchanges
    within each host over the *rank*-destination dimension, so afterwards
    worker ``(h, r)`` holds every row its host sends to rank ``r`` of any
    host; hop 2 exchanges across hosts (stride-``lanes_per_host`` groups)
    over the *host*-destination dimension, completing the permutation.  The
    composition lands row ``B_src[dst]`` at worker ``dst`` position ``src``
    — exactly the flat tiled all-to-all's layout, bit for bit — while each
    link round stays inside one tier of the mesh.  Applying it twice is the
    identity (each tiled hop is an involution and the transposes cancel),
    so the backhaul rides the same function.
    """
    h, g = num_hosts, lanes_per_host
    tail = x.shape[1:]
    intra = [[hh * g + r for r in range(g)] for hh in range(h)]
    inter = [[hh * g + r for hh in range(h)] for r in range(g)]
    perm = (1, 0) + tuple(range(2, x.ndim + 1))
    t = x.reshape((h, g) + tail).transpose(perm).reshape((g * h,) + tail)
    t = jax.lax.all_to_all(t, axis, 0, 0, tiled=True, axis_index_groups=intra)
    t = t.reshape((g, h) + tail).transpose(perm).reshape((h * g,) + tail)
    return jax.lax.all_to_all(t, axis, 0, 0, tiled=True, axis_index_groups=inter)


class HierarchicalBackend:
    """Two-tier transport: dense intra-host hop, count-priced inter-host hop.

    Composes the existing collectives as a two-level exchange over the
    spec's :class:`~repro.exchange.spec.ExchangeTopology`: hop 1 is a dense
    all-to-all *within* each host (cheap tier — padding is fine there),
    hop 2 crosses hosts in stride groups (slow tier).  The composed
    permutation is bit-identical to the flat all-to-all (see
    :func:`_two_hop_a2a`), so unpacked rows and overflow accounting match
    the flat backends exactly; only the *measured traffic* differs —
    ``shipped_rows_by_class`` prices the intra tier dense (the hop-1 pad)
    and the inter tier by real row counts, the same semantic-traffic
    convention the masked-dense ragged transport uses.

    Without a usable topology (no topology on the spec, lanes not divisible
    by ``lanes_per_host``, a single host, or a mesh whose axis size differs
    from the lane count) the collective falls back to the flat dense
    all-to-all — still bit-identical, just untiered.
    """

    name = "hierarchical"

    def bucketize(self, spec, lane, valid, payloads, slot=None, counts=None,
                  buffers=None):
        return _bucketize(spec, lane, valid, payloads, slot=slot, counts=counts,
                          buffers=buffers)

    def _plan(self, spec: ExchangeSpec) -> tuple[int, int] | None:
        """``(num_hosts, lanes_per_host)`` when the two-hop collective
        applies, else ``None`` — the flat dense collective."""
        topo, l = spec.topology, spec.num_lanes
        if topo is None:
            return None
        g = min(topo.lanes_per_host, l)
        if g <= 1 or g >= l or l % g:
            return None
        if _static_axis_size(spec.axis) != l:
            return None
        return l // g, g

    def _ship(self, spec: ExchangeSpec, x: jax.Array) -> jax.Array:
        plan = self._plan(spec)
        if plan is None:
            return _a2a(x, spec.axis)
        return _two_hop_a2a(x, spec.axis, *plan)

    def a2a_start(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        """Like dense, no count phase blocks the control plane — the traffic
        accounting is local: the intra tier ships its (statically known)
        pad, the inter tier only the measured per-lane occupancy."""
        if spec.axis is None:
            return buffers
        by = None
        if spec.topology is not None:
            counts = buffers.lane_counts
            if counts is None:
                counts = jnp.sum(buffers.valid, axis=1, dtype=jnp.int32)
            inter = _by_class_counts(spec, counts)[2]
            cap = spec.capacity
            by = jnp.stack([
                jnp.asarray(cap, jnp.int32),
                jnp.asarray((spec.num_lanes - 1) * cap, jnp.int32),
                inter,
            ])
            shipped = jnp.sum(by).astype(jnp.int32)
        else:
            shipped = jnp.asarray(spec.rows, jnp.int32)
        return buffers._replace(shipped_rows=shipped, shipped_rows_by_class=by)

    def a2a_finish(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        """Move the rows through the two-hop permutation (validity mask
        included — it is what the flat dense collective would have
        exchanged, hop-composed instead)."""
        if spec.axis is None:
            return buffers
        return buffers._replace(
            valid=self._ship(spec, buffers.valid),
            payloads=tuple(self._ship(spec, b) for b in buffers.payloads),
        )

    def all_to_all(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        return self.a2a_finish(self.a2a_start(spec, buffers))

    def backhaul(self, spec: ExchangeSpec, buffers: jax.Array, *,
                 send_counts: jax.Array | None = None,
                 recv_counts: jax.Array | None = None,
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Responses ride the two-hop permutation backward — which is the
        same permutation (it is an involution), so the forward function
        ships the return trip.  Accounting mirrors the forward hop: dense
        intra pad plus counted inter rows when counts are known."""
        if spec.axis is None:
            z = jnp.zeros((), jnp.int32)
            return buffers, z, z
        pad = jnp.asarray(spec.rows, jnp.int32)
        if spec.topology is not None and send_counts is not None:
            # hop-1 pad (the whole buffer crosses the fast tier) + the real
            # rows that cross hosts — same convention as the forward hop
            inter = _by_class_counts(spec, send_counts)[2]
            shipped = (pad + inter).astype(jnp.int32)
            occupied = jnp.sum(send_counts).astype(jnp.int32)
        else:
            shipped, occupied = pad, (jnp.sum(send_counts).astype(jnp.int32)
                                      if send_counts is not None else pad)
        return self._ship(spec, buffers), shipped, occupied

    def cost(self, spec: ExchangeSpec | None, plan_rows: np.ndarray,
             slack: float = 1.25) -> float:
        """Sizing rule: the intra tier still pads every lane to the peak
        (dense rule) — the locality discount comes from
        :func:`repro.core.migration.exchange_lane_cost` weighting the plan
        by distance class before this rule prices it."""
        plan_rows = np.asarray(plan_rows, np.float64)
        if plan_rows.size == 0:
            return 0.0
        return float(plan_rows.max()) * slack


class LocalBackend:
    """``axis=None`` fast path: bucketize only, no collective, nothing ships."""

    name = "local"

    def bucketize(self, spec, lane, valid, payloads, slot=None, counts=None,
                  buffers=None):
        return _bucketize(spec, lane, valid, payloads, slot=slot, counts=counts,
                          buffers=buffers)

    def a2a_start(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        assert spec.axis is None, (
            f"LocalBackend cannot cross mesh axis {spec.axis!r}; "
            "use the dense or ragged backend"
        )
        return buffers

    def a2a_finish(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        assert spec.axis is None, spec.axis
        return buffers

    def all_to_all(self, spec: ExchangeSpec, buffers: ExchangeResult) -> ExchangeResult:
        return self.a2a_finish(self.a2a_start(spec, buffers))

    def backhaul(self, spec: ExchangeSpec, buffers: jax.Array, *,
                 send_counts: jax.Array | None = None,
                 recv_counts: jax.Array | None = None,
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
        assert spec.axis is None, spec.axis
        z = jnp.zeros((), jnp.int32)
        return buffers, z, z

    def cost(self, spec: ExchangeSpec | None, plan_rows: np.ndarray,
             slack: float = 1.25) -> float:
        return 0.0


_BACKENDS = {
    "dense": DenseBackend,
    "ragged": RaggedBackend,
    "local": LocalBackend,
    "hierarchical": HierarchicalBackend,
}


def resolve_backend(
    backend: str | ExchangeBackend | None, spec: ExchangeSpec | None = None
) -> ExchangeBackend:
    """Turn a backend name (or instance, or ``None``) into an instance.

    ``None`` auto-selects: the local fast path when the spec has no mesh
    axis, otherwise dense — the pre-backend behavior, bit-identical.
    """
    if backend is None:
        return LocalBackend() if spec is not None and spec.axis is None else DenseBackend()
    if isinstance(backend, str):
        try:
            return _BACKENDS[backend]()
        except KeyError:
            raise ValueError(
                f"unknown exchange backend {backend!r}; have {sorted(_BACKENDS)}"
            ) from None
    return backend


def backend_name(backend: str | ExchangeBackend | None) -> str:
    """Stable display/cache name for a backend selection (``None`` = auto)."""
    if backend is None:
        return "auto"
    if isinstance(backend, str):
        return backend
    return getattr(backend, "name", type(backend).__name__)
