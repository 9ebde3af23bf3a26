"""Pallas TPU kernel: fused partition lookup + slot assignment + bucketize.

``lookup_dispatch`` fused the route (key -> partition) with the slot rank
(destination -> stable send slot) but still returned per-record vectors
that a jnp scatter re-read from HBM to build the ``[L, capacity]`` send
buffers.  This kernel extends the chain through the scatter: the
key -> partition -> lane -> slot -> send-buffer path never leaves VMEM, so
the records make one trip instead of a materialize + re-read of the whole
batch between the route kernel and ``_bucketize``.

The scatter itself is a matmul (MXU, no serial stores): for one row of 128
records with valid-masked lane one-hot ``O_lane [L, 128]`` and slot one-hot
``O_slot [cap, 128]`` (records on lanes in both), each scalar channel ``w``
lands as::

    buffer[l, c] += sum_r  O_lane[l, r] * w[r] * O_slot[c, r]
                 =  ((O_lane * w[None, :]) @ O_slot.T)[l, c]

Slot ranks are globally unique within a lane (``dispatch_count``'s
invariant), so every ``(l, c)`` entry receives at most one nonzero term
across the whole grid — the f32 accumulation is exact, and rows whose slot
falls outside ``[0, cap)`` (capacity overflow, invalid records) match no
one-hot row and drop out, exactly like the jnp scatter's ``mode="drop"``.

int32 channels (keys, partition ids) cannot ride f32 matmuls directly
(f32 is exact only to 2**24), so they are split into 16-bit halves
(``x >> 16`` / ``x & 0xFFFF``, each < 65536, exact in f32) and recombined
outside the kernel.  Payload values are f32 and ride as-is: the product
``w * 1.0`` and the single-term sum are exact.  The matmuls run at
``Precision.HIGHEST`` so the MXU keeps every channel at full f32.

The ``[L, cap]`` send buffers stay resident in VMEM for the whole grid, so
the kernel only fits small exchanges: :func:`fits` is the static size rule
the exchange plane checks before choosing it (larger exchanges run
``lookup_dispatch`` + the plane's scatter).

VMEM budget per grid step (H = 4096, B <= 1024, L <= MAX_LANES = 16,
capP <= MAX_CAPACITY = 2048, D <= MAX_PAYLOAD = 8): route + rank stages
~9.5 MiB (as ``lookup_dispatch``); slot one-hot 2048*128*4B = 1 MiB;
(5 + D) resident f32 buffers, double-buffered, 2 * 13 * 16*2048*4B =
3.3 MiB => ~14 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch_count import padded_parts, rank_row
from repro.kernels.partition_apply import (
    BLK,
    LANES,
    ROWS,
    column_spec,
    heavy_columns,
    lane_iota,
    route_row,
    row_spec,
    table_column,
    tile_records,
)

# the static size rule (see module doc): the largest exchange whose send
# buffers the kernel keeps resident in VMEM
MAX_LANES = 16
MAX_CAPACITY = 2048
MAX_PAYLOAD = 8


def padded_capacity(capacity: int) -> int:
    """Buffer width the kernel scatters into: lane-tile aligned."""
    return int(-(-capacity // LANES) * LANES)


def fits(num_lanes: int, capacity: int, payload_dim: int) -> bool:
    """True when one fused pass can hold the ``[L, capacity]`` send buffers."""
    return (num_lanes <= MAX_LANES and padded_capacity(capacity) <= MAX_CAPACITY
            and payload_dim <= MAX_PAYLOAD)


def _halves(x):
    """int32 ``[1, 128]`` -> its high and low 16 bits as exact f32 rows."""
    return (jax.lax.shift_right_logical(x, 16).astype(jnp.float32),
            (x & 0xFFFF).astype(jnp.float32))


def _kernel(
    keys_ref, valid_ref, vals_ref, hk_ref, hp_ref, host_ref, *rest,
    seed: int, num_hosts: int, num_lanes: int, capacity: int,
    num_partitions: int = 0,
):
    # with splitting active (num_partitions > 0) the heavy-replica column
    # rides along as a seventh input, ahead of the output refs
    hr_ref = rest[0] if num_partitions > 0 else None
    (part_ref, slot_ref, counts_ref,
     bvalid_ref, bkhi_ref, bklo_ref, bphi_ref, bplo_ref, bvals_ref) = rest[-9:]
    bufs = (bvalid_ref, bkhi_ref, bklo_ref, bphi_ref, bplo_ref)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        for b in bufs + (bvals_ref,):
            b[...] = jnp.zeros_like(b)

    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (capacity, LANES), 0)
    running = counts_ref[...]
    for r in range(ROWS):
        keys = keys_ref[r:r + 1, :]
        valid = valid_ref[r:r + 1, :] > 0
        # ---- stage 1 + 2: route and lane rank (shared row helpers) ----
        part = route_row(
            keys, hk_ref, hp_ref, host_ref, hr_ref,
            seed=seed, num_hosts=num_hosts, num_partitions=num_partitions,
            record_index=pl.program_id(0) * BLK + lane_iota(r),
        )
        part_ref[r:r + 1, :] = part
        slot, onehot, running = rank_row(
            jax.lax.rem(part, jnp.int32(num_lanes)), valid, running, num_lanes)
        slot_ref[r:r + 1, :] = slot

        # ---- stage 3: scatter into the send buffers (matmul, in VMEM) ----
        onehot_slot = (slot_iota == slot).astype(jnp.float32)  # [cap, 128]

        def scat(w):  # [1, 128] channel -> [Lp, cap] contribution of this row
            return jax.lax.dot_general(
                jnp.where(onehot, w, 0.0), onehot_slot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )

        channels = (jnp.ones((1, LANES), jnp.float32), *_halves(keys), *_halves(part))
        for b, w in zip(bufs, channels):
            b[...] += scat(w)
        for d in range(vals_ref.shape[0]):
            bvals_ref[d] += scat(vals_ref[d, r:r + 1, :])
    counts_ref[...] = running


@functools.partial(jax.jit, static_argnames=(
    "seed", "num_hosts", "num_lanes", "capacity", "num_partitions", "interpret"))
def route_bucketize(
    keys: jax.Array,  # int32[n]
    valid: jax.Array,  # bool[n]
    vals: jax.Array,  # f32[n, D]
    heavy_keys: jax.Array,  # int32[B] sorted, sentinel padded
    heavy_parts: jax.Array,  # int32[B]
    host_to_part: jax.Array,  # int32[H], H a power of two
    heavy_repl: jax.Array | None = None,  # int32[B] replicas (pad rows: 0)
    *,
    seed: int = 0,
    num_hosts: int = 4096,
    num_lanes: int,
    capacity: int,
    num_partitions: int = 0,
    interpret: bool = True,
):
    """Returns ``(part[n], slot[n], counts[L], bvalid[L, capP],
    bkhi/bklo/bphi/bplo [L, capP], bvals[D, L, capP])`` — raw f32 channel
    buffers at the lane-tile-aligned width ``capP``;
    ``repro.kernels.ops.route_bucketize`` recombines the 16-bit halves,
    slices ``capacity`` columns and applies fills.  ``num_partitions > 0``
    enables the split-key replica pick (see ``lookup_dispatch``); 0 traces
    the pre-split program."""
    n, d = vals.shape
    assert num_hosts & (num_hosts - 1) == 0, "H must be a power of two"
    assert fits(num_lanes, capacity, d), (num_lanes, capacity, d)
    cap_p = padded_capacity(capacity)
    keys2d = tile_records(keys.astype(jnp.int32))
    valid2d = tile_records(valid.astype(jnp.int32))
    # payload channels lane-dense like the keys: [D, n / 128, 128]
    vals3d = jnp.stack([tile_records(vals[:, i].astype(jnp.float32)) for i in range(d)])
    if num_partitions > 0:
        assert heavy_repl is not None, "splitting needs the replica table"
    tables = heavy_columns(heavy_keys, heavy_parts,
                           heavy_repl if num_partitions > 0 else None)
    tables.insert(2, table_column(host_to_part.astype(jnp.int32)))
    lp = padded_parts(num_lanes)
    buf = jax.ShapeDtypeStruct((lp, cap_p), jnp.float32)
    buf_spec = pl.BlockSpec((lp, cap_p), lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, seed=seed, num_hosts=num_hosts,
                          num_lanes=num_lanes, capacity=cap_p,
                          num_partitions=num_partitions),
        grid=(keys2d.shape[0] // ROWS,),
        in_specs=[row_spec(), row_spec(),
                  pl.BlockSpec((d, ROWS, LANES), lambda i: (0, i, 0))]
                 + [column_spec(t) for t in tables],
        out_specs=[row_spec(), row_spec(), pl.BlockSpec((lp, 1), lambda i: (0, 0))]
                  + [buf_spec] * 5
                  + [pl.BlockSpec((d, lp, cap_p), lambda i: (0, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
            jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
            jax.ShapeDtypeStruct((lp, 1), jnp.int32),
        ] + [buf] * 5 + [jax.ShapeDtypeStruct((d, lp, cap_p), jnp.float32)],
        interpret=interpret,
        name="route_bucketize",  # the op's name in a device trace
    )(keys2d, valid2d, vals3d, *tables)
    part, slot, counts, *bufs = out
    bufs = [b[:num_lanes] for b in bufs[:5]] + [bufs[5][:, :num_lanes]]
    return (part.reshape(-1)[:n], slot.reshape(-1)[:n], counts[:num_lanes, 0], *bufs)
