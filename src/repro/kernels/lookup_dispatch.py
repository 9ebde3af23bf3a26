"""Pallas TPU kernel: fused partition lookup + send-slot assignment.

The exchange plane's hot path runs two stages back to back on the same
records: ``partition_apply`` (key -> partition) and ``dispatch_count``
(destination -> stable send slot).  Fusing them keeps each row of records
in VMEM between the two stages — the ``[L, 128]`` lane one-hot for the slot
ranking is built directly from the partition ids the lookup just produced,
so the records make one trip through VMEM instead of two round trips to
HBM.

Per record ``i`` with key ``k``::

    part[i] = heavy_parts[j]        if k == heavy_keys[j] for some j
            = host_to_part[fmix32(k ^ seed) & (H - 1)]   otherwise
    lane[i] = part[i] % num_lanes
    slot[i] = #{ j < i : lane[j] == lane[i], valid[j] }  (stable rank)
    counts[l] = total valid records on lane l

Both stages are the shared row helpers (``partition_apply.route_row``,
``dispatch_count.rank_row``) over the ``(8, 128)`` record tile.

VMEM budget per grid step (H = 4096, B <= 1024, L <= 1024): the route
stage's ~8 MiB (see ``partition_apply``) plus the rank stage's ~1.5 MiB
=> ~9.5 MiB < the 16 MiB scoped VMEM default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch_count import padded_parts, rank_row
from repro.kernels.partition_apply import (
    BLK,
    ROWS,
    column_spec,
    heavy_columns,
    lane_iota,
    route_row,
    row_spec,
    table_column,
    tile_records,
)


def _kernel(keys_ref, valid_ref, hk_ref, hp_ref, host_ref, *rest, seed: int,
            num_hosts: int, num_lanes: int, num_partitions: int = 0):
    # with splitting active (num_partitions > 0) the heavy-replica column
    # rides along as a sixth input, ahead of the output refs
    hr_ref = rest[0] if num_partitions > 0 else None
    part_ref, slot_ref, counts_ref = rest[-3:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    running = counts_ref[...]
    for r in range(ROWS):
        part = route_row(
            keys_ref[r:r + 1, :], hk_ref, hp_ref, host_ref, hr_ref,
            seed=seed, num_hosts=num_hosts, num_partitions=num_partitions,
            record_index=pl.program_id(0) * BLK + lane_iota(r),
        )
        part_ref[r:r + 1, :] = part
        slot, _, running = rank_row(
            jax.lax.rem(part, jnp.int32(num_lanes)), valid_ref[r:r + 1, :] > 0,
            running, num_lanes)
        slot_ref[r:r + 1, :] = slot
    counts_ref[...] = running


@functools.partial(
    jax.jit,
    static_argnames=("seed", "num_hosts", "num_lanes", "num_partitions", "interpret"),
)
def lookup_dispatch(
    keys: jax.Array,  # int32[n]
    valid: jax.Array,  # bool[n]
    heavy_keys: jax.Array,  # int32[B] sorted, sentinel padded
    heavy_parts: jax.Array,  # int32[B]
    host_to_part: jax.Array,  # int32[H], H a power of two
    heavy_repl: jax.Array | None = None,  # int32[B] replicas (pad rows: 0)
    *,
    seed: int = 0,
    num_hosts: int = 4096,
    num_lanes: int,
    num_partitions: int = 0,
    interpret: bool = True,
):
    """Returns (part int32[n], slot int32[n] — rank within ``part % num_lanes``,
    -1 for invalid; counts int32[num_lanes]).

    ``num_partitions > 0`` switches on hot-key splitting: a heavy key with
    ``heavy_repl[b] = d > 1`` fans its records over the d consecutive
    partitions starting at ``heavy_parts[b]`` by a per-record hash.  With
    ``num_partitions == 0`` (the default) the traced program is exactly the
    pre-split one."""
    n = keys.shape[0]
    assert num_hosts & (num_hosts - 1) == 0, "H must be a power of two"
    keys2d = tile_records(keys.astype(jnp.int32))
    valid2d = tile_records(valid.astype(jnp.int32))
    if num_partitions > 0:
        assert heavy_repl is not None, "splitting needs the replica table"
    tables = heavy_columns(heavy_keys, heavy_parts,
                           heavy_repl if num_partitions > 0 else None)
    tables.insert(2, table_column(host_to_part.astype(jnp.int32)))
    lp = padded_parts(num_lanes)

    part, slot, counts = pl.pallas_call(
        functools.partial(_kernel, seed=seed, num_hosts=num_hosts,
                          num_lanes=num_lanes, num_partitions=num_partitions),
        grid=(keys2d.shape[0] // ROWS,),
        in_specs=[row_spec(), row_spec()] + [column_spec(t) for t in tables],
        out_specs=[row_spec(), row_spec(), pl.BlockSpec((lp, 1), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
            jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
            jax.ShapeDtypeStruct((lp, 1), jnp.int32),
        ],
        interpret=interpret,
        name="lookup_dispatch",  # the op's name in a device trace
    )(keys2d, valid2d, *tables)
    return part.reshape(-1)[:n], slot.reshape(-1)[:n], counts[:num_lanes, 0]
