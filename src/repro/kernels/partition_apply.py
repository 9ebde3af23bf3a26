"""Pallas TPU kernel: per-record partition lookup (the shuffle hot path).

For every key the partitioner computes::

    host = fmix32(key ^ seed) & (H - 1)
    part = heavy_parts[i]            if key == heavy_keys[i] for some i
         = host_to_part[host]        otherwise

TPU adaptation (vs. the JVM per-record hash-map of the paper): the heavy
table (B keys) and the host routing table (H entries) are pinned in VMEM as
sublane columns ``[B, 1]`` / ``[H, 1]`` for the whole kernel, and lookups
are one-hot selects reduced over sublanes — VPU work with exact int32
arithmetic, no dynamic gathers and no MXU rounding.

Layout shared by every route kernel (this module also hosts the helpers
``lookup_dispatch``, ``route_bucketize`` and ``sketch_update`` build on):
records arrive lane-dense as ``[n / 128, 128]`` int32 and each grid step
takes one ``(8, 128)`` tile — 1024 records, the TPU's native 32-bit tile,
so the block satisfies the ``(8, 128)`` rule.  Inside a step the kernel
walks the tile's 8 rows; every per-record vector is a ``[1, 128]`` row and
every per-table quantity a ``[T, 128]`` one-hot with records on lanes, so no
record ever changes layout.

VMEM budget per grid step (H = 4096, B <= 1024): host table column
4096*128*4B = 2 MiB, double-buffered 4 MiB; host one-hot of one row
4096*128*4B = 2 MiB; heavy columns + one-hot 3 * 1024*128*4B = 1.5 MiB
=> ~8 MiB < the 16 MiB scoped VMEM default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# records are processed in (ROWS, LANES) int32 tiles: one native TPU tile
LANES = 128
ROWS = 8
BLK = ROWS * LANES  # 1024 records per grid step

# heavy-table pad key: only invalid (sentinel) records can match it, and
# every consumer masks their partition
_PAD_KEY = 2**31 - 1


def _fmix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def tile_records(x: jax.Array, fill=0) -> jax.Array:
    """``[n] -> [ceil(n / BLK) * 8, 128]``: pad a record vector to whole
    tiles (with ``fill``) and lay it out lane-dense."""
    n = x.shape[0]
    pad = (-n) % BLK
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x.reshape(-1, LANES)


def table_column(x: jax.Array, fill=0) -> jax.Array:
    """``[T] -> [T', 1]`` with ``T'`` the next multiple of 8 (at least 8):
    a lookup table as a VMEM sublane column, padded with ``fill``."""
    pad = max((-x.shape[0]) % 8, 8 - x.shape[0])
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x[:, None]


def heavy_columns(heavy_keys, heavy_parts, heavy_repl=None):
    """The heavy table as kernel inputs: sentinel-padded sublane columns
    (pad rows carry part 0 and replica count 0)."""
    cols = [table_column(heavy_keys.astype(jnp.int32), _PAD_KEY),
            table_column(heavy_parts.astype(jnp.int32), 0)]
    if heavy_repl is not None:
        cols.append(table_column(heavy_repl.astype(jnp.int32), 0))
    return cols


def column_spec(col: jax.Array) -> pl.BlockSpec:
    """Whole-array block for a table column, resident across the grid."""
    return pl.BlockSpec(col.shape, lambda i: (0, 0))


def row_spec() -> pl.BlockSpec:
    """One ``(8, 128)`` record tile per grid step."""
    return pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))


def lane_iota(r: int):
    """``[1, 128]`` tile-local record index of row ``r``'s records."""
    return r * LANES + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def route_row(keys, hk_ref, hp_ref, host_ref, hr_ref=None, *, seed: int,
              num_hosts: int, num_partitions: int = 0, record_index=None):
    """Partition ids ``[1, 128]`` of one row of keys ``[1, 128]``.

    With ``num_partitions > 0`` (and the replica column ``hr_ref``) a split
    heavy key with ``d`` replicas lands on ``(home + offset) % N`` where
    ``offset = fmix32(i * golden ^ mix) mod d`` hashes the record's
    shard-local index ``record_index``."""
    mixed = _fmix32(keys.astype(jnp.uint32) ^ jnp.uint32((seed * 0x9E3779B9) & 0xFFFFFFFF))
    host = (mixed & jnp.uint32(num_hosts - 1)).astype(jnp.int32)
    table = host_ref[...]  # [H, 1]
    host_iota = jax.lax.broadcasted_iota(jnp.int32, (table.shape[0], LANES), 0)
    part_tail = jnp.sum(jnp.where(host_iota == host, table, 0), axis=0, keepdims=True)

    eq = hk_ref[...] == keys  # [B, 128]: one live match per heavy key
    hit = jnp.max(eq.astype(jnp.int32), axis=0, keepdims=True) > 0
    part_heavy = jnp.sum(jnp.where(eq, hp_ref[...], 0), axis=0, keepdims=True)
    if num_partitions > 0:
        # replicas per record (sentinel records sum pad rows' 0 -> clamp 1)
        d = jnp.maximum(jnp.sum(jnp.where(eq, hr_ref[...], 0), axis=0, keepdims=True), 1)
        h = _fmix32(record_index.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) ^ mixed)
        offset = jax.lax.rem((h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32), d)
        part_heavy = jax.lax.rem(part_heavy + offset, jnp.int32(num_partitions))
    return jnp.where(hit, part_heavy, part_tail)


def _kernel(keys_ref, hk_ref, hp_ref, host_ref, out_ref, *, seed: int, num_hosts: int):
    for r in range(ROWS):
        out_ref[r:r + 1, :] = route_row(
            keys_ref[r:r + 1, :], hk_ref, hp_ref, host_ref,
            seed=seed, num_hosts=num_hosts,
        )


@functools.partial(jax.jit, static_argnames=("seed", "num_hosts", "interpret"))
def partition_apply(
    keys: jax.Array,  # int32[n]
    heavy_keys: jax.Array,  # int32[B] sorted, sentinel padded
    heavy_parts: jax.Array,  # int32[B]
    host_to_part: jax.Array,  # int32[H]
    *,
    seed: int = 0,
    num_hosts: int = 4096,
    interpret: bool = True,
) -> jax.Array:
    n = keys.shape[0]
    assert num_hosts & (num_hosts - 1) == 0, "H must be a power of two"
    keys2d = tile_records(keys.astype(jnp.int32))
    hk, hp = heavy_columns(heavy_keys, heavy_parts)
    host = table_column(host_to_part.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_kernel, seed=seed, num_hosts=num_hosts),
        grid=(keys2d.shape[0] // ROWS,),
        in_specs=[row_spec(), column_spec(hk), column_spec(hp), column_spec(host)],
        out_specs=row_spec(),
        out_shape=jax.ShapeDtypeStruct(keys2d.shape, jnp.int32),
        interpret=interpret,
        name="partition_apply",  # the op's name in a device trace
    )(keys2d, hk, hp, host)
    return out.reshape(-1)[:n]
