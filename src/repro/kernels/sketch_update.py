"""Pallas TPU kernel: count-min-sketch accumulation (DRW sampling hot path).

Each grid step consumes one ``(8, 128)`` tile of keys and accumulates all
``depth`` sketch rows held in VMEM across the (sequential) TPU grid::

    for d in range(depth):
        col = fmix32(key ^ seed_d) % width
        sketch[d, col] += 1          # as a one-hot matmul, no dynamic scatter

The scatter-free formulation is the TPU-native rewrite of the per-record
hash-map increments a JVM worker would do: per row of 128 records the
``[width, 128]`` one-hot (records on lanes) is reduced over the records by
a matmul with a ones tile, which lands the counts lane-dense as a
``[1, width]`` sketch row.  Operands are 0/1 and each row adds at most 128,
so the f32 accumulation is exact.

VMEM budget (width <= 4096, depth <= 8): one-hot 4096*128*4B = 2 MiB;
matmul result 8*4096*4B = 128 KiB; sketch 8*4096*4B = 128 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.partition_apply import LANES, ROWS, _fmix32, row_spec, tile_records


def _kernel(keys_ref, valid_ref, out_ref, *, depth: int, width: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    col_iota = jax.lax.broadcasted_iota(jnp.int32, (width, LANES), 0)
    ones = jnp.ones((8, LANES), jnp.float32)
    for r in range(ROWS):
        keys = keys_ref[r:r + 1, :].astype(jnp.uint32)
        valid = valid_ref[r:r + 1, :] > 0
        for d in range(depth):
            seed_d = (d * 0x9E3779B9) & 0xFFFFFFFF
            col = (_fmix32(keys ^ jnp.uint32(seed_d)) % jnp.uint32(width)).astype(jnp.int32)
            onehot = ((col_iota == col) & valid).astype(jnp.float32)  # [width, 128]
            row = jax.lax.dot_general(
                ones, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )[0:1]  # [1, width]
            out_ref[d:d + 1, :] += row


@functools.partial(jax.jit, static_argnames=("depth", "width", "interpret"))
def sketch_update(
    keys: jax.Array,  # int32[n]
    valid: jax.Array,  # bool[n]
    *,
    depth: int = 4,
    width: int = 2048,
    interpret: bool = True,
) -> jax.Array:
    """Returns the float32[depth, width] count-min sketch of the batch."""
    keys2d = tile_records(keys.astype(jnp.int32))
    valid2d = tile_records(valid.astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_kernel, depth=depth, width=width),
        grid=(keys2d.shape[0] // ROWS,),
        in_specs=[row_spec(), row_spec()],
        out_specs=pl.BlockSpec((depth, width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((depth, width), jnp.float32),
        interpret=interpret,
        name="sketch_update",  # the op's name in a device trace
    )(keys2d, valid2d)
