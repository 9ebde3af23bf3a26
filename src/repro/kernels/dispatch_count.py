"""Pallas TPU kernel: shuffle bucketing — per-record send slots + counts.

Given each record's destination partition, the capacity-padded all-to-all
buffer needs, for record ``i`` with destination ``d``::

    slot[i] = #{ j < i : dest[j] == d }      (stable rank within destination)
    counts[d] = total records destined to d

The rank is computed row-wise with the classic TPU MoE-dispatch trick: an
exclusive prefix sum over the one-hot destination matrix expressed as a
triangular matmul (MXU) instead of a sequential scan.  Records sit on lanes
(``[1, 128]`` rows of the ``(8, 128)`` record tile, see
``partition_apply``), the one-hot is ``[Np, 128]`` with destinations on
sublanes, and the running per-destination counts ride across rows and
across the sequential grid in an int32 ``[Np, 1]`` column.  The matmul
operands are 0/1 and its sums stay below 128, so it is exact at any MXU
precision; every count is int32.

VMEM budget per grid step (N <= 1024): one-hot 1024*128*4B = 0.5 MiB;
triangle 128^2*4B = 64 KiB; prefix 0.5 MiB; counts column 0.5 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.partition_apply import LANES, ROWS, row_spec, tile_records


def padded_parts(num_parts: int) -> int:
    """Destinations rounded up to whole sublane groups (the one-hot height)."""
    return max(8, -(-num_parts // 8) * 8)


def rank_row(dest, valid, running, num_parts: int):
    """Stable slots of one row of records.

    ``dest`` / ``valid`` are ``[1, 128]`` (int32 / bool), ``running`` the
    ``[Np, 1]`` int32 count of earlier records per destination.  Returns
    ``(slot [1, 128] int32 — -1 for invalid, onehot [Np, 128] bool,
    running')``.  A valid record whose destination lies outside
    ``[0, num_parts)`` matches no one-hot row and gets slot 0, as in
    ``ref.dispatch_count_ref``."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (running.shape[0], LANES), 0)
    onehot = (iota == dest) & (iota < num_parts) & valid
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (r < c).astype(jnp.float32)  # upper[j, i] = 1 iff j < i
    prefix = jax.lax.dot_general(
        onehot.astype(jnp.float32), upper, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Np, 128]: earlier same-destination records in this row
    rank = jnp.sum(jnp.where(onehot, prefix, 0.0), axis=0, keepdims=True).astype(jnp.int32)
    base = jnp.sum(jnp.where(onehot, running, 0), axis=0, keepdims=True)
    slot = jnp.where(valid, base + rank, -1)
    running = running + jnp.sum(onehot.astype(jnp.int32), axis=1, keepdims=True)
    return slot, onehot, running


def _kernel(dest_ref, valid_ref, slot_ref, counts_ref, *, num_parts: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    running = counts_ref[...]
    for r in range(ROWS):
        slot, _, running = rank_row(dest_ref[r:r + 1, :], valid_ref[r:r + 1, :] > 0,
                                    running, num_parts)
        slot_ref[r:r + 1, :] = slot
    counts_ref[...] = running


@functools.partial(jax.jit, static_argnames=("num_parts", "interpret"))
def dispatch_count(
    dest: jax.Array,  # int32[n] destination partition per record
    valid: jax.Array,  # bool[n]
    *,
    num_parts: int,
    interpret: bool = True,
):
    """Returns (slot int32[n]  — rank within destination, -1 for invalid;
                counts int32[num_parts])."""
    n = dest.shape[0]
    dest2d = tile_records(dest.astype(jnp.int32))
    valid2d = tile_records(valid.astype(jnp.int32))
    np_ = padded_parts(num_parts)
    slot, counts = pl.pallas_call(
        functools.partial(_kernel, num_parts=num_parts),
        grid=(dest2d.shape[0] // ROWS,),
        in_specs=[row_spec(), row_spec()],
        out_specs=[row_spec(), pl.BlockSpec((np_, 1), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(dest2d.shape, jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        ],
        interpret=interpret,
        name="dispatch_count",  # the op's name in a device trace
    )(dest2d, valid2d)
    return slot.reshape(-1)[:n], counts[:num_parts, 0]
