"""jit'd public wrappers around the Pallas kernels.

The kernels pad records to whole tiles themselves; the wrappers pick the
execution mode (compiled on TPU, interpreted elsewhere) and expose
numpy-friendly signatures used by the shuffle/runtime layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch_count import dispatch_count
from repro.kernels.lookup_dispatch import lookup_dispatch
from repro.kernels.partition_apply import partition_apply
from repro.kernels.route_bucketize import route_bucketize as _route_bucketize_kernel
from repro.kernels.sketch_update import sketch_update


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def apply_partitioner(keys: jax.Array, tables, *, num_hosts: int, seed: int = 0) -> jax.Array:
    """Partition ids for ``keys`` using PartitionerTables (Pallas hot path)."""
    return partition_apply(
        keys, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
        seed=seed, num_hosts=num_hosts, interpret=_interpret(),
    )


def count_sketch(keys: jax.Array, valid: jax.Array | None = None, *, depth: int = 4, width: int = 2048) -> jax.Array:
    """float32[depth, width] CMS of the batch (Pallas hot path)."""
    if valid is None:
        valid = jnp.ones(keys.shape[0], bool)
    return sketch_update(keys, valid, depth=depth, width=width, interpret=_interpret())


def route_slots(keys: jax.Array, valid: jax.Array, tables, *, num_hosts: int,
                seed: int = 0, num_lanes: int, num_partitions: int = 0):
    """Fused partition lookup + lane slot (the exchange-plane hot path).

    Returns ``(part[n], slot[n], counts[num_lanes])`` — the slot ranks each
    valid record within its ``part % num_lanes`` lane.  ``num_partitions >
    0`` activates the split-key replica pick from ``tables.heavy_repl``.

    The kernel's replica pick is the stateless fmix32 offset; the jnp twin
    additionally supports the load-aware two-choice pick (``part_loads`` in
    ``kernels.ref``) — drivers that enable it must gate the Pallas path off
    statically (``use_pallas=False`` in the exchange plane), never per
    batch, so kernel and twin cannot diverge at runtime.
    """
    return lookup_dispatch(
        keys, valid, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
        tables.heavy_repl if num_partitions > 0 else None,
        seed=seed, num_hosts=num_hosts, num_lanes=num_lanes,
        num_partitions=num_partitions, interpret=_interpret(),
    )


def route_bucketize(keys: jax.Array, valid: jax.Array, tables, vals: jax.Array, *,
                    num_hosts: int, seed: int = 0, num_lanes: int, capacity: int,
                    key_fill: int, num_partitions: int = 0,
                    interpret: bool | None = None):
    """Fused route + slot + bucketize (the split-phase exchange's start path).

    Returns ``(part[n], slot[n], counts[L], buf_valid[L, cap] bool,
    buf_keys[L, cap] int32, buf_vals[L, cap, D] f32, buf_part[L, cap]
    int32)`` — the shuffle's three send buffers built in one kernel pass,
    bit-identical to ``route_slots`` + the plane's scatter.  The kernel
    emits raw f32 channels (int32 split into 16-bit halves for f32-matmul
    exactness) at a lane-tile-aligned width; this wrapper recombines them,
    slices ``capacity`` columns (overflow slots the ref drops land in the
    pad) and applies the fills.
    """
    if interpret is None:
        interpret = _interpret()
    part, slot, counts, bvalid, bkhi, bklo, bphi, bplo, bvals = _route_bucketize_kernel(
        keys, valid, vals, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
        tables.heavy_repl if num_partitions > 0 else None,
        seed=seed, num_hosts=num_hosts, num_lanes=num_lanes, capacity=capacity,
        num_partitions=num_partitions, interpret=interpret,
    )
    buf_valid = bvalid[:, :capacity] > 0.0

    def _combine(hi, lo):
        u = (hi[:, :capacity].astype(jnp.uint32) << jnp.uint32(16)) | \
            lo[:, :capacity].astype(jnp.uint32)
        return u.astype(jnp.int32)

    buf_keys = jnp.where(buf_valid, _combine(bkhi, bklo), key_fill)
    buf_part = jnp.where(buf_valid, _combine(bphi, bplo), 0)
    buf_vals = jnp.where(buf_valid[:, :, None],
                         jnp.moveaxis(bvals, 0, -1)[:, :capacity], 0.0)
    return part, slot, counts, buf_valid, buf_keys, buf_vals, buf_part


def dispatch_slots(dest: jax.Array, valid: jax.Array | None = None, *, num_parts: int):
    """(slot[n], counts[num_parts]) for building the all-to-all send buffer."""
    if valid is None:
        valid = jnp.ones(dest.shape[0], bool)
    return dispatch_count(dest, valid, num_parts=num_parts, interpret=_interpret())
