"""Per-partition keyed operator state (the stateful-reduce substrate).

State is a fixed-capacity sorted table per worker shard::

    keys   int32[S]    sorted ascending, KEY_SENTINEL padded
    values f32[S, D]   one state row per key

``merge_into`` folds a batch of (key, value) aggregates into the table
(pure jnp, works inside jit / shard_map) with two sorts and a segmented
scan, and no gather or scatter, which the TPU runs slowest:

1. one stable sort of state and batch rows together by key, carrying each
   payload column as an operand of its own;
2. a segmented inclusive scan that restarts at each run of equal keys, so
   a run's total lands on its last row; it runs as elementwise passes
   (doubling shifts within rows of 128, then across the rows' tails);
3. every row but a live run's last becomes ``KEY_SENTINEL`` / 0, and a
   second sort packs the live runs to the front in key order.

Both sorts cover the whole table, so a merge costs what the capacity
costs, however few keys are live.  The reduce op is configurable (``sum``
for counters, ``max``) — ``sum`` is what the paper's Flink experiment uses
("a reducer that simply stores a count for each key as task state").  Each
partial sum stays inside one key's run, so integer-valued f32 counts below
2^24 add exactly in any order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hashing import KEY_SENTINEL

__all__ = ["empty_state", "merge_into", "state_size"]


# reduce -> (combine, identity)
_REDUCE = {"sum": (jnp.add, 0.0), "max": (jnp.maximum, -jnp.inf)}
_ROW = 128  # the scan's row length: one lane row of a TPU vector register


def empty_state(capacity: int, dim: int, dtype=jnp.float32):
    return (
        jnp.full((capacity,), KEY_SENTINEL, jnp.int32),
        jnp.zeros((capacity, dim), dtype),
    )


def _shift(x, k, fill, axis):
    """``x`` moved ``k`` places up ``axis``; ``fill`` enters at the front."""
    lead = [slice(None)] * x.ndim
    lead[axis] = slice(0, x.shape[axis] - k)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (k, 0)
    return jnp.pad(x[tuple(lead)], pad, constant_values=fill)


def _doubling_scan(flags, cols, combine, identity, axis):
    """Segmented inclusive scan along ``axis`` in log2 steps of doubling
    shifts, each one elementwise pass.  After the step of shift ``k`` each
    element holds its run's total over the ``2k`` elements that end at it,
    and ``flags`` whether a run starts among them."""
    k = 1
    while k < flags.shape[axis]:
        cols = tuple(jnp.where(flags, c, combine(_shift(c, k, identity, axis), c)) for c in cols)
        flags = flags | _shift(flags, k, False, axis)
        k *= 2
    return flags, cols


def _segmented_scan(start, cols, combine, identity):
    """Inclusive scan of each column of ``cols`` that restarts wherever
    ``start`` is set.  Two levels: a scan within rows of ``_ROW``, a scan of
    the rows' tails, and each row's carry combined in up to its first
    restart.  No gather, no scatter and no strided slice: the strided
    slices of ``jax.lax.associative_scan`` take the TPU compiler tens of
    minutes at millions of rows."""
    n = start.shape[0]
    pad = -n % _ROW
    flags = jnp.pad(start, (0, pad), constant_values=True).reshape(-1, _ROW)
    cols = tuple(jnp.pad(c, (0, pad)).reshape(-1, _ROW) for c in cols)
    flags, cols = _doubling_scan(flags, cols, combine, identity, axis=1)
    _, tails = _doubling_scan(flags[:, -1], tuple(c[:, -1] for c in cols), combine, identity, axis=0)
    carry = tuple(_shift(t, 1, identity, axis=0)[:, None] for t in tails)
    cols = tuple(jnp.where(flags, c, combine(t, c)) for c, t in zip(cols, carry))
    return tuple(c.reshape(-1)[:n] for c in cols)


def merge_into(state_keys, state_vals, batch_keys, batch_vals, batch_valid, *, reduce: str = "sum"):
    """Fold batch aggregates into the sorted state table.

    Returns ``(keys, vals, overflowed)`` where ``overflowed`` counts distinct
    keys that did not fit in the table (capacity pressure — surfaced, never
    silent).
    """
    if reduce not in _REDUCE:
        raise ValueError(f"unknown reduce {reduce!r}")
    combine, identity = _REDUCE[reduce]
    cap = state_keys.shape[0]
    bk = jnp.where(batch_valid, batch_keys.astype(jnp.int32), KEY_SENTINEL)
    bv = jnp.where(batch_valid[:, None], batch_vals, 0)
    all_keys = jnp.concatenate([state_keys, bk])
    all_vals = jnp.concatenate([state_vals, bv])

    # one stable sort carries each payload column with its key
    sk, *cols = jax.lax.sort((all_keys, *all_vals.T), num_keys=1, is_stable=True)
    change = sk[1:] != sk[:-1]
    start = jnp.concatenate([jnp.ones((1,), bool), change])
    last = jnp.concatenate([change, jnp.ones((1,), bool)])
    totals = _segmented_scan(start, cols, combine, identity)  # a run's total on its last row
    if reduce == "max":
        totals = tuple(jnp.where(jnp.isfinite(t), t, 0) for t in totals)

    # keep each live run's last row; a second sort packs them to the front
    # (live keys are distinct and the rest is sentinel / 0: need not be stable)
    keep = last & (sk != KEY_SENTINEL)
    out_keys = jnp.where(keep, sk, KEY_SENTINEL)
    totals = tuple(jnp.where(keep, t, 0) for t in totals)
    out_keys, *totals = jax.lax.sort((out_keys, *totals), num_keys=1)
    overflow = jnp.maximum(0, jnp.sum(keep) - cap)
    return out_keys[:cap], jnp.stack([t[:cap] for t in totals], axis=1), overflow


def state_size(state_keys) -> jax.Array:
    return jnp.sum(state_keys != KEY_SENTINEL)
