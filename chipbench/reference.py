"""The plain reference and the comparison that decides ``correct``.

The deployment states exactly-once, exact per-key counts: after a run, the
job's keyed state must hold every id that received events, once, on one
worker, with a count equal to the number of events fed for it since the job
was built, and nothing else.  The reference is ``np.bincount`` over the
population indices of every event fed (kept by the traffic generator, see
``source.py``); it imports nothing of the program.

Every number compared is exact, so every limit is 0.
"""
from __future__ import annotations

import numpy as np

KEY_SENTINEL = 2**31 - 1
F32_EXACT = 2**24  # above this an f32 count no longer moves by 1


def state_counts(state_keys: np.ndarray, state_vals: np.ndarray):
    """Per-key totals of a ``[W, S]`` keyed state, and how many keys are held
    on more than one row (a key belongs to exactly one worker)."""
    keys = np.asarray(state_keys).reshape(-1)
    vals = np.asarray(state_vals, np.float64).reshape(len(keys), -1)[:, 0]
    live = keys != KEY_SENTINEL
    uniq, inverse, rows = np.unique(keys[live], return_inverse=True, return_counts=True)
    totals = np.bincount(inverse, weights=vals[live], minlength=len(uniq))
    return uniq, totals, int((rows > 1).sum())


def compare(state_keys, state_vals, ids: np.ndarray, ref_counts: np.ndarray,
            overflow: int) -> tuple[dict[str, dict], int]:
    """Every number compared, each beside its limit, and how many keys were
    compared (ids fed or held, and held keys outside the population).

    ``ids`` is the sorted population and ``ref_counts[i]`` the events fed for
    ``ids[i]``.  ``keys_wrong`` counts ids whose held count differs from the
    reference (missing ones included) plus held keys outside the reference;
    ``max_abs_err`` is the largest such difference; ``events_diff`` the gap
    between events held and events fed; ``dup_keys`` keys held on more than
    one row; ``overflow`` rows the job dropped for capacity, by its own count.
    """
    uniq, totals, dups = state_counts(state_keys, state_vals)
    pos = np.minimum(np.searchsorted(ids, uniq), len(ids) - 1)
    known = ids[pos] == uniq
    held = np.zeros(len(ids), np.float64)
    held[pos[known]] = totals[known]
    diff = np.abs(held - ref_counts)
    stray = np.abs(totals[~known])  # keys that are not in the population
    err = max(float(diff.max(initial=0.0)), float(stray.max(initial=0.0)))
    checks = {
        "keys_wrong": int((diff != 0).sum()) + int((~known).sum()),
        "max_abs_err": err,
        "events_diff": abs(float(totals.sum()) - float(ref_counts.sum())),
        "dup_keys": dups,
        "overflow": int(overflow),
    }
    checked = int(((ref_counts > 0) | (held != 0)).sum()) + int((~known).sum())
    return {name: {"value": v, "limit": 0} for name, v in checks.items()}, checked


def passed(checks: dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
