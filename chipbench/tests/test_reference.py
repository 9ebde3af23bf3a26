"""The comparison that decides ``correct``: each fault a run can show moves
one of its numbers off its limit of 0."""
import numpy as np
import pytest

import reference

S = reference.KEY_SENTINEL


def exact_state():
    ids = np.array([3, 8, 20, 41, 77], np.int32)
    counts = np.array([5, 0, 2, 9, 1], np.int64)
    keys = np.full((2, 6), S, np.int32)
    vals = np.zeros((2, 6, 1), np.float32)
    keys[0, :2], vals[0, :2, 0] = [3, 41], [5, 9]
    keys[1, :2], vals[1, :2, 0] = [20, 77], [2, 1]
    return keys, vals, ids, counts


def test_exact_state_passes():
    keys, vals, ids, counts = exact_state()
    checks, checked = reference.compare(keys, vals, ids, counts, 0)
    assert reference.passed(checks) and checked == 4
    assert all(c["limit"] == 0 and c["value"] == 0 for c in checks.values())


def fault(name):
    keys, vals, ids, counts = exact_state()
    overflow = 0
    if name == "missing":
        keys[1, 1] = S
    elif name == "wrong_count":
        vals[0, 1, 0] += 1
    elif name == "extra":
        keys[1, 2], vals[1, 2, 0] = 8, 1
    elif name == "stray":
        keys[1, 2], vals[1, 2, 0] = 99, 1
    elif name == "duplicate":  # a key on two workers, the total still right
        keys[1, 2], vals[0, 0, 0], vals[1, 2, 0] = 3, 2, 3
    elif name == "overflow":
        overflow = 7
    return reference.compare(keys, vals, ids, counts, overflow)[0]


@pytest.mark.parametrize("name,number", [
    ("missing", "keys_wrong"), ("wrong_count", "max_abs_err"), ("extra", "keys_wrong"),
    ("stray", "keys_wrong"), ("duplicate", "dup_keys"), ("overflow", "overflow")])
def test_each_fault_fails(name, number):
    checks = fault(name)
    assert not reference.passed(checks)
    assert checks[number]["value"] > checks[number]["limit"]
