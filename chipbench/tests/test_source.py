"""The traffic generator: seeded, drifting within a fixed population, and
with a reference that counts exactly the events fed."""
import numpy as np
import pytest

import source

TINY = dict(arrivals="backlog", zipf_exponent=1.2, population=1 << 12, id_range=1 << 30,
            drift_every_batches=4, drift_fraction=0.3, batch_events_per_chip=1 << 10,
            prefill="population_sweep")
BIG_SEED = 2**31 + 12345


def tiny(**kw):
    return source.Traffic(name="tiny", **{**TINY, **kw})


def test_traffic_file_loads():
    t = source.Traffic.load("backlog-zipf1.2-rotating")
    assert t.population == 1_000_000 and t.batch_events(1) == 1 << 20
    assert t.sweep_batches(1) == 1 and t.drift_every_batches == 4


def test_sweep_feeds_every_id():
    t = tiny(population=3000)  # not a whole number of 1024-event batches
    s = source.Stream(t, BIG_SEED, 1)
    assert s.sweep_batches == 3
    idx = np.concatenate([s.make(b) for b in range(3)])
    assert len(idx) == 3 * 1024 and set(idx.tolist()) == set(range(3000))


def test_same_seed_same_batches():
    a, b = source.Stream(tiny(), BIG_SEED, 1), source.Stream(tiny(), BIG_SEED, 1)
    c = source.Stream(tiny(), BIG_SEED + 1, 1)
    for i in (0, 3, 4, 9):
        assert np.array_equal(a.make(i), b.make(i))
    assert np.array_equal(a.ids, b.ids)
    assert not np.array_equal(a.make(9), c.make(9))


def test_population_ids_are_valid_keys():
    ids = source.population_ids(BIG_SEED, 1 << 12, 1 << 30)
    assert ids.dtype == np.int32 and len(np.unique(ids)) == 1 << 12
    assert np.all(np.diff(ids) > 0) and ids.max() < source.KEY_SENTINEL


def test_drift_moves_hot_set_within_population():
    t = tiny()
    s = source.Stream(t, 3, 1)
    keys = np.concatenate([s.take(b) for b in range(s.sweep_batches + 24)])
    assert set(np.unique(keys)) <= set(s.ids.tolist())  # distinct ids <= population
    hot = int(t.drift_fraction * t.population)
    perms = [s.perm(e).copy() for e in range(4)]
    for a, b in zip(perms, perms[1:]):
        assert sorted(b.tolist()) == list(range(t.population))  # still a permutation
        # the heaviest ranks take ids that were cold: no id stays hot, so no
        # count can grow past one epoch's share of the hottest rank
        assert not set(a[:hot].tolist()) & set(b[:hot].tolist())
    hottest = []
    for e in range(3):
        first = s.sweep_batches + e * t.drift_every_batches
        idx = np.concatenate([s.make(b) for b in range(first, first + t.drift_every_batches)])
        hottest.append(np.bincount(idx).argmax())
    assert len(set(hottest)) == 3  # a new hot id in every epoch
    assert np.array_equal(source.Stream(t, 3, 1).perm(2), perms[2])  # from the seed alone


@pytest.mark.parametrize("exponent", [0.0, 1.2, 2.0])
def test_alias_table_draws_zipf(exponent):
    n = 1 << 14
    thr, alias = source.zipf_alias(n, exponent)
    keep = thr.astype(np.float64) / 2.0**32  # column j keeps j below thr[j]
    drawn = keep.copy()
    np.add.at(drawn, alias, 1.0 - keep)
    p = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    assert np.allclose(drawn / n, p / p.sum(), rtol=1e-4, atol=1e-12)


def test_bincount_reference_equals_add_at():
    s = source.Stream(tiny(), 5, 1)
    keys = np.concatenate([s.take(b) for b in range(12)])
    want = np.zeros(len(s.ids), np.int64)
    np.add.at(want, np.searchsorted(s.ids, keys), 1)
    assert np.array_equal(s.counts, want)


def test_process_counts_exactly_what_was_taken():
    t = tiny()
    want = source.Stream(t, BIG_SEED, 4)
    with source.SourceProcess(t, BIG_SEED, 4, slots=2) as src:
        got = [src.next_batch() for _ in range(7)]
        counts = src.finish()
    for b, keys in enumerate(got):
        assert np.array_equal(keys, want.take(b))
    assert np.array_equal(counts, want.counts)
