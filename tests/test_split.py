"""Hot-key splitting: replica tables, kernel/ref agreement, control-plane
Split/Unsplit actions, combiner-side merge, and the ExchangeStats redesign.

The invariants under test:

* the fused kernels' replica pick is bit-identical to the jnp ref and the
  host twin (``split_replica_rows``),
* with every replica count at 1 (d=1) the split-capable path is
  bit-identical to the pre-split path,
* a hot key whose load alone exceeds one worker's budget splits, the job
  balances, and the scattered partial aggregates sum to the exact unsplit
  answer; an unsplit merges them back home through the ordinary migration,
* replica tables and split-policy state survive snapshot/restore,
* ``Telemetry.record_exchange`` takes one plane-constructed
  ``ExchangeStats``; the legacy kwarg form warns, mixing both raises.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.control import Signals, Split, SplitPolicy, Telemetry, Unsplit  # noqa: E402
from repro.core.drm import DRConfig, DRMaster  # noqa: E402
from repro.core.partitioner import (  # noqa: E402
    Partitioner,
    heavy_capacity_for,
    split_replica_rows,
    uniform_partitioner,
)
from repro.core.streaming import StreamingJob  # noqa: E402
from repro.exchange import ExchangeStats  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402


# ---------------------------------------------------------------------------
# replica tables on the Partitioner
# ---------------------------------------------------------------------------


def test_with_splits_stamps_and_clamps():
    p = uniform_partitioner(8, 4096, 0, heavy_capacity=128)
    q = p.with_splits({7: 4, 13: 2})
    assert q.split_map() == {7: 4, 13: 2}
    # homes are preserved for keys already routed by the base tables
    np.testing.assert_array_equal(
        q.lookup_np(np.array([7, 13], np.int32)),
        p.lookup_np(np.array([7, 13], np.int32)),
    )
    # d clamps to the partition count; d <= 1 drops out of the map
    assert q.with_splits({7: 100}).split_map() == {7: 8}
    assert q.with_splits({7: 1}).split_map() == {}
    # removing all splits leaves a plain-routing table
    assert q.with_splits({}).split_map() == {}


def test_heavy_capacity_for_matches_tile_padding():
    assert heavy_capacity_for(2.0, 8) == 128
    assert heavy_capacity_for(2.0, 128) == 256
    assert heavy_capacity_for(0.0, 8, floor=130) == 256
    assert heavy_capacity_for(0.0, 8) == 128  # at least one tile


# ---------------------------------------------------------------------------
# kernel == ref == host twin
# ---------------------------------------------------------------------------


def test_split_route_kernel_matches_ref_and_host():
    n_parts, lanes, cap = 8, 4, 64
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 500, 512).astype(np.int32)
    keys[::3] = 7  # hot
    valid = rng.random(512) < 0.9
    vals = rng.standard_normal((512, 2)).astype(np.float32)
    part = uniform_partitioner(n_parts, 4096, 0, heavy_capacity=128)
    part = part.with_splits({7: 4})
    t = part.tables()

    got = ops.route_bucketize(
        jnp.asarray(keys), jnp.asarray(valid), t, jnp.asarray(vals),
        num_hosts=part.num_hosts, seed=part.seed, num_lanes=lanes,
        capacity=cap, key_fill=2**31 - 1, num_partitions=n_parts,
        interpret=True,
    )
    want_part = ref.route_bucketize_ref(
        jnp.asarray(keys), jnp.asarray(valid), jnp.asarray(vals),
        t.heavy_keys, t.heavy_parts, t.host_to_part,
        seed=part.seed, num_hosts=part.num_hosts, num_lanes=lanes,
        capacity=cap, key_fill=2**31 - 1,
        heavy_repl=t.heavy_repl, num_partitions=n_parts,
    )[0]
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want_part))
    # the key fans out over its consecutive replica set
    home = int(part.lookup_np(np.array([7], np.int32))[0])
    hit_parts = np.unique(np.asarray(got[0])[(keys == 7) & valid])
    assert set(hit_parts.tolist()) <= {(home + j) % n_parts for j in range(4)}
    assert len(hit_parts) > 1  # it actually spread

    # host twin: per-partition split-row counts match the device route
    rows = split_replica_rows(part, keys, 1, valid)
    dev = np.bincount(np.asarray(got[0])[(keys == 7) & valid],
                      minlength=n_parts)
    np.testing.assert_array_equal(rows, dev)


def test_split_d1_bit_identical_route():
    """All-ones replica column routes exactly like the pre-split kernel."""
    n_parts, lanes, cap = 8, 4, 64
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 500, 512).astype(np.int32)
    valid = rng.random(512) < 0.9
    vals = rng.standard_normal((512, 1)).astype(np.float32)
    part = uniform_partitioner(n_parts, 4096, 0, heavy_capacity=128)
    t = part.tables()
    kwargs = dict(num_hosts=part.num_hosts, seed=part.seed, num_lanes=lanes,
                  capacity=cap, key_fill=2**31 - 1, interpret=True)
    plain = ops.route_bucketize(jnp.asarray(keys), jnp.asarray(valid), t,
                                jnp.asarray(vals), **kwargs)
    split = ops.route_bucketize(jnp.asarray(keys), jnp.asarray(valid), t,
                                jnp.asarray(vals), num_partitions=n_parts,
                                **kwargs)
    for a, b in zip(plain, split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# SplitPolicy decisions
# ---------------------------------------------------------------------------


def _hot_sketch_drm(cfg, share=0.4, n=8):
    part = uniform_partitioner(n, 4096, 0, heavy_capacity=128)
    drm = DRMaster(part, cfg)
    keys = np.arange(100, dtype=np.int64)
    counts = np.ones(100)
    counts[7] = share * 99 / (1 - share)
    drm.observe(keys, counts)
    return drm


def test_split_policy_fires_and_prices():
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)
    drm = _hot_sketch_drm(cfg)
    a = drm.evaluate(Signals(loads=np.full(8, 1.0), num_workers=8,
                             at_safe_point=True))
    assert isinstance(a, Split)
    assert a.key == 7 and a.replicas >= 2
    assert a.est_relief > a.est_migration  # the pricing gate passed
    assert drm.split_keys == {7: a.replicas}
    assert drm.partitioner.split_map() == drm.split_keys


def test_split_policy_patience_and_dead_zone():
    cfg = DRConfig(split_keys_enabled=True, split_patience=2,
                   imbalance_trigger=100.0)
    drm = _hot_sketch_drm(cfg)
    sig = Signals(loads=np.full(8, 1.0), num_workers=8, at_safe_point=True)
    a1 = drm.evaluate(sig)
    # the split decline falls through to the repartition policy; the streak
    # carries the "sustained" evidence to the next safe point
    assert not a1.taken and drm.split_streak == 1
    a2 = drm.evaluate(sig)
    assert isinstance(a2, Split)
    # below the trigger nothing fires (dead zone)
    drm2 = _hot_sketch_drm(cfg, share=0.10)
    d = drm2.evaluate(sig)
    assert not d.taken and "split" not in d.kind


def test_unsplit_fires_when_cooled():
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)
    drm = _hot_sketch_drm(cfg)
    sig = Signals(loads=np.full(8, 1.0), num_workers=8, at_safe_point=True)
    assert isinstance(drm.evaluate(sig), Split)
    prev = drm.partitioner
    # the key cools: fresh sketch, uniform traffic
    drm.sketch = type(drm.sketch)(512, decay=0.9)
    drm.observe(np.arange(100, dtype=np.int64), np.ones(100))
    a = drm.evaluate(sig)
    assert isinstance(a, Unsplit) and a.key == 7
    assert a.prev.split_map() == prev.split_map()  # still-split partitioner
    assert drm.split_keys == {} and drm.partitioner.split_map() == {}


def test_split_config_needs_dead_zone():
    # validation is unconditional now (PR 10): the dead-zone requirement
    # raises a ValueError whether or not splitting is enabled
    with pytest.raises(ValueError, match="dead zone"):
        DRConfig(split_keys_enabled=True, split_trigger=0.7,
                 unsplit_trigger=0.8)
    with pytest.raises(ValueError, match="dead zone"):
        DRConfig(split_trigger=0.7, unsplit_trigger=0.8)


# ---------------------------------------------------------------------------
# end-to-end streaming: balance + exactness + snapshot + merge
# ---------------------------------------------------------------------------


def _hot_batches(num, size, hot_frac, hot_key=7, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        ks = rng.integers(100, 600, size=size).astype(np.int64)
        ks[rng.random(size) < hot_frac] = hot_key
        out.append(ks)
    return out


def test_hot_key_splits_and_stays_exact():
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)  # isolate the split mechanism
    job = StreamingJob(state_capacity=8192, dr=cfg, seed=0)
    batches = _hot_batches(5, 4096, hot_frac=0.5)
    for b in batches:
        m = job.process_batch(b)
    assert any(mm.action == "split" for mm in job.metrics)
    assert m.split_keys == 1
    # splitting reduced the measured imbalance on the same workload
    assert job.metrics[-1].imbalance < job.metrics[0].imbalance
    # the scattered partials sum to the exact unsplit answer
    true = float(sum((b == 7).sum() for b in batches))
    assert job.state_count(7) == true
    # ...and are genuinely scattered over more than one worker
    sk = np.asarray(job.state_keys)
    holders = [i for i in range(job.num_workers) if (sk[i] == 7).any()]
    assert len(holders) > 1


def test_unsplit_merges_partials_home():
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)
    job = StreamingJob(state_capacity=8192, dr=cfg, seed=0)
    total = 0.0
    for b in _hot_batches(3, 4096, hot_frac=0.5):
        total += float((b == 7).sum())
        job.process_batch(b)
    assert job.drm.split_keys == {7: 2}
    # cool the stream until the policy collapses the split
    for b in _hot_batches(8, 4096, hot_frac=0.0, seed=9):
        total += float((b == 7).sum())
        m = job.process_batch(b)
        if m.action == "unsplit":
            break
    assert m.action == "unsplit" and m.repartitioned  # it moved state
    assert job.drm.split_keys == {}
    assert job.state_count(7) == total
    sk = np.asarray(job.state_keys)
    holders = [i for i in range(job.num_workers) if (sk[i] == 7).any()]
    assert len(holders) == 1  # merged back to the home worker


def test_split_survives_snapshot_restore():
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)
    job = StreamingJob(state_capacity=8192, dr=cfg, seed=0)
    batches = _hot_batches(4, 4096, hot_frac=0.5)
    for b in batches[:3]:
        job.process_batch(b)
    assert job.drm.split_keys
    snap = job.snapshot()
    restored = StreamingJob(state_capacity=8192, dr=cfg, seed=0)
    restored.restore(snap)
    assert restored.drm.split_keys == job.drm.split_keys
    assert restored.drm.partitioner.split_map() == job.drm.partitioner.split_map()
    np.testing.assert_array_equal(restored.drm.partitioner.heavy_repl,
                                  job.drm.partitioner.heavy_repl)
    assert restored.drm.last_split == job.drm.last_split
    # both continue identically on the next batch
    m1 = job.process_batch(batches[3])
    m2 = restored.process_batch(batches[3])
    assert m1.imbalance == m2.imbalance and m1.action == m2.action
    np.testing.assert_array_equal(np.asarray(job.state_keys),
                                  np.asarray(restored.state_keys))


def test_disabled_split_trajectory_unchanged():
    """split_keys_enabled=False (the default) is the pre-split trajectory."""
    batches = _hot_batches(4, 2048, hot_frac=0.3)
    jobs = [StreamingJob(state_capacity=8192, dr=DRConfig(), seed=0),
            StreamingJob(state_capacity=8192,
                         dr=DRConfig(split_keys_enabled=False), seed=0)]
    for b in batches:
        m0 = jobs[0].process_batch(b)
        m1 = jobs[1].process_batch(b)
        assert (m0.imbalance, m0.action, m0.reason) == \
               (m1.imbalance, m1.action, m1.reason)
    np.testing.assert_array_equal(np.asarray(jobs[0].state_keys),
                                  np.asarray(jobs[1].state_keys))
    np.testing.assert_array_equal(np.asarray(jobs[0].state_vals),
                                  np.asarray(jobs[1].state_vals))


# ---------------------------------------------------------------------------
# ExchangeStats telemetry API
# ---------------------------------------------------------------------------


def test_record_exchange_takes_stats_record():
    t = Telemetry("test")
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.5, padded_rows=40,
                                    occupied_rows=8,
                                    replica_rows=np.array([1, 2, 3])))
    t.record_exchange(ExchangeStats(rows=5, replica_rows=np.array([0, 1, 0])))
    s = t.snapshot(loads=np.ones(3))
    assert s.exchange_rows == 15
    assert s.exchange_padded_rows == 45  # padded defaults to rows
    assert s.exchange_occupied_rows == 13
    np.testing.assert_array_equal(s.exchange_replica_rows, [1, 3, 3])


def test_record_exchange_legacy_kwargs_removed():
    # the loose-kwargs deprecation shim is gone: the only accepted call is
    # one plane-constructed ExchangeStats record
    t = Telemetry("test")
    with pytest.raises(TypeError, match="plane-constructed"):
        t.record_exchange(10, 0.5, padded_rows=40)
    with pytest.raises(TypeError, match="plane-constructed"):
        t.record_exchange({"rows": 10})  # not an ExchangeStats record


def test_record_exchange_rejects_stats_plus_kwargs():
    t = Telemetry("test")
    with pytest.raises(TypeError):
        t.record_exchange(ExchangeStats(rows=10), 0.5)
    with pytest.raises(TypeError):
        t.record_exchange(ExchangeStats(rows=10), padded_rows=4)


def test_streaming_telemetry_carries_replica_rows():
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)
    job = StreamingJob(state_capacity=8192, dr=cfg, seed=0)
    # capture the Signals the policy stack actually sees each safe point
    seen = []
    orig = job.drm.evaluate

    def spy(signals, **kw):
        seen.append(signals)
        return orig(signals, **kw)

    job.drm.evaluate = spy
    for b in _hot_batches(3, 4096, hot_frac=0.5):
        job.process_batch(b)
    assert job.drm.split_keys
    # after the split installs, the shuffle records per-replica rows
    rr = seen[-1].exchange_replica_rows
    assert rr is not None and rr.sum() > 0
    assert (rr > 0).sum() > 1  # the hot key really landed on >1 partition


# ---------------------------------------------------------------------------
# least-load replica pick (DRConfig.split_least_load)
# ---------------------------------------------------------------------------


def test_least_load_two_choice_ref():
    """The two-choice pick steers split traffic off an overloaded replica
    partition, never leaves the replica set, and with an all-equal load
    vector is value-identical to the stateless pick (ties keep hash 1)."""
    n_parts = 8
    keys = np.full(512, 7, np.int32)
    part = uniform_partitioner(n_parts, 4096, 0, heavy_capacity=128)
    part = part.with_splits({7: 4})
    t = part.tables()
    home = int(part.lookup_np(np.array([7], np.int32))[0])
    homes = jnp.full(512, home, jnp.int32)
    kw = dict(seed=part.seed, num_partitions=n_parts)

    _, off0 = ref.split_choice_ref(jnp.asarray(keys), t.heavy_keys,
                                   t.heavy_repl, **kw)
    # all-equal loads: bit-identical routing to the stateless pick
    _, off_eq = ref.split_choice_ref(jnp.asarray(keys), t.heavy_keys,
                                     t.heavy_repl, home=homes,
                                     part_loads=jnp.ones(n_parts), **kw)
    np.testing.assert_array_equal(np.asarray(off0), np.asarray(off_eq))
    # no loads / no home: the load-aware block is inert
    _, off_nl = ref.split_choice_ref(jnp.asarray(keys), t.heavy_keys,
                                     t.heavy_repl, home=homes, **kw)
    np.testing.assert_array_equal(np.asarray(off0), np.asarray(off_nl))

    # overload the stateless pick's favourite replica: traffic moves off it
    dest0 = (home + np.asarray(off0)) % n_parts
    hot_rep = np.bincount(dest0, minlength=n_parts).argmax()
    loads = np.ones(n_parts, np.float32)
    loads[hot_rep] = 1e9
    _, off_l = ref.split_choice_ref(jnp.asarray(keys), t.heavy_keys,
                                    t.heavy_repl, home=homes,
                                    part_loads=jnp.asarray(loads), **kw)
    dest_l = (home + np.asarray(off_l)) % n_parts
    assert (dest_l == hot_rep).sum() < (dest0 == hot_rep).sum()
    # both hashes stay inside the key's consecutive replica window
    assert set(np.unique(dest_l).tolist()) <= {
        (home + j) % n_parts for j in range(4)
    }


def test_least_load_gates_pallas_statically():
    """part_loads is jnp-twin only: the plane refuses to route it through
    the Pallas kernel (the kernel keeps the stateless pick), and the
    default use_pallas resolution turns the kernel off when a load vector
    is present."""
    from repro.exchange import ExchangeSpec, make_exchange
    from repro.exchange.plane import route_dispatch

    n_parts = 8
    part = uniform_partitioner(n_parts, 4096, 0, heavy_capacity=128)
    part = part.with_splits({7: 4})
    keys = jnp.asarray(np.full(64, 7, np.int32))
    valid = jnp.ones(64, bool)
    loads = jnp.ones(n_parts, jnp.float32)
    with pytest.raises(AssertionError):
        route_dispatch(part.tables(), keys, valid, num_hosts=part.num_hosts,
                       seed=part.seed, num_lanes=4, num_partitions=n_parts,
                       part_loads=loads, use_pallas=True)
    # default resolution: loads present -> jnp twin, no raise
    p_l, _, _ = route_dispatch(part.tables(), keys, valid,
                               num_hosts=part.num_hosts, seed=part.seed,
                               num_lanes=4, num_partitions=n_parts,
                               part_loads=loads)
    p_0, _, _ = route_dispatch(part.tables(), keys, valid,
                               num_hosts=part.num_hosts, seed=part.seed,
                               num_lanes=4, num_partitions=n_parts)
    # equal loads route identically to the stateless pick
    np.testing.assert_array_equal(np.asarray(p_l), np.asarray(p_0))


def test_least_load_job_bit_identical_across_drivers():
    """split_least_load end-to-end: each batch routes on the previous
    batch's measured load vector, the split fires, and every key's
    aggregate equals ``np.bincount`` over the events fed."""
    from repro.core.hashing import KEY_SENTINEL

    batches = _hot_batches(6, 4096, hot_frac=0.5)
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0, split_least_load=True)
    job = StreamingJob(state_capacity=8192, dr=cfg, seed=0)
    ms = job.run(batches)
    assert any(m.action == "split" for m in ms)
    assert all(m.overflow == 0 for m in ms)
    # the vector the next batch would route on: the last batch's loads
    assert float(np.asarray(job._part_loads).sum()) == float(len(batches[-1]))
    want = np.bincount(np.concatenate(batches))
    sk, sv = np.asarray(job.state_keys), np.asarray(job.state_vals)
    live = sk != KEY_SENTINEL
    got = np.zeros(len(want))
    np.add.at(got, sk[live], sv[live][:, 0])
    np.testing.assert_array_equal(got, want.astype(np.float64))
