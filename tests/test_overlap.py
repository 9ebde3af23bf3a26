"""The streaming job's one schedule: split-phase, one batch in flight.

The driver enqueues batch N's start phase, then batch N-1's in-flight row
ship + merge behind it, and blocks only on start outputs.  State only
materializes at drains — before a taken action, and at every external
state read.  The reference is exact: every key's aggregate equals
``np.bincount`` over every event fed, whatever action the control plane
took in between, and no row is dropped.
"""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.exchange import ExchangeStats, FaultPlan, FaultyBackend, LaneFault
from repro.control import Telemetry
from repro.core.drm import DRConfig
from repro.core.hashing import KEY_SENTINEL
from repro.core.streaming import StreamingJob


def _skewed_batches(num_batches=10, n=384, seed=0):
    """Zipf-ish stream: keeps the imbalance trigger firing."""
    rng = np.random.default_rng(seed)
    return [(rng.zipf(1.5, n) % 200).astype(np.int64) for _ in range(num_batches)]


def _hot_batches(num, size, hot_frac, seed=1):
    """Keys 100..599 with one hot key, 7, carrying ``hot_frac`` of them."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        ks = rng.integers(100, 600, size=size).astype(np.int64)
        ks[rng.random(size) < hot_frac] = 7
        out.append(ks)
    return out


def _run_job(batches, **cfg_kw):
    cfg = DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1, **cfg_kw)
    job = StreamingJob(num_partitions=8, state_capacity=2048, payload_dim=2,
                       dr=cfg, seed=0)
    ms = job.run(batches)
    return job, ms


def _assert_counts_exact(job, batches):
    """Every key's aggregate (its partials summed across workers) equals
    ``np.bincount`` over every event fed, and the table holds no other key."""
    want = np.bincount(np.concatenate(batches))
    sk = np.asarray(job.state_keys)
    sv = np.asarray(job.state_vals)
    live = sk != KEY_SENTINEL
    assert int(sk[live].max()) < len(want)
    got = np.zeros(len(want), np.float64)
    np.add.at(got, sk[live], sv[live][:, 0])
    np.testing.assert_array_equal(got, want.astype(np.float64))


def test_skewed_trajectory_counts_exact():
    batches = _skewed_batches()
    job, ms = _run_job(batches)
    # the stream is skewed enough that state actually moved (the split
    # migrate path ran with its ship in flight)
    assert any(m.repartitioned for m in ms)
    assert all(m.overflow == 0 for m in ms)
    _assert_counts_exact(job, batches)


def test_resize_counts_exact():
    """An explicit elastic resize at a safe point: the drain-before-action
    protocol hands the cross-size migration the merged state."""
    batches = _skewed_batches(num_batches=6)
    job = StreamingJob(num_partitions=8, state_capacity=2048,
                       dr=DRConfig(imbalance_trigger=10.0), seed=0)
    ms = [job.process_batch(batches[0]), job.process_batch(batches[1])]
    job.resize(16)
    ms += [job.process_batch(b) for b in batches[2:]]
    assert any(m.resized for m in ms)
    assert ms[-1].num_partitions == 16
    assert all(m.overflow == 0 for m in ms)
    _assert_counts_exact(job, batches)


def test_snapshot_mid_stream_drains_inflight():
    """A snapshot between batches must capture the in-flight merge: restore
    into a fresh job and every count is exact."""
    batches = _skewed_batches(num_batches=5)
    job, _ = _run_job(batches)
    assert job._inflight is not None
    snap = job.snapshot()  # drains the pending finish
    assert job._inflight is None
    job2 = StreamingJob(num_partitions=8, state_capacity=2048, payload_dim=2,
                        dr=DRConfig(), seed=0)
    job2.restore(snap)
    _assert_counts_exact(job2, batches)


def test_overlapped_batches_report_phase_walls():
    """Overlapped batches attribute wall to phases: the count wall is the
    batch's blocking exchange wall, and once a drain happens (an action
    fires) the window that follows carries hidden + ship walls, surfacing
    a nonzero overlap_fraction."""
    job, ms = _run_job(_skewed_batches())
    assert any(m.repartitioned for m in ms)  # at least one drain happened
    t = job.telemetry
    # window accumulators since the last safe point + the long-lived EWMA
    assert t.wall_ewma.get("dense", 0.0) > 0.0
    sig = t.snapshot(loads=np.ones(8), num_workers=1)
    assert sig.exchange_count_wall_s >= 0.0


def test_overlap_fraction_signal():
    """Unit-level: hidden / (hidden + ship), 0.0 when nothing was recorded
    and when only the whole exchange wall was recorded."""
    t = Telemetry("test")
    sig = t.snapshot(loads=np.ones(2))
    assert sig.overlap_fraction == 0.0
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.5))  # whole-wall record: no phases
    sig = t.snapshot(loads=np.ones(2))
    assert sig.overlap_fraction == 0.0
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.2, count_wall_s=0.2))
    t.record_exchange(ExchangeStats(rows=0, ship_wall_s=0.1, hidden_wall_s=0.3))
    sig = t.snapshot(loads=np.ones(2))
    assert sig.exchange_count_wall_s == pytest.approx(0.2)
    assert sig.exchange_ship_wall_s == pytest.approx(0.1)
    assert sig.exchange_hidden_wall_s == pytest.approx(0.3)
    assert sig.overlap_fraction == pytest.approx(0.75)


def test_backend_wall_ewma_accumulates_across_windows():
    t = Telemetry("test")
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.4, backend="dense"))
    t.snapshot(loads=np.ones(2))  # window reset must not clear the EWMA
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.2, backend="dense"))
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.1, backend="ragged"))
    sig = t.snapshot(loads=np.ones(2))
    assert sig.backend_wall_ewma["dense"] == pytest.approx(0.7 * 0.4 + 0.3 * 0.2)
    assert sig.backend_wall_ewma["ragged"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# every action at a safe point keeps the counts exact
# ---------------------------------------------------------------------------


def test_split_counts_exact():
    """Hot-key split: the partial aggregates the replicas hold sum to the
    exact unsplit answer."""
    hot = _hot_batches(5, 4096, hot_frac=0.5)
    cfg = DRConfig(split_keys_enabled=True, split_patience=1,
                   imbalance_trigger=100.0)
    # over-partitioning keeps the split reachable on a 1-device mesh
    job = StreamingJob(num_partitions=4, state_capacity=8192, dr=cfg, seed=0)
    ms = job.run(hot)
    assert any(m.action == "split" for m in ms)
    assert job.state_count(7) == float(sum((b == 7).sum() for b in hot))
    _assert_counts_exact(job, hot)


def test_backend_switch_counts_exact():
    """An auto backend switch rebuilds the jitted steps at a safe point,
    after draining the finish the old step issued."""
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 500, 2048) for _ in range(6)]
    dr = DRConfig(auto_backend=True, backend_patience=2,
                  backend_cooldown=50, imbalance_trigger=1e9)
    job = StreamingJob(num_partitions=4, state_capacity=2048,
                       capacity_factor=4.0, dr=dr)
    ms = job.run(batches)
    switches = [m for m in ms if m.action == "switch_backend"]
    assert len(switches) == 1
    assert ms[-1].backend == "ragged"
    _assert_counts_exact(job, batches)


def _act_repartition():
    batches = _skewed_batches()
    job, ms = _run_job(batches)
    return job, batches, ms, "repartition"


def _act_resize(start: int, target: int):
    def act():
        batches = _skewed_batches(num_batches=5)
        job = StreamingJob(num_partitions=start, state_capacity=2048,
                           dr=DRConfig(imbalance_trigger=1e9), seed=0)
        ms = job.run(batches[:2])
        job.resize(target)
        ms += job.run(batches[2:])
        assert ms[-1].num_partitions == target
        return job, batches, ms, "resize"
    return act


def _act_split():
    batches = _hot_batches(4, 4096, hot_frac=0.5)
    job = StreamingJob(num_partitions=4, state_capacity=8192, seed=0,
                       dr=DRConfig(split_keys_enabled=True, split_patience=1,
                                   imbalance_trigger=100.0))
    return job, batches, job.run(batches), "split"


def _act_unsplit():
    batches = _hot_batches(3, 4096, hot_frac=0.5)
    job = StreamingJob(num_partitions=4, state_capacity=8192, seed=0,
                       dr=DRConfig(split_keys_enabled=True, split_patience=1,
                                   imbalance_trigger=100.0))
    ms = job.run(batches)
    assert job.drm.split_keys
    # cool the stream until the policy collapses the split
    for b in _hot_batches(8, 4096, hot_frac=0.0, seed=9):
        batches.append(b)
        ms.append(job.process_batch(b))
        if ms[-1].action == "unsplit":
            break
    assert not job.drm.split_keys
    return job, batches, ms, "unsplit"


def _act_switch_backend():
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 500, 2048) for _ in range(6)]
    job = StreamingJob(num_partitions=4, state_capacity=2048, capacity_factor=4.0,
                       dr=DRConfig(auto_backend=True, backend_patience=2,
                                   backend_cooldown=50, imbalance_trigger=1e9))
    ms = job.run(batches)
    assert (ms[0].backend, ms[-1].backend) == ("dense", "ragged")
    return job, batches, ms, "switch_backend"


def _act_restore():
    """Restore a snapshot cut with a finish in flight, onto a job that ran
    ahead; the counts are those of the snapshot's timeline."""
    batches = _skewed_batches(num_batches=6)
    job, ms = _run_job(batches[:3])
    snap = job.snapshot()
    job.run(batches[3:5])  # a timeline the restore abandons
    job.restore(snap)
    _assert_counts_exact(job, batches[:3])
    ms += job.run(batches[3:])
    return job, batches, ms, "restore"


def _act_recover():
    """Kill the only worker mid-stream: it restarts in place from the last
    auto-snapshot and replays the gap."""
    batches = _skewed_batches(num_batches=8)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    job = StreamingJob(
        mesh=mesh, num_partitions=8, state_capacity=2048, payload_dim=2, seed=0,
        dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1,
                    snapshot_interval=3),
        exchange_backend=FaultyBackend("dense", FaultPlan(faults=(LaneFault(5, 0, "kill"),))),
    )
    ms = job.run(batches)
    assert [r.kind for r in job.recoveries] == ["restart"]
    return job, batches, ms, "recover"


ACTIONS = {
    "repartition": _act_repartition,
    "resize-grow": _act_resize(8, 16),
    "resize-shrink": _act_resize(16, 4),
    "split": _act_split,
    "unsplit": _act_unsplit,
    "switch-dense-to-ragged": _act_switch_backend,
    "restore": _act_restore,
    "kill-and-restart": _act_recover,
}


@pytest.mark.parametrize("action", list(ACTIONS))
def test_every_action_keeps_counts_exact(action):
    job, batches, ms, kind = ACTIONS[action]()
    if kind not in ("restore", "recover"):  # not control-plane actions
        assert any(m.action == kind for m in ms), [m.action for m in ms]
    assert all(m.overflow == 0 for m in ms)
    _assert_counts_exact(job, batches)


@pytest.mark.parametrize("reader", ["state_keys", "state_vals", "state_count"])
def test_state_reads_drain_inflight(reader):
    """Each state read right after ``process_batch`` — the batch's finish +
    merge still in flight — completes the finish first and sees the batch."""
    batches = _skewed_batches(num_batches=3)
    # one payload column: state_count sums every column of a key
    job = StreamingJob(num_partitions=8, state_capacity=2048,
                       dr=DRConfig(imbalance_trigger=1e9), seed=0)
    for i, b in enumerate(batches):
        job.process_batch(b)
        assert job._inflight is not None
        want = np.bincount(np.concatenate(batches[:i + 1]))
        if reader == "state_keys":
            sk = np.asarray(job.state_keys)
            np.testing.assert_array_equal(np.unique(sk[sk != KEY_SENTINEL]),
                                          np.flatnonzero(want))
        elif reader == "state_vals":
            sv = np.asarray(job.state_vals)
            np.testing.assert_array_equal(sv.sum(axis=(0, 1)), [want.sum()])
        else:
            got = [job.state_count(k) for k in range(len(want))]
            np.testing.assert_array_equal(got, want.astype(np.float64))
        assert job._inflight is None


def test_steady_state_is_sync_free():
    """Between safe points the driver performs zero blocking device->host
    transfers: every fetch routes through compat.host_fetch inside a
    sanctioned safe_point region, so the audit counter stays flat across
    steady-state (noop) batches."""
    from repro import compat

    batches = _skewed_batches(num_batches=6)
    job = StreamingJob(num_partitions=8, state_capacity=2048, payload_dim=2,
                       dr=DRConfig(imbalance_trigger=1e9), seed=0)
    job.run(batches[:2])  # warmup: compile + put a finish in flight
    compat.reset_host_sync_count()
    ms = job.run(batches[2:])
    assert compat.host_sync_count() == 0
    assert all(m.action == "noop" for m in ms)
    _assert_counts_exact(job, batches)


def test_two_starts_in_flight_share_the_buffer_pool():
    """Step-level aliasing check for the ping-pong pool: a second start is
    issued while the first pending is still un-finished (the driver's
    queue shape: batch N's start before batch N-1's finish).  Both
    finishes must return the same rows as a fresh factory running each
    exchange whole — recycling a drained pending's buffers into the next
    start must never corrupt a pending still in flight."""
    import jax
    import jax.numpy as jnp

    from repro.core.partitioner import uniform_partitioner
    from repro.core.shuffle import make_shuffle_step

    mesh = jax.make_mesh((1,), ("data",))
    part = uniform_partitioner(1)
    rng = np.random.default_rng(0)
    b1 = rng.integers(0, 100, 64).astype(np.int32)
    b2 = rng.integers(0, 100, 64).astype(np.int32)
    b3 = rng.integers(0, 100, 64).astype(np.int32)
    ones = jnp.ones((64, 1), jnp.float32)
    valid = jnp.ones(64, bool)

    def whole_rows(batch):
        step = make_shuffle_step(mesh, num_partitions=1, capacity=64,
                                 num_hosts=part.num_hosts)
        res = step(part.tables(), jnp.asarray(batch), ones, valid)
        return np.asarray(res.keys), np.asarray(res.valid)

    step = make_shuffle_step(mesh, num_partitions=1, capacity=64,
                             num_hosts=part.num_hosts)
    # two starts live before the first finish, then a third start claims
    # the set the first finish recycled
    p1, _ = step.start(part.tables(), jnp.asarray(b1), ones, valid)
    p2, _ = step.start(part.tables(), jnp.asarray(b2), ones, valid)
    k1, _, va1, _ = step.finish(p1)
    p3, _ = step.start(part.tables(), jnp.asarray(b3), ones, valid)
    k2, _, va2, _ = step.finish(p2)
    k3, _, va3, _ = step.finish(p3)
    for got_k, got_va, batch in ((k1, va1, b1), (k2, va2, b2), (k3, va3, b3)):
        ref_k, ref_va = whole_rows(batch)
        np.testing.assert_array_equal(np.asarray(got_k), ref_k)
        np.testing.assert_array_equal(np.asarray(got_va), ref_va)
