"""Elastic resize end-to-end: safe-point protocol, policy hook, exactness.

The acceptance scenario: a skewed stream grows 4->8 partitions at a
checkpoint tick, every per-key state count survives bit-exactly across the
resize (and back down 8->4), and the state ships through exchange lanes
bounded by ``migration_capacity`` of the cross-size plan.
"""
import numpy as np
import pytest

from repro.core.drm import DRConfig, DRMaster
from repro.core.partitioner import uniform_partitioner
from repro.core.streaming import StreamingJob, migrate_lane_capacity
from repro.data.generators import zipf_keys
from repro.exchange import ExchangeSpec
from repro.serve.scheduler import DRScheduler


def _migration_rows(job: StreamingJob, plan_rows: int) -> int:
    """Rows a resize's migration provisions: none on one worker, where a
    resize swaps the partitioner and ships nothing; else a lane of the
    capacity the job jits for the plan from every worker."""
    if job.num_workers == 1:
        return 0
    return job.num_workers * migrate_lane_capacity(plan_rows, job.state_capacity)


def _assert_counts_exact(job: StreamingJob, batches) -> None:
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:10]:
        assert job.state_count(int(key)) == float((all_keys == key).sum()), int(key)


# ---------------------------------------------------------------------------
# DRM policy hook (synthetic loads: the pure decision logic)
# ---------------------------------------------------------------------------


def _elastic_cfg(**kw) -> DRConfig:
    base = dict(elastic=True, min_partitions=2, max_partitions=16,
                grow_trigger=1.5, shrink_trigger=1.05, resize_patience=2)
    base.update(kw)
    return DRConfig(**base)


def test_decide_resize_grow_needs_sustained_imbalance():
    drm = DRMaster(uniform_partitioner(4), _elastic_cfg())
    hot = np.array([10.0, 1.0, 1.0, 1.0])
    assert drm.decide_resize(hot) is None          # patience 1/2
    assert drm.decide_resize(hot) == 8             # sustained -> grow
    assert drm.grow_streak == 0                    # acted: streak reset


def test_decide_resize_streak_resets_when_balance_recovers():
    drm = DRMaster(uniform_partitioner(4), _elastic_cfg())
    assert drm.decide_resize(np.array([10.0, 1.0, 1.0, 1.0])) is None
    assert drm.decide_resize(np.array([1.1, 1.0, 1.0, 0.9])) is None  # resets
    assert drm.decide_resize(np.array([10.0, 1.0, 1.0, 1.0])) is None  # 1/2 again


def test_decide_resize_shrink_floors_at_workers():
    drm = DRMaster(uniform_partitioner(4), _elastic_cfg(min_partitions=1))
    flat = np.ones(4)
    assert drm.decide_resize(flat, num_workers=4) is None
    assert drm.decide_resize(flat, num_workers=4) is None  # 4 == floor: no-op
    drm2 = DRMaster(uniform_partitioner(4), _elastic_cfg(min_partitions=1))
    assert drm2.decide_resize(flat, num_workers=1) is None
    assert drm2.decide_resize(flat, num_workers=1) == 2


def test_decide_resize_respects_max_partitions():
    drm = DRMaster(uniform_partitioner(8), _elastic_cfg(max_partitions=8))
    hot = np.array([50.0] + [1.0] * 7)
    assert drm.decide_resize(hot) is None
    assert drm.decide_resize(hot) is None  # already at max: never fires
    # headroom below a non-power-of-factor ceiling is used, clamped to it
    drm2 = DRMaster(uniform_partitioner(8), _elastic_cfg(max_partitions=12))
    assert drm2.decide_resize(hot) is None
    assert drm2.decide_resize(hot) == 12


def test_decide_resize_disabled_by_default():
    drm = DRMaster(uniform_partitioner(4), DRConfig())
    assert drm.decide_resize(np.array([100.0, 1.0, 1.0, 1.0])) is None


def test_note_resize_counts_as_safe_point_decision():
    drm = DRMaster(uniform_partitioner(4), _elastic_cfg())
    seen = drm.batches_seen
    drm.note_resize(uniform_partitioner(8))
    assert drm.partitioner.num_partitions == 8
    assert drm.batches_seen == seen + 1
    assert drm.last_repartition == drm.batches_seen
    assert drm.history[-1]["resize"] == (4, 8)


# ---------------------------------------------------------------------------
# StreamingJob: the acceptance scenario
# ---------------------------------------------------------------------------


def test_grow_and_shrink_preserve_counts_with_bounded_rows():
    """Skewed keys grow 4->8 at a checkpoint tick; counts stay bit-exact
    across grow and the shrink back; shipped rows are bounded by the
    cross-size plan's migration_capacity (pow2-rounded lanes), and are none
    on one worker."""
    job = StreamingJob(
        num_partitions=4,
        state_capacity=8192,
        checkpoint_interval=2,
        dr=DRConfig(elastic=True, min_partitions=4, max_partitions=8,
                    grow_trigger=1.4, shrink_trigger=1.3, resize_patience=1,
                    imbalance_trigger=1e9),  # isolate the elastic path
    )
    batches = [zipf_keys(8192, num_keys=2_000, exponent=1.5, seed=s) for s in range(4)]
    ms = [job.process_batch(b) for b in batches]
    grow = [m for m in ms if m.resized]
    assert grow, [m.reason for m in ms]
    g = grow[0]
    assert g.reason == "resize 4->8" and g.num_partitions == 8
    assert (g.batch + 1) % 2 == 0  # fired exactly at a checkpoint tick
    # lanes sized from the cross-size plan, nothing dropped
    assert g.overflow == 0
    assert g.migration_rows == _migration_rows(job, g.migration_plan_rows)
    assert job.num_partitions == 8
    _assert_counts_exact(job, batches)

    # shrink back down 8->4 (driver scale-in at the next safe point)
    job.resize(4)
    more = [zipf_keys(8192, num_keys=2_000, exponent=1.5, seed=s) for s in (10, 11)]
    ms2 = [job.process_batch(b) for b in more]
    s = [m for m in ms2 if m.resized][0]
    assert s.reason == "resize 8->4" and job.num_partitions == 4
    assert s.overflow == 0
    assert s.migration_rows == _migration_rows(job, s.migration_plan_rows)
    _assert_counts_exact(job, batches + more)


def test_resize_waits_for_checkpoint_tick():
    job = StreamingJob(num_partitions=4, state_capacity=4096, checkpoint_interval=3,
                       dr_enabled=False)
    job.resize(8)
    rng = np.random.default_rng(0)
    m1 = job.process_batch(rng.integers(0, 1000, 2048))
    m2 = job.process_batch(rng.integers(0, 1000, 2048))
    assert not m1.resized and not m2.resized and job.num_partitions == 4
    m3 = job.process_batch(rng.integers(0, 1000, 2048))
    assert m3.resized and job.num_partitions == 8  # third batch is the tick


def test_resize_below_worker_count_rejected():
    job = StreamingJob(num_partitions=4)
    with pytest.raises(ValueError):
        job.resize(0)


def test_snapshot_restore_roundtrip_across_resize():
    """A snapshot taken after a resize restores into a job built with the
    old topology and resumes with the new one."""
    mk = lambda: StreamingJob(num_partitions=4, state_capacity=4096,
                              dr=DRConfig(imbalance_trigger=1e9))
    job = mk()
    batches = [zipf_keys(4096, num_keys=500, exponent=1.3, seed=s) for s in range(4)]
    job.process_batch(batches[0])
    job.resize(8)
    job.process_batch(batches[1])
    assert job.num_partitions == 8
    snap = job.snapshot()

    job2 = mk()  # constructed at 4 partitions — must resume at 8
    job2.restore(snap)
    assert job2.num_partitions == 8
    assert job2.drm.partitioner.num_partitions == 8
    job.process_batch(batches[2])
    job2.process_batch(batches[2])
    all_keys = np.concatenate(batches[:3])
    for key in np.unique(all_keys)[:8]:
        want = float((all_keys == key).sum())
        assert job2.state_count(int(key)) == want
        assert job.state_count(int(key)) == want


def test_exchange_spec_rederivation():
    spec = ExchangeSpec(num_lanes=4, capacity=128, axis="data")
    grown = spec.resized(num_lanes=8)
    assert grown == ExchangeSpec(num_lanes=8, capacity=128, axis="data")
    recap = spec.resized(capacity=512)
    assert recap == ExchangeSpec(num_lanes=4, capacity=512, axis="data")
    assert spec.resized() == spec


# ---------------------------------------------------------------------------
# Serving: the same mechanism one level up (replica scale-out/in)
# ---------------------------------------------------------------------------


def test_scheduler_elastic_scale_out_and_in():
    rng = np.random.default_rng(3)
    sched = DRScheduler(4, dr=DRConfig(lam=4.0, elastic=True, min_partitions=2,
                                       max_partitions=8, grow_trigger=1.5,
                                       shrink_trigger=1.02, resize_patience=1,
                                       imbalance_trigger=1e9))
    hot = [7, 8, 9]
    results = []
    for _ in range(2):
        window = []
        for _ in range(400):
            s = int(rng.choice(hot)) if rng.random() < 0.7 else int(rng.integers(100, 5000))
            sched.route(s, 32.0)
            window.append(s)
        results.append(sched.checkpoint(np.array(window)))
        sched.drain(3000.0)
    assert len(sched.replicas) == 8
    assert any(r.get("resized") for r in results)
    # every session lives exactly where the resized partitioner maps it
    for rep in sched.replicas:
        for s in rep.sessions:
            assert int(sched.drm.partitioner.lookup_np(np.asarray([s], np.int32))[0]) == rep.rid
    # explicit scale-in folds sessions and queued work onto survivors
    before = {s for rep in sched.replicas for s in rep.sessions}
    sched.resize(2)
    assert len(sched.replicas) == 2
    after = {s for rep in sched.replicas for s in rep.sessions}
    assert after == before
    for rep in sched.replicas:
        for s in rep.sessions:
            assert int(sched.drm.partitioner.lookup_np(np.asarray([s], np.int32))[0]) == rep.rid
