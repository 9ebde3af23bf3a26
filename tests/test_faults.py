"""Failure domains: fault injection, lane health, zero-loss recovery.

Everything here runs on the in-process single-device mesh (the shard_map
path is fully exercised at W=1); the multi-worker eviction proof lives in
``tests/test_distributed.py`` behind the 8-device subprocess harness.
"""
import numpy as np
import pytest

from repro.control import Evict, Quarantine, Recover, Signals, Telemetry
from repro.core.drm import DRConfig, DRMaster
from repro.core.partitioner import uniform_partitioner
from repro.core.streaming import StreamingJob
from repro.exchange import (
    ExchangeStats,
    FaultPlan,
    FaultyBackend,
    LaneFault,
    WorkerLostError,
)

pytestmark = pytest.mark.chaos


def _batches(n=8, keys=50, rows=64, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, keys, rows).astype(np.int64) for _ in range(n)]


def _mesh1():
    """Explicit single-device mesh: the restart-in-place recovery tests
    must see W=1 even when another test module forced a multi-device host
    platform (e.g. test_split sets XLA_FLAGS at import time)."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _counts(job, keys=50):
    return {k: job.state_count(k) for k in range(keys)}


def _trajectory(metrics):
    return [(m.action, m.reason, m.overflow, m.shipped_rows) for m in metrics]


# ---------------------------------------------------------------------------
# FaultPlan: schedule, serialization, determinism
# ---------------------------------------------------------------------------


def test_fault_plan_roundtrip():
    plan = FaultPlan(
        faults=(
            LaneFault(3, 1, "latency", delay_s=0.01, span=2),
            LaneFault(5, 0, "transient", failures=2),
            LaneFault(9, 2, "kill"),
        ),
        max_retries=4,
        backoff_s=0.001,
        seed=7,
    )
    assert FaultPlan.from_dict(plan.to_dict()) == plan
    assert not plan.never_fires
    assert FaultPlan().never_fires


def test_fault_plan_generate_deterministic():
    a = FaultPlan.generate(11, num_lanes=4, ticks=32, kill_at=(20, 3))
    b = FaultPlan.generate(11, num_lanes=4, ticks=32, kill_at=(20, 3))
    c = FaultPlan.generate(12, num_lanes=4, ticks=32, kill_at=(20, 3))
    assert a == b
    assert a != c
    assert any(f.kind == "kill" and f.tick == 20 and f.lane == 3
               for f in a.faults)


def test_fault_plan_validation():
    with pytest.raises(ValueError):
        LaneFault(0, 0, "meteor")
    with pytest.raises(ValueError):
        LaneFault(-1, 0, "kill")
    with pytest.raises(ValueError):
        LaneFault(0, 0, "transient", failures=0)
    with pytest.raises(ValueError):
        FaultPlan(max_retries=-1)


# ---------------------------------------------------------------------------
# the seam: never-firing identity, retries, escalation
# ---------------------------------------------------------------------------


def test_never_firing_plan_is_bit_identical():
    """An installed FaultPlan that never fires must leave the decision
    trajectory AND the keyed state bit-identical to a run with no seam at
    all — the acceptance contract for the host-boundary injection design."""
    batches = _batches()
    ref = StreamingJob(dr=DRConfig())
    ms_ref = ref.run(batches)
    seamed = StreamingJob(dr=DRConfig(),
                          exchange_backend=FaultyBackend("dense", FaultPlan()))
    ms_seam = seamed.run(batches)
    assert _trajectory(ms_ref) == _trajectory(ms_seam)
    assert _counts(ref) == _counts(seamed)
    assert seamed.exchange_backend.kills == 0
    assert seamed.exchange_backend.retries == 0


def test_transient_faults_retry_to_zero_loss():
    batches = _batches()
    ref = StreamingJob(dr=DRConfig(imbalance_trigger=1e9))
    ref.run(batches)
    plan = FaultPlan(
        faults=(LaneFault(2, 0, "transient", failures=2),
                LaneFault(5, 0, "transient", failures=1)),
        max_retries=3,
    )
    job = StreamingJob(dr=DRConfig(imbalance_trigger=1e9),
                       exchange_backend=FaultyBackend("dense", plan))
    job.run(batches)
    backend = job.exchange_backend
    assert backend.transients == 2
    assert backend.retries == 3  # 2 + 1 failed attempts, all retried
    assert _counts(job) == _counts(ref)
    assert not job.recoveries  # retries absorbed everything


def test_transient_past_budget_escalates_to_loss():
    plan = FaultPlan(faults=(LaneFault(2, 0, "transient", failures=5),),
                     max_retries=2)
    job = StreamingJob(dr=DRConfig(imbalance_trigger=1e9),
                       exchange_backend=FaultyBackend("dense", plan))
    with pytest.raises(WorkerLostError):
        job.run(_batches())  # snapshot_interval=0: loss propagates


def test_latency_fault_reports_straggle():
    plan = FaultPlan(faults=(LaneFault(1, 0, "latency",
                                       delay_s=0.002, span=3),))
    job = StreamingJob(dr=DRConfig(imbalance_trigger=1e9),
                       exchange_backend=FaultyBackend("dense", plan))
    job.run(_batches(6))
    assert job.exchange_backend.injected_sleep_s >= 0.005
    # the seam's report drained into telemetry each safe point
    assert job.exchange_backend.drain_report() == {}


# ---------------------------------------------------------------------------
# zero-loss recovery (W=1: restore + replay in place)
# ---------------------------------------------------------------------------


def test_kill_without_snapshots_propagates():
    plan = FaultPlan(faults=(LaneFault(3, 0, "kill"),))
    job = StreamingJob(dr=DRConfig(imbalance_trigger=1e9),
                       exchange_backend=FaultyBackend("dense", plan))
    with pytest.raises(WorkerLostError):
        job.run(_batches())


@pytest.mark.parametrize("kill_tick,interval", [(4, 3), (2, 1), (6, 5)])
def test_kill_recovery_is_zero_loss(kill_tick, interval):
    batches = _batches()
    ref = StreamingJob(dr=DRConfig(imbalance_trigger=1e9), mesh=_mesh1())
    ref.run(batches)
    plan = FaultPlan(faults=(LaneFault(kill_tick, 0, "kill"),))
    job = StreamingJob(
        dr=DRConfig(imbalance_trigger=1e9, snapshot_interval=interval),
        exchange_backend=FaultyBackend("dense", plan), mesh=_mesh1())
    job.run(batches)
    assert len(job.recoveries) == 1
    rec = job.recoveries[0]
    assert rec.kind == "restart"  # single worker: restore+replay in place
    assert rec.wall_s > 0.0
    assert _counts(job) == _counts(ref), "recovery lost or duplicated rows"


def test_double_kill_during_replay_still_zero_loss():
    """A second loss while replaying the gap re-enters recovery with the
    same snapshot and buffer — the protocol is idempotent under repeated
    failure until the retry budget runs out."""
    batches = _batches()
    ref = StreamingJob(dr=DRConfig(imbalance_trigger=1e9), mesh=_mesh1())
    ref.run(batches)
    plan = FaultPlan(faults=(LaneFault(4, 0, "kill"),
                             LaneFault(6, 0, "kill")))
    job = StreamingJob(
        dr=DRConfig(imbalance_trigger=1e9, snapshot_interval=3),
        exchange_backend=FaultyBackend("dense", plan), mesh=_mesh1())
    job.run(batches)
    assert len(job.recoveries) == 2
    assert _counts(job) == _counts(ref)


def test_seed_determinism_same_plan_same_trajectory():
    """Same FaultPlan seed -> same decision trajectory and same recovery
    record, run to run — the chaos tests' reproducibility contract."""
    batches = _batches()
    plan = FaultPlan.generate(21, num_lanes=1, ticks=10,
                              latency_rate=0.3, transient_rate=0.2,
                              delay_s=0.001, kill_at=(6, 0))
    runs = []
    for _ in range(2):
        job = StreamingJob(
            dr=DRConfig(imbalance_trigger=1e9, snapshot_interval=2),
            exchange_backend=FaultyBackend("dense", plan))
        ms = job.run(batches)
        runs.append((_trajectory(ms), _counts(job),
                     [(r.lane, r.kind, r.replayed) for r in job.recoveries]))
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# lane health -> typed actions (DRMaster.evaluate, synthetic signals)
# ---------------------------------------------------------------------------


def _health_cfg(**kw):
    kw.setdefault("health_enabled", True)
    kw.setdefault("health_straggler_ms", 50.0)
    kw.setdefault("health_failure_threshold", 3)
    kw.setdefault("health_patience", 2)
    kw.setdefault("imbalance_trigger", 1e9)
    return DRConfig(**kw)


def _sig(w=4, straggle=None, retries=None):
    return Signals(loads=np.ones(w), num_workers=w, at_safe_point=True,
                   lane_straggle_s=straggle, lane_retries=retries)


def test_health_quarantine_after_patience():
    drm = DRMaster(uniform_partitioner(4, 64, 0), _health_cfg())
    s = np.zeros(4)
    s[2] = 0.2  # 200ms straggle per window on lane 2
    first = drm.evaluate(_sig(straggle=s))
    assert not isinstance(first, Quarantine)  # patience holds one window
    second = drm.evaluate(_sig(straggle=s))
    assert isinstance(second, Quarantine)
    assert second.lane == 2
    assert second.straggle_ms >= 50.0
    assert second.est_migration > 0.0  # the fold is priced, not free
    assert drm.quarantined and drm.quarantined[0][0] == 2


def test_health_evict_on_consecutive_failures():
    drm = DRMaster(uniform_partitioner(4, 64, 0), _health_cfg())
    r = np.zeros(4, np.int64)
    r[1] = 2
    acts = [drm.evaluate(_sig(retries=r)) for _ in range(4)]
    evicts = [a for a in acts if isinstance(a, Evict)]
    assert len(evicts) == 1 and evicts[0].lane == 1
    assert evicts[0].failures >= 3
    assert not drm.quarantined  # evict is permanent, nothing parked


def test_health_failure_streak_resets_on_clean_window():
    drm = DRMaster(uniform_partitioner(4, 64, 0), _health_cfg())
    r = np.zeros(4, np.int64)
    r[1] = 1
    drm.evaluate(_sig(retries=r))
    drm.evaluate(_sig(retries=r))
    drm.evaluate(_sig())  # clean window: failures must be *consecutive*
    acts = [drm.evaluate(_sig(retries=r)) for _ in range(2)]
    assert not any(isinstance(a, Evict) for a in acts)


def test_health_recover_probe_after_timer():
    drm = DRMaster(uniform_partitioner(4, 64, 0),
                   _health_cfg(health_recover_after=2))
    s = np.zeros(4)
    s[0] = 0.2
    drm.evaluate(_sig(straggle=s))
    q = drm.evaluate(_sig(straggle=s))
    assert isinstance(q, Quarantine)
    acts = [drm.evaluate(_sig(w=3)) for _ in range(3)]
    recs = [a for a in acts if isinstance(a, Recover)]
    assert len(recs) == 1 and recs[0].lane == 0
    assert not drm.quarantined


def test_health_no_recover_without_timer():
    drm = DRMaster(uniform_partitioner(4, 64, 0),
                   _health_cfg(health_recover_after=0))
    s = np.zeros(4)
    s[0] = 0.2
    drm.evaluate(_sig(straggle=s))
    assert isinstance(drm.evaluate(_sig(straggle=s)), Quarantine)
    acts = [drm.evaluate(_sig(w=3)) for _ in range(4)]
    assert not any(isinstance(a, Recover) for a in acts)
    assert drm.quarantined  # parked forever until an explicit policy


def test_health_single_worker_never_folds():
    drm = DRMaster(uniform_partitioner(1, 64, 0), _health_cfg())
    s = np.asarray([0.5])
    for _ in range(4):
        a = drm.evaluate(_sig(w=1, straggle=s))
        assert not isinstance(a, (Quarantine, Evict))


def test_health_state_rides_snapshots():
    drm = DRMaster(uniform_partitioner(4, 64, 0),
                   _health_cfg(health_recover_after=4))
    s = np.zeros(4)
    s[3] = 0.2
    drm.evaluate(_sig(straggle=s))
    drm.evaluate(_sig(straggle=s))
    assert drm.quarantined
    restored = DRMaster.restore(drm.snapshot(), drm.config)
    assert restored.lane_health is not None
    assert restored.lane_health.num_lanes == drm.lane_health.num_lanes
    np.testing.assert_allclose(restored.lane_health.wall_ewma,
                               drm.lane_health.wall_ewma)
    assert restored.quarantined == drm.quarantined
    assert restored.last_health_action == drm.last_health_action


def test_legacy_snapshot_without_health_keys_restores():
    drm = DRMaster(uniform_partitioner(4, 64, 0), _health_cfg())
    snap = drm.snapshot()  # health layer never observed: no health keys
    assert not any(k.startswith("health_") for k in snap)
    restored = DRMaster.restore(snap, drm.config)
    assert restored.lane_health is None
    assert restored.quarantined == []


def test_note_lost_records_forced_eviction():
    drm = DRMaster(uniform_partitioner(4, 64, 0), _health_cfg())
    drm.evaluate(_sig())
    before = drm.batches_seen
    drm.note_lost(2, reason="worker lost on lane 2")
    assert drm.batches_seen == before + 1
    assert drm.lane_health is None  # stale labels dropped; rebuilt next window
    assert any(h.get("health", (None,))[0] == "evict"
               for h in drm.history if "health" in h)


# ---------------------------------------------------------------------------
# satellites: DRConfig validation, telemetry wall hardening
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(grow_trigger=1.2, shrink_trigger=1.3),
    dict(grow_trigger=1.2, shrink_trigger=1.2),
    dict(backend_ragged_below=0.9, backend_dense_above=0.5),
    dict(split_trigger=0.1, unsplit_trigger=0.2),
    dict(resize_cooldown=-1),
    dict(health_patience=-1),
    dict(health_cooldown=-2),
    dict(health_recover_after=-1),
    dict(snapshot_interval=-3),
    dict(health_failure_threshold=0),
    dict(health_straggler_ms=-5.0),
    dict(target_throughput=-1.0),
])
def test_drconfig_rejects_misconfiguration(kw):
    with pytest.raises(ValueError):
        DRConfig(**kw)


def test_drconfig_valid_defaults_construct():
    DRConfig()
    DRConfig(health_enabled=True, snapshot_interval=5)


def test_telemetry_clamps_degenerate_walls():
    t = Telemetry("test")
    t.record_exchange(ExchangeStats(rows=10, wall_s=float("nan"),
                                    backend="dense"))
    t.record_exchange(ExchangeStats(rows=10, wall_s=-0.5, backend="dense"))
    t.record_exchange(ExchangeStats(rows=10, wall_s=float("inf"),
                                    backend="dense"))
    t.record_exchange(ExchangeStats(rows=10, wall_s=0.25, backend="dense"))
    sig = t.snapshot(loads=np.ones(2))
    assert sig.degenerate_walls == 3
    assert sig.exchange_wall_s == 0.25  # poison clamped, clean sample kept
    assert t.wall_ewma["dense"] == 0.25  # EWMA fed only the clean sample
    assert t.degenerate_walls_total == 3
    # counter survives window resets
    t.record_exchange(ExchangeStats(rows=1, wall_s=float("nan")))
    assert t.snapshot(loads=np.ones(2)).degenerate_walls == 1
    assert t.degenerate_walls_total == 4


def test_telemetry_record_fault_grows_vectors():
    t = Telemetry("test")
    t.record_fault(2, straggle_s=0.1, retries=1)
    t.record_fault(0, straggle_s=0.05)
    t.record_fault(2, retries=2)
    sig = t.snapshot(loads=np.ones(3))
    np.testing.assert_allclose(sig.lane_straggle_s, [0.05, 0.0, 0.1])
    np.testing.assert_array_equal(sig.lane_retries, [0, 0, 3])
    # next window starts clean
    assert t.snapshot(loads=np.ones(3)).lane_straggle_s is None
