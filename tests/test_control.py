"""Control plane: signals, policy stack, cooldown guard, decision log.

Covers the tentpole wiring (all three consumers route through
``repro.control``) plus the oscillation-guard and throughput-shrink
policy rules, at both the unit (synthetic ``Signals``) and end-to-end
(``StreamingJob`` on a sawtooth workload) level.
"""
import numpy as np
import pytest

from repro.exchange import ExchangeStats
from repro.control import (
    NoOp,
    Repartition,
    Replace,
    Resize,
    Signals,
    SwitchBackend,
    Telemetry,
)
from repro.core.drm import DRConfig, DRMaster
from repro.core.migration import (
    exchange_lane_cost,
    fold_to_workers,
    migration_capacity,
    plan_migration,
)
from repro.core.partitioner import uniform_partitioner
from repro.core.streaming import StreamingJob
from repro.exchange import resolve_backend
from repro.data.generators import sawtooth_skew
from repro.moe.kip_placement import PlacementController
from repro.serve.scheduler import DRScheduler

HOT = np.array([10.0, 1.0, 1.0, 1.0])
FLAT = np.array([1.0, 1.0, 1.0, 1.0])


def _warm_drm(cfg=None, n=4) -> DRMaster:
    """DRM with a skewed sketch so the repartition policy has a histogram.

    ``total_records`` is double the summary mass, so half the traffic is
    untracked tail riding the host tables — the cost model must account it
    when hosts are re-binned."""
    drm = DRMaster(uniform_partitioner(n, heavy_capacity=128), cfg or DRConfig())
    keys = np.arange(8, dtype=np.int64)
    counts = np.array([400.0, 100, 50, 25, 12, 6, 3, 1])
    drm.observe(keys[None], counts[None], total_records=2.0 * float(counts.sum()))
    return drm


# ---------------------------------------------------------------------------
# signals + telemetry
# ---------------------------------------------------------------------------


def test_signals_derived_metrics():
    s = Signals(loads=np.array([4.0, 2, 1, 1]), num_workers=2,
                records=600.0, window_wall_s=2.0)
    assert s.imbalance == pytest.approx(2.0)
    np.testing.assert_allclose(s.worker_loads, [5.0, 3.0])  # p % 2 folding
    assert s.worker_imbalance == pytest.approx(1.25)
    assert s.throughput == pytest.approx(300.0)
    assert s.per_worker_throughput == pytest.approx(150.0)
    empty = Signals(loads=np.zeros(4))
    assert empty.imbalance == 1.0 and empty.throughput == 0.0


def test_telemetry_window_accumulates_until_safe_point():
    t = Telemetry("stream")
    t.record_batch(100)
    t.record_exchange(ExchangeStats(rows=64, wall_s=0.5))
    peek = t.snapshot(loads=FLAT, at_safe_point=False)  # peek: no reset
    t.record_batch(100)
    t.record_overflow(shuffle=3, migration=2)
    s = t.snapshot(loads=FLAT, num_workers=2, state_rows=7)
    assert peek.records == 100 and s.records == 200  # window spanned both
    assert s.exchange_rows == 64 and s.exchange_wall_s == pytest.approx(0.5)
    assert s.shuffle_overflow == 3 and s.migration_overflow == 2
    assert s.state_rows == 7 and s.consumer == "stream"
    fresh = t.snapshot(loads=FLAT)  # the safe point reset the window
    assert fresh.records == 0 and fresh.exchange_rows == 0


def test_fold_to_workers_vector_and_matrix():
    loads = np.array([5.0, 1, 2, 3, 4, 6])
    np.testing.assert_allclose(fold_to_workers(loads, 2), [11.0, 10.0])
    m = np.zeros((4, 4))
    m[0, 3] = 5.0  # worker 0 -> worker 1
    m[2, 0] = 2.0  # worker 0 -> worker 0 (same worker after folding)
    folded = fold_to_workers(m, 2)
    assert folded[0, 1] == 5.0 and folded[0, 0] == 2.0


def test_exchange_lane_cost_matches_capacity_rule():
    """The policy's cost estimate is migration_capacity's sizing rule minus
    the row quantization — same fold, same slack, same peak."""
    old = uniform_partitioner(4, seed=0)
    new = uniform_partitioner(4, seed=3)
    live = np.arange(512, dtype=np.int64)
    plan = plan_migration(old, new, live)
    cost = exchange_lane_cost(plan, num_workers=2)
    cap = migration_capacity(plan, num_workers=2)
    assert cost > 0
    assert cap == max(8, int(np.ceil(cost / 8.0) * 8))
    # unfolded (unknown workers): partition-level lanes are the unit — the
    # peak of finer lanes can only be <= the worker-folded aggregate's
    assert 0 < exchange_lane_cost(plan) <= cost


# ---------------------------------------------------------------------------
# the policy stack through DRMaster.evaluate
# ---------------------------------------------------------------------------


def test_evaluate_not_safe_point_declines_without_logging():
    drm = _warm_drm()
    a = drm.evaluate(Signals(loads=HOT, at_safe_point=False))
    assert isinstance(a, NoOp) and a.reason == "not-checkpoint-tick"
    # a peek is not a decision: the log counts safe points only
    assert len(drm.decisions) == 0 and drm.decisions.counts() == (0, 0)


def test_decision_log_bounded_with_exact_counts():
    drm = _warm_drm(DRConfig())
    drm.decisions.max_records = 16
    for _ in range(40):
        drm.evaluate(Signals(loads=FLAT), policies_enabled=False)
    assert len(drm.decisions.records) == 16  # trimmed ...
    assert drm.decisions.counts() == (0, 40)  # ... counters stay cumulative


def test_evaluate_requested_resize_wins():
    drm = _warm_drm(DRConfig(elastic=True))
    a = drm.evaluate(Signals(loads=HOT), requested_resize=8)
    assert isinstance(a, Resize) and a.target == 8 and a.requested
    assert a.reason == "resize 4->8"
    # a request equal to the current topology falls through to the policies
    a2 = drm.evaluate(Signals(loads=FLAT), requested_resize=4)
    assert isinstance(a2, NoOp)


def test_evaluate_disabled_policies_noop():
    drm = _warm_drm()
    a = drm.evaluate(Signals(loads=HOT), policies_enabled=False)
    assert isinstance(a, NoOp) and a.reason == "dr-disabled"
    assert drm.batches_seen == 0  # nothing advanced: no policy ran


def test_evaluate_repartition_installs_and_logs():
    drm = _warm_drm(DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0))
    before = drm.partitioner
    a = drm.evaluate(Signals(loads=np.array([500.0, 30, 30, 37])))
    assert isinstance(a, Repartition)
    assert drm.partitioner is a.partitioner and a.prev is before
    assert a.est_migration > 0  # exchange-lane accounting, not zero
    taken, declined = drm.decisions.counts()
    assert (taken, declined) == (1, 0)


def test_cost_model_blocks_expensive_migration():
    drm = _warm_drm(DRConfig(imbalance_trigger=1.05, migration_cost_weight=1e9))
    a = drm.evaluate(Signals(loads=np.array([500.0, 30, 30, 37])))
    assert isinstance(a, NoOp) and a.reason.startswith("gain ")
    assert a.est_migration > 0  # the declined cost is recorded too


# ---------------------------------------------------------------------------
# oscillation guard (cooldown) + throughput shrink
# ---------------------------------------------------------------------------


def _sawtooth_cfg(cooldown: int) -> DRConfig:
    return DRConfig(elastic=True, min_partitions=4, max_partitions=8,
                    grow_trigger=1.5, shrink_trigger=1.05, resize_patience=1,
                    resize_cooldown=cooldown, imbalance_trigger=1e9)


def _drive_sawtooth(drm: DRMaster, ticks: int = 12) -> list[int]:
    """Alternate hot/flat loads through the full stack; execute resizes the
    way a driver would (replan at the safe point).  Returns topology sizes."""
    sizes = []
    for t in range(ticks):
        loads = HOT if (t // 2) % 2 == 0 else FLAT
        loads = np.resize(loads, drm.partitioner.num_partitions)
        a = drm.evaluate(Signals(loads=loads))
        if isinstance(a, Resize):
            drm.replan_resize(a.target)
            sizes.append(a.target)
    return sizes


def test_cooldown_guard_stops_pingpong():
    # without the guard the sawtooth ping-pongs the partition count
    sizes = _drive_sawtooth(DRMaster(uniform_partitioner(4), _sawtooth_cfg(0)))
    dirs = [s > p for s, p in zip(sizes, [4] + sizes[:-1])]
    assert sum(1 for a, b in zip(dirs, dirs[1:]) if a != b) >= 2, sizes
    # with it on: the initial grow fires, everything after is declined
    drm = DRMaster(uniform_partitioner(4), _sawtooth_cfg(100))
    sizes = _drive_sawtooth(drm)
    assert sizes == [8]
    declined = [d for d in drm.decisions.records
                if d.detail.get("resize_declined") == "resize-cooldown"]
    assert declined, "cooldown declines must be observable in the log"


def test_cooldown_expiry_allows_followup_resize():
    drm = DRMaster(uniform_partitioner(4), _sawtooth_cfg(3))
    assert _drive_sawtooth(drm, ticks=2) == [8]   # grow at tick 0
    # flat ticks inside the cooldown: declined; after expiry: shrink fires
    sizes = _drive_sawtooth(drm, ticks=2)  # ticks are hot again: at-max
    for _ in range(6):
        a = drm.evaluate(Signals(loads=np.resize(FLAT, 8)))
        if isinstance(a, Resize):
            drm.replan_resize(a.target)
            assert a.target == 4
            return
    raise AssertionError("shrink never fired after cooldown expiry")


def test_throughput_below_target_shrinks_when_balanced():
    """An idle stream in the trigger dead zone (imbalance can't shrink it)
    still shrinks on the capacity-target signal."""
    cfg = DRConfig(elastic=True, min_partitions=2, max_partitions=16,
                   grow_trigger=1.5, shrink_trigger=0.9,  # imb >= 1 always:
                   resize_patience=2, target_throughput=1000.0)  # unreachable
    drm = DRMaster(uniform_partitioner(4), cfg)
    idle = Signals(loads=np.array([1.2, 1.0, 1.0, 1.0]),  # dead zone
                   records=100.0, window_wall_s=1.0)      # 100 rec/s << 1000
    assert isinstance(drm.resize_policy.evaluate(drm, idle), NoOp)  # patience 1/2
    a = drm.resize_policy.evaluate(drm, idle)
    assert isinstance(a, Resize) and a.target == 2
    # same loads at a healthy throughput: dead zone holds, no shrink
    drm2 = DRMaster(uniform_partitioner(4), cfg)
    busy = Signals(loads=np.array([1.2, 1.0, 1.0, 1.0]),
                   records=10_000.0, window_wall_s=1.0)
    assert isinstance(drm2.resize_policy.evaluate(drm2, busy), NoOp)
    a2 = drm2.resize_policy.evaluate(drm2, busy)
    assert isinstance(a2, NoOp) and a2.reason == "dead-zone"


def test_low_throughput_never_shrinks_a_hotspot():
    """Idle + hot-spotted at max_partitions must not shrink: fewer bins
    would concentrate the hotspot further.  The throughput shrink covers
    the trigger dead zone only."""
    cfg = DRConfig(elastic=True, min_partitions=2, max_partitions=4,
                   grow_trigger=1.5, shrink_trigger=1.05,
                   resize_patience=1, target_throughput=1000.0)
    drm = DRMaster(uniform_partitioner(4), cfg)  # n == max_partitions
    hot_idle = Signals(loads=np.array([100.0, 1.0, 1.0, 1.0]),
                       records=10.0, window_wall_s=1.0)  # 10 rec/s << 1000
    for _ in range(4):
        a = drm.resize_policy.evaluate(drm, hot_idle)
        assert isinstance(a, NoOp) and a.reason == "at-max", a


def test_scheduler_policy_scale_in_on_idle_replicas():
    """Sustained balanced (idle) queues shrink the replica set through the
    checkpoint policy path — scale-in must not be floored at the current
    replica count."""
    sched = DRScheduler(4, dr=DRConfig(lam=4.0, elastic=True, min_partitions=2,
                                       max_partitions=8, grow_trigger=1.5,
                                       shrink_trigger=1.2, resize_patience=2,
                                       imbalance_trigger=1e9))
    rng = np.random.default_rng(2)
    results = []
    for _ in range(3):
        window = rng.integers(0, 10_000, 512)  # uniform sessions: balanced
        for s in window:
            sched.route(int(s), 1.0)
        results.append(sched.checkpoint(window))
        sched.drain(1e9)  # fully idle between windows
    assert any(r["resized"] for r in results), results
    assert len(sched.replicas) == 2


def test_replan_resize_rewarns_sketch_before_growing():
    """A grow widens the heavy-key budget (lam * n); stale floor-dominated
    sketch entries must not surface in the resized heavy table."""
    drm = DRMaster(uniform_partitioner(4, heavy_capacity=128),
                   DRConfig(lam=2.0, sketch_capacity=8, sketch_decay=1.0))
    heavy = np.arange(4, dtype=np.int64)
    drm.observe(heavy[None], np.full((1, 4), 500.0))
    for k in range(100, 140):  # one-off parade: evictions raise the floor
        drm.observe(np.array([[k]], dtype=np.int64), np.array([[1.0]]))
    assert drm.sketch._floor > 0
    stale = set(drm.sketch.histogram().keys.tolist()) - set(heavy.tolist())
    assert stale  # the un-rescaled window would read into these
    new = drm.replan_resize(8)  # top_b jumps 8 -> 16
    isolated = set(new.heavy_keys[new.heavy_keys >= 0].tolist())
    assert isolated & set(heavy.tolist())
    assert not (isolated & stale), isolated & stale


def test_trigger_gap_dead_zone_enforced():
    # validated unconditionally now: an inverted dead zone is wrong even
    # while the feature flag is off
    with pytest.raises(ValueError):
        DRConfig(elastic=True, grow_trigger=1.2, shrink_trigger=1.3)
    with pytest.raises(ValueError):
        DRConfig(grow_trigger=1.2, shrink_trigger=1.3)


# ---------------------------------------------------------------------------
# end-to-end: StreamingJob sawtooth through the full runtime
# ---------------------------------------------------------------------------


def _reversals(sizes: list[int], start: int = 4) -> int:
    dirs = [s > p for s, p in zip(sizes, [start] + sizes[:-1])]
    return sum(1 for a, b in zip(dirs, dirs[1:]) if a != b)


def test_streaming_sawtooth_no_pingpong_with_guard():
    """End-to-end oscillation guard: plain DR rebalances contents during the
    flat phase (so the measured imbalance genuinely flips across the
    triggers), and the elastic policy ping-pongs the partition count unless
    the cooldown guard is on."""
    def run(cooldown):
        job = StreamingJob(
            num_partitions=4, state_capacity=8192,
            dr=DRConfig(elastic=True, min_partitions=4, max_partitions=8,
                        grow_trigger=2.0, shrink_trigger=1.45,
                        resize_patience=1, resize_cooldown=cooldown,
                        imbalance_trigger=1.3, migration_cost_weight=0.05,
                        sketch_decay=0.5),
        )
        ms = job.run(sawtooth_skew(12, 4096, num_keys=2_000, exponent=1.8,
                                   period=3, seed=7))
        return job, [m.num_partitions for m in ms if m.resized]

    job_off, sizes_off = run(cooldown=0)
    assert len(sizes_off) >= 2 and _reversals(sizes_off) >= 2, sizes_off
    job, sizes = run(cooldown=100)
    assert sizes == [8], sizes  # grow-under-skew fires once, never reverses
    assert _reversals(sizes) == 0
    declined = [d for d in job.drm.decisions.records
                if d.detail.get("resize_declined") == "resize-cooldown"]
    assert declined, "cooldown declines must be observable in the log"
    # BatchMetrics reads the decision log's action/reason
    first = [m for m in job.metrics if m.resized][0]
    assert first.action == "resize" and first.reason == "resize 4->8"


# ---------------------------------------------------------------------------
# decision-log persistence: snapshot/restore carries the history
# ---------------------------------------------------------------------------


def test_decision_log_snapshot_restore_roundtrip():
    """A restored DRM keeps its decision history — records, reasons,
    details, and the cumulative taken/declined counters — and keeps
    logging into the same history."""
    drm = _warm_drm(DRConfig(elastic=True, imbalance_trigger=1.05,
                             migration_cost_weight=0.0, resize_cooldown=100))
    drm.exchange_backend = resolve_backend("ragged")
    drm.evaluate(Signals(loads=np.array([500.0, 30, 30, 37])))  # repartition
    drm.evaluate(Signals(loads=FLAT))                           # declined
    snap = drm.snapshot()
    restored = DRMaster.restore(snap, drm.config)
    # the restored master prices plans with the same transport it ran on
    assert restored.exchange_backend.name == "ragged"
    assert restored.decisions.counts() == drm.decisions.counts() == (1, 1)
    assert len(restored.decisions) == len(drm.decisions)
    for a, b in zip(restored.decisions.records, drm.decisions.records):
        assert a == b, (a, b)
    assert restored.decisions.consumer == drm.decisions.consumer
    # the restored log keeps accumulating on the shared counters
    restored.evaluate(Signals(loads=FLAT), policies_enabled=False)
    assert restored.decisions.counts() == (1, 2)


def test_decision_log_restore_tolerates_old_snapshots():
    drm = _warm_drm()
    snap = drm.snapshot()
    for k in list(snap):
        if k.startswith("decisions_"):
            snap.pop(k)
    restored = DRMaster.restore(snap, drm.config)
    assert len(restored.decisions) == 0 and restored.decisions.counts() == (0, 0)


def test_streaming_snapshot_carries_decision_log():
    """End-to-end: a StreamingJob restore resumes with its decision history
    (ROADMAP open item: the log used to live in memory per run)."""
    job = StreamingJob(num_partitions=4, state_capacity=2048)
    rng = np.random.default_rng(0)
    for _ in range(3):
        job.process_batch(rng.integers(0, 200, 1024))
    snap = job.snapshot()
    fresh = StreamingJob(num_partitions=4, state_capacity=2048)
    fresh.restore(snap)
    assert fresh.drm.decisions.counts() == job.drm.decisions.counts()
    assert [d.reason for d in fresh.drm.decisions.records] == \
        [d.reason for d in job.drm.decisions.records]


# ---------------------------------------------------------------------------
# backend-priced migration cost + exchange padded-vs-shipped signals
# ---------------------------------------------------------------------------


def test_repartition_cost_uses_host_backend():
    """The same skewed stream priced under dense vs ragged transports: the
    ragged rule (mean real rows) is cheaper than the dense rule (padded
    peak), so a gain that cannot pay for the dense pad can still pay for
    the ragged traffic — the transport changes the decision."""
    from repro.exchange import DenseBackend, RaggedBackend

    loads = np.array([500.0, 30, 30, 37])

    def decide(backend, weight):
        drm = _warm_drm(DRConfig(imbalance_trigger=1.05,
                                 migration_cost_weight=weight))
        drm.exchange_backend = backend
        return drm.evaluate(Signals(loads=loads, num_workers=4))

    dense_free = decide(DenseBackend(), 0.0)
    assert isinstance(dense_free, Repartition)
    est_dense = dense_free.est_migration
    ragged_free = decide(RaggedBackend(), 0.0)
    assert isinstance(ragged_free, Repartition)
    est_ragged = ragged_free.est_migration
    assert 0 < est_ragged < est_dense
    # a weight between the two gains: dense declines, ragged proceeds
    gain = dense_free.measured_imbalance - dense_free.planned_imbalance
    weight = gain / ((est_dense + est_ragged) / 2.0)
    dense_gated = decide(DenseBackend(), weight)
    ragged_gated = decide(RaggedBackend(), weight)
    assert isinstance(dense_gated, NoOp) and dense_gated.reason.startswith("gain ")
    assert isinstance(ragged_gated, Repartition)


def test_telemetry_padded_vs_shipped_and_hot_lane():
    t = Telemetry("stream")
    t.record_exchange(ExchangeStats(rows=100, wall_s=0.1, padded_rows=400,
                                    lane_overflow=np.array([0, 7, 0])))
    t.record_exchange(ExchangeStats(rows=50))  # dense-style: shipped == padded
    t.record_exchange(ExchangeStats(rows=0, lane_overflow=np.array([0, 2, 1])))
    s = t.snapshot(loads=FLAT)
    assert s.exchange_rows == 150 and s.exchange_padded_rows == 450
    assert s.exchange_padding_fraction == pytest.approx(150 / 450)
    np.testing.assert_array_equal(s.lane_overflow, [0, 9, 1])
    assert s.hot_lane == 1
    empty = t.snapshot(loads=FLAT)
    assert empty.hot_lane == -1 and empty.exchange_padding_fraction == 0.0


def test_telemetry_lane_overflow_survives_lane_count_change():
    """An elastic resize changes the lane count mid-window; both vectors
    fold onto the wider one, no drop lost."""
    t = Telemetry("stream")
    t.record_exchange(ExchangeStats(rows=8, lane_overflow=np.array([1, 2])))
    t.record_exchange(ExchangeStats(rows=8, lane_overflow=np.array([0, 1, 5, 0])))
    s = t.snapshot(loads=FLAT)
    np.testing.assert_array_equal(s.lane_overflow, [1, 3, 5, 0])
    assert s.hot_lane == 2


# ---------------------------------------------------------------------------
# the transport as an actuator: BackendPolicy + SwitchBackend
# ---------------------------------------------------------------------------


def _exchange_signals(fraction: float, padded: int = 1000) -> Signals:
    """Safe-point signals whose measured lane occupancy is ``fraction``."""
    return Signals(loads=FLAT, exchange_padded_rows=padded,
                   exchange_occupied_rows=int(fraction * padded),
                   exchange_rows=padded)


def test_telemetry_explicit_zero_occupancy_is_a_measurement():
    """Occupancy 0 with a nonzero provision means all-empty lanes (maximal
    padding waste) — the fraction must read 0.0, not fall back to the
    shipped rows as if occupancy had never been recorded."""
    t = Telemetry("stream")
    t.record_exchange(ExchangeStats(rows=100, padded_rows=100, occupied_rows=0))
    s = t.snapshot(loads=FLAT)
    assert s.exchange_padding_fraction == 0.0
    # unrecorded occupancy still falls back to shipped rows
    t.record_exchange(ExchangeStats(rows=50, padded_rows=100))
    s2 = t.snapshot(loads=FLAT)
    assert s2.exchange_occupied_rows == 50
    assert s2.exchange_padding_fraction == pytest.approx(0.5)


def test_backend_policy_flips_dense_to_ragged_with_patience():
    """Sustained low lane occupancy flips a dense job to the ragged
    transport after the patience streak; the decline and the switch both
    land in the decision log, and the DRM's plan pricing follows."""
    cfg = DRConfig(auto_backend=True, backend_patience=2, imbalance_trigger=1e9)
    drm = _warm_drm(cfg)
    a1 = drm.evaluate(_exchange_signals(0.2))
    assert isinstance(a1, NoOp)
    assert drm.decisions.records[-1].detail["backend_declined"].startswith(
        "backend-patience")
    a2 = drm.evaluate(_exchange_signals(0.2))
    assert isinstance(a2, SwitchBackend) and a2.backend == "ragged"
    assert a2.padding_fraction == pytest.approx(0.2)
    assert drm.exchange_backend.name == "ragged"
    d = drm.decisions.records[-1]
    assert d.kind == "switch_backend" and d.taken
    # a window with no exchange keeps the streak untouched, and occupancy
    # inside the dead zone resets it
    drm2 = _warm_drm(cfg)
    drm2.evaluate(_exchange_signals(0.2))
    a = drm2.evaluate(Signals(loads=FLAT))
    assert isinstance(a, NoOp)
    assert drm2.backend_streak == 1
    drm2.evaluate(_exchange_signals(0.7))  # dead zone: neither threshold
    assert drm2.backend_streak == 0


def test_backend_switch_oscillation_guard():
    """A sawtooth occupancy straddling both thresholds ping-pongs the
    transport with the guard off; with the cooldown spanning the window the
    same workload produces exactly one switch and zero reversals (the
    resize ping-pong test, one actuator over)."""
    def run(cooldown):
        cfg = DRConfig(auto_backend=True, backend_patience=1,
                       backend_cooldown=cooldown, imbalance_trigger=1e9)
        drm = _warm_drm(cfg)
        switches = []
        for t in range(12):
            frac = 0.2 if t % 2 == 0 else 1.0
            a = drm.evaluate(_exchange_signals(frac))
            if isinstance(a, SwitchBackend):
                switches.append(a.backend)
        return switches

    off = run(0)
    dirs = [s == "ragged" for s in off]
    reversals_off = sum(1 for a, b in zip(dirs, dirs[1:]) if a != b)
    assert reversals_off > 0, off
    on = run(100)
    assert on == ["ragged"], on  # one flip, no reversal inside the cooldown


def test_backend_switch_survives_snapshot_restore():
    cfg = DRConfig(auto_backend=True, backend_patience=1,
                   backend_cooldown=50, imbalance_trigger=1e9)
    drm = _warm_drm(cfg)
    a = drm.evaluate(_exchange_signals(0.1))
    assert isinstance(a, SwitchBackend)
    restored = DRMaster.restore(drm.snapshot(), cfg)
    assert restored.exchange_backend.name == "ragged"
    assert restored.last_backend_switch == drm.last_backend_switch
    # still inside the cooldown: the restored master cannot reverse
    b = restored.evaluate(_exchange_signals(1.0))
    assert isinstance(b, NoOp)
    assert restored.decisions.records[-1].detail["backend_declined"] == \
        "backend-cooldown"


def test_scheduler_backend_policy_parks_without_lane_telemetry():
    """The serving scheduler records no exchange-lane occupancy (its KV
    migrations are modeled, not bufferized), so the actuator declines with
    the no-exchange-window reason instead of flipping on a signal it never
    measured — the documented contract until session moves ship through
    real lanes."""
    sched = DRScheduler(4, dr=DRConfig(auto_backend=True, backend_patience=1,
                                       imbalance_trigger=1e9))
    rng = np.random.default_rng(3)
    for _ in range(3):
        window = rng.integers(0, 100, 50)
        for s in window:
            sched.route(int(s), 8.0)
        r = sched.checkpoint(window)
        assert r["backend"] == "dense" and not r["repartitioned"]
    declines = [d.detail.get("backend_declined")
                for d in sched.drm.decisions.records]
    assert all(reason == "backend-no-exchange-window" for reason in declines)


def test_streaming_auto_backend_switch_end_to_end():
    """A generously padded dense job flips to ragged at a safe point, the
    switch is visible in BatchMetrics and the decision log, never reverses
    inside the cooldown, changes no results, and survives restore."""
    dr = DRConfig(auto_backend=True, backend_patience=2, backend_cooldown=50,
                  imbalance_trigger=1e9)
    job = StreamingJob(num_partitions=4, state_capacity=2048,
                       capacity_factor=4.0, dr=dr)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 500, 2048) for _ in range(6)]
    ms = job.run(batches)
    switches = [m for m in ms if m.action == "switch_backend"]
    assert len(switches) == 1, [m.action for m in ms]
    assert job.exchange_backend.name == "ragged"
    # a switch is taken but moves no state: it must not read as a repartition
    assert not switches[0].repartitioned and not switches[0].resized
    sw = switches[0].batch
    assert all(m.backend == "dense" for m in ms[:sw + 1])
    assert all(m.backend == "ragged" for m in ms[sw + 1:])
    # ragged batches ship fewer rows than their padded provision
    assert all(m.shipped_rows < m.padded_rows for m in ms[sw + 1:])
    assert any(d.kind == "switch_backend" and d.taken
               for d in job.drm.decisions.records)
    # bit-identical state vs. a dense-pinned job on the same stream: the
    # actuator changes traffic, never results
    pinned = StreamingJob(num_partitions=4, state_capacity=2048,
                          capacity_factor=4.0,
                          dr=DRConfig(imbalance_trigger=1e9))
    pinned.run(batches)
    for key in rng.integers(0, 500, 16):
        assert job.state_count(int(key)) == pinned.state_count(int(key))
    # restore resumes on the switched transport
    snap = job.snapshot()
    fresh = StreamingJob(num_partitions=4, state_capacity=2048,
                         capacity_factor=4.0, dr=dr)
    assert fresh.exchange_backend.name == "dense"
    fresh.restore(snap)
    assert fresh.exchange_backend.name == "ragged"
    m = fresh.process_batch(batches[0])
    assert m.backend == "ragged"


# ---------------------------------------------------------------------------
# the other consumers: serving scheduler + MoE placement
# ---------------------------------------------------------------------------


def test_scheduler_checkpoint_uniform_schema():
    """Resize, repartition, and decline branches all return the same keys."""
    rng = np.random.default_rng(0)
    sched = DRScheduler(4, dr=DRConfig(lam=4.0, elastic=True, min_partitions=2,
                                       max_partitions=8, grow_trigger=1.5,
                                       shrink_trigger=1.02, resize_patience=1,
                                       imbalance_trigger=1e9))
    keys = ["repartitioned", "resized", "num_replicas", "imbalance",
            "moved_sessions", "reason", "backend"]
    results = []
    for _ in range(2):
        window = []
        for _ in range(200):
            s = 7 if rng.random() < 0.7 else int(rng.integers(100, 5000))
            sched.route(s, 32.0)
            window.append(s)
        results.append(sched.checkpoint(np.array(window)))
        sched.drain(2000.0)
    assert any(r["resized"] for r in results)
    for r in results:
        assert sorted(r.keys()) == sorted(keys), r
        assert isinstance(r["reason"], str) and r["reason"]
    assert len(sched.drm.decisions) == len(results)


def test_placement_controller_logs_decisions():
    ctl = PlacementController(16, 4, trigger=1.05)
    ctl.observe(np.ones(16))
    changed, _, _ = ctl.maybe_update()
    assert not changed
    assert ctl.decisions.records[-1].reason == "balanced"
    loads = np.ones(16)
    loads[0], loads[1] = 20.0, 15.0
    for _ in range(3):
        ctl.observe(loads)
    changed, _, _ = ctl.maybe_update()
    assert changed
    d = ctl.decisions.records[-1]
    assert d.taken and d.kind == "replace" and d.consumer == "moe"
    taken, declined = ctl.decisions.counts()
    assert (taken, declined) == (1, 1)


def test_placement_weight_costing_gates_which_placement_wins():
    """With expert-weight bytes folded through exchange_lane_cost, the
    policy prices every candidate (including "stay"): a prohibitive cost
    weight declines the re-placement outright, a free one re-places — the
    §4 gain-vs-migration-cost rule applied to expert weights."""
    loads = np.ones(16)
    loads[0], loads[1] = 20.0, 15.0

    def drive(cost_weight):
        ctl = PlacementController(16, 4, trigger=1.05,
                                  expert_weight_bytes=4096.0,
                                  cost_weight=cost_weight)
        for _ in range(3):
            ctl.observe(loads)
        return ctl, ctl.maybe_update()

    ctl, (changed, _, perm) = drive(cost_weight=0.0)
    assert changed and (perm != np.arange(16)).any()
    assert ctl.history[-1]["migration_bytes"] > 0
    assert ctl.history[-1]["choice"] in ("pack", "waterfill")
    assert ctl.decisions.records[-1].detail["choice"] == ctl.history[-1]["choice"]

    ctl, (changed, _, perm) = drive(cost_weight=1e9)
    assert not changed and (perm == np.arange(16)).all()
    d = ctl.decisions.records[-1]
    assert not d.taken and d.reason.startswith("placement gain <= migration cost")


def test_placement_costing_off_keeps_legacy_behavior():
    """expert_weight_bytes=0 (default): the policy only decides whether, the
    host computes the KIP placement — the pre-costing path."""
    ctl = PlacementController(16, 4, trigger=1.05)
    loads = np.ones(16)
    loads[0] = 20.0
    for _ in range(3):
        ctl.observe(loads)
    changed, _, _ = ctl.maybe_update()
    assert changed
    assert ctl.decisions.records[-1].reason.startswith("imbalance ")
    assert ctl.history[-1]["migration_bytes"] == 0.0


def test_batchmetrics_carries_action_kind():
    job = StreamingJob(num_partitions=4, state_capacity=2048, dr_enabled=False)
    rng = np.random.default_rng(1)
    m = job.process_batch(rng.integers(0, 500, 1024))
    assert m.action == "noop" and m.reason == "dr-disabled"
    job.resize(8)
    m2 = job.process_batch(rng.integers(0, 500, 1024))
    assert m2.action == "resize" and m2.resized
