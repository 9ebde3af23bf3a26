"""Integration tests: shuffle, keyed state, migration, streaming DR loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Histogram, kip_update, uniform_partitioner
from repro.core.drm import DRConfig, DRMaster
from repro.core.hashing import KEY_SENTINEL
from repro.core.replay import BatchJob
from repro.core.shuffle import make_migrate_step, make_shuffle_step
from repro.core.state import empty_state, merge_into
from repro.core.streaming import StreamingJob, migrate_lane_capacity
from repro.data.generators import drifting_zipf, zipf_keys


# ---------------------------------------------------------------------------
# state store
# ---------------------------------------------------------------------------


def test_merge_into_sums():
    sk, sv = empty_state(16, 1)
    bk = jnp.asarray([3, 5, 3, 9], jnp.int32)
    bv = jnp.ones((4, 1), jnp.float32)
    valid = jnp.ones(4, bool)
    sk, sv, ov = merge_into(sk, sv, bk, bv, valid)
    sk2, sv2, ov2 = merge_into(sk, sv, bk, bv, valid)
    d = dict(zip(np.asarray(sk2).tolist(), np.asarray(sv2)[:, 0].tolist()))
    assert d[3] == 4.0 and d[5] == 2.0 and d[9] == 2.0
    assert int(ov) == 0 and int(ov2) == 0


def test_merge_overflow_reported():
    sk, sv = empty_state(4, 1)
    bk = jnp.arange(8, dtype=jnp.int32)
    sk, sv, ov = merge_into(sk, sv, bk, jnp.ones((8, 1)), jnp.ones(8, bool))
    assert int(ov) == 4


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 500))
def test_prop_merge_conserves_mass(seed):
    rng = np.random.default_rng(seed)
    sk, sv = empty_state(256, 1)
    total = 0.0
    for _ in range(3):
        bk = rng.integers(0, 100, 64).astype(np.int32)
        bv = rng.random((64, 1)).astype(np.float32)
        valid = rng.random(64) < 0.8
        total += float(bv[valid].sum())
        sk, sv, ov = merge_into(sk, sv, jnp.asarray(bk), jnp.asarray(bv), jnp.asarray(valid))
        assert int(ov) == 0
    np.testing.assert_allclose(float(jnp.sum(sv)), total, rtol=1e-5)


def _reference_merge(table, keys, vals, valid, reduce, cap):
    """Plain dict fold of one batch; the table keeps its ``cap`` smallest keys."""
    combine = {"sum": np.add, "max": np.maximum}[reduce]
    table = dict(table)
    for k, v in zip(keys[valid].tolist(), vals[valid]):
        table[k] = combine(table[k], v) if k in table else v.copy()
    kept = sorted(table)[:cap]
    return {k: table[k] for k in kept}, max(0, len(table) - cap)


@pytest.mark.parametrize("dim", [1, 3])
@pytest.mark.parametrize("reduce", ["sum", "max"])
@pytest.mark.parametrize("start", ["empty", "full"])
@pytest.mark.parametrize("keys_from", ["table", "wide"])
def test_merge_matches_dict_reference(dim, reduce, start, keys_from):
    """Chained merges against a dict: invalid rows, duplicate keys within a
    batch (one hot key whose run spans more than one scan row), a table that
    starts empty or full, and (keys from a wide range) capacity overflow
    counted as the reference counts it.  Values are integers, which f32 adds
    exactly in any order."""
    cap, n = 256, 512
    rng = np.random.default_rng([dim, len(reduce), len(start), len(keys_from)])
    sk, sv = empty_state(cap, dim)
    table = {}
    if start == "full":
        table = {k: rng.integers(-50, 50, dim).astype(np.float32) for k in range(0, 2 * cap, 2)}
        sk = jnp.asarray(sorted(table), jnp.int32)
        sv = jnp.asarray(np.stack([table[k] for k in sorted(table)]))
    for _ in range(4):
        bk = (rng.integers(0, cap // 2, n) * 2 if keys_from == "table"
              else rng.integers(0, 4 * cap, n)).astype(np.int32)
        bk[rng.random(n) < 0.4] = 2 * rng.integers(0, cap // 2)
        bv = rng.integers(-50, 50, (n, dim)).astype(np.float32)
        valid = rng.random(n) < 0.8
        sk, sv, ov = merge_into(sk, sv, jnp.asarray(bk), jnp.asarray(bv), jnp.asarray(valid), reduce=reduce)
        table, want_ov = _reference_merge(table, bk, bv, valid, reduce, cap)
        live = len(table)
        got_k, got_v = np.asarray(sk), np.asarray(sv)
        np.testing.assert_array_equal(got_k[:live], sorted(table))
        assert (got_k[live:] == KEY_SENTINEL).all()
        np.testing.assert_array_equal(got_v[:live], np.stack([table[k] for k in sorted(table)]))
        assert (got_v[live:] == 0).all()
        assert int(ov) == want_ov
    assert (want_ov > 0) == (keys_from == "wide")


def test_merge_lowers_without_scatter_or_gather():
    """The merge is two sorts and elementwise passes: no row-wise scatter or
    gather, which the TPU runs slowest, comes back unseen."""
    args = (jax.ShapeDtypeStruct((2**12,), jnp.int32), jax.ShapeDtypeStruct((2**12, 1), jnp.float32),
            jax.ShapeDtypeStruct((2**11,), jnp.int32), jax.ShapeDtypeStruct((2**11, 1), jnp.float32),
            jax.ShapeDtypeStruct((2**11,), jnp.bool_))
    for reduce in ("sum", "max"):
        text = jax.jit(merge_into, static_argnames="reduce").lower(*args, reduce=reduce).as_text()
        assert "stablehlo.scatter" not in text and "stablehlo.gather" not in text
        assert text.count("stablehlo.sort") == 2


# ---------------------------------------------------------------------------
# shuffle step (single device mesh exercises the full shard_map path)
# ---------------------------------------------------------------------------


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def test_shuffle_routes_by_partitioner():
    mesh = _mesh1()
    part = uniform_partitioner(1)
    step = make_shuffle_step(mesh, num_partitions=1, capacity=64, num_hosts=part.num_hosts)
    keys = jnp.asarray(np.arange(10), jnp.int32)
    vals = jnp.ones((10, 1), jnp.float32)
    valid = jnp.ones(10, bool)
    res = step(part.tables(), keys, vals, valid)
    got = np.sort(np.asarray(res.keys[0])[np.asarray(res.valid[0])])
    np.testing.assert_array_equal(got, np.arange(10))
    assert int(res.overflow) == 0
    assert int(res.loads.sum()) == 10


def test_shuffle_overflow_counted():
    mesh = _mesh1()
    part = uniform_partitioner(1)
    step = make_shuffle_step(mesh, num_partitions=1, capacity=8, num_hosts=part.num_hosts)
    keys = jnp.asarray(np.arange(20), jnp.int32)
    res = step(part.tables(), keys, jnp.ones((20, 1)), jnp.ones(20, bool))
    assert int(res.overflow) == 12
    assert int(np.asarray(res.valid).sum()) == 8


def test_shuffle_hist_matches_batch():
    mesh = _mesh1()
    part = uniform_partitioner(1)
    step = make_shuffle_step(mesh, num_partitions=1, capacity=512, num_hosts=part.num_hosts, hist_k=8)
    keys = np.array([7] * 30 + [11] * 20 + [13] * 10, np.int32)
    res = step(part.tables(), jnp.asarray(keys), jnp.ones((60, 1)), jnp.ones(60, bool))
    hk = np.asarray(res.hist_keys)[0]
    hc = np.asarray(res.hist_counts)[0]
    top = dict(zip(hk.tolist(), hc.tolist()))
    assert top[7] == 30 and top[11] == 20 and top[13] == 10


# ---------------------------------------------------------------------------
# streaming job end-to-end
# ---------------------------------------------------------------------------


def test_wordcount_exact():
    """Stateful word count through shuffle+DR is exactly correct."""
    job = StreamingJob(state_capacity=2048, dr_enabled=True)
    rng = np.random.default_rng(0)
    stream = rng.integers(0, 200, size=3 * 1024)
    for i in range(3):
        job.process_batch(stream[i * 1024 : (i + 1) * 1024])
    for key in [0, 17, 199]:
        assert job.state_count(int(key)) == float((stream == key).sum())


@pytest.mark.parametrize("backend,transport", [("dense", "dense"),
                                               ("ragged", "ragged/masked-dense")])
def test_batch_metrics_name_route_path_and_transport(backend, transport):
    """Off-TPU the route runs the jnp twin and the ragged row phase the
    masked-dense collective (XLA:CPU has no native ragged all-to-all); the
    metrics say so."""
    job = StreamingJob(mesh=_mesh1(), state_capacity=1024, exchange_backend=backend)
    m = job.process_batch(np.arange(512))
    assert m.route_path == "jnp twin"
    assert m.transport == transport


def test_dr_triggers_and_improves_on_skew():
    job = StreamingJob(
        num_partitions=8,
        state_capacity=8192,
        dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1),
    )
    batches = list(drifting_zipf(6, 8192, num_keys=2_000, exponent=1.4, drift_every=100, seed=1))
    ms = job.run(batches)
    assert any(m.repartitioned for m in ms)
    first, last = ms[0].imbalance, ms[-1].imbalance
    assert last < first  # DR improved partition balance
    # state must survive migration intact
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:5]:
        assert job.state_count(int(key)) == float((all_keys == key).sum())


def test_dr_idle_on_uniform_stream():
    job = StreamingJob(num_partitions=4, dr=DRConfig(imbalance_trigger=1.5))
    rng = np.random.default_rng(2)
    ms = job.run([rng.integers(0, 100_000, 4096) for _ in range(3)])
    assert not any(m.repartitioned for m in ms)


@pytest.mark.parametrize("resize", [False, True], ids=["repartition", "resize-4to8"])
def test_one_worker_migration_is_a_table_swap(resize):
    """On one worker a repartition (and a resize 4 -> 8) swaps the
    partitioner and runs no migration: no migrate step or route is built,
    nothing ships or overflows, and the migrate step called whole on each
    repartitioned batch's state under the new tables would have kept every
    row bit for bit and received none.  The counts stay exact."""
    state = 2048
    job = StreamingJob(mesh=_mesh1(), num_partitions=4, state_capacity=state,
                       dr=DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0))
    whole = make_migrate_step(job.mesh, state_capacity=state,
                              num_hosts=job.drm.partitioner.num_hosts, seed=job.seed)
    batches = list(drifting_zipf(7, 2048, num_keys=600, exponent=1.3,
                                 drift_every=2, seed=1))
    ms = []
    for i, b in enumerate(batches):
        if resize and i == 3:
            job.resize(8)
        m = job.process_batch(b)
        ms.append(m)
        if not m.repartitioned:
            continue
        assert (m.migration_rows, m.relative_migration, m.overflow) == (0, 0.0, 0), m
        sk, sv = np.asarray(job.state_keys), np.asarray(job.state_vals)
        kk, kv, _, _, rva = whole(job.drm.partitioner.tables(), job.state_keys,
                                  job.state_vals)[:5]
        assert np.array_equal(np.asarray(kk), sk), i
        assert np.array_equal(np.asarray(kv).view(np.int32), sv.view(np.int32)), i
        assert not np.asarray(rva).any(), i
    assert sum(m.repartitioned for m in ms) >= 4, [m.reason for m in ms]
    if resize:
        assert ms[3].resized and ms[3].num_partitions == 8, ms[3]
    assert job._migrate_steps == {} and job._migrate_route is None
    # exact counts over everything fed
    fed, inv = np.unique(np.concatenate(batches), return_inverse=True)
    sk, sv = np.asarray(job.state_keys)[0], np.asarray(job.state_vals)[0]
    live = sk != KEY_SENTINEL
    assert np.array_equal(sk[live], fed)
    assert np.array_equal(sv[live][:, 0], np.bincount(inv).astype(np.float32))


def test_checkpoint_restore_resumes():
    job = StreamingJob(num_partitions=4, state_capacity=4096,
                       dr=DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0))
    batches = [zipf_keys(4096, num_keys=500, exponent=1.3, seed=s) for s in range(4)]
    job.process_batch(batches[0])
    job.process_batch(batches[1])
    snap = job.snapshot()
    # simulate crash: brand-new job, restore snapshot, continue
    job2 = StreamingJob(num_partitions=4, state_capacity=4096,
                        dr=DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0))
    job2.restore(snap)
    job.process_batch(batches[2])
    job2.process_batch(batches[2])
    all_keys = np.concatenate(batches[:3])
    for key in np.unique(all_keys)[:5]:
        assert job2.state_count(int(key)) == pytest.approx(float((all_keys == key).sum()))
        assert job2.state_count(int(key)) == pytest.approx(job.state_count(int(key)))


def test_flink_mode_checkpoint_gating():
    job = StreamingJob(
        num_partitions=4,
        checkpoint_interval=3,
        dr=DRConfig(imbalance_trigger=1.0, migration_cost_weight=0.0),
    )
    batches = [zipf_keys(4096, num_keys=500, exponent=1.5, seed=s) for s in range(6)]
    ms = job.run(batches)
    for i, m in enumerate(ms):
        if (i + 1) % 3 != 0:
            assert not m.repartitioned


# ---------------------------------------------------------------------------
# batch replay
# ---------------------------------------------------------------------------


def test_batch_replay_improves():
    keys = zipf_keys(100_000, num_keys=20_000, exponent=1.2, seed=3)
    res = BatchJob(num_partitions=8, sample_fraction=0.1).run(keys)
    assert res.imbalance_after <= res.imbalance_before
    assert res.assignments.min() >= 0 and res.assignments.max() < 8


def test_batch_replay_noop_when_uniform():
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 10**6, 50_000)
    res = BatchJob(num_partitions=8).run(keys)
    assert res.imbalance_after <= res.imbalance_before + 1e-9


# ---------------------------------------------------------------------------
# legacy snapshot restore (forward compatibility with older checkpoints)
# ---------------------------------------------------------------------------


def _legacy_roundtrip(strip_prefixes):
    """Cut a snapshot, delete newer key families, restore into a fresh job
    and continue — per-key totals must still be conserved."""
    mk = lambda: StreamingJob(
        num_partitions=4, state_capacity=4096,
        dr=DRConfig(imbalance_trigger=1e9))
    batches = [zipf_keys(2048, num_keys=300, exponent=1.3, seed=s)
               for s in range(3)]
    job = mk()
    job.process_batch(batches[0])
    job.process_batch(batches[1])
    snap = job.snapshot()
    stripped = {k: v for k, v in snap.items()
                if not any(k.startswith(p) for p in strip_prefixes)}
    job2 = mk()
    job2.restore(stripped)
    job2.process_batch(batches[2])
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:5]:
        assert job2.state_count(int(key)) == pytest.approx(
            float((all_keys == key).sum()))
    return job2


def test_restore_legacy_snapshot_without_backend_key():
    job = _legacy_roundtrip(["drm_exchange_backend"])
    # pre-backend snapshot: the job's construction-time transport stands
    assert job.exchange_backend.name == "dense"
    assert job.drm.exchange_backend is job.exchange_backend


def test_restore_legacy_snapshot_without_topology_keys():
    job = _legacy_roundtrip(["drm_topology"])
    assert job.exchange_topology is None  # flat world stands


def test_restore_legacy_snapshot_without_split_keys():
    job = _legacy_roundtrip(["drm_split"])
    assert job.drm.split_keys == {}  # nothing splits until re-evidenced


def test_restore_legacy_snapshot_without_health_keys():
    job = _legacy_roundtrip(["drm_health", "drm_quarantined",
                             "drm_last_health_action"])
    assert job.drm.lane_health is None
    assert job.drm.quarantined == []


def test_restore_legacy_snapshot_minimal():
    # the original PR-5 era snapshot: state + partitioner/sketch only
    job = _legacy_roundtrip(["drm_exchange_backend", "drm_topology",
                             "drm_split", "drm_health", "drm_quarantined",
                             "drm_last_health_action", "drm_backend_streak",
                             "drm_last_backend_switch"])
    assert job.drm.lane_health is None


@pytest.mark.parametrize("plan_rows, state_capacity, want", [
    # a 2^20-row table: every plan up to a sixteenth of the table shares
    # one lane size, larger plans round up to powers of two
    (1, 1 << 20, 1 << 16), (4096, 1 << 20, 1 << 16), (40_000, 1 << 20, 1 << 16),
    (1 << 16, 1 << 20, 1 << 16), ((1 << 16) + 1, 1 << 20, 1 << 17),
    (10**9, 1 << 20, 1 << 20),
    # small tables: the floor never drops below 8 rows, and a lane never
    # outgrows the table
    (1, 64, 8), (100, 1024, 128), (10**9, 4096, 4096),
])
def test_migrate_lane_capacity_floors_cross_worker_lanes(plan_rows, state_capacity, want):
    assert migrate_lane_capacity(plan_rows, state_capacity) == want
